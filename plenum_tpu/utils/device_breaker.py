"""Shared circuit-breaker policy for attach-behind device engines.

Two subsystems attach an optional device engine behind a host
implementation (`CompactMerkleTree.attach_device_engine`,
`PruningState.attach_device_engine`) with the same fallback contract:
every engine failure serves THAT call from the host path; the first
failure logs one full traceback, later ones log at debug (a sick
device must not log-spam the serving path). This module is the ONE
place that policy lives — the seams configure the wording and the
exception types that must propagate, nothing else.

Lifecycle (classic three-state breaker, docs/robustness.md):

    CLOSED ──max_failures consecutive failures──► OPEN
      ▲                                             │ cooldown_s
      │ probe succeeds                              ▼
      └────────────────────────────────────── HALF-OPEN
                    probe fails: re-trip quietly ───┘ (one probe call)

While OPEN every call serves the fallback without touching the engine
(zero device round trips on the serving path). The first call after
the cooldown is a single probe: success closes the breaker and the
engine serves again; failure re-trips quietly (debug log) for another
cooldown. The seams therefore keep the engine ATTACHED across trips —
"re-attach" is the breaker closing again, never a new attach call, so
a transient device outage (driver or runtime restart) heals
without operator intervention.
"""
from __future__ import annotations

import logging
import time

logger = logging.getLogger(__name__)


class DeviceCircuitBreaker:
    def __init__(self, what: str, fallback: str, max_failures: int = 3,
                 reraise: tuple = (), cooldown_s: float = None,
                 clock=None):
        """what/fallback: log wording ("device proof engine" / "the
        host memo path"). reraise: exception types that are DOMAIN
        errors, not device faults (the host path would raise them too,
        or they must surface) — they propagate untouched and do not
        count against the device. cooldown_s: seconds the breaker
        stays OPEN before allowing a probe (default
        Config.BREAKER_COOLDOWN_S); clock: injectable monotonic clock
        for tests."""
        if cooldown_s is None:
            from plenum_tpu.common.config import Config
            cooldown_s = Config.BREAKER_COOLDOWN_S
        self.what = what
        self.fallback = fallback
        self.max_failures = max_failures
        self.reraise = tuple(reraise)
        self.cooldown_s = cooldown_s
        self._clock = clock or time.monotonic
        self.fail_count = 0
        # monotonic deadline of the current OPEN window; None = CLOSED
        self._open_until = None
        # observability: lifetime counts — every call the host served
        # because the engine raised (`failures`, never reset: what
        # chip_smoke.py and the benchmark read to refuse a run whose
        # "device" results came from the host), trips and successful
        # probes
        self.failures = 0
        self.trips = 0
        self.recoveries = 0

    @property
    def open(self) -> bool:
        """True while the breaker serves everything from the fallback
        (OPEN or awaiting its HALF-OPEN probe)."""
        return self._open_until is not None

    # historical name: callers used to detach the engine on `tripped`;
    # the breaker now owns recovery, so this is just "open" — kept for
    # status dumps and tests that read breaker health
    tripped = open

    def probe_due(self) -> bool:
        """True when the next run() will probe the engine (cooldown
        elapsed on an open breaker)."""
        return self._open_until is not None \
            and self._clock() >= self._open_until

    def _trip(self, quiet: bool):
        self.trips += 1
        self._open_until = self._clock() + self.cooldown_s
        if quiet:
            logger.debug("%s probe failed; re-tripping for %.0fs",
                         self.what, self.cooldown_s, exc_info=True)
        else:
            logger.warning(
                "%s failed %d times; breaker OPEN for %.0fs (%s serves; "
                "one probe call after the cooldown)", self.what,
                self.fail_count, self.cooldown_s, self.fallback)

    def run(self, fn, label: str = ""):
        """Run one engine operation under the policy → (ok, result).
        ok False means serve this call from the host fallback. While
        OPEN, fn is not called at all; after the cooldown exactly one
        call becomes the recovery probe."""
        what = "{} {}".format(self.what, label).strip()
        if self._open_until is not None:
            if self._clock() < self._open_until:
                return False, None  # OPEN: quiet fallback, no device I/O
            # HALF-OPEN: this call is the single recovery probe
            try:
                out = fn()
            except self.reraise:
                raise
            except Exception:  # plenum-lint: disable=PT006 — this IS
                # the designed host-fallback boundary: ANY engine/device
                # failure must degrade to the host path, never crash
                self.failures += 1
                self._trip(quiet=True)
                return False, None
            self._open_until = None
            self.fail_count = 0
            self.recoveries += 1
            logger.warning("%s recovered on probe; breaker CLOSED "
                           "(engine serves again)", what)
            return True, out
        try:
            out = fn()
        except self.reraise:
            raise
        except Exception:  # plenum-lint: disable=PT006 — this IS the
            # designed host-fallback boundary: ANY engine/device
            # failure must degrade to the host path, never crash
            self.failures += 1
            self.fail_count += 1
            if self.fail_count >= self.max_failures:
                self._trip(quiet=False)
            elif self.fail_count == 1:
                logger.warning("%s failed; serving from %s", what,
                               self.fallback, exc_info=True)
            else:
                logger.debug("%s failed again (%d)", what,
                             self.fail_count, exc_info=True)
            return False, None
        self.fail_count = 0
        return True, out
