"""Per-node span tracer — the flight-recorder core.

The metrics accumulators (utils/metrics.py) answer "how much time does
stage X cost in aggregate"; this tracer answers the CAUSAL question —
where one specific 3PC batch spent its time across the pool, and
whether the device seams were pipelined or idle between dispatches.

Design constraints (why this is not just `logging` with timestamps):

* Fixed cost per record. Every span is one tuple written into a slot of
  a PREALLOCATED ring buffer — no allocation growth, no I/O, no
  serialization on the hot path. When the buffer wraps, the oldest
  records are overwritten: a flight recorder keeps the newest history,
  which is the part that explains the failure/stall you just observed.
* Off by default, free when off. Instrumented call sites hold a
  `NullTracer`, or a node's DISARMED `Tracer`: either `span()` returns
  one shared no-op context manager — the disabled cost is a single
  attribute call and test, bench-gated to low single-digit percent
  even when enabled (bench.py tracing_overhead).
* Armable at runtime. A node constructs ONE `Tracer`, disarmed unless
  `Config.TRACING_ENABLED`, and injects it into every component once;
  `arm()` (the verify daemon's host trace session, server/node.py)
  turns recording on with no re-injection. The ring is allocated on
  the first `arm()`, so a node that never arms pays for no buffer.
* Thread-safe. The verify daemon records from a worker thread while its
  asyncio loop coalesces; slot claims take a lock (the write itself is
  one tuple store, so the critical section is tiny).
* Injectable clock. Tests pin a fake clock for deterministic export;
  production uses `perf_counter`, which is shared by every tracer in a
  process — so a sim pool's per-node buffers merge into one coherent
  pool-wide timeline with no clock alignment step.
* CPU beside wall time. `cpu_now()` reads the calling thread's CPU
  clock (`thread_time`, injectable like the wall clock), so a span that
  samples it at both ends can carry `cpu_us` — the thread's CPU time
  across the span; `sample_cpu()` records a `cpu` counter of
  the thread's and the whole process's CPU seconds at most once per
  CPU_SAMPLE_S, which splits the process's CPU into the loop's thread
  and every other thread.
* Dual clocks for cross-process alignment. `clock_pair()` samples the
  perf-counter AND an injectable wall clock in one call; the exporter
  records the pair as a `clock_sync` event at flush so FILE-mode
  consumers (scripts/pool_journey over Chrome dumps from different
  processes) can re-anchor each node's perf timeline onto shared wall
  time. In-process merges never need it, and `NullTracer` stays free.

Record shape (one tuple per event, fixed arity):

    (kind, name, category, t0, t1, key, args)

    kind: "X" complete span | "i" instant | "C" counter sample
    key:  correlation key — request digest for intake/propagate spans,
          "viewNo:ppSeqNo" for 3PC phases (see docs/observability.md)
    args: payload dict (batch sizes, queue depths) or None

Categories below become per-node tracks in the Perfetto export.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional, Tuple

# span categories: one Perfetto track per category per node
CAT_INTAKE = "intake"        # client request validation + acceptance
CAT_PROPAGATE = "propagate"  # PROPAGATE gossip + quorum finalisation
CAT_3PC = "3pc"              # PrePrepare/Prepare/Commit/Order
CAT_EXECUTE = "execute"      # batch apply + durable commit
CAT_DEVICE = "device"        # accelerator dispatch/collect seams
CAT_BLS = "bls"              # BLS share aggregation
CAT_REPLY = "reply"          # reply construction + audit paths
CAT_RECOVERY = "recovery"    # view change / catchup / breaker lifecycle
CAT_TRANSPORT = "transport"  # socket rx/tx seams of the prod tick
CAT_WIRE = "wire"            # frame receive path of the read loops

# the shortest interval between two `cpu` counter samples (seconds)
CPU_SAMPLE_S = 0.1

Record = Tuple[str, str, str, float, Optional[float], Optional[str],
               Optional[dict]]


class _SpanCtx:
    """One open span: records a complete ("X") event on exit."""

    __slots__ = ("_tracer", "_name", "_cat", "_key", "_args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 key: Optional[str], args: Optional[dict]):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._key = key
        self._args = args

    def __enter__(self) -> "_SpanCtx":
        self._t0 = self._tracer._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        tracer = self._tracer
        tracer._record((
            "X", self._name, self._cat, self._t0, tracer._clock(),
            self._key, self._args))
        return False

    def add(self, **args) -> None:
        """Attach payload discovered mid-span (e.g. a batch size known
        only after validation)."""
        if self._args is None:
            self._args = {}
        self._args.update(args)


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def add(self, **args) -> None:
        pass


_NULL_CTX = _NullCtx()


class NullTracer:
    """The default every instrumented component holds: the hot path is a
    no-op attribute call returning one shared context manager."""

    __slots__ = ("name",)
    enabled = False

    def __init__(self, name: str = ""):
        self.name = name

    def span(self, name, cat="", key=None, **args) -> _NullCtx:
        return _NULL_CTX

    def instant(self, name, cat="", key=None, **args) -> None:
        pass

    def counter(self, name, value=None, cat="", **values) -> None:
        pass

    def sample_cpu(self, every: float = CPU_SAMPLE_S) -> None:
        pass

    def complete(self, name, cat, t0, t1, key=None, **args) -> None:
        pass

    def now(self) -> float:
        return 0.0

    def cpu_now(self) -> float:
        return 0.0

    def clock_pair(self) -> Tuple[float, float]:
        return (0.0, 0.0)

    def spans(self) -> List[Record]:
        return []

    def clear(self) -> None:
        pass

    def stats(self) -> dict:
        return {"enabled": False, "capacity": 0, "recorded": 0,
                "buffered": 0, "dropped": 0}


class Tracer:
    """Ring-buffer span recorder for one node (or one daemon).

    Armed, it records; disarmed, `span()` hands back the shared null
    context and `instant()`/`counter()`/`complete()` return at once —
    what `NullTracer` costs. `enabled` follows `armed` (call sites that
    guard extra work test it)."""

    __slots__ = ("name", "armed", "enabled", "_capacity", "_buf", "_idx",
                 "_written", "_clock", "_wall_clock", "_thread_clock",
                 "_process_clock", "_cpu_sampled_at", "_lock")

    def __init__(self, name: str = "", capacity: int = 1 << 16,
                 clock=time.perf_counter, wall_clock=time.time,
                 armed: bool = True, thread_clock=time.thread_time,
                 process_clock=time.process_time):
        self.name = name
        self._capacity = max(1, int(capacity))
        self._buf: List[Optional[Record]] = []   # allocated by arm()
        self._idx = 0           # next slot to overwrite
        self._written = 0       # total records ever (>= buffered)
        self._clock = clock
        self._wall_clock = wall_clock
        self._thread_clock = thread_clock
        self._process_clock = process_clock
        self._cpu_sampled_at = float("-inf")
        self._lock = threading.Lock()
        self.armed = self.enabled = False
        if armed:
            self.arm()

    def arm(self) -> None:
        """Start recording; the ring is allocated on the first call."""
        with self._lock:
            if not self._buf:
                self._buf = [None] * self._capacity
        self.armed = self.enabled = True

    def disarm(self) -> None:
        """Stop recording; what the ring holds stays readable."""
        self.armed = self.enabled = False

    # ------------------------------------------------------------ record

    def _record(self, rec: Record) -> None:
        with self._lock:
            self._buf[self._idx] = rec
            self._idx = (self._idx + 1) % self._capacity
            self._written += 1

    def span(self, name: str, cat: str = "", key: Optional[str] = None,
             **args):
        """Context manager timing one complete span."""
        if not self.armed:
            return _NULL_CTX
        return _SpanCtx(self, name, cat, key, args or None)

    def instant(self, name: str, cat: str = "",
                key: Optional[str] = None, **args) -> None:
        """Zero-duration marker (quorum reached, request accepted)."""
        if not self.armed:
            return
        t = self._clock()
        self._record(("i", name, cat, t, t, key, args or None))

    def counter(self, name: str, value=None, cat: str = "",
                **values) -> None:
        """Counter sample (queue depth, batch size) — rendered by
        Perfetto as a stacked counter track. With keyword `values`
        instead of one `value`, one record carries them all: one track
        of several series."""
        if not self.armed:
            return
        self._record(("C", name, cat, self._clock(), None, None,
                      values or {name: value}))

    def sample_cpu(self, every: float = CPU_SAMPLE_S) -> None:
        """A `cpu` counter sample — `thread_s`, the calling thread's CPU
        seconds, and `process_s`, every thread's — unless the last one
        is younger than `every` seconds on this tracer's clock. A loop
        calls it every turn; the samples split the process's CPU into
        its own thread and the rest. `every=0` takes one regardless:
        the last, as a dump closes the record."""
        if not self.armed:
            return
        t = self._clock()
        if t - self._cpu_sampled_at < every:
            return
        self._cpu_sampled_at = t
        self.counter("cpu", thread_s=self._thread_clock(),
                     process_s=self._process_clock())

    def complete(self, name: str, cat: str, t0: float, t1: float,
                 key: Optional[str] = None, **args) -> None:
        """A span whose interval the caller measured with `now()` — for
        spans whose start precedes the decision to record them (a prod
        tick that turns out to have produced work, a frame's wait in a
        queue)."""
        if not self.armed:
            return
        self._record(("X", name, cat, t0, t1, key, args or None))

    def now(self) -> float:
        """This tracer's clock, for `complete()`."""
        return self._clock()

    def cpu_now(self) -> float:
        """The calling thread's CPU seconds (`time.thread_time`), for a
        span's `cpu_us`: read before the span's first wall-clock read
        and after its last (a read is a system call, which should not
        lengthen the span), the difference is the thread's CPU time
        across it."""
        return self._thread_clock()

    def clock_pair(self) -> Tuple[float, float]:
        """(perf_counter, wall) sampled back to back — the anchor pair
        wire stamps and flush-time `clock_sync` metadata carry so
        cross-process consumers can align this tracer's perf timeline
        onto wall time."""
        return (self._clock(), self._wall_clock())

    def clock_info(self) -> dict:
        """What the timestamps are readings of, for a dump's metadata:
        `perf_counter`'s implementation (on Linux the host-wide
        CLOCK_MONOTONIC, so dumps of different processes on one host
        share a time axis), or "injected" for a test's fake clock."""
        if self._clock is not time.perf_counter:
            return {"name": "injected", "implementation": "injected"}
        info = time.get_clock_info("perf_counter")
        return {"name": "perf_counter",
                "implementation": info.implementation,
                "monotonic": info.monotonic}

    # -------------------------------------------------------------- read

    def spans(self) -> List[Record]:
        """Buffered records, oldest → newest. After a wrap only the
        newest `capacity` records survive — flight-recorder semantics."""
        with self._lock:
            if self._written < self._capacity:
                return list(self._buf[:self._idx])
            return list(self._buf[self._idx:]) + list(self._buf[:self._idx])

    def oldest_written_at(self) -> Optional[float]:
        """When the oldest surviving record was written (a span's END):
        everything recorded since is still in the ring, so a reader
        whose window starts after it lost nothing to a wrap."""
        with self._lock:
            if not self._written:
                return None
            rec = self._buf[self._idx if self._written >= self._capacity
                            else 0]
        return rec[3] if rec[4] is None else rec[4]

    def clear(self) -> None:
        with self._lock:
            if self._buf:
                self._buf = [None] * self._capacity
            self._idx = 0
            self._written = 0

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": self.armed,
                "capacity": self._capacity,
                "recorded": self._written,
                "buffered": min(self._written, self._capacity),
                "dropped": max(0, self._written - self._capacity),
            }
