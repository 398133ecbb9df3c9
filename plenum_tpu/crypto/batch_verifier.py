"""Batched ed25519 verification provider — the north-star dispatch seam.

The reference authenticates each request inline through libsodium
(`plenum/server/client_authn.py:84`). Here verification requests are
gathered per prod tick and dispatched as ONE device batch when the queue
is deep enough; small batches take the scalar floor so a quiet pool never
regresses (SURVEY.md §7 "hard parts" #3: dispatch policy by queue depth).

Providers:
  - ScalarVerifier: pure-Python RFC 8032 (crypto/ed25519.py), per item —
    the reference implementation used for cross-checking only.
  - OpenSSLVerifier: per-item verification through OpenSSL's Ed25519
    (`cryptography`) — the honest CPU floor, equivalent to the
    reference's libsodium path (~10-20k verifies/s/core).
  - JaxBatchVerifier: one fused TPU dispatch (ops/ed25519_jax.py).
  - AdaptiveVerifier: routes by batch size; default `tpu_batch` provider.

All providers share one interface: verify_batch([(msg, sig, vk)]) → [bool].
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

from plenum_tpu.observability.tracing import CAT_DEVICE, NullTracer
from plenum_tpu.observability import telemetry as _telemetry

VerifyItem = Tuple[bytes, bytes, bytes]  # (message, signature64, verkey32)

_NULL_TRACER = NullTracer()


class _Ready:
    """Already-materialized result (scalar paths)."""

    def __init__(self, results: List[bool]):
        self._results = results

    def ready(self) -> bool:
        return True

    def collect(self) -> List[bool]:
        return self._results


class _PendingDevice:
    """In-flight device batch: JAX dispatch is async — creating this does
    not block; collect() materializes (blocks on the device)."""

    def __init__(self, ok_device, valid, n, tracer=None, span_args=None):
        self._ok = ok_device
        self._valid = valid
        self._n = n
        self._tracer = tracer or _NULL_TRACER
        self._span_args = span_args or {}

    def ready(self) -> bool:
        is_ready = getattr(self._ok, "is_ready", None)
        return bool(is_ready()) if is_ready is not None else True

    def collect(self) -> List[bool]:
        import numpy as np
        # waiting for the device and copying the verdicts back
        with self._tracer.span("verify_collect", CAT_DEVICE,
                               **self._span_args):
            return list(np.asarray(self._ok)[:self._n] & self._valid)


class ScalarVerifier:
    name = "scalar"

    def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        from . import ed25519
        return [ed25519.verify(m, s, vk) for (m, s, vk) in items]

    def dispatch(self, items: Sequence[VerifyItem]) -> _Ready:
        return _Ready(self.verify_batch(items))


try:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (  # noqa
        Ed25519PublicKey as _OpenSSLEd25519PublicKey)
    HAVE_OPENSSL = True
except ImportError:        # soft dep: scalar RFC 8032 fallback below
    HAVE_OPENSSL = False


class OpenSSLVerifier:
    """The CPU production floor (libsodium-equivalent): OpenSSL Ed25519
    via `cryptography`. Reference: stp_core/crypto/nacl_wrappers.py.
    When `cryptography` is not installed, falls back to the
    pure-Python RFC 8032 implementation — identical verdicts, scalar
    speed floor."""

    name = "cpu"

    def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        if not HAVE_OPENSSL:
            return ScalarVerifier().verify_batch(items)
        from cryptography.exceptions import InvalidSignature
        out = []
        for msg, sig, vk in items:
            try:
                _OpenSSLEd25519PublicKey.from_public_bytes(
                    bytes(vk)).verify(bytes(sig), bytes(msg))
                out.append(True)
            except (InvalidSignature, ValueError):
                out.append(False)
        return out

    def dispatch(self, items: Sequence[VerifyItem]) -> _Ready:
        return _Ready(self.verify_batch(items))


class JaxBatchVerifier:
    name = "tpu_batch"
    # the verify daemon sets both before each coalesced batch, so a
    # launch's host halves show inside its device_verify span with the
    # batch's items/unique; everyone else leaves the null tracer
    tracer = _NULL_TRACER
    span_args: dict = {}

    def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        return self.dispatch(items).collect()

    def dispatch(self, items: Sequence[VerifyItem]) -> "_PendingDevice":
        """Enqueue the device batch WITHOUT blocking on the result —
        JAX dispatch is asynchronous, so the caller (prod loop) overlaps
        consensus work / other nodes\' dispatches with the device round
        trip and harvests later (SURVEY.md §7 backpressure design)."""
        from plenum_tpu.ops import ed25519_jax
        tracer, span_args = self.tracer, self.span_args
        # bytes → padded host arrays
        with tracer.span("verify_pack", CAT_DEVICE, **span_args):
            msgs = [m for m, _, _ in items]
            sigs = [s for _, s, _ in items]
            vks = [vk for _, _, vk in items]
            arrays, valid, n = ed25519_jax.pack_batch(msgs, sigs, vks)
        # host-to-device transfer and the dispatch, until it returns
        with tracer.span("verify_launch", CAT_DEVICE, **span_args):
            ok_dev = ed25519_jax.launch_packed(arrays, n)
        return _PendingDevice(ok_dev, valid, n, tracer, span_args)


def _default_threshold(threshold):
    """Single-source the scalar-vs-device batch threshold from
    Config.VERIFIER_BATCH_THRESHOLD (like the MERKLE_DEVICE_* knobs);
    an explicit ctor argument still wins."""
    if threshold is not None:
        return threshold
    from plenum_tpu.common.config import Config
    return Config.VERIFIER_BATCH_THRESHOLD


class AdaptiveVerifier:
    """Scalar floor below `threshold` items, device batch above
    (default: Config.VERIFIER_BATCH_THRESHOLD)."""

    name = "adaptive"

    def __init__(self, threshold: int = None, scalar=None, batch=None):
        self.threshold = _default_threshold(threshold)
        self._scalar = scalar or OpenSSLVerifier()
        self._batch = batch or JaxBatchVerifier()

    @property
    def device_provider(self):
        """The provider that serves batches at or above the threshold
        (where a caller attaches device-side tracing)."""
        return self._batch

    def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        if len(items) >= self.threshold:
            return self._batch.verify_batch(items)
        return self._scalar.verify_batch(items)

    def dispatch(self, items: Sequence[VerifyItem]):
        if len(items) >= self.threshold:
            return self._batch.dispatch(items)
        return self._scalar.dispatch(items)


class _HubPending:
    """One dispatch's slice of a coalesced device launch."""

    def __init__(self, hub, gen, lo, hi):
        self._hub = hub
        self._gen = gen
        self._lo = lo
        self._hi = hi

    def ready(self) -> bool:
        pending = self._gen.pending
        if pending is None:
            return False  # generation not flushed yet
        r = getattr(pending, "ready", None)
        return bool(r()) if r is not None else True

    def collect(self) -> List[bool]:
        hub = self._hub
        # the harvest: when results are not yet materialized this span
        # IS the host-visible device round trip for this slice
        with hub.tracer.span("hub_collect", CAT_DEVICE,
                             n=self._hi - self._lo):
            hub._flush(self._gen)
            return self._gen.results()[self._lo:self._hi]


def dedup_items(items: Sequence[VerifyItem]
                ) -> Tuple[List[VerifyItem], List[int]]:
    """→ (unique_items, index) where index[i] is item i's slot in the
    unique list. Verification is pure, and co-resident nodes all verify
    the SAME client requests — callers sharing a device (hub,
    verify daemon) would otherwise pay n× the work for one answer."""
    uniq: dict = {}
    order: List[VerifyItem] = []
    index: List[int] = []
    for item in items:
        pos = uniq.get(item)
        if pos is None:
            pos = uniq[item] = len(order)
            order.append(item)
        index.append(pos)
    return order, index


class _HubGeneration:
    def __init__(self):
        self.items: List[VerifyItem] = []
        self.pending = None
        self._results = None
        self._index = None  # per-item slot in the deduped launch
        self._tm_device = False     # launched on the device path
        self._tm_new_shape = False  # that launch compiled a new bucket
        self._tm_hub = None         # telemetry hub stamped at flush

    def dedup(self) -> List[VerifyItem]:
        order, self._index = dedup_items(self.items)
        return order

    def results(self) -> List[bool]:
        if self._results is None:
            if self._tm_device:
                # the materialization below IS this generation's
                # dispatch→collect round trip as the host sees it
                hub = self._tm_hub or _telemetry.get_seam_hub()
                t0 = hub.clock()
                res = self.pending.collect()
                hub.record_roundtrip(
                    _telemetry.SEAM_HUB, (hub.clock() - t0) * 1e3,
                    first_call=self._tm_new_shape)
            else:
                res = self.pending.collect()
            idx = self._index
            self._results = res if idx is None \
                else [res[i] for i in idx]
        return self._results


class CoalescingVerifierHub:
    """Coalesces concurrent dispatches from co-resident consumers
    (RBFT protocol instances sharing a node, or pool nodes sharing a
    host process) into ONE device launch.

    The verify kernel is latency-bound — the 256-bit scalar-mult ladder
    is a long sequential dependency chain, so a small launch costs
    nearly as much as a full one — which makes k small concurrent
    launches cost ~k× one fused launch. The
    hub queues dispatch() calls and launches the union the first time
    any participant harvests; per-dispatch slices keep results isolated.

    Same dispatch()/verify_batch() interface as the other providers, so
    it drops into ClientAuthNr unchanged.

    Standalone construction (the gateway tier, tests, tools): every
    collaborator is an explicit ctor argument — ``tracer`` (flight
    recorder; NullTracer default), ``telemetry`` (the hub that receives
    the SEAM_HUB launch/round-trip accounting; defaults to the lazy
    process-wide seam hub so node-owned wiring is unchanged) and
    ``threshold`` (Config single-source default). Nothing here reaches
    into a Node.
    """

    name = "tpu_hub"

    def __init__(self, batch=None, scalar=None, threshold: int = None,
                 tracer=None, telemetry=None):
        self._batch = batch or JaxBatchVerifier()
        self._scalar = scalar or OpenSSLVerifier()
        self.threshold = _default_threshold(threshold)
        self._gen = _HubGeneration()
        # node/bench may still attach a recorder post-ctor (plain
        # attribute); explicit injection is the standalone path
        self.tracer = tracer if tracer is not None else NullTracer()
        self._telemetry = telemetry  # None = lazy process seam hub

    @property
    def telemetry(self):
        """The telemetry hub this hub's SEAM_HUB accounting lands in:
        the injected one, or (default) the process-wide seam hub."""
        return self._telemetry if self._telemetry is not None \
            else _telemetry.get_seam_hub()

    def dispatch(self, items: Sequence[VerifyItem]) -> _HubPending:
        gen = self._gen
        lo = len(gen.items)
        gen.items.extend(items)
        # queue-depth counter: how deep the open generation is when each
        # co-resident consumer lands — the coalescing evidence
        self.tracer.counter("hub_queue_depth", len(gen.items))
        return _HubPending(self, gen, lo, len(gen.items))

    def flush(self) -> None:
        """Close the current generation and START its (async) device
        launch now, instead of waiting for the first collect. Callers
        that know a coalescing window just ended (all co-resident nodes
        dispatched their chunk) use this to overlap the device round
        trip with the consensus work that follows; pending handles
        already issued for this generation stay valid."""
        self._flush(self._gen)

    def _flush(self, gen: _HubGeneration) -> None:
        if gen.pending is not None:
            return
        # rotate FIRST: a failing dispatch must poison only this
        # generation, not wedge every future dispatch from every
        # co-resident consumer
        if gen is self._gen:
            self._gen = _HubGeneration()
        with self.tracer.span("hub_flush", CAT_DEVICE,
                              items=len(gen.items)) as _sp:
            launch_items = gen.dedup()
            _sp.add(unique=len(launch_items))
            if not launch_items:
                gen.pending = _Ready([])
            elif len(launch_items) < self.threshold:
                # quiet pool: a lone small generation takes the CPU floor
                # rather than paying a full device launch
                gen.pending = self._scalar.dispatch(launch_items)
            else:
                # hub-seam lane accounting: unique items launched vs the
                # bucket the async verify pads them into (the SAME
                # pow2/mesh bucket math the launch pays — single-sourced
                # in ed25519_jax.launch_lanes)
                from plenum_tpu.ops.ed25519_jax import launch_lanes
                lanes = launch_lanes(len(launch_items))
                gen._tm_device = True
                gen._tm_hub = self.telemetry
                gen._tm_new_shape = gen._tm_hub.record_launch(
                    _telemetry.SEAM_HUB,
                    len(launch_items), lanes, shape=lanes)
                gen.pending = self._batch.dispatch(launch_items)

    def verify_batch(self, items: Sequence[VerifyItem]) -> List[bool]:
        return self.dispatch(items).collect()


def _make_remote(**kwargs):
    from plenum_tpu.crypto.remote_verifier import RemoteVerifier
    return RemoteVerifier(**kwargs)


_PROVIDERS = {
    "scalar": ScalarVerifier,
    "cpu": OpenSSLVerifier,
    "tpu_batch": JaxBatchVerifier,
    "tpu_hub": CoalescingVerifierHub,
    "adaptive": AdaptiveVerifier,
    "remote": _make_remote,
}


def create_verifier(name: str = "adaptive", **kwargs):
    try:
        cls = _PROVIDERS[name]
    except KeyError:
        raise ValueError(f"unknown verifier provider {name!r}; "
                         f"one of {sorted(_PROVIDERS)}")
    return cls(**kwargs)
