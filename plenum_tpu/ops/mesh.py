"""Device-mesh crypto dispatch — shard the batch axis across every chip.

The crypto kernels in this package (ed25519 batch verify, BLS12-381
aggregation, batched SHA-256 / merkle gathers) are embarrassingly
parallel over their batch axis: every row is an independent signature,
aggregation job, leaf or proof index. That makes data parallelism over
the device mesh the cheapest untapped multiplier the framework has —
committee-consensus measurements (arXiv:2302.00418) put signature
verification on the ordering critical path, and hash-tree accelerators
(MTU, arXiv:2507.16793) win precisely by saturating parallel lanes.

This module is the ONE production seam for that axis:

 - `DeviceMesh` enumerates the available devices lazily (honoring
   ``JAX_PLATFORMS`` / ``xla_force_host_platform_device_count`` through
   JAX itself, capped by ``Config.MESH_MAX_DEVICES`` and rounded down to
   a power of two so bucket padding stays divisible).
 - `dispatch` pads a ragged batch to ``n_devices × per-device bucket``
   (power-of-two buckets, so every batch size in a bucket shares one
   compiled SPMD executable), places the arrays with a batch-axis
   ``NamedSharding``, and launches the jitted kernel asynchronously —
   the returned arrays are un-awaited device handles, so callers keep
   the same dispatch/collect overlap they had on one chip.  The kernels
   are row-wise pure, so XLA inserts ZERO collectives.
 - Passthrough: with ``MESH_ENABLED = False``, a single-device host, or
   a batch below ``Config.MESH_SHARD_MIN``, callers take their existing
   single-device path untouched (bench-gated to <5% overhead).
 - `MeshPipeline` double-buffers dispatch/collect across batches (the
   shape ``ops/merkle.ProofPipeline`` uses), keeping every chip's next
   batch enqueued while the host drains the previous download.
 - `probe_platform` / `is_accelerator` / `device_facts` are the ONE
   lazy "which device did this process get?" probe — modules must route
   capability questions here instead of touching ``jax.devices()[0]``
   directly (which force-initializes the backend). A backend that
   cannot be initialised RAISES: a lost or busy chip is an error, never
   a silent CPU run.

Import of this module NEVER initializes JAX: server code (node
bootstrap, validator-info dumps) reads configuration and stats without
waking an accelerator; JAX loads on the first probe or dispatch.

Consumers: ``ops/ed25519_jax.verify_batch_async`` (and through it the
``CoalescingVerifierHub`` and the verify daemon), ``ops/bls381_jax``'s
batched aggregate path, and ``ops/merkle`` builds + proof gathers.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Callable, List, Optional, Sequence

import numpy as np

from plenum_tpu.observability.tracing import CAT_DEVICE, NullTracer
from plenum_tpu.observability import telemetry as _telemetry

logger = logging.getLogger(__name__)

# --------------------------------------------------------- capability probe

_PROBE_LOCK = threading.Lock()
_PROBE = {"platform": None, "device_count": None, "device_kind": None}


def _note_devices_locked(devs) -> None:
    """Record device facts from an enumeration (caller holds
    _PROBE_LOCK); first writer wins."""
    if _PROBE["platform"] is None and devs:
        _PROBE["platform"] = devs[0].platform
        _PROBE["device_kind"] = devs[0].device_kind
        _PROBE["device_count"] = len(devs)


def probe_platform() -> str:
    """Platform of device 0 ("cpu" / "tpu" / "gpu"), probed lazily.
    First call initializes the JAX backend; every later call is a dict
    read. A backend that cannot be initialised RAISES (the chip is held
    by another process, the runtime is broken): the caller asked for a
    device, and reading that as "cpu" would let a run that lost its
    chip print numbers under device names. An explicit
    ``JAX_PLATFORMS=cpu`` still reads "cpu" — JAX succeeds there."""
    with _PROBE_LOCK:
        if _PROBE["platform"] is None:
            import jax
            _note_devices_locked(jax.devices())
        return _PROBE["platform"]


def device_facts() -> dict:
    """``{"platform", "kind", "count"}`` of this process's devices as
    JAX reports them — what the verify daemon states in its ready file
    and chip_smoke.py prints. Initializes the backend (and raises if it
    cannot), like probe_platform."""
    probe_platform()
    return {"platform": _PROBE["platform"], "kind": _PROBE["device_kind"],
            "count": _PROBE["device_count"]}


def is_accelerator() -> bool:
    """True iff device 0 is a real accelerator (not the CPU backend)."""
    return probe_platform() not in ("cpu",)


def probed() -> bool:
    """Whether the backend has been probed THROUGH THIS MODULE already —
    lets status dumps report device facts without ever being the caller
    that wakes the backend."""
    return _PROBE["platform"] is not None


def _reset_probe() -> None:
    """Test hook: forget the cached probe result. Also clears the
    Pallas-backend availability cache below — both derive from the
    same platform probe, so a caller that re-probes (dryrun_multichip
    after un-pinning JAX_PLATFORMS) must re-decide Pallas too, or a
    stale "cpu" answer would disable the Pallas kernels process-wide
    on a real TPU."""
    with _PROBE_LOCK:
        _PROBE["platform"] = None
        _PROBE["device_kind"] = None
        _PROBE["device_count"] = None
        _PALLAS_BACKENDS.clear()


# ---------------------------------------------- pallas kernel availability

# env-var name -> bool; ONE probe-backed decision per kernel family
# (ed25519, sha256). Guarded by _PROBE_LOCK like the probe itself.
_PALLAS_BACKENDS = {}
# env-var name -> lifetime count of run-time step-downs (never cleared,
# not even by _reset_probe): what chip_smoke.py and the benchmark read
# to refuse a run in which a kernel family silently left the device
_STEP_DOWNS = {}


def pallas_backend_enabled(env_var: str) -> bool:
    """THE availability gate every Pallas kernel consults (the ed25519
    whole-verify kernel and the SHA-256 compression kernel): enabled
    exactly when device 0 is a real accelerator, unless the kernel's
    env var pins ``"xla"``. Cached per kernel family so a permanent
    runtime failure (``disable_pallas_backend``) sticks; the cache is
    cleared together with the platform probe (``_reset_probe``)."""
    with _PROBE_LOCK:
        state = _PALLAS_BACKENDS.get(env_var)
    if state is None:
        state = (os.environ.get(env_var) != "xla") and is_accelerator()
        with _PROBE_LOCK:
            state = _PALLAS_BACKENDS.setdefault(env_var, state)
    return state


def xla_backend_enabled(env_var: str) -> bool:
    """Availability gate for device kernels written as plain XLA (the
    bls381 pairing/MSM path): these run on ANY backend — CPU included —
    so, unlike ``pallas_backend_enabled``, no accelerator is required.
    Enabled unless the kernel's env var pins the native/scalar path
    (``"native"``/``"off"``) or a runtime failure stepped it down
    (``disable_pallas_backend`` — same registry, same permanence)."""
    with _PROBE_LOCK:
        state = _PALLAS_BACKENDS.get(env_var)
    if state is None:
        state = os.environ.get(env_var, "").lower() \
            not in ("native", "off", "0")
        with _PROBE_LOCK:
            state = _PALLAS_BACKENDS.setdefault(env_var, state)
    return state


def disable_pallas_backend(env_var: str) -> None:
    """Permanent step-down for one kernel family — the fallback engine
    (launch_survives for the Pallas seams, crypto/bls_ops for the BLS
    tower) calls this after a RUN-TIME failure of a launched kernel so
    every later dispatch goes straight to the fallback path. Counted
    (step_down_counts): a serving process keeps answering, but no run
    may report device results without saying this happened."""
    with _PROBE_LOCK:
        _PALLAS_BACKENDS[env_var] = False
        _STEP_DOWNS[env_var] = _STEP_DOWNS.get(env_var, 0) + 1


# (env-var name, shape key) of launches whose execution has completed
_PROVEN = set()


def launch_survives(env_var: str, key, outputs, what: str) -> bool:
    """Prove ONCE per (kernel family, shape key) that a launched kernel
    really executes — THE run-time half of the failure policy every
    Pallas seam shares (ed25519 verify, SHA-256 routing, merkle
    builds). JAX dispatch is async: a run-time failure at an untested
    shape would otherwise surface at the caller's np.asarray, where
    nothing can serve around it. So the first launch per key blocks
    until ready; later launches with that key stay fully async.

    → True when the launch is (or was already) proven. → False after a
    run-time failure: logged, and the family is stepped down for the
    life of the process — COUNTED (step_down_counts) — so the caller
    serves this call from its XLA expression. Trace, lowering and
    compile failures never reach here: they raise from the launch
    itself, because a kernel the installed compiler refuses is a
    program bug, not something to serve around."""
    with _PROBE_LOCK:
        if (env_var, key) in _PROVEN:
            return True
    try:
        import jax
        # deliberate ONE-TIME sync per shape key
        jax.block_until_ready(outputs)  # plenum-lint: disable=PT002
    except Exception:  # pragma: no cover  # plenum-lint: disable=PT006
        # serving-path robustness: the process keeps answering from
        # the fallback path, and the count says so
        logger.exception("%s failed at run time; stepping down to XLA",
                         what)
        disable_pallas_backend(env_var)
        return False
    with _PROBE_LOCK:
        _PROVEN.add((env_var, key))
    return True


def step_down_counts() -> dict:
    """env-var name -> how many times that kernel family stepped down
    in this process ({} = every family still on its device path)."""
    with _PROBE_LOCK:
        return dict(_STEP_DOWNS)


def kernel_backends() -> dict:
    """env-var name -> current availability decision of every kernel
    family that has been consulted (True = device/Pallas path)."""
    with _PROBE_LOCK:
        return dict(_PALLAS_BACKENDS)


def default_device():
    """Device 0 — the landing spot for single-device programs after a
    mesh-sharded build. The ONE sanctioned ``jax.devices()`` access
    besides the probe: callers (ops/merkle.py) must route through here
    so backend initialization stays observable via probed()."""
    import jax
    devs = jax.devices()
    with _PROBE_LOCK:
        _note_devices_locked(devs)
    return devs[0]


# ------------------------------------------------------------------ helpers

from plenum_tpu.ops import pow2_at_least as _pow2_at_least


def _pow2_at_most(n: int) -> int:
    return 1 << (n.bit_length() - 1) if n >= 1 else 1


def pad_rows(arrays: Sequence, padded: int) -> List[np.ndarray]:
    """Pad the leading axis of every array to `padded` rows by repeating
    row 0. The mesh kernels are row-wise pure, so repeated rows only add
    redundant device work whose results the caller slices off — and
    repeating a REAL row (not zeros) keeps padding on the same code path
    the kernel already validated."""
    out = []
    for a in arrays:
        a = np.asarray(a)
        n = a.shape[0]
        if n == padded:
            out.append(a)
            continue
        reps = np.repeat(a[:1], padded - n, axis=0)
        out.append(np.concatenate([a, reps], axis=0))
    return out


# --------------------------------------------------------------- the mesh

class DeviceMesh:
    """Batch-axis sharding over the host's device mesh.

    Thread-safe: the verify daemon's worker thread and a node's prod
    loop may both dispatch; device enumeration and sharding construction
    are locked, counters are plain int bumps (GIL-atomic enough for
    stats).
    """

    def __init__(self, enabled: Optional[bool] = None,
                 max_devices: Optional[int] = None,
                 shard_min: Optional[int] = None,
                 min_per_device: int = 8,
                 cpu_shard: Optional[bool] = None):
        from plenum_tpu.common.config import Config
        self.enabled = Config.MESH_ENABLED if enabled is None else enabled
        self.max_devices = (Config.MESH_MAX_DEVICES
                            if max_devices is None else max_devices)
        self.shard_min = (Config.MESH_SHARD_MIN
                          if shard_min is None else shard_min)
        self.cpu_shard = (Config.MESH_CPU_SHARD
                          if cpu_shard is None else cpu_shard)
        self.min_per_device = min_per_device
        self.tracer = NullTracer()
        self._lock = threading.Lock()
        self._devices = None          # enumerated + capped device list
        self._sharding = None         # NamedSharding over axis "dp"
        self._replicated = None
        # stats (validator info / bench)
        self.dispatches = 0
        self.sharded_dispatches = 0
        self.passthrough_dispatches = 0
        self.last_batch = 0
        self.last_per_device = 0

    # ------------------------------------------------------ device facts

    def _init_devices_locked(self) -> None:
        if self._devices is not None:
            return
        # a backend that cannot be initialised raises, exactly like
        # probe_platform: "no backend" must never read as "one device"
        import jax
        devs = list(jax.devices())
        with _PROBE_LOCK:
            _note_devices_locked(devs)
        cap = self.max_devices if self.max_devices else len(devs)
        n = max(1, min(len(devs), cap))
        # power-of-two device counts keep per-device buckets divisible
        # and match real TPU topologies; a 6-chip cap uses 4
        self._devices = devs[:_pow2_at_most(n)]

    @property
    def devices(self) -> list:
        with self._lock:
            self._init_devices_locked()
            return list(self._devices)

    @property
    def n_devices(self) -> int:
        with self._lock:
            self._init_devices_locked()
            return max(1, len(self._devices))

    def reset_devices(self) -> None:
        """Re-enumerate on next use (tests / reconfiguration)."""
        with self._lock:
            self._devices = None
            self._sharding = None
            self._replicated = None

    # -------------------------------------------------------- shardings

    def sharding(self):
        """NamedSharding that splits the leading (batch) axis over the
        mesh and replicates every other axis."""
        with self._lock:
            self._init_devices_locked()
            if self._sharding is None:
                from jax.sharding import Mesh, NamedSharding, PartitionSpec
                mesh = Mesh(np.array(self._devices), axis_names=("dp",))
                self._sharding = NamedSharding(mesh, PartitionSpec("dp"))
                self._replicated = NamedSharding(mesh, PartitionSpec())
            return self._sharding

    def replicated(self):
        """NamedSharding that replicates an array on every mesh device
        (read-shared operands of index-sharded gathers)."""
        self.sharding()
        return self._replicated

    # -------------------------------------------------------- dispatch

    def should_shard(self, n: int) -> bool:
        """The passthrough gate: shard only when the mesh is enabled,
        more than one chip is present, the batch clears MESH_SHARD_MIN
        (below it, sharding overhead exceeds the win), AND the devices
        are real accelerators — XLA's virtual CPU devices share the
        physical cores, so sharding over them only adds partition
        overhead (the BENCH_r05 merkle-build collapse). Tests and
        dryrun_multichip force the CPU-sharded paths via cpu_shard /
        PLENUM_TPU_MESH_CPU_SHARD=1 (env, so spawned node processes
        inherit it)."""
        if not self.enabled or n < self.shard_min:
            return False
        if self.n_devices <= 1:
            return False
        return (is_accelerator() or self.cpu_shard
                or os.environ.get("PLENUM_TPU_MESH_CPU_SHARD") == "1")

    def padded_size(self, n: int, min_per_device: Optional[int] = None
                    ) -> int:
        """Smallest n_devices × (power-of-two per-device bucket) that
        holds n rows — every batch size inside a bucket shares ONE
        compiled SPMD executable, so variable queue depths never hit a
        fresh XLA compile mid-run."""
        d = self.n_devices
        mpd = self.min_per_device if min_per_device is None \
            else min_per_device
        per = _pow2_at_least(max(mpd, -(-n // d)))
        return per * d

    def put_sharded(self, arrays: Sequence) -> list:
        """Place already-padded arrays with the batch-axis sharding."""
        import jax
        sh = self.sharding()
        return [jax.device_put(a, sh) for a in arrays]

    def dispatch(self, fn: Callable, arrays: Sequence, n: Optional[int]
                 = None, label: str = "mesh_dispatch"):
        """Shard `arrays` (leading axis already padded to padded_size)
        over the mesh and launch the jitted `fn` asynchronously.

        Returns fn's un-awaited output arrays — JAX dispatch is async,
        so the caller overlaps host work with all chips' round trips
        and materializes later (np.asarray). The span + counters feed
        the flight recorder: per-device batch size is the number that
        says whether the mesh actually spread the work."""
        b = int(np.shape(arrays[0])[0])
        d = self.n_devices
        per = b // d
        # lane accounting: every padded row is a launched-but-wasted
        # device lane; the (padded, devices) pair is the SPMD compile
        # shape, so a new one is a compile event
        _telemetry.get_seam_hub().record_launch(
            _telemetry.SEAM_MESH, b if n is None else n, b, shape=(b, d))
        with self.tracer.span(label, CAT_DEVICE, n=b if n is None else n,
                              padded=b, devices=d, per_device=per):
            outs = fn(*self.put_sharded(arrays))
        self.dispatches += 1
        self.sharded_dispatches += 1
        self.last_batch = b
        self.last_per_device = per
        self.tracer.counter("mesh_devices", d)
        self.tracer.counter("mesh_per_device_batch", per)
        return outs

    def note_passthrough(self, n: int) -> None:
        """Bookkeeping for a dispatch that took the single-device path
        (counted so validator info shows the gate working)."""
        self.dispatches += 1
        self.passthrough_dispatches += 1
        self.last_batch = n

    # ------------------------------------------------------------ stats

    def stats(self) -> dict:
        """Snapshot for ValidatorNodeInfoTool / bench. Never initializes
        a backend: device facts appear only once something already
        enumerated the mesh (or probed the platform)."""
        out = {
            "enabled": self.enabled,
            "max_devices": self.max_devices,
            "shard_min": self.shard_min,
            "cpu_shard": self.cpu_shard,
            "dispatches": self.dispatches,
            "sharded_dispatches": self.sharded_dispatches,
            "passthrough_dispatches": self.passthrough_dispatches,
            "last_batch": self.last_batch,
            "last_per_device_batch": self.last_per_device,
        }
        if self._devices is not None:
            out["n_devices"] = len(self._devices)
        if probed():
            out["platform"] = _PROBE["platform"]
            out["device_kind"] = _PROBE["device_kind"]
            out["host_device_count"] = _PROBE["device_count"]
        return out


# ----------------------------------------------------------- pipelining

class MeshPipeline:
    """Depth-bounded dispatch/collect streamer over mesh dispatches —
    the same per-device double-buffering shape as
    ops/merkle.ProofPipeline: up to `depth` sharded launches stay in
    flight, so every chip's next batch is already enqueued while the
    host materializes the previous results. Used by the MULTICHIP
    harness (__graft_entry__) and available to any dispatch/collect
    pair (the merkle and verify seams keep their specialized
    pipelines)."""

    def __init__(self, dispatch_fn: Callable, collect_fn: Callable,
                 depth: int = 2, tracer=None):
        self._dispatch = dispatch_fn
        self._collect = collect_fn
        self._depth = max(1, depth)
        self._tracer = tracer or NullTracer()

    def stream(self, batches):
        from collections import deque
        pending = deque()
        tracer = self._tracer
        for batch in batches:
            with tracer.span("mesh_pipe_dispatch", CAT_DEVICE):
                pending.append(self._dispatch(batch))
            tracer.counter("mesh_pipe_inflight", len(pending))
            if len(pending) >= self._depth:
                with tracer.span("mesh_pipe_collect", CAT_DEVICE):
                    out = self._collect(pending.popleft())
                yield out
        while pending:
            with tracer.span("mesh_pipe_collect", CAT_DEVICE):
                out = self._collect(pending.popleft())
            yield out

    def run(self, batches) -> list:
        return list(self.stream(batches))


# ----------------------------------------------------- process singleton

_MESH: Optional[DeviceMesh] = None
_MESH_LOCK = threading.Lock()


def get_mesh() -> DeviceMesh:
    """The process-wide mesh every dispatch seam consults. Constructed
    lazily from Config class defaults; node bootstrap / bench / tests
    reconfigure it via configure()/configure_from()."""
    global _MESH
    with _MESH_LOCK:
        if _MESH is None:
            _MESH = DeviceMesh()
        return _MESH


def configure(enabled: Optional[bool] = None,
              max_devices: Optional[int] = None,
              shard_min: Optional[int] = None,
              tracer=None,
              cpu_shard: Optional[bool] = None) -> DeviceMesh:
    """Reconfigure the process-wide mesh. Changing the device cap resets
    the enumeration (and compiled-sharding cache) so the next dispatch
    sees the new mesh shape."""
    m = get_mesh()
    if enabled is not None:
        m.enabled = enabled
    if shard_min is not None:
        m.shard_min = shard_min
    if cpu_shard is not None:
        m.cpu_shard = cpu_shard
    if max_devices is not None and max_devices != m.max_devices:
        m.max_devices = max_devices
        m.reset_devices()
    if tracer is not None:
        m.tracer = tracer
    return m


def configure_from(config) -> DeviceMesh:
    """Apply a Config instance's MESH_* knobs (node bootstrap seam)."""
    return configure(
        enabled=getattr(config, "MESH_ENABLED", None),
        max_devices=getattr(config, "MESH_MAX_DEVICES", None),
        shard_min=getattr(config, "MESH_SHARD_MIN", None),
        cpu_shard=getattr(config, "MESH_CPU_SHARD", None))


def mesh_stats() -> dict:
    """Stats for status dumps; safe to call from paths that must never
    initialize a device runtime. Always carries the process's kernel
    step-down counts — the one thing a status reader must not miss."""
    with _MESH_LOCK:
        m = _MESH
    out = m.stats() if m is not None else {"enabled": None}
    out["step_downs"] = step_down_counts()
    return out
