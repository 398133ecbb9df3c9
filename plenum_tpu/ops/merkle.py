"""Device-resident merkle tree — the TPU-native proof/build engine.

The reference hashes merkle nodes one at a time through OpenSSL
(ledger/tree_hasher.py:7). The host-side CompactMerkleTree batches leaf
hashing through ops/sha256, but proofs and rebuilds were host work. This
module keeps the WHOLE tree on device and serves production shapes:

 - `build` runs ONE fused jit: leaf SHA-256, then every interior level
   derived on device (node blocks are packed from digest pairs with pure
   uint32 shifts — no host byte juggling).
 - `append_leaf_hashes` is the incremental path: device-resident level
   tails grow by ~2b hashes for b appended leaves instead of a full
   rebuild — complete RFC 6962 nodes are immutable, so an append only
   ever writes NEW rows. Levels are hashed in FUSED groups of
   Config.MERKLE_FUSED_LEVELS per dispatch (hash level i, pair
   in-kernel, hash level i+1, …), so dispatches-per-append drop from
   O(log n) to O(log n / K).
 - the SHA-256 compression itself routes per batch size
   (ops/sha256.select_backend): the fused Pallas kernel on
   accelerators, the cache-tiled XLA expression on the CPU backend,
   the plain expression for small levels — one static decision per
   build/append jit, byte-identical outputs on every path.
 - `dispatch_proof_batch`/`collect_proof_batch` serve RFC 6962
   inclusion proofs for ANY tree size (ragged included): an inclusion
   proof decomposes into the leaf's path inside its full aligned
   frontier subtree (a plain sibling gather, heights 0..h_j-1) plus one
   fold of the frontier subtrees to its right and the roots of those to
   its left — all O(log n) host joins shared across the batch.
 - the sibling gather is FUSED with big-endian byte packing in one jit,
   so a proof batch leaves the device as a single dense uint8 buffer
   already in wire byte order (the download plus a host-side byteswap
   was the bottleneck of the last recorded run, BENCH_r05: 0.66x the
   host proof floor).
 - `ProofPipeline` double-buffers dispatch/collect across batches so
   the next gather overlaps the current download.

Top levels (few nodes, shared by every proof) are mirrored to host
LAZILY — first proof batch after a build/growth pays one download; the
mirror then grows incrementally with each append, so per-batch device
traffic carries only the huge bottom levels.

Multi-chip (ops/mesh.py): builds clearing the mesh gate hash their
leaves and interior levels as ONE batch-axis-sharded SPMD program over
every chip (the leaf level dominates the hash count), then land the
level arrays back on the default device so the incremental append and
mirror paths are unchanged. Proof gathers shard the INDEX axis — each
proof row is an independent sibling gather — against bottom levels
replicated across the mesh (memoized per level array, invalidated by
appends; serving is read-heavy, so replication amortizes over batches).
"""
from __future__ import annotations

import functools
import logging
import os
from collections import deque
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from plenum_tpu.observability.tracing import CAT_DEVICE
from plenum_tpu.observability.telemetry import (
    SEAM_MERKLE_APPEND as _TM_SEAM_APPEND,
    SEAM_MERKLE_BUILD as _TM_SEAM_BUILD,
    get_seam_hub as _get_telemetry)
from plenum_tpu.ops import pow2_at_least as _pow2_at_least
from plenum_tpu.ops.sha256 import (
    _sha256_blocks, compress_blocks, digests_to_array, pad_messages,
    select_backend)

logger = logging.getLogger(__name__)

_async_copy_noted = False


def _start_async_copy(arr):
    """Begin the D2H copy for `arr` so a later np.asarray doesn't block.
    Narrow except: only the backend's not-supported signals are
    swallowed (logged once at debug); anything else is a real error."""
    global _async_copy_noted
    try:
        arr.copy_to_host_async()
    except (AttributeError, NotImplementedError) as exc:
        if not _async_copy_noted:
            _async_copy_noted = True
            logger.debug("async device->host copy unavailable (%s); "
                         "proof collects will block on transfer", exc)


def _get_mesh():
    from plenum_tpu.ops import mesh as mesh_mod
    return mesh_mod.get_mesh()


def _to_default_device(levels):
    """Land (possibly mesh-sharded) level arrays on the default device:
    the append/mirror/read paths dispatch single-device programs, and
    jit rejects operands committed to different device sets — one
    device-to-device copy after a sharded build keeps every downstream
    path byte-identical and oblivious."""
    import jax
    from plenum_tpu.ops import mesh as mesh_mod
    dev = mesh_mod.default_device()
    return [jax.device_put(lv, dev) for lv in levels]


@functools.partial(jax.jit, static_argnames=("msg_len", "nblocks"))
def _pack_uniform(raw, msg_len: int, nblocks: int):
    """[B, msg_len] u8 → [B, nblocks, 16] u32 SHA-padded words, entirely
    on device — uploading raw bytes instead of padded u32 words cuts the
    host→device transfer ~2.5× for typical txn-sized leaves."""
    b = raw.shape[0]
    out = jnp.zeros((b, nblocks * 64), dtype=jnp.uint8)
    out = out.at[:, :msg_len].set(raw)
    out = out.at[:, msg_len].set(jnp.uint8(0x80))
    bitlen = (msg_len * 8).to_bytes(8, "big")
    end = ((msg_len + 9 + 63) // 64) * 64
    out = out.at[:, end - 8:end].set(
        jnp.asarray(np.frombuffer(bitlen, dtype=np.uint8)))
    w = out.reshape(b, nblocks, 16, 4).astype(jnp.uint32)
    return (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) \
        | w[..., 3]


def _node_blocks(left, right):
    """[B,8],[B,8] u32 digests → [B,2,16] u32 message blocks for
    H(0x01 || left || right) (65 bytes, SHA-padded)."""
    l8 = left >> jnp.uint32(8)
    lc = (left & jnp.uint32(0xff)) << jnp.uint32(24)
    r8 = right >> jnp.uint32(8)
    rc = (right & jnp.uint32(0xff)) << jnp.uint32(24)
    w0 = jnp.uint32(0x01 << 24) | l8[:, 0]
    ws = [w0]
    for i in range(1, 8):
        ws.append(lc[:, i - 1] | l8[:, i])
    ws.append(lc[:, 7] | r8[:, 0])
    for i in range(1, 8):
        ws.append(rc[:, i - 1] | r8[:, i])
    w16 = rc[:, 7] | jnp.uint32(0x80 << 16)
    zeros = jnp.zeros_like(w0)
    block1 = [w16] + [zeros] * 14 + [
        jnp.broadcast_to(jnp.uint32(65 * 8), w0.shape)]
    words = jnp.stack(ws + block1, axis=1)  # [B, 32]
    return words.reshape(words.shape[0], 2, 16)


def _hash_pairs(cur, backend: str = "plain"):
    """[2m, 8] u32 digests → [m, 8] parent digests (device). The
    compression routes per-level: a batch big enough for the Pallas
    kernel / CPU cache tiling takes it, the small top levels keep the
    plain expression (compress_blocks re-checks the static shape)."""
    blocks = _node_blocks(cur[0::2], cur[1::2])
    nv = jnp.full((blocks.shape[0],), 2, dtype=jnp.int32)
    return compress_blocks(blocks, nv, 2, backend)


@functools.partial(jax.jit, static_argnames=("nblocks", "depth",
                                             "backend"))
def _build_levels(leaf_words, leaf_nvalid, nblocks: int, depth: int,
                  backend: str = "plain"):
    """leaf_words [P, nblocks, 16] → tuple of P, P/2, … 1 digest
    arrays ([*, 8] u32), all resident on device. ONE jit covers leaf
    hashing and every interior level — `backend` (static) decides the
    compression lowering (Pallas kernel / CPU tiles / plain XLA) and
    rides the mesh-sharded dispatch unchanged."""
    cur = compress_blocks(leaf_words, leaf_nvalid, nblocks, backend)
    levels = [cur]
    for _ in range(depth):
        cur = _hash_pairs(cur, backend)
        levels.append(cur)
    return tuple(levels)


@functools.partial(jax.jit, static_argnames=("depth", "backend"))
def _build_levels_from_digest_bytes(arr_u8, depth: int,
                                    backend: str = "plain"):
    """[P, 32] u8 big-endian leaf DIGESTS → device level tuple (no leaf
    hashing — the resync path feeds hash-store contents straight in)."""
    w = arr_u8.reshape(arr_u8.shape[0], 8, 4).astype(jnp.uint32)
    cur = (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) \
        | w[..., 3]
    levels = [cur]
    for _ in range(depth):
        cur = _hash_pairs(cur, backend)
        levels.append(cur)
    return tuple(levels)


@jax.jit
def _digest_words(arr_u8):
    """[B, 32] u8 big-endian digest bytes → [B, 8] u32 words."""
    w = arr_u8.reshape(arr_u8.shape[0], 8, 4).astype(jnp.uint32)
    return (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) \
        | w[..., 3]


@jax.jit
def _place(level, vals, start, count):
    """Scatter vals[0:count] into level[start:start+count]; rows past
    `count` are dropped (vals is bucket-padded to bound recompiles)."""
    ar = jnp.arange(vals.shape[0], dtype=jnp.int32)
    idx = jnp.where(ar < count, start + ar, level.shape[0])
    return level.at[idx].set(vals, mode="drop")


@functools.partial(jax.jit, static_argnames=("bucket",))
def _append_level_step(child, parent, p0, cnt, bucket: int):
    """Hash parent nodes [p0, p0+cnt) from consecutive child pairs and
    scatter them into `parent`. Gathers clamp / scatters drop the
    bucket-padding rows, so one compile serves every append of up to
    `bucket` new nodes at this level shape."""
    ar = jnp.arange(bucket, dtype=jnp.int32)
    pi = p0 + ar
    dig = _sha256_blocks(
        _node_blocks(child[2 * pi], child[2 * pi + 1]),
        jnp.full((bucket,), 2, dtype=jnp.int32), 2)
    idx = jnp.where(ar < cnt, pi, parent.shape[0])
    return parent.at[idx].set(dig, mode="drop"), dig


@functools.partial(jax.jit, static_argnames=("buckets", "backend"))
def _append_levels_fused(child, parents, p0s, cnts, buckets,
                         backend: str = "plain"):
    """Multi-level tree fusion: hash K=len(parents) consecutive tree
    levels in ONE device dispatch — hash level i's new pairs, scatter
    them into level i+1, pair THOSE in-kernel, hash level i+2, … —
    instead of one dispatch per level. Node hashes are always exactly
    2 blocks (65 bytes), so the whole chain is a fixed-shape uint32
    program; dispatches-per-append drop from O(log n) to
    O(log n / K) (the MTU tree-unit schedule, PAPERS.md).

    child is the lowest (already-updated) level; parents are the K
    levels above it, p0s/cnts the per-level write windows (dynamic —
    one compile serves every append with these array shapes and
    `buckets`). Per level the gather clamps / the scatter drops the
    bucket-padding rows, exactly like _append_level_step, so the
    padding rows never corrupt parent state even though their hashes
    are garbage. Returns the updated parent arrays plus each level's
    bucket-padded digests (for hash-store persistence / mirrors)."""
    outs = []
    digs = []
    cur = child
    for j, (parent, bucket) in enumerate(zip(parents, buckets)):
        ar = jnp.arange(bucket, dtype=jnp.int32)
        pi = p0s[j] + ar
        dig = compress_blocks(
            _node_blocks(cur[2 * pi], cur[2 * pi + 1]),
            jnp.full((bucket,), 2, dtype=jnp.int32), 2, backend)
        idx = jnp.where(ar < cnts[j], pi, parent.shape[0])
        cur = parent.at[idx].set(dig, mode="drop")
        outs.append(cur)
        digs.append(dig)
    return tuple(outs), tuple(digs)


@functools.partial(jax.jit, static_argnames=("rows",))
def _grown(old, rows: int):
    pad = jnp.zeros((rows - old.shape[0], 8), dtype=jnp.uint32)
    return jnp.concatenate([old, pad], axis=0)


@jax.jit
def _gather_pack(levels, indices):
    """FUSED sibling-gather + big-endian byte packing: for each level h
    in the tuple, gather digests at (m >> h) ^ 1 and emit ONE dense
    uint8 buffer [k, len(levels)*32] — the proof batch leaves the
    device already in wire byte order, so collect is a plain reshape
    instead of a host-side astype('>u4') byteswap over megabytes."""
    cols = []
    for h, level in enumerate(levels):
        sib = (indices >> h) ^ 1
        cols.append(level[sib])
    g = jnp.stack(cols, axis=1)  # [k, n_low, 8] u32
    b = jnp.stack([(g >> 24) & 0xff, (g >> 16) & 0xff,
                   (g >> 8) & 0xff, g & 0xff], axis=-1)
    return b.astype(jnp.uint8).reshape(g.shape[0], len(levels) * 32)


@jax.jit
def _read_row(level, idx):
    return jax.lax.dynamic_slice(level, (idx, 0), (1, 8))


class DeviceMerkleTree:
    """An RFC 6962 tree whose node hashes live in device memory.

    Supports ANY size (ragged included) for builds, incremental appends
    and inclusion-proof batches. Complete nodes are immutable, so the
    level arrays only ever grow; capacity doubles like a vector to
    bound reallocation and recompiles.
    """

    # levels at or under this node count are mirrored to host (lazily,
    # on first proof batch; then kept fresh incrementally by appends)
    # so proof batches never re-download them; only the huge bottom
    # levels are gathered per batch: what a batch downloads is what it
    # costs, so per-batch bytes are kept to the bottom levels.
    _TOP_CACHE = int(os.environ.get("PLENUM_MERKLE_TOP_CACHE", "262144"))

    def __init__(self, hasher=None):
        from plenum_tpu.ledger.tree_hasher import TreeHasher
        from plenum_tpu.observability.tracing import NullTracer
        self.hasher = hasher or TreeHasher()
        self._levels: Optional[List] = None  # device arrays, leaves first
        self._size = 0
        self._cap = 0
        self._mirror = {}          # height -> host uint8 [cap>>h, 32]
        self._mirror_count = {}    # height -> mirrored complete prefix
        self._froot_cache = {}     # proof size n -> frontier root bytes
        self._repl_cache = {}      # height -> (replica, snap rows, sharding)
        self.tracer = NullTracer()
        # cumulative device-IO counters (never reset with the tree):
        # the flight-recorder spans carry the same events; these make
        # "no re-materialization" assertable in tests/bench without a
        # tracer attached
        self.dispatch_stats = {
            "build_dispatches": 0,        # fused build jits launched
            "append_dispatches": 0,       # _place + level-group steps
            "gather_dispatches": 0,       # per-proof-batch low gathers
            "mirror_level_downloads": 0,  # full-level host downloads
            "mirror_rows_downloaded": 0,
            "replica_broadcasts": 0,      # mesh replications of a level
            "row_reads": 0,               # single-row device reads
        }

    def attach_tracer(self, tracer) -> None:
        """Feed this tree's dispatch spans to a flight recorder (the
        serving ProofPipeline carries its own tracer; this one covers
        builds/appends/mirror downloads)."""
        from plenum_tpu.observability.tracing import NullTracer
        self.tracer = tracer or NullTracer()

    # ------------------------------------------------------------ state

    @property
    def tree_size(self) -> int:
        return self._size

    @property
    def _padded(self) -> int:
        # kept for introspection/back-compat: capacity == padded size
        return self._cap if self._size else 0

    def reset(self):
        self._levels, self._size, self._cap = None, 0, 0
        self._mirror, self._mirror_count, self._froot_cache = {}, {}, {}
        self._repl_cache = {}

    def _depth(self) -> int:
        return self._cap.bit_length() - 1 if self._cap else 0

    def _n_low(self) -> int:
        """First host-mirrored height; heights below it are gathered on
        device per proof batch."""
        h = 0
        while h < self._depth() and (self._cap >> h) > self._TOP_CACHE:
            h += 1
        return h

    def _invalidate(self):
        self._froot_cache = {}

    # ----------------------------------------------------------- builds

    def _run_build(self, launch, padded: int, key: tuple, shard: bool):
        """Backend-routed build launch: `launch(backend)` returns the
        level tuple. A Pallas build the compiler refuses raises
        (program bug); its execution is proven once per build-shape
        key, and a launch that dies on the device is a counted
        step-down rebuilt through the XLA expression
        (ops/mesh.launch_survives — the policy every Pallas seam
        shares).

        Mesh-sharded builds take the XLA expression: the SPMD
        partitioner splits it over the batch axis with no code change,
        whereas a Mosaic kernel cannot be partitioned automatically
        (the TPU compiler refuses the sharded fused build with the
        Pallas backend — same choice as the ed25519 mesh path)."""
        backend = select_backend(padded)
        if shard and backend == "pallas":
            backend = "plain"
        levels = launch(backend)
        self.dispatch_stats["build_dispatches"] += 1
        if backend == "pallas":
            from plenum_tpu.ops import mesh as mesh_mod
            from plenum_tpu.ops import sha256_pallas as sp
            if not mesh_mod.launch_survives(sp.PALLAS_ENV, ("build",) + key,
                                            levels, "pallas sha256 build"):
                return self._run_build(launch, padded, key, shard)
        return levels

    def build(self, leaves: Sequence[bytes]) -> bytes:
        """Hash `leaves` and every interior level on device; → root."""
        n = len(leaves)
        if n == 0:
            self.reset()
            return self.hasher.hash_empty()
        padded = _pow2_at_least(n)
        msgs = [b"\x00" + d for d in leaves]
        if padded > n:
            # pad garbage only ever mixes into INCOMPLETE nodes, which
            # no read path touches
            msgs = msgs + [msgs[-1]] * (padded - n)
        depth = padded.bit_length() - 1
        ln0 = len(msgs[0])
        dm = _get_mesh()
        # builds shard the tree's power-of-two capacity as-is (no extra
        # row padding), so the capacity must divide over the mesh —
        # with a sub-device-count MESH_SHARD_MIN the gate can pass on a
        # tree smaller than the device count, where device_put would
        # reject the sharding
        shard = dm.should_shard(padded) and padded % dm.n_devices == 0
        if all(len(m) == ln0 for m in msgs):
            # uniform leaves: upload raw bytes, pad/pack on device
            nblocks = 1
            while nblocks * 64 < ln0 + 9:
                nblocks *= 2
            raw = np.frombuffer(b"".join(msgs), dtype=np.uint8) \
                .reshape(padded, ln0)
            nv_host = np.full((padded,), (ln0 + 9 + 63) // 64,
                              dtype=np.int32)
            if shard:
                raw_dev, nvalid = dm.put_sharded([raw, nv_host])
            else:
                raw_dev, nvalid = jnp.asarray(raw), jnp.asarray(nv_host)
            words = _pack_uniform(raw_dev, ln0, nblocks)
        else:
            host_words, host_nvalid, nblocks = pad_messages(msgs)
            if shard:
                words, nvalid = dm.put_sharded([host_words, host_nvalid])
            else:
                words = jnp.asarray(host_words)
                nvalid = jnp.asarray(host_nvalid)
        def launch(be):
            _get_telemetry().record_launch(
                _TM_SEAM_BUILD, n, padded, shape=(padded, nblocks))
            if shard:
                return _to_default_device(dm.dispatch(
                    lambda w, nv: _build_levels(w, nv, nblocks, depth, be),
                    [words, nvalid], n=padded))
            dm.note_passthrough(padded)
            with self.tracer.span("merkle_build_dispatch", CAT_DEVICE,
                                  n=padded):
                return _build_levels(words, nvalid, nblocks, depth, be)

        levels = self._run_build(launch, padded,
                                 ("leaves", nblocks, depth), shard)
        self._levels = list(levels)
        self._size, self._cap = n, padded
        self._mirror, self._mirror_count, self._froot_cache = {}, {}, {}
        self._repl_cache = {}
        return self.root_hash

    def build_from_leaf_hashes(self, digests) -> bytes:
        """Build the device levels from precomputed RFC 6962 LEAF
        DIGESTS (list of 32-byte bytes or uint8 [n, 32]) — the resync
        path from a hash store: no leaf hashing, one fused dispatch."""
        arr = self._digest_rows(digests)
        n = arr.shape[0]
        if n == 0:
            self.reset()
            return self.hasher.hash_empty()
        padded = _pow2_at_least(n)
        if padded > n:
            arr = np.concatenate(
                [arr, np.zeros((padded - n, 32), dtype=np.uint8)])
        depth = padded.bit_length() - 1
        dm = _get_mesh()
        shard = dm.should_shard(padded) and padded % dm.n_devices == 0

        def launch(be):
            _get_telemetry().record_launch(
                _TM_SEAM_BUILD, n, padded, shape=(padded, 1))
            if shard:
                return _to_default_device(dm.dispatch(
                    lambda a: _build_levels_from_digest_bytes(a, depth, be),
                    [arr], n=padded))
            dm.note_passthrough(padded)
            with self.tracer.span("merkle_build_dispatch", CAT_DEVICE,
                                  n=padded):
                return _build_levels_from_digest_bytes(
                    jnp.asarray(arr), depth, be)

        levels = self._run_build(launch, padded, ("digests", depth),
                                 shard)
        self._levels = list(levels)
        self._size, self._cap = n, padded
        self._mirror, self._mirror_count, self._froot_cache = {}, {}, {}
        self._repl_cache = {}
        return self.root_hash

    @staticmethod
    def _digest_rows(digests) -> np.ndarray:
        if isinstance(digests, np.ndarray):
            return np.ascontiguousarray(digests, dtype=np.uint8) \
                .reshape(-1, 32)
        return np.frombuffer(b"".join(digests), dtype=np.uint8) \
            .reshape(-1, 32).copy()

    # ------------------------------------------------ incremental append

    def _ensure_capacity(self, n: int):
        if self._levels is None:
            cap = _pow2_at_least(max(n, 1))
            self._cap = cap
            self._levels = [jnp.zeros((cap >> h, 8), dtype=jnp.uint32)
                            for h in range(cap.bit_length())]
            self._mirror, self._mirror_count = {}, {}
            self._repl_cache = {}
            return
        if n <= self._cap:
            return
        new_cap = self._cap
        while new_cap < n:
            new_cap *= 2
        levels = [_grown(lv, new_cap >> h)
                  for h, lv in enumerate(self._levels)]
        for h in range(len(levels), new_cap.bit_length()):
            levels.append(jnp.zeros((new_cap >> h, 8), dtype=jnp.uint32))
        self._levels, self._cap = levels, new_cap
        # complete node rows are immutable, so growth PRESERVES the
        # host mirrors: grow each kept level's array (zero rows for
        # nodes not yet complete) and keep its mirrored-prefix count.
        # Flushing here (the PR-2/PR-4 behavior) made the first proof
        # batch after every capacity doubling re-download the whole
        # mirrored top of the tree — and build() always fills capacity
        # exactly, so the FIRST append after any build paid it (the
        # r05 audit-path regression suspect). Levels that fall below
        # the new _n_low() move to the per-batch device gather.
        n_low = self._n_low()
        for h in list(self._mirror):
            if h < n_low:
                del self._mirror[h]
                self._mirror_count.pop(h, None)
                continue
            rows = new_cap >> h
            old = self._mirror[h]
            if old.shape[0] < rows:
                grown = np.zeros((rows, 32), dtype=np.uint8)
                grown[:old.shape[0]] = old
                self._mirror[h] = grown
        # replica snapshots survive too: _replicated_level re-checks
        # row needs against each snapshot's complete prefix

    def append_leaf_hashes(self, digests, return_nodes: bool = False):
        """Append leaf DIGESTS incrementally: ~2b device hashes for b
        leaves, no rebuild. Levels are hashed in FUSED groups of
        Config.MERKLE_FUSED_LEVELS per device dispatch
        (_append_levels_fused: hash level i, pair in-kernel, hash
        level i+1, …), so an append costs 1 + ceil(levels/K)
        dispatches instead of 1 + levels.

        With return_nodes=True, returns [(height, first_node_index,
        uint8 [cnt, 32])] for every newly COMPLETE node — exactly the
        (start, height) entries a CompactMerkleTree hash store persists
        for the same append."""
        from plenum_tpu.common.config import Config
        arr = self._digest_rows(digests)
        b = arr.shape[0]
        if b == 0:
            return [] if return_nodes else None
        n0 = self._size
        n1 = n0 + b
        self._ensure_capacity(n1)
        bucket0 = _pow2_at_least(b)
        if bucket0 > b:
            arr_up = np.zeros((bucket0, 32), dtype=np.uint8)
            arr_up[:b] = arr
        else:
            arr_up = arr
        _tm_hub = _get_telemetry()
        _tm_hub.record_launch(_TM_SEAM_APPEND, b, bucket0, shape=bucket0)
        with self.tracer.span("merkle_append_dispatch", CAT_DEVICE,
                              levels=0, n=b):
            self._levels[0] = _place(
                self._levels[0], _digest_words(jnp.asarray(arr_up)),
                n0, b)
        self.dispatch_stats["append_dispatches"] += 1
        news = [(0, n0, b, None)]  # level-0 digests are the host input
        fuse = max(1, int(getattr(Config, "MERKLE_FUSED_LEVELS", 1)))
        h = 0
        while True:
            group = []   # [(level, p0, cnt)] for up to `fuse` levels
            while len(group) < fuse:
                level = h + len(group) + 1
                p0 = n0 >> level
                cnt = (n1 >> level) - p0
                if cnt == 0:
                    break
                group.append((level, p0, cnt))
            if not group:
                break
            if len(group) == 1:
                level, p0, cnt = group[0]
                _tm_hub.record_launch(_TM_SEAM_APPEND, cnt,
                                      _pow2_at_least(cnt),
                                      shape=_pow2_at_least(cnt))
                with self.tracer.span("merkle_append_dispatch",
                                      CAT_DEVICE, levels=1, n=cnt):
                    self._levels[level], dig = _append_level_step(
                        self._levels[level - 1], self._levels[level],
                        p0, cnt, _pow2_at_least(cnt))
                digs = (dig,)
            else:
                parents = tuple(self._levels[lv] for lv, _, _ in group)
                buckets = tuple(_pow2_at_least(c) for _, _, c in group)
                p0s = jnp.asarray([p for _, p, _ in group],
                                  dtype=jnp.int32)
                cnts = jnp.asarray([c for _, _, c in group],
                                   dtype=jnp.int32)
                _tm_hub.record_launch(_TM_SEAM_APPEND,
                                      sum(c for _, _, c in group),
                                      sum(buckets), shape=buckets)
                with self.tracer.span("merkle_append_dispatch",
                                      CAT_DEVICE, levels=len(group),
                                      n=int(group[0][2])):
                    outs, digs = _append_levels_fused(
                        self._levels[h], parents, p0s, cnts, buckets,
                        select_backend(buckets[0]))
                for (level, _, _), out_lv in zip(group, outs):
                    self._levels[level] = out_lv
            self.dispatch_stats["append_dispatches"] += 1
            for (level, p0, cnt), dig in zip(group, digs):
                news.append((level, p0, cnt, dig))
            h += len(group)
        self._size = n1
        self._invalidate()
        out = []
        for height, pos, cnt, dig in news:
            mirrored = height in self._mirror
            if not (return_nodes or mirrored):
                continue
            rows = arr[:b] if dig is None \
                else digests_to_array(np.asarray(dig))[:cnt]
            if mirrored and self._mirror_count.get(height, 0) == pos:
                self._mirror[height][pos:pos + cnt] = rows
                self._mirror_count[height] = pos + cnt
            if return_nodes:
                out.append((height, pos, rows))
        return out if return_nodes else None

    # ---------------------------------------------------------- mirrors

    def _ensure_mirrors(self):
        """Materialize/refresh the host mirror of every top level (node
        count <= _TOP_CACHE). One full-level download per build/growth;
        appends keep the mirror fresh incrementally after that."""
        for h in range(self._n_low(), self._depth() + 1):
            want = self._size >> h
            if self._mirror_count.get(h, 0) < want or h not in self._mirror:
                with self.tracer.span("merkle_mirror_download",
                                      CAT_DEVICE, height=h,
                                      rows=int(self._levels[h].shape[0])):
                    self._mirror[h] = digests_to_array(
                        np.asarray(self._levels[h]))
                self._mirror_count[h] = want
                self.dispatch_stats["mirror_level_downloads"] += 1
                self.dispatch_stats["mirror_rows_downloaded"] += \
                    int(self._levels[h].shape[0])

    # ------------------------------------------------------------- reads

    def _node_bytes(self, height: int, index: int) -> bytes:
        mc = self._mirror_count.get(height, 0)
        if index < mc:
            return self._mirror[height][index].tobytes()
        self.dispatch_stats["row_reads"] += 1
        row = np.asarray(_read_row(self._levels[height],
                                   jnp.int32(index)))
        return digests_to_array(row).tobytes()

    @staticmethod
    def _frontier_of(n: int) -> List[Tuple[int, int]]:
        """Full aligned subtrees of a size-n tree: [(height, node_idx)]
        left to right (descending height)."""
        return [(h, (n >> h) - 1)
                for h in range(n.bit_length() - 1, -1, -1)
                if (n >> h) & 1]

    def _frontier_roots(self, n: int) -> List[bytes]:
        roots = self._froot_cache.get(n)
        if roots is None:
            roots = [self._node_bytes(h, idx)
                     for h, idx in self._frontier_of(n)]
            self._froot_cache[n] = roots
        return roots

    @property
    def root_hash(self) -> bytes:
        if self._size == 0:
            return self.hasher.hash_empty()
        roots = self._frontier_roots(self._size)
        accum = roots[-1]
        for r in reversed(roots[:-1]):
            accum = self.hasher.hash_children(r, accum)
        return accum

    # ------------------------------------------- proofs (any tree size)

    def _replicated_level(self, h: int, dm, need_rows: int):
        """Mesh-replicated copy of level h, memoized as a SNAPSHOT:
        complete node rows are immutable, so a replica broadcast when
        the level held `snap` complete nodes serves ANY later gather
        whose sibling rows stay inside that prefix — appends no longer
        invalidate it. (The PR-4 memo was keyed on array IDENTITY,
        and appends swap every level array, so serving under a write
        load re-broadcast the whole bottom of the tree across the mesh
        after every append — the read-path re-materialization the r05
        numbers flagged.) A gather needing rows beyond the snapshot
        re-broadcasts and advances it; a mesh reconfiguration rebuilds
        the sharding object, which misses the identity check."""
        import jax
        sh = dm.replicated()
        cached = self._repl_cache.get(h)
        if cached is not None and cached[2] is sh \
                and cached[1] >= need_rows:
            return cached[0]
        repl = jax.device_put(self._levels[h], sh)
        self.dispatch_stats["replica_broadcasts"] += 1
        self._repl_cache[h] = (repl, self._size >> h, sh)
        return repl

    def _gather_low(self, idx_np: np.ndarray, g: int, n: int):
        """Fused sibling-gather+pack of the bottom g levels for one
        proof batch against the size-`n` prefix. Batches clearing the
        mesh gate (ops/mesh.py) shard the INDEX axis over every chip —
        each proof row is an independent gather — against replicated
        level operands; smaller batches keep the single-device
        dispatch."""
        dm = _get_mesh()
        k = int(idx_np.shape[0])
        self.dispatch_stats["gather_dispatches"] += 1
        if dm.should_shard(k):
            levels = []
            for h in range(g):
                # rows this gather USES at level h: every sibling a
                # leaf's path actually keeps lies inside its full
                # aligned subtree, hence inside the size-n prefix's
                # complete nodes (RFC 6962). The raw sibling max can
                # exceed that — collect discards entries at or above a
                # leaf's subtree height — so clamp to the complete
                # count or the snapshot memo could never satisfy a
                # batch touching the ragged tail.
                need = min(int(np.max((idx_np >> h) ^ 1)) + 1,
                           max(1, n >> h))
                levels.append(self._replicated_level(h, dm, need))
            levels = tuple(levels)
            kp = dm.padded_size(k, min_per_device=1)
            idx_p = idx_np if kp == k else np.concatenate(
                [idx_np, np.repeat(idx_np[:1], kp - k)])
            low = dm.dispatch(lambda ix: _gather_pack(levels, ix),
                              [idx_p], n=k)
            return low[:k] if kp != k else low
        dm.note_passthrough(k)
        return _gather_pack(tuple(self._levels[:g]), jnp.asarray(idx_np))

    def dispatch_proof_batch(self, indices: Sequence[int],
                             n: Optional[int] = None):
        """Start the device gather for one RFC 6962 inclusion-proof
        batch against the size-`n` prefix tree (default: current size).
        Pair with collect_proof_batch; interleaving dispatch/collect
        across batches overlaps the next gather with the current
        download (ProofPipeline does this for you)."""
        n = self._size if n is None else n
        if not 0 < n <= self._size:
            raise ValueError("invalid proof-batch size {} for tree of "
                             "size {}".format(n, self._size))
        idx_np = np.asarray(list(indices), dtype=np.int32)
        if idx_np.size and not (0 <= idx_np.min()
                                and int(idx_np.max()) < n):
            raise ValueError("proof index out of range for size "
                             "{}".format(n))
        if n == 1:
            return (idx_np, None, n, 0, [], [])
        self._ensure_mirrors()
        fr = self._frontier_of(n)
        roots = self._frontier_roots(n)
        h0 = fr[0][0]
        g = min(self._n_low(), h0)
        low = None
        if g and idx_np.size:
            low = self._gather_low(idx_np, g, n)
            _start_async_copy(low)
        return (idx_np, low, n, g, fr, roots)

    def collect_proof_batch(self, handle) -> List[List[bytes]]:
        """Await a dispatch_proof_batch handle → per-leaf RFC 6962
        audit paths (leaf-sibling first), byte-identical to
        CompactMerkleTree.inclusion_proofs_batch."""
        idx_np, low, n, g, fr, roots = handle
        k = idx_np.shape[0]
        if n == 1 or k == 0:
            return [[] for _ in range(k)]
        low_np = (np.asarray(low).reshape(k, g, 32)
                  if low is not None else None)
        r = len(fr)
        starts = np.asarray([node_idx << h for h, node_idx in fr],
                            dtype=np.int64)
        js = np.searchsorted(starts, idx_np.astype(np.int64),
                             side="right") - 1
        # MTH of everything right of subtree j, shared across the batch
        sfx: List[Optional[bytes]] = [None] * r
        accum = None
        hash_children = self.hasher.hash_children
        for j in range(r - 1, 0, -1):
            accum = roots[j] if accum is None \
                else hash_children(roots[j], accum)
            sfx[j - 1] = accum
        h0 = fr[0][0]
        # vectorized host joins for the mirrored middle heights
        mirror_cols = {h: self._mirror[h][(idx_np >> h) ^ 1]
                       for h in range(g, h0)}
        out = []
        for i in range(k):
            j = int(js[i])
            hj = fr[j][0]
            path = []
            for h in range(hj):
                if h < g:
                    path.append(low_np[i, h].tobytes())
                else:
                    path.append(mirror_cols[h][i].tobytes())
            if j < r - 1:
                path.append(sfx[j])
            for jj in range(j - 1, -1, -1):
                path.append(roots[jj])
            out.append(path)
        return out

    def inclusion_proofs(self, indices: Sequence[int],
                         n: Optional[int] = None) -> List[List[bytes]]:
        """Audit paths for many leaves of the size-n prefix tree, served
        from device levels — works for ANY n <= tree_size."""
        return self.collect_proof_batch(
            self.dispatch_proof_batch(indices, n))

    # ------------------------------ dense power-of-two fast path (bench)

    def _check_pow2(self):
        if self._size != self._cap:
            raise ValueError("dense audit-path batches need a "
                             "power-of-two tree (got size {}); use "
                             "inclusion_proofs for ragged sizes"
                             .format(self._size))

    def dispatch_path_batch(self, indices: Sequence[int]):
        """Dense power-of-two variant of dispatch_proof_batch: the
        collect returns one uint8[k, depth, 32] buffer."""
        self._check_pow2()
        idx_np = np.asarray(list(indices), dtype=np.int32)
        if self._depth() == 0:
            return (idx_np, None)
        self._ensure_mirrors()
        g = min(self._n_low(), self._depth())
        low = None
        if g:
            low = self._gather_low(idx_np, g, self._size)
            _start_async_copy(low)
        return (idx_np, low)

    def collect_path_batch(self, handle) -> np.ndarray:
        """Await a dispatch_path_batch handle -> uint8[k, depth, 32]
        (leaf-sibling first). The device half arrives already packed
        big-endian (no host byteswap); top levels come from the host
        mirror via vectorized numpy gathers."""
        idx_np, low = handle
        depth = self._depth()
        k = idx_np.shape[0]
        out = np.empty((k, depth, 32), dtype=np.uint8)
        g = min(self._n_low(), depth)
        if low is not None:
            out[:, :g] = np.asarray(low).reshape(k, g, 32)
        for h in range(g, depth):
            out[:, h] = self._mirror[h][(idx_np >> h) ^ 1]
        return out

    def audit_path_batch_array(self, indices) -> np.ndarray:
        """Audit paths for many leaves -> uint8[k, depth, 32] in one
        device gather (bottom levels) + host joins (mirrored top
        levels). Power-of-two sizes only (the dense shape); ragged
        sizes go through inclusion_proofs."""
        return self.collect_path_batch(self.dispatch_path_batch(indices))

    def audit_path_batch(self, indices: Sequence[int]) -> List[List[bytes]]:
        """List-of-lists audit paths for the CURRENT tree size — ragged
        sizes included (RFC 6962 frontier decomposition)."""
        if self._size == self._cap:
            # dense fast path
            if self._depth() == 0:
                return [[] for _ in indices]
            arr = self.audit_path_batch_array(indices)
            k, depth = arr.shape[0], arr.shape[1]
            flat = arr.reshape(k * depth, 32).tobytes()
            mv = memoryview(flat)
            return [[bytes(mv[(i * depth + h) * 32:
                             (i * depth + h + 1) * 32])
                     for h in range(depth)] for i in range(k)]
        return self.inclusion_proofs(indices, self._size)

    def verify_path(self, leaf: bytes, index: int, path: List[bytes],
                    root: bytes) -> bool:
        """Power-of-two fold check (kept for the dense bench path; use
        MerkleVerifier for ragged sizes)."""
        h = self.hasher.hash_leaf(leaf)
        for height, sibling in enumerate(path):
            if (index >> height) & 1:
                h = self.hasher.hash_children(sibling, h)
            else:
                h = self.hasher.hash_children(h, sibling)
        return h == root


class ProofPipeline:
    """Double-buffered proof-batch streamer over a DeviceMerkleTree.

    Generalizes the dispatch/collect interleave into the serving shape
    used by `Ledger.merkleInfoBatch` routing and the catchup rep
    seeder: up to `depth` gathers stay in flight, so the device works
    on batch i+1 while the host drains batch i's download."""

    def __init__(self, tree: DeviceMerkleTree, depth: int = 2,
                 dense: bool = False, tracer=None):
        from plenum_tpu.observability.tracing import NullTracer
        self._tree = tree
        self._depth = max(1, depth)
        self._dense = dense
        self._tracer = tracer or NullTracer()

    def stream(self, batches, n: Optional[int] = None):
        """Yield one result per index batch, in order. Results are
        uint8[k, depth, 32] buffers in dense mode, per-leaf bytes-list
        paths otherwise."""
        if self._dense:
            dispatch = self._tree.dispatch_path_batch
            collect = self._tree.collect_path_batch
        else:
            dispatch = functools.partial(
                self._tree.dispatch_proof_batch, n=n)
            collect = self._tree.collect_proof_batch
        from plenum_tpu.observability.tracing import CAT_DEVICE
        tracer = self._tracer
        pending = deque()
        for batch in batches:
            # dispatch span = host-side launch cost; the in-flight
            # counter shows whether the double-buffering actually keeps
            # the device busy between collects
            with tracer.span("proof_dispatch", CAT_DEVICE, n=len(batch)):
                pending.append(dispatch(batch))
            tracer.counter("proof_inflight", len(pending))
            if len(pending) >= self._depth:
                with tracer.span("proof_collect", CAT_DEVICE):
                    out = collect(pending.popleft())
                yield out
        while pending:
            with tracer.span("proof_collect", CAT_DEVICE):
                out = collect(pending.popleft())
            yield out

    def run(self, indices: Sequence[int], n: Optional[int] = None,
            chunk: int = None) -> List[List[bytes]]:
        """Split one large proof request into pipelined chunks and
        return the concatenated per-leaf paths. chunk defaults from
        Config.MERKLE_DEVICE_PROOF_CHUNK (single-sourced; explicit
        callers — the ledger routing — pass their own)."""
        if chunk is None:
            from plenum_tpu.common.config import Config
            chunk = Config.MERKLE_DEVICE_PROOF_CHUNK
        idx = list(indices)
        if not idx:
            return []
        batches = [idx[i:i + chunk] for i in range(0, len(idx), chunk)]
        out: List[List[bytes]] = []
        for part in self.stream(batches, n=n):
            out.extend(part)
        return out
