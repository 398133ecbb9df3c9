"""Batched SHA-256 in JAX — the merkle tree's TPU hash path.

The reference hashes merkle leaves/nodes one at a time through OpenSSL
(`ledger/tree_hasher.py:7`, `hashlib.sha256`). Here the compression
function is a pure uint32 JAX program, `vmap`-style batched over thousands
of independent messages per device step: leaf hashing during bulk ledger
append/catchup, node hashing level-by-level when rebuilding or batch-proving
(BASELINE.json "1M-leaf audit-path batch" config).

Design notes (TPU-first):
 - All arithmetic is uint32 — native on the VPU; no 64-bit emulation.
 - Message padding happens on host (cheap, data-dependent lengths); the
   device sees fixed-shape [batch, nblocks, 16] uint32 words plus a
   per-message block count, and masks inactive blocks inside a lax.scan.
 - One compiled executable per (nblocks) bucket; callers bucket message
   lengths (merkle node hashes are always exactly 2 blocks: 65 bytes).
 - The 64 rounds run under lax.fori_loop with the schedule computed
   in-loop from a rolling 16-word window, keeping VMEM pressure flat.

Backend routing (`select_backend` / `compress_blocks`): on a real
accelerator, batches of a kernel block or more run the fused Pallas
compression kernel (ops/sha256_pallas.py — schedule + 64 rounds in
VMEM, no op-by-op lowering); on the CPU backend, large batches run the
same XLA expression TILED over cache-sized chunks (`lax.map`) so the
per-op temps stay L2-resident instead of sweeping HBM per op (~2.4x
measured); small batches keep the plain expression. The routing is a
trace-time (static) decision, so ops/merkle's fused build jit rides
whichever backend the caller selected.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from plenum_tpu.observability import telemetry as _tmy
from plenum_tpu.ops import pow2_at_least, scatter_ragged_rows

_IV = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)

_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5,
    0x3956c25b, 0x59f111f1, 0x923f82a4, 0xab1c5ed5,
    0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174,
    0xe49b69c1, 0xefbe4786, 0x0fc19dc6, 0x240ca1cc,
    0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7,
    0xc6e00bf3, 0xd5a79147, 0x06ca6351, 0x14292967,
    0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85,
    0xa2bfe8a1, 0xa81a664b, 0xc24b8b70, 0xc76c51a3,
    0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5,
    0x391c0cb3, 0x4ed8aa4a, 0x5b9cca4f, 0x682e6ff3,
    0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)


def _rotr(x, n):
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _compress(state, block):
    """One SHA-256 compression. state: [..., 8] u32, block: [..., 16] u32."""
    a, b, c, d, e, f, g, h = [state[..., i] for i in range(8)]
    k = jnp.asarray(_K)

    # Rolling 16-word schedule window, advanced one word per round.
    w = jnp.moveaxis(block, -1, 0)  # [16, ...]

    def round_fn(t, carry):
        a, b, c, d, e, f, g, h, w = carry
        wt = w[0]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k[t] + wt
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        # next schedule word from the rolling window
        w1 = w[1]
        w14 = w[14]
        sig0 = _rotr(w1, 7) ^ _rotr(w1, 18) ^ (w1 >> jnp.uint32(3))
        sig1 = _rotr(w14, 17) ^ _rotr(w14, 19) ^ (w14 >> jnp.uint32(10))
        w_next = w[0] + sig0 + w[9] + sig1
        w = jnp.concatenate([w[1:], w_next[None]], axis=0)
        return (t1 + t2, a, b, c, d + t1, e, f, g, w)

    init = (a, b, c, d, e, f, g, h, w)
    a, b, c, d, e, f, g, h, _ = lax.fori_loop(0, 64, round_fn, init)
    out = jnp.stack([a, b, c, d, e, f, g, h], axis=-1)
    return state + out


@functools.partial(jax.jit, static_argnames=("nblocks",))
def _sha256_blocks(blocks, nvalid, nblocks: int):
    """blocks: [B, nblocks, 16] u32; nvalid: [B] i32 → digests [B, 8] u32."""
    state = jnp.broadcast_to(jnp.asarray(_IV), blocks.shape[:-2] + (8,))

    def step(state, xs):
        block, idx = xs
        new = _compress(state, block)
        mask = (idx < nvalid)[..., None]
        return jnp.where(mask, new, state), None

    idxs = jnp.arange(nblocks, dtype=jnp.int32)
    # scan over the block axis
    blocks_t = jnp.moveaxis(blocks, -2, 0)  # [nblocks, B, 16]
    state, _ = lax.scan(step, state, (blocks_t, idxs))
    return state


@functools.partial(jax.jit, static_argnames=("nblocks", "tile"))
def _sha256_blocks_tiled(blocks, nvalid, nblocks: int, tile: int):
    """CPU-backend variant of _sha256_blocks: identical math, but the
    batch axis is processed `tile` rows at a time under lax.map so
    every intermediate of the ~1600-op compression chain is a
    tile-sized (L2-resident) temp instead of a batch-wide HBM sweep —
    the XLA CPU lowering is memory-bound without it (~2.4x measured at
    tile=4096 on 1M-row batches). Requires B % tile == 0 (callers pad;
    merkle level sizes are powers of two)."""
    b = blocks.shape[0]
    bt = blocks.reshape(b // tile, tile, nblocks, 16)
    nvt = nvalid.reshape(b // tile, tile)

    def one(args):
        blk, nv = args
        state = jnp.broadcast_to(jnp.asarray(_IV), (tile, 8))

        def step(state, xs):
            block, idx = xs
            new = _compress(state, block)
            mask = (idx < nv)[..., None]
            return jnp.where(mask, new, state), None

        idxs = jnp.arange(nblocks, dtype=jnp.int32)
        state, _ = lax.scan(step, state,
                            (jnp.moveaxis(blk, -2, 0), idxs))
        return state

    return lax.map(one, (bt, nvt)).reshape(b, 8)


# ------------------------------------------------------ backend routing

def _config_tile() -> int:
    from plenum_tpu.common.config import Config
    return Config.SHA256_CPU_TILE


def select_backend(batch_rows: int) -> str:
    """Trace-time backend decision for one compression dispatch:
    "pallas" (accelerator, batch fills a kernel block), "tiled" (CPU
    backend, batch spans 2+ cache tiles) or "plain". The env override
    PLENUM_TPU_SHA256_BACKEND supports "xla" (disable Pallas — the
    shared probe handles it) and "pallas_interp" (force the Pallas
    kernel in interpreter mode: byte-for-byte kernel coverage on
    CPU-only hosts; tests use it through this exact seam)."""
    import os
    from plenum_tpu.common.config import Config
    from plenum_tpu.ops import mesh as mesh_mod
    from plenum_tpu.ops import sha256_pallas as sp
    if os.environ.get(sp.PALLAS_ENV) == "pallas_interp" \
            and batch_rows >= sp.BLOCK:
        return "pallas_interp"
    if sp.pallas_available() \
            and batch_rows >= Config.SHA256_PALLAS_MIN_BATCH:
        return "pallas"
    if mesh_mod.probe_platform() == "cpu" \
            and batch_rows >= 2 * Config.SHA256_CPU_TILE:
        return "tiled"
    return "plain"


def compress_blocks(blocks, nvalid, nblocks: int, backend: str = "plain"):
    """Route one [B, nblocks, 16]-words compression to `backend`.
    Traceable — ops/merkle's fused build/append jits call this inline
    with a static backend string; the pallas_call and the lax.map tile
    loop both trace into the enclosing jit."""
    if backend in ("pallas", "pallas_interp"):
        from plenum_tpu.ops import sha256_pallas as sp
        if int(blocks.shape[0]) >= sp.BLOCK:
            return sp.sha256_blocks(blocks, nvalid, nblocks,
                                    interpret=(backend == "pallas_interp"))
        # small batches (the top tree levels inside a fused build jit)
        # would pad to a full kernel block — the plain expression is
        # cheaper than hashing up to BLOCK-1 garbage rows
        return _sha256_blocks(blocks, nvalid, nblocks)
    if backend == "tiled":
        tile = _config_tile()
        b = int(blocks.shape[0])
        if b % tile == 0 and b >= 2 * tile:
            return _sha256_blocks_tiled(blocks, nvalid, nblocks, tile)
    return _sha256_blocks(blocks, nvalid, nblocks)


def sha256_blocks_routed(blocks, nvalid, nblocks: int):
    """Standalone dispatch half with backend routing: pick the backend
    for this batch size and launch. A Pallas kernel the compiler
    refuses raises (program bug); its execution is proven once per
    nblocks, and a launch that dies on the device is a counted
    step-down served by the XLA expression
    (ops/mesh.launch_survives — the policy every Pallas seam shares)."""
    b = int(blocks.shape[0])
    backend = select_backend(b)
    pad = (-b) % _config_tile() if backend == "tiled" else 0
    if pad:
        out = compress_blocks(
            jnp.pad(blocks, ((0, pad), (0, 0), (0, 0))),
            jnp.pad(nvalid, (0, pad), constant_values=1),
            nblocks, backend)[:b]
    else:
        out = compress_blocks(blocks, nvalid, nblocks, backend)
    if backend == "pallas":
        from plenum_tpu.ops import mesh as mesh_mod
        from plenum_tpu.ops import sha256_pallas as sp
        if not mesh_mod.launch_survives(sp.PALLAS_ENV, ("routed", nblocks),
                                        out, "pallas sha256"):
            # re-route: select_backend now skips the stepped-down family
            return sha256_blocks_routed(blocks, nvalid, nblocks)
    return out


def pad_messages(msgs: Sequence[bytes], nblocks: int = None
                 ) -> Tuple[np.ndarray, np.ndarray, int]:
    """SHA-pad `msgs` into ([B, nblocks, 16] u32 big-endian words, [B] i32)."""
    need = [(len(m) + 9 + 63) // 64 for m in msgs]
    maxb = max(need) if need else 1
    if nblocks is None:
        # bucket to power of two to bound recompiles
        nblocks = 1
        while nblocks < maxb:
            nblocks *= 2
    assert maxb <= nblocks
    ln0 = len(msgs[0]) if msgs else 0
    uniform = bool(msgs) and all(len(m) == ln0 for m in msgs)
    if not msgs or uniform:
        out = np.zeros((len(msgs), nblocks * 64), dtype=np.uint8)
    if uniform:
        # uniform lengths (merkle node hashes, fixed-size leaves): one
        # vectorized fill instead of a per-message Python loop — the
        # host-side padding is the bottleneck at 1M-leaf scale
        out[:, :ln0] = np.frombuffer(b"".join(msgs), dtype=np.uint8) \
            .reshape(len(msgs), ln0)
        out[:, ln0] = 0x80
        end = need[0] * 64
        out[:, end - 8:end] = np.frombuffer(
            (ln0 * 8).to_bytes(8, "big"), dtype=np.uint8)
    elif msgs:
        # mixed lengths: one flat vectorized scatter covering every
        # block-count bucket at once (shared core in
        # ops.scatter_ragged_rows — sha3 pads through the same helper).
        # The bucket (block count) only decides where each row's 64-bit
        # length field lands, and the row-relative scatter handles that
        # per message.
        width = nblocks * 64
        out, lens = scatter_ragged_rows(msgs, width)
        flat = out.reshape(-1)
        rows = np.arange(len(msgs), dtype=np.int64)
        flat[rows * width + lens] = 0x80
        ends = np.asarray(need, dtype=np.int64) * 64
        bits = lens * 8
        base = rows * width + ends - 8
        for k in range(8):
            flat[base + k] = (bits >> (8 * (7 - k))) & 0xff
    words = out.reshape(len(msgs), nblocks, 16, 4)
    words = (words[..., 0].astype(np.uint32) << 24
             | words[..., 1].astype(np.uint32) << 16
             | words[..., 2].astype(np.uint32) << 8
             | words[..., 3].astype(np.uint32))
    nvalid = np.asarray(need, dtype=np.int32)
    # block-lane accounting: every message occupies a full `nblocks`
    # row on device but only `need` of its blocks do compression work —
    # the bucket's wasted compressions are this seam's padding
    _tmy.get_seam_hub().record_launch(
        _tmy.SEAM_SHA256, int(nvalid.sum()), len(msgs) * nblocks,
        shape=(len(msgs), nblocks))
    return words, nvalid, nblocks


def digests_to_bytes(dig: np.ndarray) -> List[bytes]:
    """[B, 8] u32 → list of 32-byte digests."""
    arr = np.asarray(dig).astype(">u4")
    return [arr[i].tobytes() for i in range(arr.shape[0])]


def digests_to_array(dig: np.ndarray) -> np.ndarray:
    """[B, 8] u32 → [B, 32] u8 big-endian digest bytes: the array
    sibling of digests_to_bytes for callers that immediately re-consume
    the digests (level pairing, device upload, dense proof buffers)
    instead of needing per-digest Python bytes objects."""
    arr = np.ascontiguousarray(np.asarray(dig).astype(">u4"))
    return arr.view(np.uint8).reshape(-1, 32)


@jax.jit
def _node_words_from_digest_pairs(pairs_u8):
    """[m, 64] u8 rows (left||right digest bytes) → [m, 2, 16] u32
    SHA-padded words for H(0x01 || left || right), entirely on device —
    no per-pair Python message objects on host."""
    m = pairs_u8.shape[0]
    out = jnp.zeros((m, 128), dtype=jnp.uint8)
    out = out.at[:, 0].set(jnp.uint8(0x01))
    out = out.at[:, 1:65].set(pairs_u8)
    out = out.at[:, 65].set(jnp.uint8(0x80))
    out = out.at[:, 120:128].set(jnp.asarray(
        np.frombuffer((65 * 8).to_bytes(8, "big"), dtype=np.uint8)))
    w = out.reshape(m, 2, 16, 4).astype(jnp.uint32)
    return (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) \
        | w[..., 3]


def sha256_node_pairs_array(pairs: np.ndarray) -> np.ndarray:
    """[m, 64] u8 rows of left||right digests → [m, 32] u8 node digests
    H(0x01||l||r). Digest bytes stay in arrays end to end."""
    pairs = np.ascontiguousarray(pairs, dtype=np.uint8).reshape(-1, 64)
    m = pairs.shape[0]
    # bucket the row axis: level-wise bulk builds hand this every
    # distinct level size, and the raw m paid one XLA compile each
    # (the PT014 per-distinct-size incident class); pad rows hash
    # garbage the tail slice drops
    mp = pow2_at_least(max(m, 1))
    if mp != m:
        padded = np.zeros((mp, 64), dtype=np.uint8)
        padded[:m] = pairs
        pairs = padded
    words = _node_words_from_digest_pairs(jnp.asarray(pairs))
    nvalid = jnp.full((pairs.shape[0],), 2, dtype=jnp.int32)
    return digests_to_array(np.asarray(
        sha256_blocks_routed(words, nvalid, 2)))[:m]


def sha256_many(msgs: Sequence[bytes]) -> List[bytes]:
    """Batched SHA-256 over arbitrary same-or-mixed-length messages."""
    if not msgs:
        return []
    words, nvalid, nblocks = pad_messages(msgs)
    return digests_to_bytes(np.asarray(sha256_blocks_routed(
        jnp.asarray(words), jnp.asarray(nvalid), nblocks)))


class JaxSha256Backend:
    """Batch backend for `TreeHasher` (ledger/tree_hasher.py seam)."""

    def leaf_hashes(self, datas: Sequence[bytes]) -> List[bytes]:
        return sha256_many([b"\x00" + d for d in datas])

    def node_hashes(self, pairs: Sequence[Tuple[bytes, bytes]]) -> List[bytes]:
        return sha256_many([b"\x01" + l + r for l, r in pairs])

    def node_hashes_array(self, pairs: np.ndarray) -> np.ndarray:
        """[m, 64] u8 (left||right) → [m, 32] u8 — the array seam for
        level-wise bulk tree building (no per-pair Python objects)."""
        return sha256_node_pairs_array(pairs)


_default_backend = None


def get_default_backend() -> JaxSha256Backend:
    """Process-wide backend so every ledger shares the compiled
    executables (one per nblocks bucket)."""
    global _default_backend
    if _default_backend is None:
        _default_backend = JaxSha256Backend()
    return _default_backend
