"""Batched BLS12-381 G1 aggregation on TPU (JAX).

The reference aggregates BLS signature shares one at a time through
Hyperledger Ursa (`crypto/bls/indy_crypto/bls_crypto_indy_crypto.py:99`,
`create_multi_sig`). This kernel aggregates MANY independent share-sets
per device dispatch — B jobs x n compressed signatures in, B aggregate
points out — so one launch and one result download amortize over
hundreds of aggregations (the BASELINE.json "BLS aggregate n=4/25/100" configs).

TPU-first design (same recipe as ops/ed25519_jax.py, adapted to a
generic 381-bit prime):
 - Field arithmetic over Fq (q = BLS12-381 modulus) in radix 2^12:
   32 int32 limbs per element. Limb products are <= 2^24 and 32-column
   sums <= 2^29, so everything stays in native int32 on the VPU.
 - q has no pseudo-Mersenne structure, so reduction is MONTGOMERY:
   values live in the Montgomery domain (a*2^384 mod q) and `mont_mul`
   runs a 32-step radix-2^12 REDC inside the kernel. Entry/exit from
   the domain happens on device (mul by R^2 / by 1), so the host only
   does byte->limb bit-plumbing (vectorized numpy, no Python bigints).
 - Decompression (the per-signature cost that dominates the C scalar
   path at ~70 us/share) is batched: sqrt(x^3+4) is one fixed-exponent
   fori_loop over all B*n shares at once.
 - Point addition uses the Renes-Costello-Batina COMPLETE formulas for
   a=0 short-Weierstrass curves (12M + 2*mul_b3): branchless, handles
   identity/doubling/inverses uniformly — no data-dependent control
   flow, exactly what XLA wants. (E(Fq) has odd order, so the formulas
   are complete on the whole curve.)
 - Aggregation is a log2(n) tree reduction over the share axis; the
   batch axis is embarrassingly parallel, so `aggregate_dispatch`
   shards the job axis across the device mesh through the production
   dispatcher (ops/mesh.py) with zero collectives — job batches at or
   above `Config.MESH_SHARD_MIN` on a multi-chip host are identity-
   padded per device and launched as one SPMD program.

The scalar/native paths stay authoritative for single aggregates (a
device dispatch costs more than one 100-share aggregate on CPU). This
kernel is currently exercised by bench.py, the multichip dryrun
(__graft_entry__) and tests only — the ordering path aggregates through
crypto/bls_ops (native C / pure Python); wiring a queue-depth router
that batches concurrent ordering-path aggregations onto this kernel is
future work and NOT yet a production code path.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from plenum_tpu.observability import telemetry as _tmy
from plenum_tpu.ops import pow2_at_least

# ---------------------------------------------------------------- constants

NLIMB = 32
RADIX = 12
MASK = (1 << RADIX) - 1

Q = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R_MONT = 1 << (NLIMB * RADIX)          # 2^384
R2 = (R_MONT * R_MONT) % Q             # to-Montgomery factor
QPRIME = (-pow(Q, -1, 1 << RADIX)) % (1 << RADIX)  # -q^-1 mod 2^12
HALF = (Q - 1) // 2


def _int_to_limbs(v: int, n: int = NLIMB) -> np.ndarray:
    out = np.zeros(n, dtype=np.int32)
    for i in range(n):
        out[i] = v & MASK
        v >>= RADIX
    assert v == 0
    return out


def limbs_to_int(limbs) -> int:
    v = 0
    for i in reversed(range(len(limbs))):
        v = (v << RADIX) | int(limbs[i])
    return v


def _exp_bits(e: int) -> np.ndarray:
    return np.array([int(b) for b in bin(e)[2:]], dtype=np.int32)


_Q_L = _int_to_limbs(Q)
_2Q_L = _int_to_limbs(2 * Q)
_HALF_P1_L = _int_to_limbs(HALF + 1)
_R2_L = _int_to_limbs(R2)
_ONE_STD_L = _int_to_limbs(1)
_ONE_M_L = _int_to_limbs(R_MONT % Q)          # 1 in Montgomery form
_FOUR_M_L = _int_to_limbs(4 * R_MONT % Q)     # curve b=4, Montgomery
_B3_M_L = _int_to_limbs(12 * R_MONT % Q)      # 3b = 12, Montgomery
_SQRT_BITS = _exp_bits((Q + 1) // 4)          # q = 3 mod 4 sqrt exponent

# Anti-diagonal scatter: flat outer-product index (i*32+j) -> column i+j.
# One [..,1024]x[1024,63] int32 matmul replaces 1024 unrolled MACs.
def _fold_matrix() -> np.ndarray:
    m = np.zeros((NLIMB * NLIMB, 2 * NLIMB - 1), dtype=np.int32)
    for i in range(NLIMB):
        for j in range(NLIMB):
            m[i * NLIMB + j, i + j] = 1
    return m


_FOLD_MAT = _fold_matrix()


# Squaring variant: only the 528 i<=j products, with weight 2 off the
# diagonal — halves the outer-product work of fsq, and the sqrt chain
# that dominates decompression is ~2/3 squarings.
def _sq_fold():
    ii, jj = [], []
    m = np.zeros((NLIMB * (NLIMB + 1) // 2, 2 * NLIMB), dtype=np.int32)
    for i in range(NLIMB):
        for j in range(i, NLIMB):
            m[len(ii), i + j] = 1 if i == j else 2
            ii.append(i)
            jj.append(j)
    return np.array(ii), np.array(jj), m


_SQ_I, _SQ_J, _SQ_FOLD = _sq_fold()


# ----------------------------------------------------- limb normalization

def _carry_par(c):
    """One parallel carry round; caller guarantees top-column headroom."""
    cr = c >> RADIX
    pad = [(0, 0)] * (c.ndim - 1) + [(1, 0)]
    return (c & MASK) + jnp.pad(cr[..., :-1], pad)


def _carry_seq(x):
    """Exact sequential carry chain as a lax.scan over the limb axis.
    Handles negative limbs via arithmetic shifts; the final value must
    fit 32 limbs nonnegative.

    This used to be 32 unrolled elementwise steps ("noise next to a
    mul's 2k multiplies") — true for runtime, catastrophically false
    for COMPILE time once the pairing tower landed: every _cond_sub a
    bound-normalization inserts and every _redc tail carries one of
    these, so the unrolled form put ~130 HLO ops at hundreds of sites
    inside the Miller fori body (104 s XLA compile for the loop alone,
    measured on CPU). The scan body is ~4 ops traced once per site;
    same arithmetic, ~8x smaller module."""
    xm = jnp.moveaxis(x, -1, 0)

    def step(c, col):
        t = col + c
        cr = t >> RADIX
        return cr, t - (cr << RADIX)

    cr, cols = lax.scan(step, jnp.zeros_like(xm[0]), xm[:-1])
    last = (xm[-1] + cr)[None]
    return jnp.moveaxis(jnp.concatenate([cols, last], axis=0), 0, -1)


def _cond_sub(v, const_l: np.ndarray):
    """v - const if v >= const else v, for carry-normalized nonneg v."""
    d = _carry_seq(v - jnp.asarray(const_l))
    neg = (d[..., -1:] < 0)
    return jnp.where(neg, v, d)


def _geq(v, const_l: np.ndarray):
    """v >= const (both canonical-normalized), -> bool[...]."""
    d = _carry_seq(v - jnp.asarray(const_l))
    return d[..., -1] >= 0


# ----------------------------------------------------- field arithmetic
#
# Invariant: a "normalized" element has limbs in [0, 2^12) (mul outputs
# may briefly sit at MASK+1 before the final seq chain — we always end
# with _carry_seq so the invariant is exact) and value < 2q. mont_mul
# output < q*(1 + 4q/2^384) < 1.41q; fadd/fsub re-establish < 2q with
# one conditional subtract of 2q.

def fadd(a, b):
    return _cond_sub(_carry_seq(a + b), _2Q_L)


def fsub(a, b):
    return _cond_sub(_carry_seq(a + jnp.asarray(_2Q_L) - b), _2Q_L)


def _redc(c, unroll=None):
    """Montgomery reduction of 63 product columns (cols < 2^29) to a
    normalized < 1.41q element: 32-step radix-2^12 REDC.

    unroll=True flattens the step chain so XLA fuses it — right for
    code traced ONCE (the fpow loop body that dominates decompression)
    running on TPU. unroll=False keeps a compact fori_loop — right for
    padd (traced at every tree level, ~3% of the arithmetic) and for
    the CPU backend, where the 32x bigger unrolled graph buys nothing
    but compile time (tests + the driver's CPU-mesh dryrun).
    Measured on the v5e: unrolling bought nothing (the fold matmul
    dominates, not loop bookkeeping) at 2x the compile time, so auto
    resolves to the compact loop everywhere."""
    if unroll is None:
        unroll = False
    # pad to 64 BEFORE carrying (col 62 carries into 63) and so the 32
    # REDC shift-downs leave 32 result columns
    pad = [(0, 0)] * (c.ndim - 1) + [(0, 1)]
    c = jnp.pad(c, pad)
    c = _carry_par(c)
    acc = _carry_par(c)                         # cols <= MASK + 2^6
    if unroll:
        # no physical shifting: step i computes its m from column i and
        # adds m * q into columns i..i+31
        cols = [acc[..., i] for i in range(2 * NLIMB)]
        for i in range(NLIMB):
            m = ((cols[i] & MASK) * QPRIME) & MASK
            for j in range(NLIMB):
                cols[i + j] = cols[i + j] + m * np.int32(_Q_L[j])
            cols[i + 1] = cols[i + 1] + (cols[i] >> RADIX)  # exact carry
        c = jnp.stack(cols[NLIMB:], axis=-1)    # cols < 2^30
    else:
        ql = jnp.asarray(np.pad(_Q_L, (0, NLIMB)))

        def redc_step(i, acc):
            m = ((acc[..., 0] & MASK) * QPRIME) & MASK
            full = acc + m[..., None] * ql
            carry = full[..., 0] >> RADIX       # low 12 bits are 0
            nxt = jnp.concatenate(
                [full[..., 1:], jnp.zeros_like(full[..., :1])], axis=-1)
            return nxt.at[..., 0].add(carry)

        acc = lax.fori_loop(0, NLIMB, redc_step, acc)
        c = acc[..., :NLIMB]                    # cols < 2^30, value < 1.41q
    c = _carry_par(c)
    c = _carry_par(c)
    return _carry_seq(c)


def mont_mul(a, b, unroll=None):
    """a * b * 2^-384 mod q (Montgomery product). a, b normalized < 2q;
    output normalized < 1.41q."""
    outer = a[..., :, None] * b[..., None, :]
    flat = outer.reshape(outer.shape[:-2] + (NLIMB * NLIMB,))
    return _redc(flat @ jnp.asarray(_FOLD_MAT)[:, :2 * NLIMB - 1],
                 unroll=unroll)


def fsq(a, unroll=None):
    prods = a[..., _SQ_I] * a[..., _SQ_J]
    return _redc((prods @ jnp.asarray(_SQ_FOLD))[..., :2 * NLIMB - 1],
                 unroll=unroll)


def fpow(x, bits: np.ndarray):
    """x^e (Montgomery domain) for a fixed public msb-first exponent."""
    bits_j = jnp.asarray(bits)
    one = jnp.broadcast_to(jnp.asarray(_ONE_M_L), x.shape)

    def body(i, acc):
        acc = fsq(acc)
        return jnp.where(bits_j[i] == 1, mont_mul(acc, x), acc)

    return lax.fori_loop(0, len(bits), body, one)


def to_mont(a_std):
    return mont_mul(a_std, jnp.broadcast_to(jnp.asarray(_R2_L), a_std.shape))


def from_mont(a_m):
    return mont_mul(
        a_m, jnp.broadcast_to(jnp.asarray(_ONE_STD_L), a_m.shape))


def fcanon(v):
    """Canonical representative in [0, q) from a < 2q normalized value."""
    return _cond_sub(v, _Q_L)


def feq(a, b):
    return jnp.all(fcanon(a) == fcanon(b), axis=-1)


def fneg(a):
    return fsub(jnp.zeros_like(a), a)


# ----------------------------------------------------- curve arithmetic
#
# Projective (X:Y:Z), y^2 = x^3 + 4, identity (0:1:0). Complete addition
# per Renes-Costello-Batina 2016 Alg. 7 (a=0, b3=12) — validated against
# the scalar reference over identity/doubling/inverse cases.

def _pm(a, b):
    # padd is traced at every tree level: compact-graph variant
    return mont_mul(a, b, unroll=False)


def padd(P1, P2):
    X1, Y1, Z1 = P1
    X2, Y2, Z2 = P2
    b3 = jnp.broadcast_to(jnp.asarray(_B3_M_L), X1.shape)
    t0 = _pm(X1, X2); t1 = _pm(Y1, Y2); t2 = _pm(Z1, Z2)
    t3 = fadd(X1, Y1); t4 = fadd(X2, Y2); t3 = _pm(t3, t4)
    t4 = fadd(t0, t1); t3 = fsub(t3, t4); t4 = fadd(Y1, Z1)
    X3 = fadd(Y2, Z2); t4 = _pm(t4, X3); X3 = fadd(t1, t2)
    t4 = fsub(t4, X3); X3 = fadd(X1, Z1); Y3 = fadd(X2, Z2)
    X3 = _pm(X3, Y3); Y3 = fadd(t0, t2); Y3 = fsub(X3, Y3)
    X3 = fadd(t0, t0); t0 = fadd(X3, t0); t2 = _pm(b3, t2)
    Z3 = fadd(t1, t2); t1 = fsub(t1, t2); Y3 = _pm(b3, Y3)
    X3 = _pm(t4, Y3); t2 = _pm(t3, t1); X3 = fsub(t2, X3)
    Y3 = _pm(Y3, t0); t1 = _pm(t1, Z3); Y3 = fadd(t1, Y3)
    t0 = _pm(t0, t3); Z3 = _pm(Z3, t4); Z3 = fadd(Z3, t0)
    return (X3, Y3, Z3)


def _identity(shape):
    z = jnp.zeros(shape + (NLIMB,), dtype=jnp.int32)
    one = jnp.broadcast_to(jnp.asarray(_ONE_M_L), shape + (NLIMB,))
    return (z, one, z)


# ----------------------------------------------------- decompress + sum

def decompress(x_std, sign_big, is_inf, valid_in):
    """Batched G1 decompress. x_std: [..., 32] standard-domain limbs
    (x < q enforced host-side), sign_big/is_inf/valid_in: bool[...].
    Returns ((X, Y, Z) Montgomery projective, valid[...])."""
    x_m = to_mont(x_std)
    u = fadd(mont_mul(fsq(x_m), x_m),
             jnp.broadcast_to(jnp.asarray(_FOUR_M_L), x_m.shape))
    y = fpow(u, _SQRT_BITS)
    on_curve = feq(fsq(y), u)
    y_canon = fcanon(from_mont(y))
    got_big = _geq(y_canon, _HALF_P1_L)              # y > (q-1)/2
    flip = got_big != sign_big
    y = jnp.where(flip[..., None], fneg(y), y)
    Xp, Yp, Zp = (x_m, y,
                  jnp.broadcast_to(jnp.asarray(_ONE_M_L), x_m.shape))
    idX, idY, idZ = _identity(x_std.shape[:-1])
    inf = is_inf[..., None]
    P = (jnp.where(inf, idX, Xp), jnp.where(inf, idY, Yp),
         jnp.where(inf, idZ, Zp))
    valid = valid_in & (on_curve | is_inf)
    return P, valid


def _tree_sum(P, n_pad: int):
    """Sum points over axis 1 ([B, n_pad] -> [B]) via log2 levels of
    complete additions. n_pad must be a power of two (identity-padded)."""
    levels = int(n_pad).bit_length() - 1
    assert 1 << levels == n_pad
    for _ in range(levels):
        P = padd(tuple(c[:, 0::2] for c in P),
                 tuple(c[:, 1::2] for c in P))
    return tuple(c[:, 0] for c in P)


@jax.jit
def _aggregate_kernel(x_std, sign_big, is_inf, valid_in):
    """[B, n, 32] limbs + flags -> ([B,32]x3 standard-domain projective
    coords, valid[B] = all shares decodable). Decompression (the
    dominant cost: one sqrt per share) runs on exactly the n real
    shares; identity padding to the tree's power-of-two width happens
    at the point level afterwards."""
    P, valid = decompress(x_std, sign_big, is_inf, valid_in)
    n = x_std.shape[1]
    n_pad = 1 << max(0, (n - 1).bit_length())
    if n_pad > n:
        idX, idY, idZ = _identity((x_std.shape[0], n_pad - n))
        P = tuple(jnp.concatenate([c, pad], axis=1)
                  for c, pad in zip(P, (idX, idY, idZ)))
    X, Y, Z = _tree_sum(P, n_pad)
    return (fcanon(from_mont(X)), fcanon(from_mont(Y)),
            fcanon(from_mont(Z)), jnp.all(valid, axis=1))


# ----------------------------------------------------- host byte plumbing

def pack_compressed(sigs: np.ndarray):
    """[N, 48] uint8 big-endian compressed G1 -> (x limbs [N, 32] int32,
    sign_big [N], is_inf [N], valid [N]) — vectorized numpy, no Python
    bigints on the hot path."""
    sigs = np.asarray(sigs, dtype=np.uint8)
    N = sigs.shape[0]
    flags = sigs[:, 0]
    compressed = (flags & 0x80) != 0
    is_inf = (flags & 0x40) != 0
    sign_big = (flags & 0x20) != 0
    body = sigs.copy()
    body[:, 0] &= 0x1F
    le = body[:, ::-1].astype(np.int32)              # little-endian bytes
    groups = le.reshape(N, 16, 3)                    # 3 bytes = 2 limbs
    v24 = groups[:, :, 0] + (groups[:, :, 1] << 8) + (groups[:, :, 2] << 16)
    limbs = np.empty((N, NLIMB), dtype=np.int32)
    limbs[:, 0::2] = v24 & MASK
    limbs[:, 1::2] = v24 >> RADIX
    # x < q (lexicographic compare against q's limbs, from the top)
    lt = np.zeros(N, dtype=bool)
    decided = np.zeros(N, dtype=bool)
    for i in range(NLIMB - 1, -1, -1):
        qi = int(_Q_L[i])
        lt |= (~decided) & (limbs[:, i] < qi)
        decided |= limbs[:, i] != qi
    inf_ok = is_inf & (flags == 0xC0) & ~np.any(sigs[:, 1:], axis=1)
    valid = compressed & (inf_ok | (~is_inf & lt))
    limbs[~valid | is_inf] = 0
    return limbs, sign_big & ~is_inf, is_inf & valid, valid


def _proj_to_affine(x: int, y: int, z: int) -> Optional[Tuple[int, int]]:
    if z == 0:
        return None
    zi = pow(z, Q - 2, Q)
    return (x * zi % Q, y * zi % Q)


_POW2 = np.array([1 << (RADIX * i) for i in range(NLIMB)], dtype=object)


def _limbs_to_ints(arr: np.ndarray) -> np.ndarray:
    """[..., 32] int32 -> [...] Python-int (object) array, vectorized."""
    return (arr.astype(object) * _POW2).sum(axis=-1)


def aggregate_g1_jobs(jobs: Sequence[Sequence[bytes]]):
    """Aggregate B independent share-sets in one device dispatch.

    jobs: B sequences of 48-byte compressed G1 signatures (ragged ok —
    each job is identity-padded to the common power-of-two width).
    Returns (points, valid): points[i] is the affine aggregate
    (x, y) | None of job i, valid[i] is False if any share of job i
    failed to decode (mirror of g1_decompress raising).
    """
    B = len(jobs)
    if B == 0:
        return [], np.zeros(0, dtype=bool)
    nmax = max(1, max(len(j) for j in jobs))
    X, Y, Z, ok = aggregate_dispatch(jobs, nmax)
    X, Y, Z, ok = (np.asarray(X), np.asarray(Y), np.asarray(Z),
                   np.asarray(ok))
    xs, ys, zs = _limbs_to_ints(X), _limbs_to_ints(Y), _limbs_to_ints(Z)
    pts = [_proj_to_affine(int(xs[i]), int(ys[i]), int(zs[i]))
           if ok[i] else None for i in range(B)]
    return pts, ok


def aggregate_dispatch(jobs, n: int):
    """Device-async building block for pipelined benchmarking and the
    verify-hub path: returns the un-awaited device arrays for a batch
    of jobs padded to a common (static) width n. Short jobs are padded
    with compressed-infinity shares (identity under addition).

    Job batches clearing the mesh gate (ops/mesh.py) shard the job
    axis over every chip: padding JOBS are all-infinity share sets
    (decode valid, aggregate to the identity) and their rows are
    sliced off lazily, so collect sees exactly B results."""
    B = len(jobs)
    from plenum_tpu.ops import mesh as mesh_mod
    m = mesh_mod.get_mesh()
    sharded = m.should_shard(B)
    # both branches bucket the job axis: the unsharded path used to
    # launch the raw B and paid one XLA compile per distinct job-batch
    # size (the PT014 / r05 regression shape); identity-padded jobs
    # aggregate to infinity and their rows are sliced off lazily
    Bp = m.padded_size(B, min_per_device=1) if sharded \
        else pow2_at_least(max(B, 1))
    # job-axis lane accounting: real shares vs the Bp×n identity-padded
    # grid (short jobs pad with infinity shares, padding jobs are whole
    # wasted rows)
    _tmy.get_seam_hub().record_launch(
        _tmy.SEAM_BLS, sum(len(j) for j in jobs), Bp * n, shape=(Bp, n))
    raw = np.zeros((Bp, n, 48), dtype=np.uint8)
    raw[:, :, 0] = 0xC0
    for i, job in enumerate(jobs):
        for j, s in enumerate(job):
            raw[i, j] = np.frombuffer(s, dtype=np.uint8)
    limbs, sign_big, is_inf, valid = pack_compressed(
        raw.reshape(Bp * n, 48))
    arrays = (limbs.reshape(Bp, n, NLIMB), sign_big.reshape(Bp, n),
              is_inf.reshape(Bp, n), valid.reshape(Bp, n))
    if sharded:
        outs = m.dispatch(_aggregate_kernel, arrays, n=B)
        if Bp != B:
            outs = tuple(o[:B] for o in outs)
        return outs
    m.note_passthrough(B)
    outs = _aggregate_kernel(*(jnp.asarray(a) for a in arrays))
    if Bp != B:
        outs = tuple(o[:B] for o in outs)
    return outs


def aggregate_collect(handles) -> Tuple[List[Optional[Tuple[int, int]]],
                                        np.ndarray]:
    """Await + post-process a handle from aggregate_dispatch."""
    X, Y, Z, ok = (np.asarray(h) for h in handles)
    xs, ys, zs = _limbs_to_ints(X), _limbs_to_ints(Y), _limbs_to_ints(Z)
    pts = [_proj_to_affine(int(xs[i]), int(ys[i]), int(zs[i]))
           if ok[i] else None for i in range(len(ok))]
    return pts, ok
