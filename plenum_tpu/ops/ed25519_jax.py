"""Batched ed25519 signature verification on TPU (JAX).

The reference verifies client-request and propagate signatures one at a
time through libsodium (`plenum/server/client_authn.py:84`,
`stp_core/crypto/nacl_wrappers.py`). This kernel verifies THOUSANDS of
signatures per device dispatch — the north-star batch path of
BASELINE.json ("ed25519 batch verify 1/1k/100k").

TPU-first design:
 - Field arithmetic over GF(2^255-19) in radix 2^13: 20 int32 limbs per
   element. Limb products are ≤ 2^26 and column sums ≤ 20·2^26 < 2^31, so
   everything fits native int32 on the VPU — no 64-bit emulation, no
   floats, fully deterministic.
 - All control flow is static: `lax.fori_loop` over 256 scalar bits with
   per-bit conditional point additions via `jnp.where` (constant shape —
   XLA-friendly, and constant-time as a bonus).
 - Host does the cheap data-dependent work (SHA-512 of R||A||M via
   hashlib's C core, canonicality checks, limb packing); the device does
   the ~500 field multiplications per signature that dominate.
 - Verification is cofactorless: [S]B == R + [k]A, computed as
   [S]B + [k](-A) vs decompressed R, batched over the whole array.

Layout: an element is [..., 20] int32; batch ops are elementwise over the
leading axes, so the batch axis shards across a device mesh with zero
collectives (embarrassingly parallel). `verify_batch_async` routes
batches through the production mesh dispatcher (`ops/mesh.DeviceMesh`):
on a multi-chip host, batches at or above `Config.MESH_SHARD_MIN` are
bucket-padded per device and launched as ONE SPMD program over every
chip; single-device hosts and small batches take the unchanged
passthrough path.
"""
from __future__ import annotations

import functools
import hashlib
from typing import List, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from plenum_tpu.observability import telemetry as _tmy

# ---------------------------------------------------------------- constants

NLIMB = 20
RADIX = 13
MASK = (1 << RADIX) - 1

P = 2 ** 255 - 19
L = 2 ** 252 + 27742317777372353535851937790883648493
D_INT = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1_INT = pow(2, (P - 1) // 4, P)
G_Y_INT = (4 * pow(5, P - 2, P)) % P


def _int_to_limbs(v: int) -> np.ndarray:
    out = np.zeros(NLIMB, dtype=np.int32)
    for i in range(NLIMB):
        out[i] = v & MASK
        v >>= RADIX
    assert v == 0
    return out


def _limbs_to_int(limbs) -> int:
    v = 0
    for i in reversed(range(len(limbs))):
        v = (v << RADIX) | int(limbs[i])
    return v


def _exp_bits(e: int) -> np.ndarray:
    """Exponent bits, msb first."""
    return np.array([int(b) for b in bin(e)[2:]], dtype=np.int32)


_D_L = _int_to_limbs(D_INT)
_TWOD_L = _int_to_limbs(2 * D_INT % P)
_SQRT_M1_L = _int_to_limbs(SQRT_M1_INT)
_ONE_L = _int_to_limbs(1)
_E58_BITS = _exp_bits((P - 5) // 8)

# 8p in radix-2^13 digits, spread so that every limb of the constant
# dominates any normalized operand limb (enables borrow-free subtraction:
# a - b computed as a + SPREAD_8P - b with nonnegative limbs throughout).
def _spread_8p() -> np.ndarray:
    d = _int_to_limbs(8 * P).astype(np.int64)
    e = d.copy()
    e[0] += 1 << (RADIX + 1)
    for i in range(1, NLIMB - 1):
        e[i] += (1 << (RADIX + 1)) - 2
    e[NLIMB - 1] -= 2
    assert _limbs_to_int(e) == 8 * P
    assert all(e[i] >= MASK + 2 for i in range(NLIMB - 1))
    assert e[NLIMB - 1] >= 1 << 10  # dominates the ≤2^9 top limb invariant
    return e.astype(np.int32)


_SPREAD_8P = _spread_8p()


# ----------------------------------------------------- field arithmetic

# Anti-diagonal scatter matrix: flat outer-product index (i*20+j) → column
# i+j. One [.., 400]×[400, 42] int32 matmul replaces 400 unrolled
# multiply-adds — tiny XLA graphs and VPU-friendly vector work.
def _fold_matrix() -> np.ndarray:
    m = np.zeros((NLIMB * NLIMB, 2 * NLIMB + 2), dtype=np.int32)
    for i in range(NLIMB):
        for j in range(NLIMB):
            m[i * NLIMB + j, i + j] = 1
    return m


_FOLD_MAT = _fold_matrix()


def _shift_up(c):
    """Shift columns up one position (carry from col k lands in col k+1)."""
    pad = [(0, 0)] * (c.ndim - 1) + [(1, 0)]
    return jnp.pad(c[..., :-1], pad)


def _carry_round(c):
    """One parallel carry step over all columns; top carry must be vacuous
    (caller guarantees headroom in the last column)."""
    cr = c >> RADIX
    return (c & MASK) + _shift_up(cr)


def _carry_wrap_round(c):
    """Parallel carry on 20 columns where the top carry wraps to column 0
    multiplied by 608 (2^260 ≡ 19·2^5 mod p)."""
    cr = c >> RADIX
    wrapped = jnp.concatenate([cr[..., -1:] * 608, cr[..., :-1]], axis=-1)
    return (c & MASK) + wrapped


def _finalize20(c):
    """Normalize 20 columns (each < 2^25 — the verified headroom: the
    first wrap round's cr·608 term then stays < 2^21, far from int32
    overflow) to the invariant: limbs ≤ MASK+1, top limb < 2^9 (bits
    ≥ 255 folded back ×19)."""
    c = _carry_wrap_round(c)
    c = _carry_wrap_round(c)
    top = c[..., -1:] >> 8
    c = jnp.concatenate([c[..., :1] + top * 19, c[..., 1:-1],
                         c[..., -1:] - (top << 8)], axis=-1)
    return _carry_wrap_round(c)


def fmul(a, b):
    """Field multiply. a, b: [..., 20] int32, limbs ≤ MASK+1, top < 2^9."""
    outer = a[..., :, None] * b[..., None, :]
    flat = outer.reshape(outer.shape[:-2] + (NLIMB * NLIMB,))
    c = flat @ jnp.asarray(_FOLD_MAT)          # [..., 42], cols < 20·2^26
    c = _carry_round(c)
    c = _carry_round(c)
    c = _carry_round(c)                         # all 42 cols ≤ MASK+1
    # fold: col 20+k carries weight 2^260·2^13k ≡ 608·2^13k, col 40+k
    # carries (2^260)²·2^13k ≡ 608²·2^13k (cols 40-41 hold only carry
    # residue ≤ 2^5 after the rounds above, so 608² ≈ 2^18.5 is safe)
    extra = c[..., 40:42] * (608 * 608)
    pad = [(0, 0)] * (extra.ndim - 1) + [(0, NLIMB - 2)]
    c = c[..., :20] + c[..., 20:40] * 608 + jnp.pad(extra, pad)
    return _finalize20(c)                       # input cols < 2^25


def fsq(a):
    return fmul(a, a)


def fadd(a, b):
    return _finalize20(a + b)


def fsub(a, b):
    return _finalize20(a + jnp.asarray(_SPREAD_8P) - b)


def fneg(a):
    return fsub(jnp.zeros_like(a), a)


def _stack(c: List):
    return jnp.stack(c, axis=-1)


def _cols(x):
    return [x[..., i] for i in range(x.shape[-1])]


def fcanon(x):
    """Canonical representative in [0, p): conditional single subtract of p.

    Input invariant (post-reduction limbs) bounds the value below 2p.
    """
    c = _cols(x)
    # t = x + 19, full carry: bit 255 of t tells whether x >= p
    t = [ci for ci in c]
    t[0] = t[0] + 19
    for k in range(NLIMB - 1):
        cr = t[k] >> RADIX
        t[k] = t[k] - (cr << RADIX)
        t[k + 1] = t[k + 1] + cr
    q = t[NLIMB - 1] >> 8  # 0 or 1
    # x - q*p  ==  x + q*19 - q*2^255
    r = [ci for ci in c]
    r[0] = r[0] + q * 19
    r[NLIMB - 1] = r[NLIMB - 1] - (q << 8)
    for k in range(NLIMB - 1):
        cr = r[k] >> RADIX  # arithmetic shift: signed carries OK
        r[k] = r[k] - (cr << RADIX)
        r[k + 1] = r[k + 1] + cr
    return _stack(r)


def fiszero(x):
    """x (post-reduction) ≡ 0 mod p?  → bool[...]."""
    xc = fcanon(x)
    return jnp.all(xc == 0, axis=-1)


def feq(a, b):
    return fiszero(fsub(a, b))


def fpow(x, bits: np.ndarray):
    """x^e for fixed public exponent given as msb-first bit array."""
    bits_j = jnp.asarray(bits)
    one = jnp.broadcast_to(jnp.asarray(_ONE_L), x.shape)

    def body(i, acc):
        acc = fsq(acc)
        withmul = fmul(acc, x)
        return jnp.where((bits_j[i] == 1), withmul, acc)

    return lax.fori_loop(0, len(bits), body, one)


def _sqn(x, n: int):
    def body(i, acc):
        return fsq(acc)
    return lax.fori_loop(0, n, body, x) if n > 4 else \
        functools.reduce(lambda a, _: fsq(a), range(n), x)


def pow_p58(x):
    """x^((p-5)/8) via the standard ed25519 addition chain (ref10
    pow22523 structure): 252 squarings + 11 multiplies instead of
    square-and-multiply's ~125 extra multiplies — decompress is on the
    critical path of every verify."""
    z2 = fsq(x)                       # 2
    z9 = fmul(_sqn(z2, 2), x)         # 9 = 2^3+1
    z11 = fmul(z9, z2)                # 11
    z22 = fsq(z11)                    # 22
    z_5_0 = fmul(z22, z9)             # 2^5 - 2^0
    z_10_0 = fmul(_sqn(z_5_0, 5), z_5_0)
    z_20_0 = fmul(_sqn(z_10_0, 10), z_10_0)
    z_40_0 = fmul(_sqn(z_20_0, 20), z_20_0)
    z_50_0 = fmul(_sqn(z_40_0, 10), z_10_0)
    z_100_0 = fmul(_sqn(z_50_0, 50), z_50_0)
    z_200_0 = fmul(_sqn(z_100_0, 100), z_100_0)
    z_250_0 = fmul(_sqn(z_200_0, 50), z_50_0)
    return fmul(_sqn(z_250_0, 2), x)  # 2^252 - 3


# ----------------------------------------------------- point arithmetic
# Extended twisted-Edwards coordinates (X, Y, Z, T), a = -1.

def pt_double(X, Y, Z, T):
    A = fsq(X)
    B = fsq(Y)
    C = fadd(fsq(Z), fsq(Z))
    E = fsub(fsub(fsq(fadd(X, Y)), A), B)
    G = fsub(B, A)
    F = fsub(G, C)
    H = fsub(fneg(A), B)
    return fmul(E, F), fmul(G, H), fmul(F, G), fmul(E, H)


def pt_add(X1, Y1, Z1, T1, X2, Y2, Z2, T2):
    A = fmul(fsub(Y1, X1), fsub(Y2, X2))
    B = fmul(fadd(Y1, X1), fadd(Y2, X2))
    C = fmul(fmul(T1, jnp.broadcast_to(jnp.asarray(_TWOD_L), T1.shape)), T2)
    Dv = fadd(fmul(Z1, Z2), fmul(Z1, Z2))
    E = fsub(B, A)
    F = fsub(Dv, C)
    G = fadd(Dv, C)
    H = fadd(B, A)
    return fmul(E, F), fmul(G, H), fmul(F, G), fmul(E, H)


def _pt_add_prescaled(X1, Y1, Z1, T1, X2, Y2, Z2, T2_2d):
    """pt_add where the second point's T is pre-multiplied by 2d
    (runtime window tables): 8 field muls."""
    A = fmul(fsub(Y1, X1), fsub(Y2, X2))
    B = fmul(fadd(Y1, X1), fadd(Y2, X2))
    C = fmul(T1, T2_2d)
    Dv = fmul(fadd(Z1, Z1), Z2)
    E = fsub(B, A)
    F = fsub(Dv, C)
    G = fadd(Dv, C)
    H = fadd(B, A)
    return fmul(E, F), fmul(G, H), fmul(F, G), fmul(E, H)


def _select_pt(cond, pa, pb):
    c = cond[..., None]
    return tuple(jnp.where(c, a, b) for a, b in zip(pa, pb))


def decompress(ylimbs, sign):
    """(x, ok): recover x from y and sign bit; ok=False if not on curve."""
    yy = fsq(ylimbs)
    one = jnp.broadcast_to(jnp.asarray(_ONE_L), ylimbs.shape)
    u = fsub(yy, one)
    v = fadd(fmul(jnp.broadcast_to(jnp.asarray(_D_L), yy.shape), yy), one)
    v2 = fsq(v)
    v3 = fmul(v2, v)
    v7 = fmul(fsq(v3), v)
    x = fmul(fmul(u, v3), pow_p58(fmul(u, v7)))
    vxx = fmul(v, fsq(x))
    is_root = feq(vxx, u)
    is_neg_root = fiszero(fadd(vxx, u))
    x = jnp.where((is_neg_root & ~is_root)[..., None],
                  fmul(x, jnp.broadcast_to(jnp.asarray(_SQRT_M1_L), x.shape)),
                  x)
    ok = is_root | is_neg_root
    xc = fcanon(x)
    x_zero = jnp.all(xc == 0, axis=-1)
    ok = ok & ~(x_zero & (sign == 1))
    parity = xc[..., 0] & 1
    x = jnp.where((parity != sign)[..., None], fneg(xc), xc)
    return x, ok


def pt_add_niels(X1, Y1, Z1, T1, n_sub, n_add, n_t2d):
    """Mixed addition with a precomputed (Y2-X2, Y2+X2, 2d*T2, Z2=1)
    "niels" point: 7 field muls instead of pt_add's 9 (the 2d mult and
    the Z2 mult are folded into the table entry). Complete formulas —
    the identity entry (1, 1, 0) is handled with no special case."""
    A = fmul(fsub(Y1, X1), n_sub)
    B = fmul(fadd(Y1, X1), n_add)
    C = fmul(T1, n_t2d)
    Dv = fadd(Z1, Z1)
    E = fsub(B, A)
    F = fsub(Dv, C)
    G = fadd(Dv, C)
    H = fadd(B, A)
    return fmul(E, F), fmul(G, H), fmul(F, G), fmul(E, H)


# --------------------------------------- host-side integer curve ops
# (table construction at import time; python ints, exact)

def _ed_add_affine(p1, p2):
    """Affine Edwards addition over python ints (import-time tables)."""
    x1, y1 = p1
    x2, y2 = p2
    dxy = D_INT * x1 % P * x2 % P * y1 % P * y2 % P
    x3 = (x1 * y2 + x2 * y1) % P * pow(1 + dxy, P - 2, P) % P
    y3 = (y1 * y2 + x1 * x2) % P * pow(1 - dxy, P - 2, P) % P
    return x3, y3


def _base_affine():
    gy = G_Y_INT
    u = (gy * gy - 1) % P
    v = (D_INT * gy * gy + 1) % P
    gx = (u * pow(v, 3, P) * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P)) % P
    if (v * gx * gx - u) % P != 0:
        gx = gx * SQRT_M1_INT % P
    if gx & 1 != 0:
        gx = P - gx
    return gx, gy


def _niels_from_affine(pt) -> List[np.ndarray]:
    x, y = pt
    return [_int_to_limbs((y - x) % P), _int_to_limbs((y + x) % P),
            _int_to_limbs(2 * D_INT * x % P * y % P)]


def _build_base_window_table() -> List[np.ndarray]:
    """d*B for d=0..15 in niels form → 3 constant arrays [16, 20]."""
    entries = [[_int_to_limbs(1), _int_to_limbs(1), _int_to_limbs(0)]]
    acc = None
    base = _base_affine()
    for d in range(1, 16):
        acc = base if acc is None else _ed_add_affine(acc, base)
        entries.append(_niels_from_affine(acc))
    return [np.stack([e[c] for e in entries]) for c in range(3)]


_NB_SUB, _NB_ADD, _NB_T2D = _build_base_window_table()


# ----------------------------------------------------- the verify kernel

def _base_point_ext() -> List[np.ndarray]:
    gy = G_Y_INT
    u = (gy * gy - 1) % P
    v = (D_INT * gy * gy + 1) % P
    gx = (u * pow(v, 3, P) * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P)) % P
    if (v * gx * gx - u) % P != 0:
        gx = gx * SQRT_M1_INT % P
    if gx & 1 != 0:
        gx = P - gx
    return [_int_to_limbs(gx), _int_to_limbs(gy), _int_to_limbs(1),
            _int_to_limbs(gx * gy % P)]


_B_EXT = _base_point_ext()


def _digits4(words):
    """[B, 8] uint32 → [B, 64] int32 4-bit digits, least significant
    digit first."""
    shifts = jnp.arange(0, 32, 4, dtype=jnp.uint32)        # [8]
    d = (words[..., :, None] >> shifts[None, None, :]) & 0xF  # [B, 8, 8]
    return d.reshape(d.shape[:-2] + (64,)).astype(jnp.int32)


def _select_const_niels(onehot):
    """One-hot [B,16] → niels point from the constant base table."""
    return (onehot @ jnp.asarray(_NB_SUB),
            onehot @ jnp.asarray(_NB_ADD),
            onehot @ jnp.asarray(_NB_T2D))


def _select_batched(onehot, table):
    """One-hot [B,16] × per-batch table [B,16,20] → [B,20] per coord."""
    return tuple(jnp.einsum("bd,bdl->bl", onehot, t) for t in table)


@jax.jit
def _verify_kernel(ay, asign, ry, rsign, s_words, k_words):
    """All inputs batched; returns bool[B].

    ay/ry: [B, 20] int32 limbs of the y coordinates (canonical, < p)
    asign/rsign: [B] int32 sign bits
    s_words/k_words: [B, 8] uint32 little-endian scalar words

    Interleaved 4-bit windowed double-scalar multiplication
    (VERDICT round-1 item 5): per 64 windows, 4 shared doublings + one
    niels-form add from the CONSTANT d*B table (fixed-base, 7 muls) +
    one add from the per-signature d*(-A) table (8 muls, 2d*T
    pre-scaled) — ~2.4x fewer field muls than bitwise double-and-add
    with two conditional adds per bit. Digit selection is one-hot
    matmuls (constant-shape, MXU/VPU-friendly, no gathers).
    """
    ax, ok_a = decompress(ay, asign)
    rx, ok_r = decompress(ry, rsign)

    one = jnp.broadcast_to(jnp.asarray(_ONE_L), ay.shape)
    zero = jnp.zeros_like(ay)
    twod = jnp.broadcast_to(jnp.asarray(_TWOD_L), ay.shape)

    # ---- per-signature table: d * (-A), d = 0..15, extended coords
    # with T pre-scaled by 2d (so the loop add costs 8 muls)
    nax = fneg(ax)
    na = (nax, ay, one, fmul(nax, ay))
    tab = [(zero, one, one, zero), na]
    for d in range(2, 16):
        if d % 2 == 0:
            tab.append(pt_double(*tab[d // 2]))
        else:
            tab.append(pt_add(*tab[d - 1], *na))
    tab_x = jnp.stack([t[0] for t in tab], axis=-2)   # [B, 16, 20]
    tab_y = jnp.stack([t[1] for t in tab], axis=-2)
    tab_z = jnp.stack([t[2] for t in tab], axis=-2)
    tab_t2d = jnp.stack([fmul(t[3], twod) for t in tab], axis=-2)
    a_table = (tab_x, tab_y, tab_z, tab_t2d)

    sd = _digits4(s_words)   # [B, 64]
    kd = _digits4(k_words)

    ident = (zero, one, one, zero)
    eye16 = jnp.eye(16, dtype=jnp.int32)

    def body(i, st):
        w = 63 - i
        st = pt_double(*pt_double(*pt_double(*pt_double(*st))))
        s_dig = lax.dynamic_index_in_dim(sd, w, axis=-1, keepdims=False)
        k_dig = lax.dynamic_index_in_dim(kd, w, axis=-1, keepdims=False)
        s_oh = eye16[s_dig]                     # [B, 16]
        k_oh = eye16[k_dig]
        st = pt_add_niels(*st, *_select_const_niels(s_oh))
        x2, y2, z2, t2d2 = _select_batched(k_oh, a_table)
        st = _pt_add_prescaled(*st, x2, y2, z2, t2d2)
        return st

    X, Y, Z, _ = lax.fori_loop(0, 64, body, ident)

    ok_x = fiszero(fsub(fmul(rx, Z), X))
    ok_y = fiszero(fsub(fmul(ry, Z), Y))
    return ok_a & ok_r & ok_x & ok_y


# ----------------------------------------------------- host-side wrapper

def _pack_fe(values: Sequence[int]) -> np.ndarray:
    out = np.empty((len(values), NLIMB), dtype=np.int32)
    for i, v in enumerate(values):
        for k in range(NLIMB):
            out[i, k] = v & MASK
            v >>= RADIX
    return out


def _pack_words(values: Sequence[int]) -> np.ndarray:
    out = np.empty((len(values), 8), dtype=np.uint32)
    for i, v in enumerate(values):
        for k in range(8):
            out[i, k] = v & 0xFFFFFFFF
            v >>= 32
    return out


def _bit_fold_matrix() -> np.ndarray:
    """[256, 20] f32: bit j of a little-endian 256-bit value contributes
    2^(j-13i) to limb i (radix-2^13). Values stay < 2^13 — exact in f32,
    so limb packing is one numpy matmul instead of a per-item loop."""
    m = np.zeros((256, NLIMB), dtype=np.float32)
    for j in range(256):
        i = j // RADIX
        if i < NLIMB:
            m[j, i] = float(1 << (j - RADIX * i))
    return m


_BIT_FOLD = _bit_fold_matrix()


def _le_words(a_bytes: np.ndarray) -> np.ndarray:
    """[B, 32] uint8 → [B, 4] uint64 little-endian words."""
    return a_bytes.view(np.uint64).reshape(a_bytes.shape[0], 4)


def _ge_const(words: np.ndarray, const: int) -> np.ndarray:
    """Vectorized (value >= const) over [B, 4] LE uint64 words."""
    cw = np.array([(const >> (64 * i)) & 0xFFFFFFFFFFFFFFFF
                   for i in range(4)], dtype=np.uint64)
    ge = np.zeros(words.shape[0], dtype=bool)
    decided = np.zeros(words.shape[0], dtype=bool)
    for i in (3, 2, 1, 0):  # most significant first
        gt = words[:, i] > cw[i]
        lt = words[:, i] < cw[i]
        ge |= gt & ~decided
        decided |= gt | lt
    ge |= ~decided  # equal ⇒ >=
    return ge


def _limbs_from_bytes(a_bytes: np.ndarray) -> np.ndarray:
    """[B, 32] uint8 (LE) → [B, 20] int32 radix-2^13 limbs, vectorized."""
    bits = np.unpackbits(a_bytes, axis=1, bitorder="little")  # [B, 256]
    return (bits.astype(np.float32) @ _BIT_FOLD).astype(np.int32)


def host_pack(msgs: Sequence[bytes], sigs: Sequence[bytes],
              verkeys: Sequence[bytes]):
    """Host-side preprocessing: parse/canonicality-check sigs and keys,
    compute k = SHA-512(R||A||M) mod L (hashlib C core), pack limb arrays.

    → ([ay, asign, ry, rsign, s_words, k_words] host np arrays — the
    jit transfers them once; keeping them in numpy lets callers pad the
    batch axis without device round-trips — and valid bool[B])

    Fully vectorized (VERDICT round-1: the device kernel is ~1ms for 8k
    sigs — a per-item python loop here would dominate the whole verify):
    numpy views/unpackbits/matmul do the parsing; the only per-item C
    calls are SHA-512 and the 512→253-bit modular reduction of k.
    """
    n = len(msgs)
    assert len(sigs) == n and len(verkeys) == n
    valid = np.ones(n, dtype=bool)

    DUMMY_SIG = b"\x00" * 64
    DUMMY_VK = b"\x01" + b"\x00" * 31
    norm_sigs = []
    norm_vks = []
    for i in range(n):
        if len(sigs[i]) != 64 or len(verkeys[i]) != 32:
            valid[i] = False
            norm_sigs.append(DUMMY_SIG)
            norm_vks.append(DUMMY_VK)
        else:
            norm_sigs.append(bytes(sigs[i]))
            norm_vks.append(bytes(verkeys[i]))

    sig_b = np.frombuffer(b"".join(norm_sigs), dtype=np.uint8).reshape(n, 64)
    vk_b = np.frombuffer(b"".join(norm_vks), dtype=np.uint8).reshape(n, 32)
    r_b = np.ascontiguousarray(sig_b[:, :32])
    s_b = np.ascontiguousarray(sig_b[:, 32:])

    asign = (vk_b[:, 31] >> 7).astype(np.int32)
    rsign = (r_b[:, 31] >> 7).astype(np.int32)
    ay_b = vk_b.copy()
    ay_b[:, 31] &= 0x7F
    ry_b = r_b.copy()
    ry_b[:, 31] &= 0x7F

    # canonicality: y < p, s < L (vectorized big-int compares)
    bad = _ge_const(_le_words(ay_b), P) | _ge_const(_le_words(ry_b), P) \
        | _ge_const(_le_words(s_b), L)
    valid &= ~bad
    if bad.any():
        idx = np.nonzero(bad)[0]
        ay_b[idx] = 0
        ry_b[idx] = 0
        ay_b[idx, 0] = 1
        ry_b[idx, 0] = 1
        s_b = s_b.copy()
        s_b[idx] = 0

    # k = SHA-512(R || A || M) mod L — hashlib + bigint mod are the only
    # per-item C calls left
    k_parts = []
    for i in range(n):
        h = hashlib.sha512()
        h.update(norm_sigs[i][:32])
        h.update(norm_vks[i])
        h.update(msgs[i])
        k_int = int.from_bytes(h.digest(), "little") % L
        k_parts.append(k_int.to_bytes(32, "little"))
    k_b = np.frombuffer(b"".join(k_parts), dtype=np.uint8).reshape(n, 32)

    arrays = [_limbs_from_bytes(ay_b),
              asign,
              _limbs_from_bytes(ry_b),
              rsign,
              np.ascontiguousarray(s_b).view(np.uint32).reshape(n, 8),
              k_b.view(np.uint32).reshape(n, 8)]
    return arrays, valid


def verify_batch(msgs: Sequence[bytes], sigs: Sequence[bytes],
                 verkeys: Sequence[bytes]) -> np.ndarray:
    """Batched cofactorless ed25519 verify → np.bool_ array [B].

    Host does the cheap data-dependent prep (host_pack); the device does
    all elliptic-curve math in one dispatch.
    """
    ok_dev, valid, n = verify_batch_async(msgs, sigs, verkeys)
    if n == 0:
        return np.zeros(0, dtype=bool)
    return np.asarray(ok_dev)[:n] & valid


def launch_lanes(n: int) -> int:
    """The padded batch-lane count a verify_batch_async(n) launch will
    occupy: the mesh bucket when the batch shards, the power-of-two
    (min 8) single-device bucket otherwise. Single-sourced so callers
    that account lane occupancy for their OWN seam (the coalescing hub)
    report the same bucket the launch actually pays for."""
    if n <= 0:
        return 0
    from plenum_tpu.ops import mesh as mesh_mod
    m = mesh_mod.get_mesh()
    if m.should_shard(n):
        return m.padded_size(n)
    padded = 8
    while padded < n:
        padded *= 2
    return padded


def pack_batch(msgs: Sequence[bytes], sigs: Sequence[bytes],
               verkeys: Sequence[bytes]):
    """Host half of a launch: bytes → host arrays padded to the lane
    count the launch will run. → (arrays, valid_host_bools, n); arrays
    is None for an empty batch.

    The batch axis is padded to the next power of two (min 8) by
    repeating row 0 so every size in [1, 2^k] shares one compiled
    kernel — variable pool queue depths must not trigger XLA
    recompiles; batches clearing the mesh gate (ops/mesh.py) are
    bucket-padded per device instead."""
    n = len(msgs)
    if n == 0:
        return None, np.zeros(0, dtype=bool), 0
    arrays, valid = host_pack(msgs, sigs, verkeys)
    from plenum_tpu.ops import mesh as mesh_mod
    padded = launch_lanes(n)
    _tmy.get_seam_hub().record_launch(
        _tmy.SEAM_ED25519, n, padded, shape=padded)
    if mesh_mod.get_mesh().should_shard(n):
        arrays = mesh_mod.pad_rows(arrays, padded)
    elif padded != n:
        arrays = [np.concatenate(
            [a, np.repeat(a[:1], padded - n, axis=0)], axis=0)
            for a in arrays]
    return arrays, valid, n


def launch_packed(arrays, n: int):
    """Device half: transfer `pack_batch`'s arrays and enqueue the
    kernel; returns its un-awaited ok array (None for an empty batch).

    Multi-chip: batches clearing the mesh gate are launched as one
    batch-axis-sharded SPMD program over every chip (zero
    collectives). The mesh path runs the XLA kernel: it SPMD-partitions
    over the batch axis with no code change, whereas the Pallas kernel
    is a per-chip program (its per-device halves still run the winning
    tile grid when each shard fills a block)."""
    if arrays is None:
        return None
    from plenum_tpu.ops import mesh as mesh_mod
    m = mesh_mod.get_mesh()
    if m.should_shard(n):
        return m.dispatch(_verify_kernel, arrays, n=n)
    m.note_passthrough(n)
    return _dispatch_kernel(*arrays)


def verify_batch_async(msgs: Sequence[bytes], sigs: Sequence[bytes],
                       verkeys: Sequence[bytes]):
    """Non-blocking batched verify: enqueues the device computation and
    returns (ok_device_array, valid_host_bools, n) immediately — JAX
    dispatch is async, so the caller overlaps host work with the device
    round trip and materializes later (np.asarray(ok)[:n] & valid).
    `pack_batch` then `launch_packed`, for callers that time neither."""
    arrays, valid, n = pack_batch(msgs, sigs, verkeys)
    return launch_packed(arrays, n), valid, n


# Backend selection: the Pallas whole-verify kernel (its VMEM-resident
# limb registers avoid the per-fmul HBM round trips) for any batch
# filling a block on a TPU; the XLA kernel otherwise (smaller batches,
# CPU tests, or after a counted run-time step-down).
_ED25519_PALLAS_ENV = "PLENUM_TPU_ED25519_BACKEND"


def _pallas_available() -> bool:
    # ONE shared probe-backed availability gate for every Pallas
    # kernel family (ops/mesh.pallas_backend_enabled) — probing
    # jax.devices()[0] here would force backend init and assume
    # device 0, and a private cache would escape dryrun_multichip's
    # probe reset
    from plenum_tpu.ops import mesh as mesh_mod
    return mesh_mod.pallas_backend_enabled(_ED25519_PALLAS_ENV)


def _dispatch_kernel(ay, asign, ry, rsign, s_words, k_words):
    from plenum_tpu.ops import ed25519_pallas as edp
    from plenum_tpu.ops import mesh as mesh_mod
    # the pallas kernel serves from ONE block (4,096 signatures) up;
    # below a block the XLA kernel serves (small batches don't fill
    # the tile grid)
    if _pallas_available() and ay.shape[0] >= edp.BLOCK:
        # a kernel the compiler refuses raises here (program bug); a
        # launch that dies on the device is a counted step-down
        ok = edp.verify_kernel(ay, asign, ry, rsign, s_words, k_words)
        n_blocks = -(-ay.shape[0] // edp.BLOCK)
        if mesh_mod.launch_survives(_ED25519_PALLAS_ENV, n_blocks, ok,
                                    "pallas ed25519 verify"):
            return ok
    return _verify_kernel(ay, asign, ry, rsign, s_words, k_words)
