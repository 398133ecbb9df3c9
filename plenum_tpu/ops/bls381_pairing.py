"""Batched BLS12-381 pairing verification and windowed MSM (JAX).

The last crypto hot path living outside the device: every multi-sig
*verify* bottoms out in two scalar pairings per signature
(``crypto/bls_ops.multi_pairing_is_one``). This module batches MANY
independent pairing-product checks into ONE device dispatch — B jobs x
P (G1, G2) pairs in, B booleans out — so the Miller loops of a whole
committee's worth of proofs and the single shared final exponentiation
amortize one launch, exactly the `aggregate_dispatch` recipe one level
up the tower.

Kernel shape (see ops/bls381_tower.py for the field layer):

 - decompress G1 (bls381_jax) and G2 (tower fp2 sqrt) for all B*P
   points at once;
 - one branchless Jacobian Miller loop (fori over the 63 fixed bits of
   |x|, addition step always computed and bit-selected) accumulating
   the sparse line A + B*w^3 + C*w^5 per pair — every multiply layer
   is ONE stacked mont_mul across all pairs and Karatsuba lanes;
 - fp12 product over the pair axis, then ONE shared final
   exponentiation: easy part conj*inv + frobenius^2, hard part a w=2
   windowed fori over the 635 base-4 digits of (q^4-q^2+1)/r;
 - verdict: product == 1 AND every pair of the job decoded. Invalid /
   infinity pairs contribute the neutral factor (their curve slots are
   filled with generator points so the arithmetic stays nondegenerate,
   then masked to one) — garbage can flip a verdict to False, never
   crash, and the condition f^((q^12-1)/r) == 1 is the SAME exponent
   test the python/native backends apply, so verdicts match bit for
   bit for every decodable input.

The MSM kernel aggregates sum(s_i * P_i) for one shared-weight set per
dispatch: per-point multiples table (w=4, 16 entries, complete RCB
additions so the identity rows cost nothing), a Horner fori over the
64 scalar nibbles, then a log2(N) tree sum.

Routing: `crypto/bls_ops` consults `mesh.xla_backend_enabled(ENV)` and
steps the whole family down permanently on any device failure — same
registry, same validate-once discipline as the Pallas SHA-256 path.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from plenum_tpu.observability import telemetry as _tmy
from plenum_tpu.ops import pow2_at_least
from plenum_tpu.ops.bls381_jax import (
    NLIMB, Q, _limbs_to_ints, _proj_to_affine,
    decompress, from_mont, fcanon, pack_compressed)
from plenum_tpu.ops.bls381_tower import (
    TV, _Fp2Field, _FqField, _mont_l, _norm, _radd, _rsub, _tstack,
    fp2_mul_many, fp12_conj, fp12_frob2, fp12_inv, fp12_mul, fp12_one,
    fp12_eq_one, fp12_sq, g2_decompress, g2_identity, pack_g2_compressed,
    padd_rcb, tneg, _ONE2_M)

# step-down family for the whole device tower path (pairing + MSM +
# G2 aggregation); "native"/"off" pins the scalar backends. Defined in
# crypto/bls_ops (the router) so the two never diverge.
from plenum_tpu.crypto.bls_ops import BLS_TOWER_ENV  # noqa: E402

# ---------------------------------------------------------------- constants

X_ABS = 0xD201000000010000                    # |x|, the BLS parameter
R_ORD = 0x73EDA753299D7D483339D80809A1D805_53BDA402FFFE5BFEFFFFFFFF00000001
_MILLER_BITS = np.array(
    [int(b) for b in bin(X_ABS)[2:]][1:], dtype=np.int32)

_HARD_D = (Q ** 4 - Q ** 2 + 1) // R_ORD      # hard-part exponent


def _base4_digits(e: int) -> np.ndarray:
    out = []
    while e:
        out.append(e & 3)
        e >>= 2
    return np.array(out[::-1], dtype=np.int32)


_HARD_DIGITS = _base4_digits(_HARD_D)
assert _HARD_DIGITS[0] != 0

# generators (standard BLS12-381), substituted into inactive pair
# slots so the branchless curve arithmetic never degenerates
_G1X = int("17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905"
           "A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB", 16)
_G1Y = int("08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF6"
           "00DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1", 16)
_G2X = (int("024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02"
            "B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8", 16),
        int("13E02B6052719F607DACD3A088274F65596BD0D09920B61A"
            "B5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E", 16))
_G2Y = (int("0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A7"
            "6D429A695160D12C923AC9CC3BACA289E193548608B82801", 16),
        int("0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF"
            "267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE", 16))
assert (_G1Y * _G1Y - _G1X ** 3 - 4) % Q == 0
_G1X_M = _mont_l(_G1X)
_G1Y_M = _mont_l(_G1Y)
_G2X_M = np.stack([_mont_l(_G2X[0]), _mont_l(_G2X[1])])
_G2Y_M = np.stack([_mont_l(_G2Y[0]), _mont_l(_G2Y[1])])


# ------------------------------------------------------------ Miller loop

def _sparse12(A: TV, Bc: TV, C: TV) -> TV:
    """Line value A + B·w^3 + C·w^5 as a full fp12 element: fp2 slots
    0 (c0.e0), 4 (c1.e1), 5 (c1.e2) — w-power k = i + 2j."""
    A = _norm(A, 2.0)
    Bc = _norm(Bc, 2.0)
    C = _norm(C, 2.0)
    z = jnp.zeros_like(A.a[..., 0, :])
    rows = [A.a[..., 0, :], A.a[..., 1, :], z, z, z, z, z, z,
            Bc.a[..., 0, :], Bc.a[..., 1, :],
            C.a[..., 0, :], C.a[..., 1, :]]
    return TV(jnp.stack(rows, axis=-2), 2.0)


def _lane(p: TV, k: int) -> TV:
    return TV(p.a[..., k, :, :], p.b)


def _n2(t: TV) -> TV:
    return _norm(t, 2.0)


def _miller(px, py, qx, qy) -> TV:
    """Batched ate Miller loop. px/py: [..., 32] Montgomery affine G1;
    qx/qy: [..., 2, 32] Montgomery affine G2 (twist). Returns the
    conjugated (x < 0) Miller value f as TV [..., 12, 32].

    Jacobian doubling/addition with polynomial (inversion-free) line
    coefficients; the dropped Fq2* scalings (xi, 2YZ^3, HZ) lie in
    subfields killed by the final exponentiation's easy part. The
    addition step runs every iteration and is bit-selected — one traced
    body, no data-dependent control flow."""
    bits_j = jnp.asarray(_MILLER_BITS)
    PXE = TV(jnp.stack([px, jnp.zeros_like(px)], axis=-2), 2.0)
    pyv = TV(py, 2.0)
    two_py = _n2(_radd(pyv, pyv))
    PY2XI = _tstack([two_py, two_py], -2)     # xi·2py (tangent line A)
    PY1XI = _tstack([pyv, pyv], -2)           # xi·py  (chord line A)
    QX = TV(qx, 2.0)
    QY = TV(qy, 2.0)
    one2 = jnp.broadcast_to(jnp.asarray(_ONE2_M), qx.shape)
    f0 = fp12_one(px.shape[:-1])

    def body(i, carry):
        Xa_, Ya_, Za_, fa_ = carry
        X, Y, Z = TV(Xa_, 2.0), TV(Ya_, 2.0), TV(Za_, 2.0)
        f = TV(fa_, 2.0)
        # ---- doubling step: T <- 2T, tangent line, eval at P
        l1 = fp2_mul_many(_tstack([X, Y, Z, Y], -3),
                          _tstack([X, Y, Z, Z], -3))
        X2, Y2, Z2, YZ = (_lane(l1, k) for k in range(4))
        M = _radd(_radd(X2, X2), X2)                      # 3X^2
        l2 = fp2_mul_many(
            _tstack([Y2, X2, X, YZ, X2, M], -3),
            _tstack([Y2, X, Y2, Z2, Z2, M], -3))
        Y4, X3, XY2, YZ3, X2Z2, M2 = (_lane(l2, k) for k in range(6))
        S2x = _radd(XY2, XY2)
        S4 = _n2(_radd(S2x, S2x))                         # 4·X·Y^2
        Xd = _n2(_rsub(_rsub(M2, S4), S4))                # M^2 - 2S
        Zd = _n2(_radd(YZ, YZ))                           # 2YZ
        SmX = _rsub(S4, Xd)
        T3 = _radd(_radd(X2Z2, X2Z2), X2Z2)               # 3·X^2·Z^2
        l3 = fp2_mul_many(
            _tstack([YZ3, T3, M, Zd], -3),
            _tstack([PY2XI, PXE, _n2(SmX), Zd], -3))
        Ad, Cm, MS, Z2a = (_lane(l3, k) for k in range(4))
        e2 = _radd(Y4, Y4)
        e4 = _radd(e2, e2)
        e8 = _radd(e4, e4)                                # 8·Y^4
        Yd = _n2(_rsub(MS, _n2(e8)))
        Bd = _rsub(_radd(_radd(X3, X3), X3),
                   _radd(Y2, Y2))                         # 3X^3 - 2Y^2
        fd = fp12_mul(fp12_sq(f), _sparse12(Ad, Bd, tneg(Cm)))
        # ---- addition step: T <- T + Q, chord line (always computed,
        # bit-selected — one traced body, no data-dependent control)
        l4 = fp2_mul_many(_tstack([QX, Z2a], -3),
                          _tstack([Z2a, Zd], -3))
        U, Z3a = _lane(l4, 0), _lane(l4, 1)
        H = _n2(_rsub(U, Xd))
        l5 = fp2_mul_many(_tstack([QY, H, H], -3),
                          _tstack([Z3a, H, Zd], -3))
        S2c, H2, HZ = (_lane(l5, k) for k in range(3))
        Rr = _n2(_rsub(S2c, Yd))
        l6 = fp2_mul_many(
            _tstack([H2, Xd, HZ, Rr, HZ, Rr, Rr], -3),
            _tstack([H, H2, PY1XI, QX, QY, PXE, Rr], -3))
        H3, XH2, Aa, Rqx, HZqy, CmA, R2 = (_lane(l6, k)
                                           for k in range(7))
        Ba = _rsub(Rqx, _n2(HZqy))
        Xa = _n2(_rsub(_rsub(R2, _n2(H3)), _n2(_radd(XH2, XH2))))
        XmX = _rsub(XH2, Xa)
        l7 = fp2_mul_many(_tstack([Rr, Yd, Zd], -3),
                          _tstack([_n2(XmX), H3, H], -3))
        Ya1, YH3, Za2 = (_lane(l7, k) for k in range(3))
        Ya = _rsub(Ya1, _n2(YH3))
        fa = fp12_mul(fd, _sparse12(Aa, Ba, tneg(CmA)))
        bit = (bits_j[i] == 1)

        def sel(a: TV, d: TV):
            return jnp.where(bit, _n2(a).a, _n2(d).a)

        return (sel(Xa, Xd), sel(Ya, Yd), sel(_norm(Za2, 2.0), Zd),
                sel(fa, fd))

    init = (qx, qy, one2, f0)
    _, _, _, f_end = lax.fori_loop(0, len(_MILLER_BITS), body, init)
    return fp12_conj(TV(f_end, 2.0))


def _final_exp(f: TV) -> TV:
    """f^((q^12-1)/r), split: easy part (q^6-1)(q^2+1) via conj, inv
    and frobenius^2; hard part (q^4-q^2+1)/r as a w=2 windowed fori
    over 635 base-4 digits (digit 0 multiplies by one — branchless)."""
    z = fp12_mul(fp12_conj(f), fp12_inv(f))         # f^(q^6-1)
    y = fp12_mul(fp12_frob2(z), z)                  # ^(q^2+1)
    y2 = fp12_sq(y)
    y3 = fp12_mul(y2, y)
    one = fp12_one(y.a.shape[:-2])
    tab = jnp.stack([one, y.a, y2.a, y3.a], axis=0)
    dig = jnp.asarray(_HARD_DIGITS)

    def body(i, acc):
        a = fp12_sq(fp12_sq(TV(acc, 2.0)))
        m = lax.dynamic_index_in_dim(tab, dig[i], 0, keepdims=False)
        return fp12_mul(a, TV(m, 2.0)).a

    acc0 = tab[int(_HARD_DIGITS[0])]          # leading digit is static
    out = lax.fori_loop(1, len(_HARD_DIGITS), body, acc0)
    return TV(out, 2.0)


@jax.jit
def _pairing_kernel(g1x, g1s, g1i, g1v, g2c1, g2c0, g2s, g2i, g2v):
    """[B, P, ...] packed compressed points -> (verdict[B], decode_ok
    [B]). verdict = decode_ok AND prod_j e(G1_j, G2_j) == 1."""
    (X1, Y1, _Z1), v1 = decompress(g1x, g1s, g1i, g1v)
    qx, qy, v2 = g2_decompress(g2c1, g2c0, g2s, g2i, g2v)
    # both-infinity pairs are NEUTRAL (bucket padding); a one-sided
    # identity point is a malformed check and fails the whole job —
    # the host backends apply the identical rule, so verdicts agree
    pad_pair = g1i & g2i
    live = ~g1i & ~g2i
    pair_ok = v1 & v2 & (pad_pair | live)
    active = pair_ok & live
    am1 = active[..., None]
    am2 = active[..., None, None]
    px = jnp.where(am1, X1, jnp.asarray(_G1X_M))
    py = jnp.where(am1, Y1, jnp.asarray(_G1Y_M))
    qxa = jnp.where(am2, qx.a, jnp.asarray(_G2X_M))
    qya = jnp.where(am2, qy.a, jnp.asarray(_G2Y_M))
    f = _miller(px, py, qxa, qya)                   # [B, P, 12, 32]
    f = TV(jnp.where(am2, f.a, fp12_one(f.a.shape[:-2])), 2.0)
    width = f.a.shape[1]
    while width > 1:                                # pair-axis product
        f = fp12_mul(TV(f.a[:, 0::2], 2.0), TV(f.a[:, 1::2], 2.0))
        width //= 2
    f = TV(f.a[:, 0], 2.0)
    is_one = fp12_eq_one(_final_exp(f))
    job_ok = jnp.all(pair_ok, axis=1)
    return is_one & job_ok, job_ok


# --------------------------------------------------------------- MSM

def _tree_sum_rcb(P, n_pad: int, field):
    """[n_pad, ...] identity-padded points -> single point, log2 levels
    of stacked complete additions (3 stacked multiplies per level)."""
    levels = int(n_pad).bit_length() - 1
    assert 1 << levels == n_pad
    for _ in range(levels):
        P = padd_rcb(tuple(TV(c.a[0::2], c.b) for c in P),
                     tuple(TV(c.a[1::2], c.b) for c in P), field)
    return tuple(TV(c.a[0], c.b) for c in P)


@jax.jit
def _msm_kernel(x_std, sign_big, is_inf, valid_in, digits):
    """sum(s_i * P_i): [N, 32] compressed-G1 limbs + [N, 64] base-16
    scalar digits (msb-first) -> standard-domain projective coords +
    ok (= all points decoded). Per-point w=4 multiples table, Horner
    over nibble windows, then a tree sum across the point axis."""
    (X, Y, Z), valid = decompress(x_std, sign_big, is_inf, valid_in)
    N = x_std.shape[0]
    Pt = (TV(X, 2.0), TV(Y, 2.0), TV(Z, 2.0))
    idX, idY, idZ = g1_identity_flat(N)
    tab0 = tuple(jnp.broadcast_to(c.a[None], (16,) + c.a.shape)
                 for c in (idX, idY, idZ))

    def build(k, tab):
        prev = tuple(TV(lax.dynamic_index_in_dim(
            c, k - 1, 0, keepdims=False), 2.0) for c in tab)
        nxt = padd_rcb(prev, Pt, _FqField)
        return tuple(lax.dynamic_update_index_in_dim(
            c, _norm(n, 2.0).a, k, 0) for c, n in zip(tab, nxt))

    tab = tuple(lax.dynamic_update_index_in_dim(c, p.a, 1, 0)
                for c, p in zip(tab0, Pt))
    tab = lax.fori_loop(2, 16, build, tab)
    dig_t = jnp.transpose(digits)                   # [64, N]

    def horner(w, acc):
        accP = tuple(TV(c, 2.0) for c in acc)
        for _ in range(4):                          # acc <- 16*acc
            accP = padd_rcb(accP, accP, _FqField)
        d = lax.dynamic_index_in_dim(dig_t, w, 0, keepdims=False)
        sel = tuple(jnp.take_along_axis(
            c, d[None, :, None], axis=0)[0] for c in tab)
        accP = padd_rcb(accP, tuple(TV(s, 2.0) for s in sel),
                        _FqField)
        return tuple(_norm(c, 2.0).a for c in accP)

    acc = lax.fori_loop(0, digits.shape[1], horner,
                        tuple(c.a for c in (idX, idY, idZ)))
    n_pad = 1 << max(0, (N - 1).bit_length())
    accP = tuple(TV(c, 2.0) for c in acc)
    if n_pad > N:
        pad = g1_identity_flat(n_pad - N)
        accP = tuple(TV(jnp.concatenate([c.a, p.a], axis=0), 2.0)
                     for c, p in zip(accP, pad))
    Xs, Ys, Zs = _tree_sum_rcb(accP, n_pad, _FqField)
    return (fcanon(from_mont(Xs.a)), fcanon(from_mont(Ys.a)),
            fcanon(from_mont(Zs.a)), jnp.all(valid))


def g1_identity_flat(n: int):
    z = jnp.zeros((n, NLIMB), dtype=jnp.int32)
    one = jnp.broadcast_to(jnp.asarray(_mont_l(1)), (n, NLIMB))
    return TV(z, 1.0), TV(one, 1.0), TV(z, 1.0)


# ----------------------------------------------------- G2 aggregation

@jax.jit
def _g2_aggregate_kernel(c1_std, c0_std, sign_big, is_inf, valid_in):
    """[B, n, 32] G2 limb halves + flags -> standard-domain projective
    fp2 coords [B, 2, 32] x3 + valid[B] — the G2 mirror of the G1
    `_aggregate_kernel` (pubkey aggregation for multi-sig verify)."""
    x, y, valid = g2_decompress(c1_std, c0_std, sign_big, is_inf,
                                valid_in)
    B, n = c1_std.shape[0], c1_std.shape[1]
    idX, idY, idZ = g2_identity((B, n))
    dead = (~valid | is_inf)[..., None, None]
    one2b = jnp.broadcast_to(jnp.asarray(_ONE2_M), x.a.shape)
    P = (TV(jnp.where(dead, idX.a, x.a), 2.0),
         TV(jnp.where(dead, idY.a, y.a), 2.0),
         TV(jnp.where(dead, idZ.a, one2b), 2.0))
    n_pad = 1 << max(0, (n - 1).bit_length())
    if n_pad > n:
        pad = g2_identity((B, n_pad - n))
        P = tuple(TV(jnp.concatenate([c.a, p.a], axis=1), 2.0)
                  for c, p in zip(P, pad))
    levels = int(n_pad).bit_length() - 1
    for _ in range(levels):
        P = padd_rcb(tuple(TV(c.a[:, 0::2], c.b) for c in P),
                     tuple(TV(c.a[:, 1::2], c.b) for c in P),
                     _Fp2Field)
    Xs, Ys, Zs = (TV(c.a[:, 0], c.b) for c in P)
    std = tuple(fcanon(from_mont(c.a)) for c in (Xs, Ys, Zs))
    return std[0], std[1], std[2], jnp.all(valid_in & (valid | is_inf),
                                           axis=1)


# ----------------------------------------------- dispatch / collect

def _pack_pair_arrays(jobs, Bp: int, Pp: int):
    g1raw = np.zeros((Bp, Pp, 48), dtype=np.uint8)
    g1raw[:, :, 0] = 0xC0
    g2raw = np.zeros((Bp, Pp, 96), dtype=np.uint8)
    g2raw[:, :, 0] = 0xC0
    for i, job in enumerate(jobs):
        for j, (s1, s2) in enumerate(job):
            g1raw[i, j] = np.frombuffer(s1, dtype=np.uint8)
            g2raw[i, j] = np.frombuffer(s2, dtype=np.uint8)
    l1, s1, i1, v1 = pack_compressed(g1raw.reshape(Bp * Pp, 48))
    c1, c0, s2, i2, v2 = pack_g2_compressed(g2raw.reshape(Bp * Pp, 96))
    return (l1.reshape(Bp, Pp, NLIMB), s1.reshape(Bp, Pp),
            i1.reshape(Bp, Pp), v1.reshape(Bp, Pp),
            c1.reshape(Bp, Pp, NLIMB), c0.reshape(Bp, Pp, NLIMB),
            s2.reshape(Bp, Pp), i2.reshape(Bp, Pp),
            v2.reshape(Bp, Pp))


def pairing_dispatch(jobs: Sequence[Sequence[Tuple[bytes, bytes]]]):
    """Launch one batched pairing-product check for B jobs, each a
    list of (compressed G1 48 B, compressed G2 96 B) pairs. Both axes
    are pow2-bucketed (short jobs pad with infinity pairs = neutral
    factors; padding jobs are all-infinity rows sliced off lazily);
    job batches clearing the mesh gate shard the job axis. Returns the
    un-awaited device arrays for `pairing_collect`."""
    B = len(jobs)
    pmax = max(1, max((len(j) for j in jobs), default=1))
    Pp = pow2_at_least(pmax)
    from plenum_tpu.ops import mesh as mesh_mod
    m = mesh_mod.get_mesh()
    sharded = m.should_shard(B)
    Bp = m.padded_size(B, min_per_device=1) if sharded \
        else pow2_at_least(max(B, 1))
    _tmy.get_seam_hub().record_launch(
        _tmy.SEAM_BLS_PAIR, sum(len(j) for j in jobs), Bp * Pp,
        shape=(Bp, Pp))
    arrays = _pack_pair_arrays(jobs, Bp, Pp)
    if sharded:
        outs = m.dispatch(_pairing_kernel, arrays, n=B,
                          label="pairing_dispatch")
    else:
        m.note_passthrough(B)
        from plenum_tpu.observability.tracing import CAT_BLS
        with m.tracer.span("pairing_dispatch", CAT_BLS, n=B,
                           padded=Bp, pairs=Pp):
            outs = _pairing_kernel(*(jnp.asarray(a) for a in arrays))
    if Bp != B:
        outs = tuple(o[:B] for o in outs)
    return outs


def pairing_collect(handles) -> Tuple[np.ndarray, np.ndarray]:
    """Await a `pairing_dispatch` handle -> (verdict[B], decode_ok[B])
    numpy bools."""
    from plenum_tpu.ops import mesh as mesh_mod
    from plenum_tpu.observability.tracing import CAT_BLS
    m = mesh_mod.get_mesh()
    with m.tracer.span("pairing_collect", CAT_BLS):
        verdict, ok = (np.asarray(h) for h in handles)
    return verdict, ok


def pairing_jobs(jobs) -> Tuple[np.ndarray, np.ndarray]:
    """Dispatch + collect in one call (the synchronous routing entry
    used by crypto/bls_ops)."""
    if len(jobs) == 0:
        return np.zeros(0, dtype=bool), np.zeros(0, dtype=bool)
    return pairing_collect(pairing_dispatch(jobs))


def msm_dispatch(points: Sequence[bytes], scalars: Sequence[int]):
    """Launch sum(s_i * P_i) over compressed G1 points. The point axis
    is pow2-bucketed (infinity points with zero scalars pad — every
    multiple of the identity is the identity, so padding rows cost
    nothing through the complete additions). Reduction crosses the
    point axis, so this seam never mesh-shards (note_passthrough)."""
    N = len(points)
    Np = pow2_at_least(max(N, 1))
    from plenum_tpu.ops import mesh as mesh_mod
    m = mesh_mod.get_mesh()
    _tmy.get_seam_hub().record_launch(_tmy.SEAM_BLS_MSM, N, Np,
                                      shape=(Np,))
    raw = np.zeros((Np, 48), dtype=np.uint8)
    raw[:, 0] = 0xC0
    for i, p in enumerate(points):
        raw[i] = np.frombuffer(p, dtype=np.uint8)
    digits = np.zeros((Np, 64), dtype=np.int32)
    sb = np.zeros((Np, 32), dtype=np.uint8)
    for i, s in enumerate(scalars):
        sb[i] = np.frombuffer((s % R_ORD).to_bytes(32, "big"),
                              dtype=np.uint8)
    digits[:, 0::2] = sb >> 4
    digits[:, 1::2] = sb & 0xF
    limbs, sign_big, is_inf, valid = pack_compressed(raw)
    m.note_passthrough(N)
    from plenum_tpu.observability.tracing import CAT_BLS
    with m.tracer.span("msm_dispatch", CAT_BLS, n=N, padded=Np):
        outs = _msm_kernel(jnp.asarray(limbs), jnp.asarray(sign_big),
                           jnp.asarray(is_inf), jnp.asarray(valid),
                           jnp.asarray(digits))
    return outs


def msm_collect(handles) -> Optional[Tuple[int, int]]:
    """Await an `msm_dispatch` handle -> affine (x, y) ints or None
    (identity / undecodable input)."""
    from plenum_tpu.ops import mesh as mesh_mod
    from plenum_tpu.observability.tracing import CAT_BLS
    m = mesh_mod.get_mesh()
    with m.tracer.span("msm_collect", CAT_BLS):
        X, Y, Z, ok = (np.asarray(h) for h in handles)
    if not bool(ok):
        return None
    xi = int(_limbs_to_ints(X[None])[0])
    yi = int(_limbs_to_ints(Y[None])[0])
    zi = int(_limbs_to_ints(Z[None])[0])
    return _proj_to_affine(xi, yi, zi)


def msm_result(handles):
    """Await an `msm_dispatch` handle → (affine point | None,
    decode_ok) — the blocking half crypto/bls_ops guards (a run-time
    device failure surfaces here, the compile in msm_dispatch)."""
    ok = bool(np.asarray(handles[3]))
    return msm_collect(handles), ok


def msm_g1(points: Sequence[bytes], scalars: Sequence[int]):
    """Synchronous MSM: (affine point | None, decode_ok)."""
    if len(points) == 0:
        return None, True
    return msm_result(msm_dispatch(points, scalars))


def g2_aggregate_dispatch(jobs: Sequence[Sequence[bytes]], n: int):
    """Batched G2 aggregation (pubkey sets), mirror of the G1
    `aggregate_dispatch`: B jobs x n compressed 96-byte points, both
    axes identity-padded to pow2 buckets."""
    B = len(jobs)
    from plenum_tpu.ops import mesh as mesh_mod
    m = mesh_mod.get_mesh()
    Bp = pow2_at_least(max(B, 1))
    _tmy.get_seam_hub().record_launch(
        _tmy.SEAM_BLS, sum(len(j) for j in jobs), Bp * n, shape=(Bp, n))
    raw = np.zeros((Bp, n, 96), dtype=np.uint8)
    raw[:, :, 0] = 0xC0
    for i, job in enumerate(jobs):
        for j, s in enumerate(job):
            raw[i, j] = np.frombuffer(s, dtype=np.uint8)
    c1, c0, sg, inf, valid = pack_g2_compressed(raw.reshape(Bp * n, 96))
    arrays = (c1.reshape(Bp, n, NLIMB), c0.reshape(Bp, n, NLIMB),
              sg.reshape(Bp, n), inf.reshape(Bp, n),
              valid.reshape(Bp, n))
    m.note_passthrough(B)
    outs = _g2_aggregate_kernel(*(jnp.asarray(a) for a in arrays))
    if Bp != B:
        outs = tuple(o[:B] for o in outs)
    return outs


def g2_aggregate_collect(handles):
    """-> (points, valid): points[i] = affine (Fq2-int-pair x, y) |
    None per job."""
    X, Y, Z, ok = (np.asarray(h) for h in handles)
    out: List[Optional[Tuple[Tuple[int, int], Tuple[int, int]]]] = []
    for i in range(len(ok)):
        if not ok[i]:
            out.append(None)
            continue
        x0, x1 = (int(_limbs_to_ints(X[i][None, c])[0])
                  for c in range(2))
        y0, y1 = (int(_limbs_to_ints(Y[i][None, c])[0])
                  for c in range(2))
        z0, z1 = (int(_limbs_to_ints(Z[i][None, c])[0])
                  for c in range(2))
        if z0 == 0 and z1 == 0:
            out.append(None)        # projective identity
            continue
        # affine via Fq2 inversion on host ints
        den = (z0 * z0 + z1 * z1) % Q
        di = pow(den, Q - 2, Q)
        iz = (z0 * di % Q, (-z1) * di % Q)

        def fq2mul(a, b):
            return ((a[0] * b[0] - a[1] * b[1]) % Q,
                    (a[0] * b[1] + a[1] * b[0]) % Q)

        out.append((fq2mul((x0, x1), iz), fq2mul((y0, y1), iz)))
    return out, ok
