"""Pallas TPU kernel: the ENTIRE batched ed25519 verification in one
kernel launch.

Why: the XLA expression of the verify (ops/ed25519_jax.py) is a chain of
~3,500 field multiplies, each lowered around a [B,400]x[400,42] int32
matmul. The matmuls are fusion barriers, so every fmul round-trips its
operands through HBM — the kernel is bandwidth-bound at ~100us per fmul
(B=8192) and the compiled executable is enormous (30-110s compiles).

Here the whole computation lives in VMEM: a field element is 20 limb
*registers* of shape [BLOCK_R,128] (BLOCK_R x 128 = one batch block of
BLOCK signatures; see BLOCK_R below), the 20x20 limb convolution is unrolled
multiply-adds on those tiles, and the only HBM traffic per block is the
kernel's inputs (~700KB) and the ok-bit output. Same radix-2^13 limb
discipline, carry schedule, windowed double-scalar multiplication, and
niels-form tables as the XLA kernel — outputs are bit-identical (tests
cross-check both against the RFC 8032 scalar implementation).

Reference for the math: ops/ed25519_jax.py (which cites RFC 8032 and the
ref10 pow22523 chain); this file only re-schedules it for the VPU.
"""
import functools

import numpy as np
import jax.numpy as jnp

from plenum_tpu.ops import ed25519_jax as edj

NLIMB = edj.NLIMB
RADIX = edj.RADIX
MASK = edj.MASK

# scalar (python-int) constants: folded into the kernel as immediates
_SPREAD = [int(v) for v in edj._SPREAD_8P]
_ONE = [int(v) for v in edj._ONE_L]
_D = [int(v) for v in edj._D_L]
_TWOD = [int(v) for v in edj._TWOD_L]
_SQRT_M1 = [int(v) for v in edj._SQRT_M1_L]
_NB_SUB = np.asarray(edj._NB_SUB)      # [16, 20] int32 (constant table)
_NB_ADD = np.asarray(edj._NB_ADD)
_NB_T2D = np.asarray(edj._NB_T2D)

BLOCK_R = 32         # sublanes per batch block (32x128 = 4096 sigs/block;
                     # needs the raised vmem limit below — the window
                     # table, 16 entries x 80 limb-tiles, dominates)
BLOCK_L = 128        # lanes
BLOCK = BLOCK_R * BLOCK_L
VMEM_LIMIT_BYTES = 100 * 1024 * 1024   # v5e has 128MB VMEM; the default
                                       # 16MB scoped limit is what an
                                       # R=32 working set (~26MB) trips


# ------------------------------------------------- field ops on limb lists
# A field element is a list of NLIMB [8,128] int32 arrays. All helpers
# mirror ops/ed25519_jax.py exactly (same bounds discipline), just in
# limb-major "structure of registers" form.

def _finalize20(out):
    """Normalize 20 columns to the limb invariant (edj._finalize20):
    2x carry-wrap, fold bits >= 255 (x19), 1x carry-wrap. Applied after
    every add/sub exactly as the XLA kernel does — keeping every field
    element < ~2^255.2 is what makes fcanon's single-subtract zero test
    sound AND keeps the convolution column sums inside int32."""
    for _ in range(2):
        nxt = [oi & MASK for oi in out]
        for k in range(NLIMB - 1):
            nxt[k + 1] = nxt[k + 1] + (out[k] >> RADIX)
        nxt[0] = nxt[0] + (out[NLIMB - 1] >> RADIX) * 608
        out = nxt
    top = out[NLIMB - 1] >> 8
    out[0] = out[0] + top * 19
    out[NLIMB - 1] = out[NLIMB - 1] - (top << 8)
    nxt = [oi & MASK for oi in out]
    for k in range(NLIMB - 1):
        nxt[k + 1] = nxt[k + 1] + (out[k] >> RADIX)
    nxt[0] = nxt[0] + (out[NLIMB - 1] >> RADIX) * 608
    return nxt


def _fadd(a, b):
    return _finalize20([x + y for x, y in zip(a, b)])


def _fsub(a, b):
    return _finalize20([x + s - y for x, y, s in zip(a, b, _SPREAD)])


def _fneg(a):
    return _finalize20([s - x for x, s in zip(a, _SPREAD)])


def _conv_carry_fold(c):
    """Shared tail of mul/square: 3 carry rounds on 42 columns, fold
    cols >= 20 (x608 per 2^260 wrap), finalize to the limb invariant."""
    zero = jnp.zeros_like(c[0])
    c = c + [zero] * (42 - len(c))
    for _ in range(3):
        nxt = [ci & MASK for ci in c]
        for k in range(41):
            nxt[k + 1] = nxt[k + 1] + (c[k] >> RADIX)
        c = nxt
    out = [c[k] + c[20 + k] * 608 for k in range(20)]
    out[0] = out[0] + c[40] * (608 * 608)
    out[1] = out[1] + c[41] * (608 * 608)
    return _finalize20(out)


def _fmul(a, b):
    c = []
    for k in range(2 * NLIMB - 1):
        terms = [a[i] * b[k - i]
                 for i in range(max(0, k - NLIMB + 1), min(NLIMB, k + 1))]
        acc = terms[0]
        for t in terms[1:]:
            acc = acc + t
        c.append(acc)
    return _conv_carry_fold(c)


def _fmul_const(a, const_limbs):
    """a x compile-time constant (list of python ints); zero limbs of
    the constant drop their partial products entirely."""
    c = []
    for k in range(2 * NLIMB - 1):
        acc = None
        for i in range(max(0, k - NLIMB + 1), min(NLIMB, k + 1)):
            cv = const_limbs[k - i]
            if cv == 0:
                continue
            term = a[i] * cv
            acc = term if acc is None else acc + term
        if acc is None:
            acc = jnp.zeros_like(a[0])
        c.append(acc)
    return _conv_carry_fold(c)


def _fsq(a):
    """Squaring: symmetric convolution, ~half the multiplies."""
    c = []
    for k in range(2 * NLIMB - 1):
        acc = None
        lo = max(0, k - NLIMB + 1)
        hi = min(NLIMB - 1, k)
        i = lo
        while i < k - i:
            term = a[i] * a[k - i]
            term = term + term
            acc = term if acc is None else acc + term
            i += 1
        if 2 * i == k:
            term = a[i] * a[i]
            acc = term if acc is None else acc + term
        c.append(acc)
    return _conv_carry_fold(c)


def _fcanon(x):
    """Canonical representative in [0, p) (edj.fcanon, list form)."""
    t = list(x)
    t[0] = t[0] + 19
    for k in range(NLIMB - 1):
        cr = t[k] >> RADIX
        t[k] = t[k] - (cr << RADIX)
        t[k + 1] = t[k + 1] + cr
    q = t[NLIMB - 1] >> 8
    r = list(x)
    r[0] = r[0] + q * 19
    r[NLIMB - 1] = r[NLIMB - 1] - (q << 8)
    for k in range(NLIMB - 1):
        cr = r[k] >> RADIX
        r[k] = r[k] - (cr << RADIX)
        r[k + 1] = r[k + 1] + cr
    return r


def _fiszero(x):
    xc = _fcanon(x)
    acc = xc[0] == 0
    for limb in xc[1:]:
        acc = acc & (limb == 0)
    return acc


def _feq(a, b):
    return _fiszero(_fsub(a, b))


def _where_fe(mask, a, b):
    return [jnp.where(mask, x, y) for x, y in zip(a, b)]


def _sqn(x, n):
    import jax.lax as lax
    if n <= 4:
        return functools.reduce(lambda acc, _: _fsq(acc), range(n), x)

    def body(i, acc):
        return tuple(_fsq(list(acc)))
    return list(lax.fori_loop(0, n, body, tuple(x)))


def _pow_p58(x):
    """x^((p-5)/8), ref10 pow22523 chain (as edj.pow_p58)."""
    z2 = _fsq(x)
    z9 = _fmul(_sqn(z2, 2), x)
    z11 = _fmul(z9, z2)
    z22 = _fsq(z11)
    z_5_0 = _fmul(z22, z9)
    z_10_0 = _fmul(_sqn(z_5_0, 5), z_5_0)
    z_20_0 = _fmul(_sqn(z_10_0, 10), z_10_0)
    z_40_0 = _fmul(_sqn(z_20_0, 20), z_20_0)
    z_50_0 = _fmul(_sqn(z_40_0, 10), z_10_0)
    z_100_0 = _fmul(_sqn(z_50_0, 50), z_50_0)
    z_200_0 = _fmul(_sqn(z_100_0, 100), z_100_0)
    z_250_0 = _fmul(_sqn(z_200_0, 50), z_50_0)
    return _fmul(_sqn(z_250_0, 2), x)


def _const_fe(value_limbs, like):
    return [jnp.full_like(like, v) for v in value_limbs]


def _decompress(y, sign):
    """(x, ok) from y limbs + sign bit (edj.decompress, list form)."""
    yy = _fsq(y)
    one = _const_fe(_ONE, y[0])
    u = _fsub(yy, one)
    v = _fadd(_fmul_const(yy, _D), one)
    v2 = _fsq(v)
    v3 = _fmul(v2, v)
    v7 = _fmul(_fsq(v3), v)
    x = _fmul(_fmul(u, v3), _pow_p58(_fmul(u, v7)))
    vxx = _fmul(v, _fsq(x))
    is_root = _feq(vxx, u)
    is_neg_root = _fiszero(_fadd(vxx, u))
    x = _where_fe(is_neg_root & ~is_root, _fmul_const(x, _SQRT_M1), x)
    ok = is_root | is_neg_root
    xc = _fcanon(x)
    x_zero = xc[0] == 0
    for limb in xc[1:]:
        x_zero = x_zero & (limb == 0)
    ok = ok & ~(x_zero & (sign == 1))
    parity = xc[0] & 1
    x = _where_fe(parity != sign, _fneg(xc), xc)
    return x, ok


# -------------------------------------------------------------- point ops

def _pt_double(X, Y, Z, T):
    A = _fsq(X)
    B = _fsq(Y)
    Zs = _fsq(Z)
    C = _fadd(Zs, Zs)
    E = _fsub(_fsub(_fsq(_fadd(X, Y)), A), B)
    G = _fsub(B, A)
    F = _fsub(G, C)
    H = _fsub(_fneg(A), B)
    return _fmul(E, F), _fmul(G, H), _fmul(F, G), _fmul(E, H)


def _pt_add(X1, Y1, Z1, T1, X2, Y2, Z2, T2):
    A = _fmul(_fsub(Y1, X1), _fsub(Y2, X2))
    B = _fmul(_fadd(Y1, X1), _fadd(Y2, X2))
    C = _fmul(_fmul_const(T1, _TWOD), T2)
    ZZ = _fmul(Z1, Z2)
    Dv = _fadd(ZZ, ZZ)
    E = _fsub(B, A)
    F = _fsub(Dv, C)
    G = _fadd(Dv, C)
    H = _fadd(B, A)
    return _fmul(E, F), _fmul(G, H), _fmul(F, G), _fmul(E, H)


def _pt_add_prescaled(X1, Y1, Z1, T1, X2, Y2, Z2, T2_2d):
    A = _fmul(_fsub(Y1, X1), _fsub(Y2, X2))
    B = _fmul(_fadd(Y1, X1), _fadd(Y2, X2))
    C = _fmul(T1, T2_2d)
    Dv = _fmul(_fadd(Z1, Z1), Z2)
    E = _fsub(B, A)
    F = _fsub(Dv, C)
    G = _fadd(Dv, C)
    H = _fadd(B, A)
    return _fmul(E, F), _fmul(G, H), _fmul(F, G), _fmul(E, H)


def _pt_add_niels_const(X1, Y1, Z1, T1, n_sub, n_add, n_t2d):
    """Mixed add with a CONSTANT niels point, each coord a python-int
    limb list (selected per-lane before the call)."""
    A = _fmul(_fsub(Y1, X1), n_sub)
    B = _fmul(_fadd(Y1, X1), n_add)
    C = _fmul(T1, n_t2d)
    Dv = _fadd(Z1, Z1)
    E = _fsub(B, A)
    F = _fsub(Dv, C)
    G = _fadd(Dv, C)
    H = _fadd(B, A)
    return _fmul(E, F), _fmul(G, H), _fmul(F, G), _fmul(E, H)


def _select_const_table(dig, table):
    """Per-lane select from a [16, 20] CONSTANT table: limb k becomes
    sum_d (dig==d) * table[d,k] with the scalars folded as immediates."""
    masks = [(dig == d) for d in range(16)]
    out = []
    for k in range(NLIMB):
        acc = None
        for d in range(16):
            v = int(table[d, k])
            if v == 0:
                continue
            term = jnp.where(masks[d], v, 0)
            acc = term if acc is None else acc + term
        out.append(acc if acc is not None else jnp.zeros_like(dig))
    return out


def _select_batched_table(dig, entries):
    """Per-lane select of one of 16 runtime points (tuples of limb
    lists): tree of where-selects on the 4 digit bits."""
    b0 = (dig & 1) == 1
    b1 = (dig & 2) == 2
    b2 = (dig & 4) == 4
    b3 = (dig & 8) == 8

    def sel(mask, pa, pb):
        return tuple([jnp.where(mask, x, y) for x, y in zip(ca, cb)]
                     for ca, cb in zip(pa, pb))

    lvl1 = [sel(b0, entries[2 * i + 1], entries[2 * i]) for i in range(8)]
    lvl2 = [sel(b1, lvl1[2 * i + 1], lvl1[2 * i]) for i in range(4)]
    lvl3 = [sel(b2, lvl2[2 * i + 1], lvl2[2 * i]) for i in range(2)]
    return sel(b3, lvl3[1], lvl3[0])


# ------------------------------------------------------------- the kernel

def _verify_kernel_pallas(ay_ref, asign_ref, ry_ref, rsign_ref,
                          sd_ref, kd_ref, ok_ref):
    import jax.lax as lax
    from jax.experimental import pallas as pl   # noqa: F401 (pl.ds below)

    ay = [ay_ref[i] for i in range(NLIMB)]
    ry = [ry_ref[i] for i in range(NLIMB)]
    asign = asign_ref[0]
    rsign = rsign_ref[0]

    ax, ok_a = _decompress(ay, asign)
    rx, ok_r = _decompress(ry, rsign)

    one = _const_fe(_ONE, ay[0])
    zero = _const_fe([0] * NLIMB, ay[0])

    # per-signature table: d * (-A) for d = 0..15, extended coords
    nax = _fneg(ax)
    na = (nax, ay, one, _fmul(nax, ay))
    tab = [(zero, one, one, zero), na]
    for d in range(2, 16):
        if d % 2 == 0:
            tab.append(_pt_double(*tab[d // 2]))
        else:
            tab.append(_pt_add(*tab[d - 1], *na))
    # pre-scale T by 2d so the loop add costs 8 muls
    tab = [(X, Y, Z, _fmul_const(T, _TWOD)) for (X, Y, Z, T) in tab]

    def body(i, st):
        w = 63 - i
        X, Y, Z, T = [list(c) for c in st]
        for _ in range(4):
            X, Y, Z, T = _pt_double(X, Y, Z, T)
        s_dig = sd_ref[pl.ds(w, 1)][0]
        k_dig = kd_ref[pl.ds(w, 1)][0]
        n_sub = _select_const_table(s_dig, _NB_SUB)
        n_add = _select_const_table(s_dig, _NB_ADD)
        n_t2d = _select_const_table(s_dig, _NB_T2D)
        X, Y, Z, T = _pt_add_niels_const(X, Y, Z, T, n_sub, n_add, n_t2d)
        x2, y2, z2, t2d2 = _select_batched_table(k_dig, tab)
        X, Y, Z, T = _pt_add_prescaled(X, Y, Z, T, x2, y2, z2, t2d2)
        return tuple(tuple(c) for c in (X, Y, Z, T))

    ident = tuple(tuple(c) for c in (zero, one, one, zero))
    X, Y, Z, _T = lax.fori_loop(0, 64, body, ident)

    ok_x = _fiszero(_fsub(_fmul(rx, list(Z)), list(X)))
    ok_y = _fiszero(_fsub(_fmul(ry, list(Z)), list(Y)))
    ok = ok_a & ok_r & ok_x & ok_y
    ok_ref[0] = ok.astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _build_verify(n_blocks: int, interpret: bool = False):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = (n_blocks,)
    fe_spec = pl.BlockSpec((NLIMB, BLOCK_R, BLOCK_L),
                           lambda i: (0, i, 0))
    sign_spec = pl.BlockSpec((1, BLOCK_R, BLOCK_L), lambda i: (0, i, 0))
    dig_spec = pl.BlockSpec((64, BLOCK_R, BLOCK_L), lambda i: (0, i, 0))
    nb8 = n_blocks * BLOCK_R

    def to_blocks(x_bt):
        """[B, K] int32 → [K, nb8, 128] (limb-major, 8x128 tiles)."""
        return jnp.transpose(x_bt, (1, 0)).reshape(
            x_bt.shape[1], nb8, BLOCK_L)

    # ONE jitted function does digit extraction + relayout + the pallas
    # call: each un-jitted jnp op would otherwise be its own dispatch
    # with its own HBM round trip for the intermediates
    def run(ay, asign, ry, rsign, s_words, k_words):
        sd = to_blocks(edj._digits4(s_words))
        kd = to_blocks(edj._digits4(k_words))
        out = pl.pallas_call(
            _verify_kernel_pallas,
            grid=grid,
            in_specs=[fe_spec, sign_spec, fe_spec, sign_spec,
                      dig_spec, dig_spec],
            out_specs=sign_spec,
            out_shape=jax.ShapeDtypeStruct(
                (1, nb8, BLOCK_L), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(to_blocks(ay), to_blocks(asign[:, None].astype(jnp.int32)),
          to_blocks(ry), to_blocks(rsign[:, None].astype(jnp.int32)),
          sd, kd)
        return out.reshape(nb8 * BLOCK_L) != 0

    jitted = jax.jit(run)
    # the store engages from what can be observed, not from a knob: a
    # compiled Mosaic kernel on a TPU. Interpret mode and the CPU
    # backend (tests, rehearsals, served nodes) trace as they always did
    from plenum_tpu.ops import mesh as mesh_mod
    if interpret or mesh_mod.probe_platform() != "tpu":
        return jitted
    return _stored_verify(jitted, n_blocks)


def _stored_verify(jitted, n_blocks: int):
    """The compiled kernel out of the built-kernel store
    (ops/kernel_store.py): every process but the first on a cache loads
    it and pays neither this file's Python (90-125 s of tracing and
    lowering to Mosaic on the chip host) nor a compile."""
    import jax
    from plenum_tpu.ops import kernel_store
    from plenum_tpu.ops import mesh as mesh_mod
    batch = n_blocks * BLOCK
    specs = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
        ((batch, NLIMB), jnp.int32), ((batch,), jnp.int32),
        ((batch, NLIMB), jnp.int32), ((batch,), jnp.int32),
        ((batch, 8), jnp.uint32), ((batch, 8), jnp.uint32))]
    key = kernel_store.kernel_key(
        [__file__, edj.__file__],
        {"n_blocks": n_blocks, "vmem_limit_bytes": VMEM_LIMIT_BYTES},
        kernel_store.runtime_versions(),
        mesh_mod.device_facts()["kind"])
    return kernel_store.default_store().load_or_build(
        "ed25519_verify-%d" % n_blocks, key,
        lambda: jitted.lower(*specs).compile(),
        lambda fn: known_answer(fn, batch))


def known_answer(fn, batch: int) -> bool:
    """One launch of a fixed batch (valid signatures and three
    corruptions of each, crypto/fixtures.py) through `fn`, its verdicts
    compared item for item with the host reference: what keeps "an
    accepted signature is a valid one" true of a kernel that was loaded
    and not built here."""
    from plenum_tpu.crypto.batch_verifier import OpenSSLVerifier
    from plenum_tpu.crypto.fixtures import make_known_answer_batch
    from plenum_tpu.ops import mesh as mesh_mod
    msgs, sigs, vks = make_known_answer_batch()
    want = OpenSSLVerifier().verify_batch(list(zip(msgs, sigs, vks)))
    arrays, valid = edj.host_pack(msgs, sigs, vks)
    got = np.asarray(fn(*mesh_mod.pad_rows(arrays, batch)))
    return (got[:len(msgs)] & valid).tolist() == want


def verify_kernel(ay, asign, ry, rsign, s_words, k_words,
                  interpret: bool = False):
    """Drop-in equivalent of edj._verify_kernel (same arguments, same
    bool[B] result) running the single-launch Pallas kernel. Batch is
    padded to a BLOCK multiple internally."""

    B = int(ay.shape[0])
    pad = (-B) % BLOCK
    if pad:
        def padb(x):
            widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, widths)
        ay, asign, ry, rsign, s_words, k_words = (
            padb(x) for x in (ay, asign, ry, rsign, s_words, k_words))
    total = B + pad
    ok = _build_verify(total // BLOCK, interpret)(
        ay, asign, ry, rsign, s_words, k_words)
    return ok[:B]
