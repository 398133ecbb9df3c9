"""The Pallas whole-verify kernel (ops/ed25519_pallas.py).

The field/point helpers are plain array expressions, so they are unit-
tested here against python-int ground truth with numpy standing in for
jnp — no XLA, no device, every limb-discipline subtlety (carry wraps,
the finalize-after-add/sub invariant, fcanon's multi-p handling)
pinned down exactly. The WHOLE kernel body runs the same way
(test_whole_kernel_body_matches_reference): refs are numpy arrays, the
ladder's fori_loop a Python loop. The compiled kernel is checked where
it can be: tests/test_tpu_compile.py compiles it for a described v5e,
and chip_smoke.py compares its verdicts with OpenSSL on the chip.
"""
import functools
import random

import numpy as np
import pytest

import plenum_tpu.ops.ed25519_pallas as edp
from plenum_tpu.ops import ed25519_jax as edj

P = edj.P


@pytest.fixture
def numpy_field(monkeypatch):
    """Run the module's array code on numpy (no jax op dispatch)."""
    monkeypatch.setattr(edp, "jnp", np)
    monkeypatch.setattr(
        edp, "_sqn",
        lambda x, n: functools.reduce(lambda a, _: edp._fsq(a), range(n), x))


def _to_blocks(vals):
    arr = np.stack([edj._int_to_limbs(v) for v in vals])
    return [np.ascontiguousarray(arr[:, i].reshape(1, len(vals)))
            for i in range(edp.NLIMB)]


def _value(limbs, j):
    return sum(int(l[0, j]) << (13 * i) for i, l in enumerate(limbs)) % P


def test_field_ops_match_integers(numpy_field):
    rng = random.Random(3)
    a_int = [rng.randrange(P) for _ in range(128)]
    b_int = [rng.randrange(P) for _ in range(128)]
    A, B = _to_blocks(a_int), _to_blocks(b_int)
    m = edp._fmul(A, B)
    s = edp._fsq(A)
    mc = edp._fmul_const(A, edp._TWOD)
    sub = edp._fsub(A, B)
    add = edp._fadd(A, B)
    td = edj._limbs_to_int(np.asarray(edp._TWOD, dtype=np.int64))
    for j in range(128):
        assert _value(m, j) == a_int[j] * b_int[j] % P
        assert _value(s, j) == a_int[j] * a_int[j] % P
        assert _value(mc, j) == a_int[j] * td % P
        assert _value(sub, j) == (a_int[j] - b_int[j]) % P
        assert _value(add, j) == (a_int[j] + b_int[j]) % P


def test_pow_p58_and_square_chain(numpy_field):
    rng = random.Random(4)
    vals = [rng.randrange(P) for _ in range(128)]
    A = _to_blocks(vals)
    r = edp._pow_p58(A)
    for j in range(0, 128, 17):
        assert _value(r, j) == pow(vals[j], (P - 5) // 8, P)
    x = A
    for _ in range(50):
        x = edp._fsq(x)
    for j in range(0, 128, 31):
        assert _value(x, j) == pow(vals[j], 2 ** 50, P)
    # the invariant every chain preserves: limbs stay inside radix
    assert max(int(l.max()) for l in x) <= edp.MASK + 1


def test_feq_handles_spread_representations(numpy_field):
    """feq/fiszero must see through the +8p spread and the finalize
    residues — the exact shapes decompress's root checks produce."""
    rng = random.Random(5)
    vals = [rng.randrange(P) for _ in range(128)]
    A = _to_blocks(vals)
    negA = _to_blocks([(P - v) % P for v in vals])
    assert np.asarray(edp._fiszero(edp._fadd(A, negA))).all()
    assert np.asarray(edp._feq(A, edp._fsub(edp._fadd(A, A), A))).all()
    B = _to_blocks([(v + 1) % P for v in vals])
    assert not np.asarray(edp._feq(A, B)).any()


def _curve_points(count, seed):
    rng = random.Random(seed)
    pts = []
    for _ in range(count):
        k = rng.randrange(1, 2 ** 252)
        base = edj._base_affine()
        acc = None
        while k:
            if k & 1:
                acc = base if acc is None else edj._ed_add_affine(acc, base)
            base = edj._ed_add_affine(base, base)
            k >>= 1
        pts.append(acc)
    return pts


def test_decompress_recovers_x(numpy_field):
    pts = _curve_points(16, seed=4)
    # pad the lane axis to a full vector with copies of point 0
    pts_lane = (pts * 8)[:128]
    ay = np.stack([edj._int_to_limbs(y) for (_, y) in pts_lane])
    sg = np.asarray([x & 1 for (x, _) in pts_lane],
                    dtype=np.int32).reshape(1, 128)
    ayl = [np.ascontiguousarray(ay[:, i].reshape(1, 128))
           for i in range(edp.NLIMB)]
    x, ok = edp._decompress(ayl, sg)
    assert np.asarray(ok).all()
    for j in range(16):
        assert _value(x, j) == pts_lane[j][0] % P
    # flipped sign bit must yield the OTHER root (-x)
    x2, ok2 = edp._decompress(ayl, 1 - sg)
    assert np.asarray(ok2).all()
    for j in range(16):
        assert _value(x2, j) == (P - pts_lane[j][0]) % P


class _NpRef:
    """Stand-in for a Pallas ref over a numpy array: integer indexing,
    pl.ds(start, size) slices, and the output store."""

    def __init__(self, arr):
        self.arr = arr

    def __getitem__(self, idx):
        if hasattr(idx, "start") and hasattr(idx, "size"):     # pl.ds
            return self.arr[int(idx.start):int(idx.start) + int(idx.size)]
        return self.arr[idx]

    def __setitem__(self, idx, val):
        self.arr[idx] = val


def test_whole_kernel_body_matches_reference(numpy_field, monkeypatch):
    """The ENTIRE kernel body (_verify_kernel_pallas: both
    decompressions, the per-signature window table, the 64-window
    double-scalar ladder, the final compare) on one small tile, against
    the RFC 8032 reference — valid signatures, a flipped signature bit,
    a wrong message, a wrong key and an off-curve R. (The compiled
    kernel's cross-check runs on the chip: chip_smoke.py.)"""
    import jax.lax
    from plenum_tpu.crypto import ed25519 as ref
    from plenum_tpu.crypto.fixtures import make_signed_batch

    def python_fori(lo, hi, body, init):
        st = init
        for i in range(lo, hi):
            st = body(i, st)
        return st

    monkeypatch.setattr(jax.lax, "fori_loop", python_fori)
    n = 8
    msgs, sigs, vks = (list(x) for x in make_signed_batch(n, seed=5))
    sigs[1] = sigs[1][:10] + bytes([sigs[1][10] ^ 1]) + sigs[1][11:]
    msgs[2] = msgs[2] + b"!"
    vks[3] = vks[4]
    # R.y = 2 is not on the curve: u/v has no square root
    sigs[5] = (2).to_bytes(32, "little") + sigs[5][32:]
    want = [ref.verify(m, s, v) for m, s, v in zip(msgs, sigs, vks)]
    assert want == [True, False, False, False, True, False, True, True]

    arrays, valid = edj.host_pack(msgs, sigs, vks)
    ay, asign, ry, rsign, s_words, k_words = (np.asarray(a) for a in arrays)

    def tiles(x_bt):                  # [B, K] -> [K, 1, B], as to_blocks
        return np.ascontiguousarray(x_bt.T.reshape(x_bt.shape[1], 1, n))

    ok = np.zeros((1, 1, n), dtype=np.int32)
    edp._verify_kernel_pallas(
        _NpRef(tiles(ay)), _NpRef(tiles(asign[:, None])),
        _NpRef(tiles(ry)), _NpRef(tiles(rsign[:, None])),
        _NpRef(tiles(np.asarray(edj._digits4(s_words)))),
        _NpRef(tiles(np.asarray(edj._digits4(k_words)))), _NpRef(ok))
    got = [bool(o) and bool(v) for o, v in zip(ok[0, 0], valid)]
    assert got == want
