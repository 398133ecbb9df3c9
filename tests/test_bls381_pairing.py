"""Device BLS12-381 pairing / MSM tests (ops/bls381_pairing.py and the
crypto/bls_ops routing above it).

The suite-wide conftest pins PLENUM_TPU_BLS_TOWER=native so unrelated
consensus/client tests never pay a Miller-loop compile; the device
tests here force the family back on through the mesh step-down
registry (the sha256-Pallas test precedent) and stay inside TWO small
bucket shapes — (Bp=8, Pp=2) pairs and Np=8 MSM — so the persistent
compile cache (.jax_cache) makes every run after the first load in
milliseconds.

Verdict parity is the contract under test: the device kernel must be
bit-identical to ``bls_ops.pairing_job_host`` (the python/native
reference semantics) on EVERY adversarial shape — bit-flipped
aggregates, identity and non-subgroup points, one-sided infinities,
wrong and reordered key sets, ragged jobs shorter than the bucket.
"""
import os
import random

import pytest

from plenum_tpu.crypto import bls12_381 as B
from plenum_tpu.crypto import bls_ops as bls
from plenum_tpu.crypto.bls12_381 import (
    G1_GEN, G2_GEN, Q, R, g1_compress, g1_mul, g2_compress, g2_mul,
    g2_neg)

G1_INF = bytes([0xC0] + [0] * 47)
G2_INF = bytes([0xC0] + [0] * 95)


@pytest.fixture
def tower_on():
    """Force the device tower family ON through the step-down registry
    (conftest pins the env to native for everyone else), restoring the
    prior state afterwards."""
    from plenum_tpu.ops import mesh as mesh_mod
    with mesh_mod._PROBE_LOCK:
        prev = mesh_mod._PALLAS_BACKENDS.get(bls.BLS_TOWER_ENV)
        mesh_mod._PALLAS_BACKENDS[bls.BLS_TOWER_ENV] = True
    yield
    with mesh_mod._PROBE_LOCK:
        if prev is None:
            mesh_mod._PALLAS_BACKENDS.pop(bls.BLS_TOWER_ENV, None)
        else:
            mesh_mod._PALLAS_BACKENDS[bls.BLS_TOWER_ENV] = prev


def _good_pair_job(sk=7, msg=b"m"):
    """A verifying 2-pair job: e(sig,-G2)·e(H(m),pk) == 1."""
    pk = g2_mul(G2_GEN, sk)
    h = B.hash_to_g1(msg)
    sig = g1_mul(h, sk)
    return [(g1_compress(sig), g2_compress(g2_neg(G2_GEN))),
            (g1_compress(h), g2_compress(pk))]


def _non_subgroup_g1():
    """An on-curve G1 point OUTSIDE the r-order subgroup (the cofactor
    is > 1, so clearing it from a hashed point and adding the generator
    stays on curve; scalar-mult by r then almost surely != identity)."""
    x = 3
    while True:
        yy = (x * x * x + 4) % Q
        y = pow(yy, (Q + 1) // 4, Q)
        if y * y % Q == yy:
            p = (x, y)
            if not B.g1_in_subgroup(p):
                return p
        x += 1


# ------------------------------------------------------------ host path


def test_pairing_job_host_semantics():
    """The reference semantics the device kernel is pinned to, stated
    on the host path alone: neutral both-infinity pairs, failing
    one-sided infinities, failing undecodable bytes, empty product=1."""
    good = _good_pair_job()
    assert bls.pairing_job_host(good) is True
    # both-infinity pair is NEUTRAL: appending it changes nothing
    assert bls.pairing_job_host(good + [(G1_INF, G2_INF)]) is True
    # one-sided infinity fails the job even when the rest verifies
    assert bls.pairing_job_host(good + [(G1_INF, g2_compress(G2_GEN))]) \
        is False
    assert bls.pairing_job_host(good + [(g1_compress(G1_GEN), G2_INF)]) \
        is False
    # undecodable bytes fail the job, never raise
    assert bls.pairing_job_host([(b"\x00" * 48, g2_compress(G2_GEN))]) \
        is False
    assert bls.pairing_job_host([(b"junk", b"junk")]) is False
    # all pairs neutral -> empty product -> 1
    assert bls.pairing_job_host([(G1_INF, G2_INF)]) is True
    # wrong message -> product != 1
    bad = [good[0], _good_pair_job(msg=b"other")[1]]
    assert bls.pairing_job_host(bad) is False


def test_threshold_and_env_gate(monkeypatch):
    from plenum_tpu.common.config import Config
    monkeypatch.setattr(Config, "BLS_PAIRING_DEVICE_MIN", 4,
                        raising=False)
    assert bls.pairing_device_ready(3) is False
    monkeypatch.setattr(Config, "BLS_DEVICE_PAIRING", False,
                        raising=False)
    assert bls.pairing_device_ready(100) is False


@pytest.mark.parametrize("stage", ["run", "compile"])
def test_device_failure_policy(monkeypatch, tower_on, stage):
    """A kernel that dies at RUN time (surfacing at collect) must serve
    host verdicts AND step the family down permanently — counted, so no
    run can pass host verdicts off as device ones (the sha256/ed25519
    contract). A failure in the dispatch half (trace / lowering /
    compile) is a program bug and raises."""
    import sys
    import types
    from plenum_tpu.ops import mesh as mesh_mod

    fake = types.ModuleType("plenum_tpu.ops.bls381_pairing")

    def _boom(*_a):
        raise RuntimeError("induced %s failure" % stage)
    fake.pairing_dispatch = _boom if stage == "compile" \
        else (lambda jobs: "handles")
    fake.pairing_collect = _boom
    monkeypatch.setitem(sys.modules, "plenum_tpu.ops.bls381_pairing",
                        fake)
    jobs = [_good_pair_job(sk=k) for k in (2, 3, 4, 5)]
    jobs.append([(b"\x00" * 48, g2_compress(G2_GEN))])
    before = mesh_mod.step_down_counts().get(bls.BLS_TOWER_ENV, 0)
    if stage == "compile":
        with pytest.raises(RuntimeError, match="induced compile"):
            bls.multi_pairing_is_one_jobs(jobs)
        assert mesh_mod.xla_backend_enabled(bls.BLS_TOWER_ENV) is True
        assert mesh_mod.step_down_counts().get(
            bls.BLS_TOWER_ENV, 0) == before
        return
    got = bls.multi_pairing_is_one_jobs(jobs)
    assert got == [True, True, True, True, False]
    assert mesh_mod.xla_backend_enabled(bls.BLS_TOWER_ENV) is False
    assert mesh_mod.step_down_counts()[bls.BLS_TOWER_ENV] == before + 1
    # the step-down sticks: later batches go host without retrying
    assert bls.pairing_device_ready(len(jobs)) is False


def test_batch_apis_fall_back_to_scalar_below_threshold():
    """Below BLS_PAIRING_DEVICE_MIN the verifier batch APIs are the
    scalar loop verbatim (prepared-pairing caches and all)."""
    from plenum_tpu.crypto.bls import (
        BlsCryptoSignerPlenum, BlsCryptoVerifierPlenum)
    v = BlsCryptoVerifierPlenum()
    s, _proof = BlsCryptoSignerPlenum.generate(b"\x01")
    msg = b"tick"
    checks = [(s.sign(msg), msg, s.pk), (s.sign(msg), b"other", s.pk)]
    assert v.verify_sigs_batch(checks) == [True, False]
    assert v.verify_multi_sigs_batch(
        [(s.sign(msg), msg, [s.pk]), (s.sign(msg), msg, [])]) \
        == [True, False]


def test_abc_default_batch_is_scalar_loop():
    from plenum_tpu.crypto.bls import BlsCryptoVerifier

    class Fixed(BlsCryptoVerifier):
        def verify_sig(self, signature, message, pk):
            return signature == "ok"

        def verify_multi_sig(self, signature, message, pks):
            return signature == "ok"

        def create_multi_sig(self, signatures):
            return ""

        def verify_key_proof_of_possession(self, key_proof, pk):
            return False

    v = Fixed()
    assert v.verify_sigs_batch(
        [("ok", b"", ""), ("no", b"", "")]) == [True, False]
    assert v.verify_multi_sigs_batch(
        [("no", b"", []), ("ok", b"", [])]) == [False, True]


# ---------------------------------------------------------- device path


def test_device_verdicts_pin_host_reference(tower_on):
    """THE parity pin: one bucketed launch over an adversarial job set
    — bit-flipped signature, one-sided identity, neutral identity pair,
    non-subgroup point, wrong message, ragged single-pair jobs — must
    return exactly the host reference verdict for every job."""
    from plenum_tpu.ops import bls381_pairing as P

    rng = random.Random(17)
    good = _good_pair_job(sk=rng.randrange(2, R))
    flip = bytearray(good[0][0])
    flip[19] ^= 0x10
    ns = _non_subgroup_g1()
    cp = B.g1_mul(G1_GEN, 5)
    cancel = [(g1_compress(cp), g2_compress(G2_GEN)),
              (g1_compress(B.g1_neg(cp)), g2_compress(G2_GEN))]
    jobs = [
        good,                                            # True
        [good[0], _good_pair_job(msg=b"z")[1]],          # wrong msg
        [(bytes(flip), good[0][1]), good[1]],            # bit-flipped
        [(G1_INF, g2_compress(g2_mul(G2_GEN, 5)))],      # one-sided inf
        cancel,                                          # e(P,Q)e(-P,Q)=1
        [(g1_compress(ns), g2_compress(G2_GEN))],        # non-subgroup
        [good[1], (G1_INF, G2_INF)],                     # neutral + !=1
        [(G1_INF, G2_INF), (G1_INF, G2_INF)],            # all neutral
    ]
    want = [bls.pairing_job_host(j) for j in jobs]
    assert want == [True, False, False, False,
                    True, False, False, True]
    verdict, _ok = P.pairing_jobs(jobs)
    assert verdict.tolist() == want


def test_verifier_batch_matches_scalar_on_device(tower_on):
    """verify_sigs_batch / verify_multi_sigs_batch through the device
    path agree item-for-item with the scalar native/python calls —
    including wrong, subset and reordered key sets."""
    from plenum_tpu.crypto.bls import (
        BlsCryptoSignerPlenum, BlsCryptoVerifierPlenum, b58_decode,
        b58_encode)
    v = BlsCryptoVerifierPlenum()
    signers = [BlsCryptoSignerPlenum.generate(bytes([i]))[0]
               for i in range(4)]
    msg = b"batch"
    checks = [(s.sign(msg), msg, s.pk) for s in signers]
    checks.append((signers[0].sign(b"x"), msg, signers[0].pk))
    flip = list(checks[0])
    raw = bytearray(b58_decode(flip[0]))
    raw[20] ^= 1
    flip[0] = b58_encode(bytes(raw))
    checks.append(tuple(flip))
    got = v.verify_sigs_batch(checks)
    assert got == [v.verify_sig(*c) for c in checks]
    assert got == [True] * 4 + [False, False]

    sigs = [s.sign(msg) for s in signers]
    agg = v.create_multi_sig(sigs)
    pks = [s.pk for s in signers]
    foreign = BlsCryptoSignerPlenum.generate(b"\xee")[0]
    ms = [(agg, msg, pks),
          (agg, msg, list(reversed(pks))),      # reordered: same sum
          (agg, msg, pks[:3]),                  # subset: wrong key set
          (agg, b"other", pks),
          (agg, msg, pks[:3] + [foreign.pk]),   # swapped-in wrong key
          (sigs[0], msg, [signers[0].pk]),      # 1-member multi
          (agg, msg, []),                       # pre-check fail, no job
          (agg, msg, pks + [pks[0]])]           # duplicated key
    got_m = v.verify_multi_sigs_batch(ms)
    assert got_m == [v.verify_multi_sig(*c) for c in ms]
    assert got_m == [True, True, False, False, False, True, False,
                     False]


def test_msm_matches_host_double_and_add(tower_on):
    rng = random.Random(23)
    ks = [rng.randrange(1, R) for _ in range(8)]
    ss = [rng.randrange(1, R) for _ in range(8)]
    pts = [g1_compress(g1_mul(G1_GEN, k)) for k in ks]
    got = bls.g1_msm(pts, ss)
    want = g1_mul(G1_GEN, sum(k * s for k, s in zip(ks, ss)) % R)
    assert got == want
    # identity rows and zero scalars fold away on both paths
    pts2 = pts[:6] + [G1_INF, g1_compress(g1_mul(G1_GEN, 9))]
    ss2 = ss[:6] + [12345, 0]
    got2 = bls.g1_msm(pts2, ss2)
    want2 = g1_mul(G1_GEN, sum(k * s for k, s in
                               zip(ks[:6], ss[:6])) % R)
    assert got2 == want2
    # undecodable input raises on the device path like the host path
    with pytest.raises(ValueError):
        bls.g1_msm([b"\x00" * 48] * 8, ss)


def test_g2_aggregate_jobs_cross_check(tower_on):
    from plenum_tpu.ops import bls381_pairing as P
    sets = [[g2_compress(g2_mul(G2_GEN, k)) for k in (3, 5)],
            [g2_compress(g2_mul(G2_GEN, 9)), G2_INF]]
    pts, ok = P.g2_aggregate_collect(P.g2_aggregate_dispatch(sets, 2))
    assert ok.tolist() == [True, True]
    w0 = B.g2_add(g2_mul(G2_GEN, 3), g2_mul(G2_GEN, 5))
    w1 = g2_mul(G2_GEN, 9)
    assert pts[0] == ((w0[0].c0, w0[0].c1), (w0[1].c0, w0[1].c1))
    assert pts[1] == ((w1[0].c0, w1[0].c1), (w1[1].c0, w1[1].c1))


# ------------------------------------------------------------- slow sweep


@pytest.mark.slow
@pytest.mark.skipif(not os.environ.get("RUN_SLOW_OPS"),
                    reason="set RUN_SLOW_OPS=1 to compile extra "
                           "pairing bucket shapes")
def test_randomized_job_shapes_pin_host_reference(tower_on):
    """Randomized ragged batches across MULTIPLE bucket shapes — every
    device verdict byte-equal to the host reference. Opt-in: each new
    (Bp, Pp) bucket costs a fresh Miller compile on CPU."""
    from plenum_tpu.ops import bls381_pairing as P

    rng = random.Random(5)
    for trial in range(3):
        n_jobs = rng.choice([2, 3, 5, 9])
        jobs = []
        for _ in range(n_jobs):
            n_pairs = rng.choice([1, 2, 3])
            kind = rng.random()
            if kind < 0.5:
                job = _good_pair_job(sk=rng.randrange(2, R),
                                     msg=bytes([trial]))
                jobs.append(job[:n_pairs] if n_pairs < 2 else job)
            elif kind < 0.7:
                jobs.append([(g1_compress(g1_mul(G1_GEN,
                                                 rng.randrange(2, R))),
                              g2_compress(g2_mul(G2_GEN,
                                                 rng.randrange(2, R))))
                             for _ in range(n_pairs)])
            elif kind < 0.85:
                raw = bytearray(g1_compress(g1_mul(
                    G1_GEN, rng.randrange(2, R))))
                raw[rng.randrange(1, 48)] ^= 1 << rng.randrange(8)
                jobs.append([(bytes(raw), g2_compress(G2_GEN))])
            else:
                jobs.append([(G1_INF, G2_INF)] * n_pairs)
        want = [bls.pairing_job_host(j) for j in jobs]
        verdict, _ok = P.pairing_jobs(jobs)
        assert verdict.tolist() == want, (trial, jobs)
