"""Device-mesh crypto dispatch (ops/mesh.py) on the virtual 8-device CPU
mesh (conftest forces xla_force_host_platform_device_count=8).

The contract under test: sharded verify / BLS-aggregate / merkle results
are BIT-IDENTICAL to the single-device path across ragged batch sizes
(including sizes < n_devices and non-divisible sizes), the computation's
sharding actually spans every device, and the passthrough gate engages
below MESH_SHARD_MIN / when disabled.

Batch shapes are deliberately reused across tests so the process-wide
jit cache amortizes XLA compiles.
"""
import numpy as np
import pytest

from plenum_tpu.common.config import Config
from plenum_tpu.crypto.fixtures import make_signed_batch
from plenum_tpu.ops import mesh as mesh_mod


@pytest.fixture
def mesh():
    """Save/restore the process-wide mesh configuration around a test."""
    m = mesh_mod.get_mesh()
    prior = (m.enabled, m.max_devices, m.shard_min)
    yield m
    mesh_mod.configure(enabled=prior[0], max_devices=prior[1],
                       shard_min=prior[2])


def _signed_items(n, tamper=()):
    msgs, sigs, vks = make_signed_batch(n, seed=3, msg_prefix=b"mesh")
    sigs = list(sigs)
    for i in tamper:
        sigs[i] = bytes(64)
    return msgs, sigs, vks


# ------------------------------------------------------------ mesh basics

def test_enumerates_forced_cpu_mesh(mesh):
    assert mesh.n_devices == 8
    assert mesh_mod.probe_platform() == "cpu"
    assert not mesh_mod.is_accelerator()


@pytest.mark.parametrize("reader", ["probe_platform", "device_facts",
                                    "is_accelerator", "mesh_devices"])
def test_backend_error_propagates(mesh, monkeypatch, reader):
    """A backend that cannot be initialised (the chip is held by
    another process, the runtime is broken) RAISES from every probe —
    it used to read as "cpu" / "one device", which let a run that lost
    its chip go on under device names. An explicit CPU pin (this suite)
    still reads "cpu" afterwards: JAX succeeds there."""
    import jax

    def unavailable(*_a, **_k):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    mesh_mod._reset_probe()
    mesh.reset_devices()
    monkeypatch.setattr(jax, "devices", unavailable)
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            if reader == "mesh_devices":
                mesh.n_devices
            else:
                getattr(mesh_mod, reader)()
        assert not mesh_mod.probed()      # the failure is not cached
    finally:
        monkeypatch.undo()
        mesh_mod._reset_probe()
        mesh.reset_devices()
    assert mesh_mod.probe_platform() == "cpu"
    assert mesh_mod.device_facts() == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": 8}
    assert mesh.n_devices == 8


def test_max_devices_cap_rounds_down_to_pow2(mesh):
    mesh_mod.configure(max_devices=6)
    assert mesh.n_devices == 4
    mesh_mod.configure(max_devices=2)
    assert mesh.n_devices == 2
    mesh_mod.configure(max_devices=0)
    assert mesh.n_devices == 8


def test_padded_size_buckets(mesh):
    mesh_mod.configure(max_devices=0)
    # 8 devices, min 8/device
    assert mesh.padded_size(3) == 64
    assert mesh.padded_size(64) == 64
    assert mesh.padded_size(65) == 128      # 16/device bucket
    assert mesh.padded_size(100) == 128
    assert mesh.padded_size(3, min_per_device=1) == 8


def test_should_shard_gate(mesh):
    mesh_mod.configure(enabled=True, shard_min=16)
    assert mesh.should_shard(16)
    assert not mesh.should_shard(15)
    mesh_mod.configure(enabled=False)
    assert not mesh.should_shard(10 ** 6)
    mesh_mod.configure(enabled=True, max_devices=1)
    assert not mesh.should_shard(10 ** 6)   # single-device host


def test_mesh_pipeline_orders_and_bounds_inflight(mesh):
    """MeshPipeline yields one result per batch IN ORDER and never
    holds more than `depth` dispatches in flight."""
    inflight = {"now": 0, "max": 0}

    def dispatch(batch):
        inflight["now"] += 1
        inflight["max"] = max(inflight["max"], inflight["now"])
        return batch * 10

    def collect(handle):
        inflight["now"] -= 1
        return handle + 1

    pipe = mesh_mod.MeshPipeline(dispatch, collect, depth=2)
    assert pipe.run(range(7)) == [i * 10 + 1 for i in range(7)]
    assert inflight["max"] == 2
    assert inflight["now"] == 0


def test_stats_counters(mesh):
    mesh_mod.configure(enabled=True, shard_min=16, max_devices=0)
    before = mesh.sharded_dispatches
    msgs, sigs, vks = _signed_items(37)
    from plenum_tpu.ops import ed25519_jax as edj
    edj.verify_batch(msgs, sigs, vks)
    stats = mesh_mod.mesh_stats()
    assert stats["sharded_dispatches"] == before + 1
    assert stats["n_devices"] == 8
    assert stats["platform"] == "cpu"
    assert stats["last_per_device_batch"] == 8   # 37 -> 64 over 8 chips


# --------------------------------------------------------- ed25519 verify

@pytest.mark.parametrize("n", [3, 5, 37, 100])
def test_sharded_verify_bit_identical_ragged(mesh, n):
    """Sizes < n_devices (3, 5) and non-divisible sizes included; bad
    signatures must stay bad in exactly the same slots."""
    from plenum_tpu.ops import ed25519_jax as edj
    tamper = {0, n - 1} if n > 1 else {0}
    msgs, sigs, vks = _signed_items(n, tamper=tamper)
    mesh_mod.configure(enabled=True, shard_min=1, max_devices=0)
    sharded = edj.verify_batch(msgs, sigs, vks)
    mesh_mod.configure(enabled=False)
    single = edj.verify_batch(msgs, sigs, vks)
    assert sharded.shape == (n,)
    assert (sharded == single).all()
    for i in range(n):
        assert sharded[i] == (i not in tamper)


def test_verify_sharding_spans_all_devices(mesh):
    from plenum_tpu.ops import ed25519_jax as edj
    mesh_mod.configure(enabled=True, shard_min=1, max_devices=0)
    msgs, sigs, vks = _signed_items(37)
    ok_dev, valid, n = edj.verify_batch_async(msgs, sigs, vks)
    assert n == 37
    assert len(ok_dev.sharding.device_set) == 8
    assert (np.asarray(ok_dev)[:n] & valid).all()


def test_verify_passthrough_below_shard_min(mesh):
    from plenum_tpu.ops import ed25519_jax as edj
    mesh_mod.configure(enabled=True, shard_min=1000, max_devices=0)
    before = mesh.passthrough_dispatches
    msgs, sigs, vks = _signed_items(37)
    ok_dev, valid, n = edj.verify_batch_async(msgs, sigs, vks)
    assert len(ok_dev.sharding.device_set) == 1
    assert mesh.passthrough_dispatches == before + 1
    assert (np.asarray(ok_dev)[:n] & valid).all()


def test_verify_passthrough_when_disabled(mesh):
    from plenum_tpu.ops import ed25519_jax as edj
    mesh_mod.configure(enabled=False, shard_min=1)
    msgs, sigs, vks = _signed_items(37)
    ok_dev, _valid, _n = edj.verify_batch_async(msgs, sigs, vks)
    assert len(ok_dev.sharding.device_set) == 1


# ----------------------------------------------------------- BLS aggregate

def test_sharded_bls_aggregate_bit_identical(mesh):
    from plenum_tpu.crypto import bls12_381 as B
    from plenum_tpu.ops import bls381_jax as bjk
    pts = [B.g1_mul(B.G1_GEN, 11 + i) for i in range(2)]
    job = [B.g1_compress(p) for p in pts]
    want = B.g1_add(pts[0], pts[1])
    bad_job = [job[0], b"\xff" * 48]        # undecodable share
    jobs = [job] * 17 + [bad_job] + [job] * 3    # ragged: 21 jobs
    mesh_mod.configure(enabled=True, shard_min=1, max_devices=0)
    pts_s, ok_s = bjk.aggregate_g1_jobs(jobs)
    mesh_mod.configure(enabled=False)
    pts_1, ok_1 = bjk.aggregate_g1_jobs(jobs)
    assert list(ok_s) == list(ok_1)
    assert pts_s == pts_1
    assert len(pts_s) == 21
    assert not ok_s[17] and pts_s[17] is None
    assert all(p == want for i, p in enumerate(pts_s) if i != 17)


def test_sharded_bls_dispatch_spans_devices(mesh):
    from plenum_tpu.crypto import bls12_381 as B
    from plenum_tpu.ops import bls381_jax as bjk
    job = [B.g1_compress(B.g1_mul(B.G1_GEN, 5))]
    mesh_mod.configure(enabled=True, shard_min=1, max_devices=0)
    handles = bjk.aggregate_dispatch([job] * 16, 1)
    assert len(handles[0].sharding.device_set) == 8


# ---------------------------------------------------------------- merkle

def test_sharded_merkle_build_and_proofs_bit_identical(mesh):
    from plenum_tpu.ops.merkle import DeviceMerkleTree
    leaves = [b"leaf-%05d" % i for i in range(300)]   # ragged (cap 512)
    idx = list(range(0, 300, 3))
    mesh_mod.configure(enabled=True, shard_min=16, max_devices=0)
    t_s = DeviceMerkleTree()
    root_s = t_s.build(leaves)
    proofs_s = t_s.inclusion_proofs(idx)
    mesh_mod.configure(enabled=False)
    t_1 = DeviceMerkleTree()
    root_1 = t_1.build(leaves)
    proofs_1 = t_1.inclusion_proofs(idx)
    assert root_s == root_1
    assert proofs_s == proofs_1


def test_tiny_tree_below_device_count_stays_unsharded(mesh):
    """A sub-device-count MESH_SHARD_MIN must not crash a build whose
    power-of-two capacity cannot divide over the mesh (device_put
    rejects a 4-row array under an 8-way sharding)."""
    from plenum_tpu.ops.merkle import DeviceMerkleTree
    from plenum_tpu.ledger.tree_hasher import TreeHasher
    mesh_mod.configure(enabled=True, shard_min=2, max_devices=0)
    t = DeviceMerkleTree()
    root = t.build([b"a", b"b", b"c"])
    h = TreeHasher()
    want = h.hash_children(
        h.hash_children(h.hash_leaf(b"a"), h.hash_leaf(b"b")),
        h.hash_leaf(b"c"))
    assert root == want
    t2 = DeviceMerkleTree()
    t2.build_from_leaf_hashes([h.hash_leaf(x) for x in (b"a", b"b", b"c")])
    assert t2.root_hash == want


def test_sharded_device_gather_bit_identical(mesh):
    """With the default top-level host cache a small tree serves proofs
    entirely from mirrors; shrinking _TOP_CACHE forces the bottom
    levels through the DEVICE gather — the path that shards the index
    axis against mesh-replicated levels."""
    from plenum_tpu.ops.merkle import DeviceMerkleTree
    leaves = [b"g-%05d" % i for i in range(500)]
    idx = list(range(0, 500, 2))

    def tree():
        t = DeviceMerkleTree()
        t._TOP_CACHE = 8          # levels with > 8 nodes gather on device
        t.build(leaves)
        return t

    mesh_mod.configure(enabled=True, shard_min=16, max_devices=0)
    t_s = tree()
    assert t_s._n_low() > 0       # the device-gather path is actually on
    handle = t_s.dispatch_proof_batch(idx)
    assert len(handle[1].sharding.device_set) == 8
    proofs_s = t_s.collect_proof_batch(handle)
    # second batch reuses the memoized replicated levels
    proofs_s2 = t_s.inclusion_proofs(idx)
    mesh_mod.configure(enabled=False)
    t_1 = tree()
    proofs_1 = t_1.inclusion_proofs(idx)
    assert proofs_s == proofs_1
    assert proofs_s2 == proofs_1


def test_append_after_sharded_build_identical(mesh):
    """A sharded build lands its levels back on the default device, so
    the incremental append path must keep working and agree with the
    never-sharded tree byte for byte."""
    from plenum_tpu.ledger.tree_hasher import TreeHasher
    from plenum_tpu.ops.merkle import DeviceMerkleTree
    hasher = TreeHasher()
    leaves = [b"leaf-%05d" % i for i in range(300)]
    extra = [hasher.hash_leaf(b"extra-%d" % i) for i in range(37)]
    mesh_mod.configure(enabled=True, shard_min=16, max_devices=0)
    t_s = DeviceMerkleTree()
    t_s.build(leaves)
    t_s.append_leaf_hashes(extra)
    mesh_mod.configure(enabled=False)
    t_1 = DeviceMerkleTree()
    t_1.build(leaves)
    t_1.append_leaf_hashes(extra)
    assert t_s.root_hash == t_1.root_hash
    idx = list(range(0, 337, 5))
    assert t_s.inclusion_proofs(idx) == t_1.inclusion_proofs(idx)


# ------------------------------------------------------------ hub + daemon

def test_hub_verdicts_unchanged_under_mesh(mesh):
    from plenum_tpu.crypto.batch_verifier import CoalescingVerifierHub
    mesh_mod.configure(enabled=True, shard_min=16, max_devices=0)
    hub = CoalescingVerifierHub(threshold=8)
    a = _signed_items(20, tamper={2})
    b = _signed_items(17, tamper={5})
    pa = hub.dispatch(list(zip(*a)))
    pb = hub.dispatch(list(zip(*b)))
    ra, rb = pa.collect(), pb.collect()
    assert len(ra) == 20 and len(rb) == 17
    assert not ra[2] and sum(ra) == 19
    assert not rb[5] and sum(rb) == 16


def test_daemon_bucketed_verify_under_mesh(mesh):
    """The daemon's fused launches span the mesh: its bucket scales by
    the device count and verdicts stay exact after the tail padding is
    sliced off."""
    from plenum_tpu.server.verify_daemon import VerifyDaemon
    mesh_mod.configure(enabled=True, shard_min=16, max_devices=0)
    daemon = VerifyDaemon(backend="adaptive", bucket=8, cpu_floor=1)
    msgs, sigs, vks = _signed_items(20, tamper={4, 11})
    results = daemon._verify_bucketed(list(zip(msgs, sigs, vks)))
    assert len(results) == 20
    assert not results[4] and not results[11] and sum(results) == 18


# ------------------------------------------------------- threshold config

def test_verifier_threshold_single_sourced(mesh, monkeypatch):
    from plenum_tpu.crypto.batch_verifier import (
        AdaptiveVerifier, CoalescingVerifierHub, create_verifier)
    assert AdaptiveVerifier().threshold == Config.VERIFIER_BATCH_THRESHOLD
    assert CoalescingVerifierHub().threshold \
        == Config.VERIFIER_BATCH_THRESHOLD
    monkeypatch.setattr(Config, "VERIFIER_BATCH_THRESHOLD", 7)
    assert create_verifier("adaptive").threshold == 7
    assert create_verifier("tpu_hub").threshold == 7
    # explicit ctor argument still wins
    assert AdaptiveVerifier(threshold=3).threshold == 3


def test_node_config_reaches_mesh(mesh, tdir):
    """Node bootstrap applies its Config's MESH_* knobs to the
    process-wide dispatcher."""
    from plenum_tpu.common.config import Config as Cfg
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork
    timer = MockTimer()
    net = SimNetwork(timer, DefaultSimRandom(0))
    conf = Cfg(MESH_ENABLED=False, MESH_SHARD_MIN=4096)
    Node("Alpha", ["Alpha"], timer, net.create_peer("Alpha"), config=conf)
    assert mesh.enabled is False
    assert mesh.shard_min == 4096
