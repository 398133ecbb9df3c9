"""Pipeline-parallel node runtime (runtime/pipeline.py).

The contract under test, rung by rung:

* unit — the stage plumbing itself: bounded-queue FIFO + backpressure,
  the single worker-sizing rule, the positive-only prescreen cache,
  drain order == submission order, the dead-worker inline step-down,
  order-preserving execution fan-out, and the ``bind_owner_thread``
  guard that makes prod-thread ownership of 3PC intake a hard error
  instead of a convention;
* e2e determinism — a pipelined 4-node pool and a serial one drain the
  IDENTICAL workload (including a randomized adversarial injection
  stream: malformed envelopes, conflicting digests, future views,
  wrong instances, above-watermark strays) to byte-equal ledger/state
  roots, the same ordered sequence, and the same per-node suspicion /
  stash / vote-store snapshots — the pipeline is a latency refactor,
  never a semantics fork;
* epoch drains — a mid-stream view change leaves no parse job
  straddling the epoch boundary;
* observability — causal journeys stay COMPLETE with the pipeline on
  (the worker-side parse must not drop wire stamps).
"""
import random
import threading
import time

import pytest

from plenum_tpu.common.config import Config
from plenum_tpu.common.messages.internal_messages import (
    RaisedSuspicion, ViewChangeStarted)
from plenum_tpu.common.messages.node_messages import (
    Commit, FlatBatch, Prepare)
from plenum_tpu.common.serializers import flat_wire
from plenum_tpu.common.serializers.base58 import b58encode
from plenum_tpu.runtime.pipeline import (
    BoundedQueue, NodePipeline, PrescreenCache, resolve_queue_depth,
    resolve_workers)

from tests.test_columnar_3pc import _run_pool

ROOT58 = b58encode(b"\x11" * 32)


# ------------------------------------------------------------------ unit


def test_bounded_queue_fifo_and_close():
    q = BoundedQueue(8)
    for i in range(5):
        q.put(i)
    assert len(q) == 5
    assert [q.get() for _ in range(5)] == [0, 1, 2, 3, 4]
    assert q.get(timeout=0.01) is None          # empty + timeout
    q.close()
    assert q.get() is None                      # closed, no block


def test_bounded_queue_backpressure_blocks_producer():
    """put() on a full queue blocks until the consumer drains — that
    IS the backpressure (no unbounded buffer, no drop)."""
    q = BoundedQueue(2)
    q.put("a")
    q.put("b")
    got = []

    def consume():
        time.sleep(0.05)
        got.append(q.get())

    t = threading.Thread(target=consume)
    t.start()
    t0 = time.perf_counter()
    q.put("c")                       # full: must wait for the consumer
    waited = time.perf_counter() - t0
    t.join()
    assert got == ["a"]
    assert waited >= 0.02
    assert [q.get(), q.get()] == ["b", "c"]


def test_resolve_workers_single_rule():
    import os
    assert resolve_workers(3) == 3
    assert resolve_workers(0) == 1              # floor
    assert resolve_workers(None, fallback=1) == 1   # daemon floor
    assert resolve_workers(2, fallback=1) == 2      # explicit wins
    cores = os.cpu_count() or 1
    assert resolve_workers() == max(1, min(4, cores - 1))
    assert resolve_queue_depth() == 256
    assert resolve_queue_depth(0) == 1


def test_prescreen_cache_exact_triple_only():
    c = PrescreenCache()
    c.add(b"ser", b"sig", b"vk")
    assert c.check((b"ser", b"sig", b"vk"))
    # ANY component differing (the rotated-verkey case) is a miss —
    # a hit can only skip a verify that was bound to succeed
    assert not c.check((b"ser", b"sig", b"vk2"))
    assert not c.check((b"ser", b"sig2", b"vk"))
    assert not c.check(None)                    # malformed probe
    assert not c.check((b"ser",))


def test_prescreen_cache_wholesale_eviction():
    c = PrescreenCache(max_entries=4)
    for i in range(4):
        c.add(b"s%d" % i, b"g", b"v")
    assert len(c) == 4
    c.add(b"s4", b"g", b"v")                    # clear-then-add
    assert len(c) == 1
    assert c.check((b"s4", b"g", b"v"))
    assert not c.check((b"s0", b"g", b"v"))


def _make_pipeline(delivered, workers=2, depth=8):
    conf = Config(PIPELINE_WORKERS=workers, PIPELINE_QUEUE_DEPTH=depth)
    return NodePipeline(
        lambda job: delivered.append((job.msg, job.result, job.error)),
        config=conf)


def test_drain_delivers_in_submission_order():
    delivered = []
    pipe = _make_pipeline(delivered)
    try:
        # parse jobs interleaved with passthroughs — ONE FIFO
        pipe.submit(lambda: "r0", "m0", "A")
        pipe.submit(None, "m1", "B")
        pipe.submit(lambda: "r2", "m2", "C")
        assert pipe.depth == 3
        assert pipe.drain() == 3
        assert pipe.depth == 0
        assert delivered == [("m0", "r0", None), ("m1", None, None),
                             ("m2", "r2", None)]
    finally:
        pipe.stop()


def test_worker_exception_is_delivered_not_raised():
    """A parse failure crosses back as job.error for the prod thread
    to attribute (suspicion), never as a worker-thread crash."""
    delivered = []
    pipe = _make_pipeline(delivered)
    try:
        boom = ValueError("bad envelope")
        pipe.submit(lambda: (_ for _ in ()).throw(boom), "m", "A")
        pipe.drain()
        assert len(delivered) == 1
        assert delivered[0][2] is boom
    finally:
        pipe.stop()


def test_dead_worker_steps_down_to_inline_parse():
    """The step-down philosophy of every device seam: a dead worker
    degrades to inline parsing at the submit site — slower, never
    wedged."""
    delivered = []
    pipe = _make_pipeline(delivered)
    pipe.stop()
    pipe._worker.join(timeout=2)
    assert not pipe._worker.is_alive()
    pipe.submit(lambda: "inline", "m", "A")
    assert pipe.drain() == 1
    assert delivered == [("m", "inline", None)]


def test_exec_map_preserves_order():
    pipe = _make_pipeline([], workers=3)
    try:
        assert pipe.exec_map(lambda x: x * 2, range(7)) == \
            [0, 2, 4, 6, 8, 10, 12]
        assert pipe.exec_map(lambda x: x + 1, [41]) == [42]  # inline
    finally:
        pipe.stop()


def test_exec_fanout_sizing():
    from plenum_tpu.server.execution_lanes import exec_fanout
    assert exec_fanout(0) == 1
    assert exec_fanout(1) == 1
    assert exec_fanout(8, workers=3) == 3
    assert exec_fanout(2, workers=3) == 2


def test_ordering_intake_owner_guard():
    """bind_owner_thread turns the ownership convention into a hard
    RuntimeError: 3PC intake off the prod thread must never count."""
    from tests.test_3pc_verdicts import make_pp, make_replica
    replica = make_replica("Beta")
    o = replica.ordering
    o.bind_owner_thread(threading.get_ident())
    o.process_preprepare_batch([], "Gamma")     # owner thread: fine
    errs = []

    def off_thread():
        try:
            o.process_preprepare_batch([make_pp()], "Alpha")
        except RuntimeError as e:
            errs.append(e)

    t = threading.Thread(target=off_thread)
    t.start()
    t.join()
    assert len(errs) == 1
    assert "prod thread" in str(errs[0])


# --------------------------------------------- e2e: determinism A/B


def test_pipeline_on_off_byte_equal_roots():
    """The headline contract: a pipelined pool and a serial pool drain
    the identical workload to byte-equal domain/audit/state roots and
    the same ordered sequence."""
    on = _run_pool(n_reqs=12, pipeline=True)
    off = _run_pool(n_reqs=12, pipeline=False)
    assert on == off


def _pool_snapshot(node, suspicions):
    """Observable consensus state of one pool node — everything the
    pipeline refactor could bend (mirrors test_columnar_3pc.snapshot,
    minus the test-executor-only fields)."""
    o = node.replica.ordering
    stashes = {}
    for (typ, code), stash in o._stasher._stashes.items():
        items = sorted(repr(item) for item in stash)
        if items:
            stashes[(typ.__name__, code)] = items
    return {
        "prepares": {k: {s: p.digest for s, p in v.items()}
                     for k, v in o.prepares.items() if v},
        "commits": {k: sorted(v) for k, v in o.commits.items() if v},
        "prepare_count": {k: v for k, v in o._prepare_vote_count.items()
                          if v},
        "commit_count": {k: v for k, v in o._commit_vote_count.items()
                         if v},
        "ordered": sorted(o.ordered),
        "stashes": stashes,
        "suspicions": sorted(
            (s.ex.code, s.ex.node) for s in suspicions),
        "suspicion_counts": dict(node.blacklister.suspicion_counts),
        "blacklisted": sorted(node.blacklister.blacklisted),
        "view_no": node.replica.data.view_no,
        "last_ordered": node.replica.data.last_ordered_3pc,
    }


def _adversarial_payloads(rng):
    """A deterministic (per-rng) injection stream: the PR-1 adversary's
    repertoire re-expressed as raw flat-wire envelopes, plus bytes that
    are not an envelope at all."""
    def prep(view, seq, digest):
        return Prepare(instId=0, viewNo=view, ppSeqNo=seq,
                       ppTime=1600000000, digest=digest,
                       stateRootHash=ROOT58, txnRootHash=ROOT58)

    payloads = [
        bytes([rng.randrange(256) for _ in range(40)]),     # malformed
        flat_wire.encode_three_pc(
            [], [prep(0, 1, "forged-" + "f" * 20)], []),    # conflict
        flat_wire.encode_three_pc([], [prep(3, 1, "d" * 8)], []),
        flat_wire.encode_three_pc(
            [], [], [Commit(instId=0, viewNo=0, ppSeqNo=10 ** 6)]),
        flat_wire.encode_three_pc(
            [], [], [Commit(instId=5, viewNo=0, ppSeqNo=1)]),
    ]
    rng.shuffle(payloads)
    return payloads


def _run_adversarial_pool(pipeline, seed, n_reqs=10, sanitizer=None):
    """A 4-node flat-wire pool ordering n_reqs NYMs while every node is
    fed a seeded adversarial FlatBatch stream mid-run. → (roots, seq,
    per-node snapshots). `sanitizer` pins Config.SANITIZER_ENABLED so
    test_sanitizer.py can A/B the ownership sanitizer on the identical
    adversarial workload."""
    from plenum_tpu.common.constants import NYM, TARGET_NYM, VERKEY
    from plenum_tpu.common.txn_util import get_payload_data
    from plenum_tpu.crypto.signer import SimpleSigner
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork

    names = ["Alpha", "Beta", "Gamma", "Delta"]
    timer = MockTimer()
    timer.set_time(1600000000)
    # fixed latency for the same reason as _run_pool: network timing
    # must be mode-independent so any drift is a real pipeline bug
    net = SimNetwork(timer, DefaultSimRandom(77),
                     min_latency=0.003, max_latency=0.003)
    conf = Config(Max3PCBatchSize=5, Max3PCBatchWait=0.2,
                  PIPELINE_ENABLED=pipeline,
                  SANITIZER_ENABLED=sanitizer)
    nodes = [Node(name, names, timer, net.create_peer(name), config=conf)
             for name in names]
    sus = {n.name: [] for n in nodes}
    for n in nodes:
        n.replica.internal_bus.subscribe(
            RaisedSuspicion, lambda m, _s=sus[n.name]: _s.append(m))
    signer = SimpleSigner(seed=b"\x33" * 32)
    for i in range(n_reqs):
        dest = "adv-%06d" % i + "x" * 12
        req = {"identifier": signer.identifier, "reqId": i + 1,
               "protocolVersion": 2,
               "operation": {"type": NYM, TARGET_NYM: dest,
                             VERKEY: "~" + dest[:22]}}
        req["signature"] = signer.sign(dict(req))
        for n in nodes:
            n.process_client_request(dict(req), "adv-client")
    rng = random.Random(seed)
    inject_steps = sorted(rng.sample(range(1, 30), 4))
    for step in range(400):
        if step in inject_steps:
            # every node gets the same seeded garbage, attributed to a
            # (distinct) live peer, straight through its receive seam —
            # the pipelined intake and the serial intake must absorb it
            # identically
            for i, n in enumerate(nodes):
                frm = names[(i + 1) % len(names)]
                for payload in _adversarial_payloads(
                        random.Random(seed * 1000 + step)):
                    n.network.process_incoming(
                        FlatBatch(payload=payload), frm)
        for n in nodes:
            n.service()
        timer.run_for(0.01)
        if step > max(inject_steps) \
                and all(n.domain_ledger.size >= n_reqs for n in nodes):
            break
    assert all(n.domain_ledger.size == n_reqs for n in nodes)
    node = nodes[0]
    seq = [get_payload_data(txn)["dest"]
           for _seq_no, txn in node.domain_ledger.getAllTxn()]
    from plenum_tpu.common.constants import NYM as NYM_TYPE
    state = node.write_manager.request_handlers[NYM_TYPE].state
    snaps = {n.name: _pool_snapshot(n, sus[n.name]) for n in nodes}
    return (node.domain_ledger.root_hash, node.audit_ledger.root_hash,
            state.committedHeadHash, seq, snaps)


@pytest.mark.parametrize("seed", range(3))
def test_pipeline_on_off_equal_under_adversarial_stream(seed):
    """Byte-equal roots AND identical per-node suspicion / stash /
    vote-store snapshots, pipeline on vs off, under a randomized
    adversarial injection stream — malformed envelopes, conflicting
    digests, future views, wrong instances, above-watermark strays."""
    on = _run_adversarial_pool(pipeline=True, seed=seed)
    off = _run_adversarial_pool(pipeline=False, seed=seed)
    assert on[0] == off[0] and on[1] == off[1] and on[2] == off[2]
    assert on[3] == off[3]                       # ordered sequence
    assert on[4] == off[4]                       # per-node snapshots
    # the stream actually raised suspicions somewhere (vacuity guard)
    assert any(s["suspicion_counts"] for s in on[4].values())


def test_view_change_drains_pipeline_mid_stream():
    """No parse job may straddle a protocol epoch: ViewChangeStarted on
    the internal bus drains every queued job before the view change
    proceeds."""
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork

    names = ["Alpha", "Beta", "Gamma", "Delta"]
    timer = MockTimer()
    timer.set_time(1600000000)
    net = SimNetwork(timer, DefaultSimRandom(7))
    conf = Config(Max3PCBatchSize=5, Max3PCBatchWait=0.2,
                  PIPELINE_ENABLED=True)
    nodes = [Node(name, names, timer, net.create_peer(name), config=conf)
             for name in names]
    node = nodes[0]
    assert node._pipeline is not None
    payload = flat_wire.encode_three_pc(
        [], [], [Commit(instId=0, viewNo=0, ppSeqNo=10 ** 6)])
    node.network.process_incoming(FlatBatch(payload=payload), "Beta")
    assert node._pipeline.depth >= 1            # queued, not delivered
    node.replica.internal_bus.send(ViewChangeStarted(view_no=1))
    assert node._pipeline.depth == 0            # epoch boundary drained


def test_journeys_stay_complete_with_pipeline_on():
    """The worker-side parse must not drop wire stamps: causal journeys
    come out COMPLETE — intake anchor, named propagate closer, batch
    critical path — with the pipeline enabled."""
    from plenum_tpu.observability import journey
    from plenum_tpu.observability.export import pool_tracers
    from tests.test_journey import (
        assert_complete_report, run_traced_pool, traced_conf)

    nodes, _ = run_traced_pool(
        n_reqs=3, conf=traced_conf(PIPELINE_ENABLED=True))
    report = journey.journeys_from_tracers(pool_tracers(nodes))
    assert_complete_report(report, 3)
    assert not report["degraded"]
