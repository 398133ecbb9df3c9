"""Columnar-vs-per-message 3PC equivalence.

The columnar intake (`process_prepare_columns` /
`process_commit_columns` / `process_preprepare_batch`, fed by the flat
envelope) is a pure dataflow refactor of the per-message wire: for ANY
inbound message stream — stragglers,
duplicates, conflicting digests from the PR-1 adversary, wrong
instances, future views, watermark strays, a view change mid-batch —
the replica must end in the SAME observable state as a reference
per-message replay of the identical stream: equal vote stores and
incremental counters, equal stash contents, equal suspicions, the
identical ordered sequence, and byte-equal executor roots.

Rungs:

* unit — two `ReplicaService`s on silent networks; one consumes
  randomized per-sender envelopes through the columnar intake, the
  other replays the same messages one by one through the stashing
  router (the per-message wire's exact delivery path).
* e2e — two full 4-node sim pools running the identical deterministic
  workload, one on the flat wire and one held to the per-message wire
  by a pass-through tap on every bus: byte-equal ledger + state roots
  and identical ordered txn sequence at drain.
"""
import random

import pytest

from plenum_tpu.common.config import Config
from plenum_tpu.common.messages.internal_messages import (
    NewViewAccepted, RaisedSuspicion, ViewChangeStarted)
from plenum_tpu.common.messages.node_messages import (
    Commit, PrePrepare, Prepare)
from plenum_tpu.common.serializers import flat_wire as fw
from tests.test_3pc_verdicts import (
    VALIDATORS, KnownSetExecutor, make_pp, make_replica)

PRIMARY = "Alpha"          # view-0 primary for VALIDATORS
NODE = "Beta"              # the replica under test
PEERS = [v for v in VALIDATORS if v != NODE]


# ---------------------------------------------------------------- helpers

def make_prepare_for(pp, frm_view=None, digest=None):
    return Prepare(
        instId=pp.instId,
        viewNo=pp.viewNo if frm_view is None else frm_view,
        ppSeqNo=pp.ppSeqNo, ppTime=pp.ppTime,
        digest=pp.digest if digest is None else digest,
        stateRootHash=pp.stateRootHash, txnRootHash=pp.txnRootHash)


def make_commit_for(pp, frm_view=None):
    return Commit(instId=pp.instId,
                  viewNo=pp.viewNo if frm_view is None else frm_view,
                  ppSeqNo=pp.ppSeqNo)


def feed_flat(replica, envelopes):
    """The wire-accurate flat feed: each sender envelope is ENCODED to
    flat bytes, parsed, and routed exactly as Node._process_flat_batch
    routes sections (PPs materialized through the stasher, vote columns
    straight into process_*_columns)."""
    o = replica.ordering
    for frm, msgs in envelopes:
        pps = [m for m in msgs if isinstance(m, PrePrepare)]
        prepares = [m for m in msgs if isinstance(m, Prepare)]
        commits = [m for m in msgs if isinstance(m, Commit)]
        env = fw.parse_envelope(fw.encode_three_pc(pps, prepares,
                                                   commits))
        for sec in env.sections:
            if sec.kind == fw.KIND_PREPREPARE:
                batch = [sec.materialize(i) for i in range(sec.n)]
                o.process_preprepare_batch(
                    [m for m in batch if m is not None], frm)
            elif sec.kind == fw.KIND_PREPARE:
                o.process_prepare_columns(sec, frm)
            elif sec.kind == fw.KIND_COMMIT:
                o.process_commit_columns(sec, frm)


def feed_per_message(replica, envelopes):
    """The reference replay: the same messages in the same effective
    order, each through the stashing router exactly as a per-message
    wire delivery would arrive."""
    route = replica.ordering._stasher.route
    for frm, msgs in envelopes:
        for kind in (PrePrepare, Prepare, Commit):
            for m in msgs:
                if isinstance(m, kind):
                    route(m, frm)


def snapshot(replica, suspicions):
    """Every piece of observable 3PC state the refactor could bend."""
    o = replica.ordering
    ex = o._executor
    stashes = {}
    for (typ, code), stash in o._stasher._stashes.items():
        # the stash containers are iterable ((message, *args) entries);
        # an attribute probe here once read a nonexistent `_items` and
        # silently compared empty lists — every stash assertion was
        # vacuous until the flat-wire catchup test caught it
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
        "ordered_log": [(m.viewNo, m.ppSeqNo, tuple(m.valid_reqIdr))
                        for m in replica.ordered_log],
        "applied": ex.applied,
        "committed_root": ex.committed_root,
        "stashes": stashes,
        "suspicions": sorted(
            (s.ex.code, s.ex.node) for s in suspicions),
        "view_no": replica.data.view_no,
        "last_ordered": replica.data.last_ordered_3pc,
    }


def build_pair(known):
    """Two identical replicas + their suspicion sinks."""
    out = []
    for _ in range(2):
        replica = make_replica(NODE, known=frozenset(known))
        sus = []
        replica.internal_bus.subscribe(
            RaisedSuspicion, lambda m, _s=sus: _s.append(m))
        out.append((replica, sus))
    return out


def gen_stream(rng, n_batches=4, reqs_per_batch=3):
    """Randomized single-sender envelope stream over `n_batches` 3PC
    batches: correct votes plus stragglers (votes before their PP),
    duplicates, conflicting digests, wrong instances, future views and
    watermark strays — the PR-1 adversary's repertoire at the message
    level. → (envelopes, known_digests)."""
    pps, known = [], []
    for seq in range(1, n_batches + 1):
        reqs = ["req-%d-%d" % (seq, i) for i in range(reqs_per_batch)]
        known.extend(reqs)
        pps.append(make_pp(pp_seq_no=seq, reqs=tuple(reqs)))
    per_sender = {frm: [] for frm in PEERS}
    per_sender[PRIMARY].extend(pps)
    for pp in pps:
        for frm in PEERS:
            if frm != PRIMARY:
                per_sender[frm].append(make_prepare_for(pp))
        for frm in PEERS:
            per_sender[frm].append(make_commit_for(pp))
    # adversarial garnish, per sender
    for frm in PEERS:
        msgs = per_sender[frm]
        garnish = []
        for m in list(msgs):
            roll = rng.random()
            if roll < 0.25:
                garnish.append(m)                      # duplicate
            elif roll < 0.35 and isinstance(m, Prepare):
                garnish.append(make_prepare_for(        # conflicting
                    pps[m.ppSeqNo - 1], digest="forged-" + m.digest))
            elif roll < 0.45:
                garnish.append(type(m)(**{**m.as_dict(),
                                          "instId": 5}))  # wrong inst
        msgs.extend(garnish)
        msgs.append(make_prepare_for(pps[0], frm_view=3))   # future view
        stray = make_commit_for(pps[0])
        msgs.append(Commit(instId=0, viewNo=0, ppSeqNo=10 ** 6))  # > H
        msgs.append(stray)                                  # duplicate
        # stragglers: a sender's envelope is FIFO per phase, but ACROSS
        # senders any interleaving can happen — shuffle sender order
        # per round below; within a sender keep phase-legal order
    # split each sender's stream into 1-4 random envelopes
    envelopes = []
    for frm, msgs in per_sender.items():
        cuts = sorted(rng.sample(range(1, len(msgs)),
                                 min(rng.randint(0, 3),
                                     len(msgs) - 1))) + [len(msgs)]
        start = 0
        for cut in cuts:
            envelopes.append((frm, msgs[start:cut]))
            start = cut
    rng.shuffle(envelopes)
    # stragglers for real: with PRIMARY envelopes shuffled anywhere,
    # some PREPAREs/COMMITs arrive before their PRE-PREPARE
    return envelopes, known


# ------------------------------------------------------------------ unit

# seeds 0-11 run through the same feed in tests/test_flat_wire.py
# (test_flat_intake_equals_per_message_randomized): no stream twice
@pytest.mark.parametrize("seed", range(12, 24))
def test_columnar_equals_per_message_randomized(seed):
    rng = random.Random(seed)
    envelopes, known = gen_stream(rng)
    (ra, sus_a), (rb, sus_b) = build_pair(known)
    feed_flat(ra, envelopes)
    feed_per_message(rb, envelopes)
    assert snapshot(ra, sus_a) == snapshot(rb, sus_b)
    # the stream actually ordered something (vacuous equality guard)
    assert ra.ordering.ordered


@pytest.mark.parametrize("seed", range(6))
def test_columnar_equals_per_message_across_view_change(seed):
    """View change MID-STREAM: both replicas get the same envelopes,
    a ViewChangeStarted after a random prefix, the rest of the stream
    while waiting (columnar precheck must stash exactly like the
    per-message wire), then the same NewViewAccepted — state must stay
    equal at every rung."""
    rng = random.Random(1000 + seed)
    envelopes, known = gen_stream(rng)
    cut = rng.randint(1, len(envelopes) - 1)
    (ra, sus_a), (rb, sus_b) = build_pair(known)
    for replica, feed in ((ra, feed_flat), (rb, feed_per_message)):
        feed(replica, envelopes[:cut])
        replica.internal_bus.send(ViewChangeStarted(view_no=1))
        replica.data.primary_name = "Beta"
        feed(replica, envelopes[cut:])
    assert snapshot(ra, sus_a) == snapshot(rb, sus_b)
    for replica in (ra, rb):
        replica.internal_bus.send(NewViewAccepted(
            view_no=1, view_changes=[], checkpoint=None, batches=[]))
    assert snapshot(ra, sus_a) == snapshot(rb, sus_b)


def test_columnar_batch_with_only_garbage_is_noop():
    """An envelope of pure junk (wrong instance, below watermark)
    leaves both replicas untouched."""
    (ra, sus_a), (rb, sus_b) = build_pair([])
    junk = [("Gamma", [Commit(instId=5, viewNo=0, ppSeqNo=1),
                       Commit(instId=0, viewNo=0, ppSeqNo=0)])]
    feed_flat(ra, junk)
    feed_per_message(rb, junk)
    assert snapshot(ra, sus_a) == snapshot(rb, sus_b)
    assert not ra.ordering.commits


# ------------------------------------------------------------------- e2e

def _run_pool(n_reqs: int = 24, per_message: bool = False,
              pipeline: bool = None):
    """One deterministic 4-node sim pool ordering n_reqs NYMs;
    → (domain_root, audit_root, state_root, ordered txn sequence).
    per_message=True installs a pass-through tap (the adversary's
    benign `Behavior` base) on every node's bus: no option selects a
    wire, the senders read `network.has_tap`, and a tap that changes
    nothing puts outbox and propagator on the reference wire.
    pipeline pins Config.PIPELINE_ENABLED (None = the class default;
    the pipeline on/off determinism A/B in tests/test_pipeline.py)."""
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
    # FIXED latency: the two wire modes send different NUMBERS of
    # messages, so with random latency the shared draw stream diverges
    # after the first 3PC send and every later PROPAGATE lands at a
    # different sim time — ppTime (which is txn content) then differs
    # for reasons that have nothing to do with the dataflow under test.
    # Constant latency makes network conditions mode-independent;
    # any remaining root drift is a real equivalence bug.
    net = SimNetwork(timer, DefaultSimRandom(77),
                     min_latency=0.003, max_latency=0.003)
    overrides = dict(Max3PCBatchSize=5, Max3PCBatchWait=0.2)
    if pipeline is not None:
        overrides["PIPELINE_ENABLED"] = pipeline
    conf = Config(**overrides)
    nodes = [Node(name, names, timer, net.create_peer(name), config=conf)
             for name in names]
    if per_message:
        from plenum_tpu.testing.adversary.behaviors import Behavior
        for n in nodes:
            n.replica.install_network_tap(Behavior())
    signer = SimpleSigner(seed=b"\x31" * 32)
    for i in range(n_reqs):
        dest = "col-%06d" % i + "x" * 12
        req = {"identifier": signer.identifier, "reqId": i + 1,
               "protocolVersion": 2,
               "operation": {"type": NYM, TARGET_NYM: dest,
                             VERKEY: "~" + dest[:22]}}
        req["signature"] = signer.sign(dict(req))
        for n in nodes:
            n.process_client_request(dict(req), "col-client")
    for _ in range(400):
        for n in nodes:
            n.service()
        timer.run_for(0.01)
        if all(n.domain_ledger.size >= n_reqs for n in nodes):
            break
    assert all(n.domain_ledger.size == n_reqs for n in nodes)
    # the pool really ran on the wire asked for
    assert all((n._outbox_3pc.flushed_batches == 0) == per_message
               for n in nodes)
    node = nodes[0]
    # all nodes agree internally first
    assert len({n.domain_ledger.root_hash for n in nodes}) == 1
    assert len({n.audit_ledger.root_hash for n in nodes}) == 1
    seq = [get_payload_data(txn)["dest"]
           for _seq_no, txn in node.domain_ledger.getAllTxn()]
    from plenum_tpu.common.constants import NYM as NYM_TYPE
    state = node.write_manager.request_handlers[NYM_TYPE].state
    return (node.domain_ledger.root_hash, node.audit_ledger.root_hash,
            state.committedHeadHash, seq)


class _CommitDroppingTap:
    """Per-type fault-injection tap: records every incoming message
    type, drops Commits, passes everything else through."""

    def __init__(self):
        self.seen = []

    def on_send(self, msg, dst):
        return None

    def on_incoming(self, msg, frm):
        self.seen.append(type(msg).__name__)
        if isinstance(msg, Commit):
            return []
        return None


def test_incoming_envelopes_unwrap_for_network_tap():
    """The receive-side mirror of the outbox's send-side tap degrade:
    honest (untapped) peers coalesce their votes into flat envelopes,
    and a per-type tap on the RECEIVING node must still see
    (and be able to drop) the inner votes — an envelope passed through
    whole would smuggle every vote past the fault injector. A tap
    dropping every Commit starves the tapped node's commit quorum
    while the rest of the pool orders."""
    from plenum_tpu.common.constants import NYM, TARGET_NYM, VERKEY
    from plenum_tpu.crypto.signer import SimpleSigner
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork

    names = ["Alpha", "Beta", "Gamma", "Delta"]
    timer = MockTimer()
    timer.set_time(1600000000)
    net = SimNetwork(timer, DefaultSimRandom(55))
    conf = Config(Max3PCBatchSize=5, Max3PCBatchWait=0.2)
    nodes = [Node(name, names, timer, net.create_peer(name), config=conf)
             for name in names]
    tap = _CommitDroppingTap()
    tapped = nodes[3]
    tapped.replica.install_network_tap(tap)
    signer = SimpleSigner(seed=b"\x32" * 32)
    for i in range(5):
        dest = "tap-%06d" % i + "x" * 12
        req = {"identifier": signer.identifier, "reqId": i + 1,
               "protocolVersion": 2,
               "operation": {"type": NYM, TARGET_NYM: dest,
                             VERKEY: "~" + dest[:22]}}
        req["signature"] = signer.sign(dict(req))
        for n in nodes:
            n.process_client_request(dict(req), "tap-client")
    for _ in range(200):
        for n in nodes:
            n.service()
        timer.run_for(0.01)
        if all(n.domain_ledger.size >= 5 for n in nodes[:3]):
            break
    # untapped nodes reach commit quorum without the tapped node
    assert all(n.domain_ledger.size == 5 for n in nodes[:3])
    # the tap saw per-type votes, never a whole envelope...
    assert "FlatBatch" not in tap.seen
    assert "Prepare" in tap.seen and "Commit" in tap.seen
    # ...and the drop BIT: with every peer Commit eaten the tapped
    # node can never reach its commit quorum
    assert tapped.domain_ledger.size == 0


@pytest.mark.slow
def test_wire_modes_order_identically_e2e():
    """Full-node rung: the flat wire and the per-message wire (every
    bus under a pass-through tap) drain the identical deterministic
    workload under FIXED sim latency to byte-equal ledger roots, state
    root and ordered sequence."""
    flat = _run_pool()
    single = _run_pool(per_message=True)
    assert flat[3] == single[3]     # same txns in the same order
    assert flat[0] == single[0]     # domain ledger root, byte-equal
    assert flat[1] == single[1]     # audit ledger root (same batching)
    assert flat[2] == single[2]     # committed state root
