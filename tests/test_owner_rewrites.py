"""Owners rewriting their own NYMs (ISSUE 29, the deployment
`pool4-owners`): the stream benchmark/operations/nym_rewrite_owners.py
makes, put through the program at a size a CPU test can hold, against
the plain reference of benchmark/reference/ (which imports nothing of
plenum_tpu). What no test had driven: updates of leaves that exist,
several writes of one key inside one batch, lanes of more than one
request, and the author caches popped under the DIDs that read them.
"""
import collections
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(1, os.path.join(ROOT, "benchmark", "tests"))
sys.path.insert(2, ROOT)

import check  # noqa: E402
import test_control  # noqa: E402
import traffic  # noqa: E402
from client import Op, reply_body  # noqa: E402
from operations import nym_rewrite_owners  # noqa: E402
from operations import nym_write_authors  # noqa: E402
from operations.nym_write_authors import authors  # noqa: E402
from reference import pool as ref  # noqa: E402

from plenum_tpu.common.config import Config  # noqa: E402
from plenum_tpu.common.constants import (  # noqa: E402
    AUDIT_LEDGER_ID, DOMAIN_LEDGER_ID, NYM)
from plenum_tpu.common.messages.node_messages import (  # noqa: E402
    Ordered, Propagate, Reply, RequestNack)
from plenum_tpu.common.request import Request  # noqa: E402
from plenum_tpu.common.state_codec import nym_to_state_key  # noqa: E402
from plenum_tpu.server.client_authn import CoreAuthNr  # noqa: E402
from plenum_tpu.server.execution_lanes import plan_lanes  # noqa: E402
from plenum_tpu.server.executor import NodeBatchExecutor  # noqa: E402
from plenum_tpu.runtime.sim_random import DefaultSimRandom  # noqa: E402
from plenum_tpu.server.node import Node, NodeBootstrap  # noqa: E402
from plenum_tpu.state import pruning_state  # noqa: E402
from plenum_tpu.state.trie import Trie  # noqa: E402
from plenum_tpu.testing.mock_timer import MockTimer  # noqa: E402
from plenum_tpu.testing.sim_network import SimNetwork  # noqa: E402

from tests.test_node_e2e import (  # noqa: E402
    NAMES, SIM_EPOCH, pump, submit_to_all)

MIX = {"kind": "nym_rewrite_owners", "zipf_constant": 0.99,
       "corrupted_every": 50}
IDENTITIES = 300
TS = 1700000000


def genesis(seed, identities=IDENTITIES):
    return test_control.genesis(seed, identities)


def made(seed, count, identities=IDENTITIES, mix=MIX):
    return nym_rewrite_owners.make(seed, count, mix,
                                   {"identities": identities})


# ------------------------------------------------------------ the maker

def test_maker_is_deterministic_and_the_owner_writes_its_own_record():
    seed = 2147483900
    stream = made(seed, 400)
    assert stream == made(seed, 400)
    assert stream != made(seed + 1, 400)
    drawn = authors(seed, 400, IDENTITIES, MIX["zipf_constant"])
    for i, (req, _valid) in enumerate(stream):
        owner = traffic.identity(seed, drawn[i])
        assert req["identifier"] == req["operation"]["dest"] \
            == owner.identifier
        assert req["reqId"] == i + 1
        assert ref.full_verkey(owner.identifier,
                               req["operation"]["verkey"]) == owner.verkey
        assert set(req["operation"]) == {"type", "dest", "verkey"}


def test_form_alternates_per_did_over_its_valid_writes():
    seed = 77
    forms_of = {s.identifier: nym_rewrite_owners.verkey_forms(s) for s in (
        traffic.identity(seed, i) for i in range(IDENTITIES))}
    seen = collections.defaultdict(list)
    for req, valid in made(seed, 600):
        if valid:
            seen[req["identifier"]].append(req["operation"]["verkey"])
    assert max(len(v) for v in seen.values()) > 40   # a hot DID
    for did, verkeys in seen.items():
        whole, abbreviated = forms_of[did]
        assert abbreviated.startswith("~")   # what the genesis holds
        assert verkeys == [whole, abbreviated] * (len(verkeys) // 2) \
            + [whole] * (len(verkeys) % 2)


def test_corrupted_writes_of_both_kinds():
    seed = 2147483900
    by_did = {s.identifier: s.verkey for s in (
        traffic.identity(seed, i) for i in range(IDENTITIES))}
    stream = made(seed, 400)
    bad = [req for req, valid in stream if not valid]
    assert len(bad) == 8
    for n, req in enumerate(bad):
        assert req["identifier"] == req["operation"]["dest"]
        assert not ref.signature_valid(req, by_did[req["identifier"]])
        signed_by = [did for did, vk in by_did.items()
                     if ref.signature_valid(req, vk)]
        if n % 2:
            # somebody else's valid signature over the owner's record
            assert len(signed_by) == 1
            assert signed_by[0] != req["identifier"]
        else:
            assert signed_by == []
    assert all(ref.signature_valid(req, by_did[req["identifier"]])
               for req, valid in stream if valid)


def test_hottest_did_takes_seven_to_nine_percent_at_100000():
    count = 6000
    stream = made(5, count, identities=100000)
    written = collections.Counter(req["identifier"] for req, _ in stream)
    assert 0.07 < written.most_common(1)[0][1] / count < 0.09
    assert len(written) > count // 3


# -------------------------------- write manager and executor, by path

def ordered(batch_no, roots, pp_time):
    return Ordered(
        instId=0, viewNo=0, valid_reqIdr=["r"], invalid_reqIdr=[],
        ppSeqNo=batch_no, ppTime=pp_time, ledgerId=DOMAIN_LEDGER_ID,
        stateRootHash=roots[0], txnRootHash=roots[1],
        auditTxnRootHash=None, primaries=["P"])


def apply_requests(executor, store, reqs, pp_time):
    digests = []
    for req in reqs:
        request = Request.from_dict(dict(req))
        store[request.digest] = request
        digests.append(request.digest)
    return executor.apply_batch(digests, DOMAIN_LEDGER_ID, pp_time)


PATHS = [(engine, lanes, fused, native, "auto")
         for engine in (False, True) for lanes in (False, True)
         for fused in (False, True) for native in (True, False)] + [
    (True, lanes, False, native, True)
    for lanes in (False, True) for native in (True, False)]


@pytest.mark.parametrize("engine,lanes,fused,native,device_hash", PATHS)
def test_three_batches_reach_the_references_roots(
        monkeypatch, engine, lanes, fused, native, device_hash):
    """STATE_DEVICE_ENGINE x EXEC_LANES x FUSED_BATCH_DISPATCH over the
    native and the Python trie, and the engine's merged resolve with
    its level hashes pinned to the device dispatches
    (EXEC_MERGED_DEVICE_HASH = True): whichever of the trie's update
    paths (trie.py _update, mpt_c.c set_many, device_state.py
    _DeferredTrie and _bulk_merge) a batch of rewrites takes, the
    uncommitted and the committed roots are Replay's."""
    monkeypatch.setattr(Config, "EXEC_MERGED_DEVICE_HASH", device_hash)
    if not native:
        monkeypatch.setattr(pruning_state, "_TrieBackend", Trie)
    elif pruning_state._TrieBackend is Trie:
        pytest.skip("no native trie here (cc missing)")
    seed, per_batch = 2147483900, 60
    conf = Config(STATE_DEVICE_ENGINE=engine)
    dm = NodeBootstrap.init_storage(config=conf)
    wm, _rm = NodeBootstrap.init_managers(dm, conf)
    txns = genesis(seed)
    NodeBootstrap.load_genesis(wm, txns)
    rejects, store = [], {}
    executor = NodeBatchExecutor(
        wm, store.get, lanes=lanes, lane_min=2, fused_dispatch=fused,
        on_request_rejected=lambda *a: rejects.append(a))
    replay = ref.Replay(txns)
    ledger, state = dm.get_ledger(DOMAIN_LEDGER_ID), \
        dm.get_state(DOMAIN_LEDGER_ID)
    assert str(ledger.root_hash) == replay.ledger_root()
    valid = [req for req, ok in made(seed, 3 * per_batch + 10) if ok]
    staged = []
    for b in range(3):
        reqs = valid[b * per_batch:(b + 1) * per_batch]
        assert len({r["identifier"] for r in reqs}) < len(reqs)
        pp_time = TS + b
        state_root, txn_root, _audit = apply_requests(
            executor, store, reqs, pp_time)
        for req in reqs:
            replay.append(ref.expected_txn(req, replay.size + 1, pp_time))
        assert txn_root == replay.ledger_root()
        assert state_root == replay.state_root()
        staged.append((b + 1, (state_root, txn_root), pp_time))
    assert not rejects
    for batch_no, roots, pp_time in staged:
        executor.commit_batch(ordered(batch_no, roots, pp_time))
    assert ledger.size == replay.size == len(txns) + 3 * per_batch
    assert str(ledger.root_hash) == replay.ledger_root()
    assert ledger.hashToStr(state.committedHeadHash) == replay.state_root()
    # every record is what its last write in ledger order left
    handler = wm.request_handlers[NYM]
    for did, record in replay.records.items():
        got, _seq, _time = handler.get_nym_details(did)
        assert got == record, did


# ----------------------- the fused window's route: host against engines

def thousand_writes(seed):
    """A 3PC batch of 1,000 valid writes: 600 creations of fresh DIDs
    by role-less genesis identities and 400 owners' rewrites of their
    own genesis NYMs (zipfian, several writes of one key), interleaved
    three to two."""
    create = [r for r, ok in nym_write_authors.make(
        seed, 620, MIX, {"identities": IDENTITIES}, first_req_id=10 ** 6)
        if ok][:600]
    rewrite = [r for r, ok in made(seed, 420) if ok][:400]
    return [req for i in range(200)
            for req in create[3 * i:3 * i + 3] + rewrite[2 * i:2 * i + 2]]


def route_run(monkeypatch, fused, lanes, seed=2147483911):
    """The batch through NodeBatchExecutor, in the fused window
    (`fused`) or through the engine's merged resolve with its level
    hashes pinned to the device dispatches: apply, revert, apply again,
    commit. A genesis DID that no write names is deleted from the state
    while the 500th request applies (no handler deletes, so the delete
    is planted). → (the roots after each step, the fused_dispatch
    spans' arguments)."""
    from plenum_tpu.observability.tracing import Tracer
    monkeypatch.setattr(Config, "EXEC_MERGED_DEVICE_HASH", True)
    dm = NodeBootstrap.init_storage(config=Config(STATE_DEVICE_ENGINE=True))
    wm, _rm = NodeBootstrap.init_managers(dm)
    NodeBootstrap.load_genesis(wm, genesis(seed))
    reqs = thousand_writes(seed)
    named = {r["operation"]["dest"] for r in reqs}
    gone = next(t["txn"]["data"]["dest"] for t in genesis(seed)[1:]
                if t["txn"]["data"]["dest"] not in named)
    state = dm.get_state(DOMAIN_LEDGER_ID)
    apply = wm.apply_request_deferred
    at = Request.from_dict(dict(reqs[499])).digest

    def apply_and_delete(request, *args):
        out = apply(request, *args)
        if request.digest == at:
            state.remove(nym_to_state_key(gone))
        return out

    wm.apply_request_deferred = apply_and_delete
    store = {}
    executor = NodeBatchExecutor(wm, store.get, lanes=lanes,
                                 fused_dispatch=fused)
    executor.tracer = Tracer(name="X", capacity=4096)
    roots = [apply_requests(executor, store, reqs, TS)]
    assert executor.revert_unordered_batches() == 1
    roots.append(state.headHash)
    roots.append(apply_requests(executor, store, reqs, TS))
    executor.commit_batch(ordered(1, roots[-1], TS))
    ledgers = [dm.get_ledger(lid) for lid in (DOMAIN_LEDGER_ID,
                                              AUDIT_LEDGER_ID)]
    roots.append([str(lg.root_hash) for lg in ledgers]
                 + [state.committedHeadHash, state.get(
                     nym_to_state_key(gone)), ledgers[0].size])
    args = [a for _k, name, _c, _t0, _t1, _key, a
            in executor.tracer.spans() if name == "fused_dispatch"]
    return roots, args


@pytest.mark.parametrize("lanes", [True, False])
def test_fused_window_applies_on_the_host_byte_equal_to_the_engines(
        monkeypatch, lanes):
    """The fused window serves a batch of 1,000 creations, rewrites and
    a delete on the host (hashlib leaves, one set_many) with the engines
    attached and their level hashes pinned to the device, and every
    root (state, txn, audit) after apply, revert, re-apply and commit
    is the engine's merged resolve's."""
    host_roots, host_args = route_run(monkeypatch, True, lanes)
    device_roots, device_args = route_run(monkeypatch, False, lanes)
    assert host_roots == device_roots
    assert host_roots[-1][3] is None               # the delete landed
    assert host_roots[-1][4] == IDENTITIES + 1 + 1000
    assert [a["host_applied"] for a in host_args] == [1000, 1000]
    assert [a["batch_size"] for a in host_args] == [1000, 1000]
    assert device_args == []


# ------------------------------------------------------------ the lanes

def test_lanes_are_exactly_the_per_did_groups():
    seed = 77
    dm = NodeBootstrap.init_storage(config=Config(
        STATE_DEVICE_ENGINE=False))
    wm, _rm = NodeBootstrap.init_managers(dm)
    batch = [Request.from_dict(dict(req))
             for req, ok in made(seed, 204) if ok]
    assert len(batch) == 200
    plan = plan_lanes([wm.touched_keys(r) for r in batch])
    by_did = collections.defaultdict(list)
    for i, request in enumerate(batch):
        by_did[request.identifier].append(i)
    by_lane = collections.defaultdict(list)
    for i, lane in enumerate(plan.lanes):
        by_lane[lane].append(i)
    assert sorted(by_lane.values()) == sorted(by_did.values())
    assert plan.serial_requests == 0
    assert plan.n_lanes == len(by_did)
    conflicted = sum(len(v) for v in by_did.values() if len(v) > 1)
    assert 0 < conflicted < len(batch)
    assert plan.conflicted == conflicted
    assert plan.conflict_ratio == conflicted / len(batch)
    assert max(plan.lane_sizes.values()) \
        == max(len(v) for v in by_did.values())
    # every write key is also a read key: the owner reads what it writes
    assert set(plan.write_keys_by_ledger[DOMAIN_LEDGER_ID]) \
        == set(plan.read_keys_by_ledger[DOMAIN_LEDGER_ID]) \
        == {nym_to_state_key(did) for did in by_did}


def test_spans_carry_the_conflicts_and_the_cache_misses():
    """What the per-layer metrics read: exec_validate's `conflicted`
    and `largest_lane`, lane_apply's `nym_misses`; and a disarmed
    tracer records none of it."""
    from plenum_tpu.observability.tracing import Tracer
    seed = 77
    dm = NodeBootstrap.init_storage(config=Config(
        STATE_DEVICE_ENGINE=False))
    wm, _rm = NodeBootstrap.init_managers(dm)
    NodeBootstrap.load_genesis(wm, genesis(seed))
    store = {}
    executor = NodeBatchExecutor(wm, store.get, lanes=True, lane_min=2)
    executor.tracer = Tracer(name="X", capacity=256, armed=False)
    reqs = [req for req, ok in made(seed, 120) if ok]
    apply_requests(executor, store, reqs[:50], TS)
    assert executor.tracer.spans() == []
    executor.tracer.arm()
    # a role edit reads its author's role through the record cache
    handler = wm.request_handlers[NYM]
    before = handler.nym_misses
    assert handler.cached_nym_record(reqs[0]["identifier"]) is not None
    assert handler.cached_nym_record(reqs[0]["identifier"]) is not None
    assert handler.nym_misses == before + 1
    apply_requests(executor, store, reqs[50:100], TS + 1)
    args = {name: a for _k, name, _c, _t0, _t1, _key, a
            in executor.tracer.spans()}
    sizes = collections.Counter(r["identifier"] for r in reqs[50:100])
    assert args["exec_validate"]["conflicted"] == sum(
        n for n in sizes.values() if n > 1)
    assert args["exec_validate"]["largest_lane"] == max(sizes.values())
    assert args["exec_validate"]["batch_size"] == 50
    assert args["lane_apply"]["nym_misses"] == 0   # no role is read


# ------------------------------------- the verkey a node authenticates by

def sim_nodes(names, txns, conf=None, heard=None):
    """Full Nodes of a four-validator pool on SimNetwork + MockTimer,
    their domain genesis loaded → (timer, nodes)."""
    timer = MockTimer()
    timer.set_time(SIM_EPOCH)
    net = SimNetwork(timer, DefaultSimRandom(29))
    return timer, [
        Node(name, NAMES, timer, net.create_peer(name),
             config=conf or Config(), genesis_txns=txns,
             client_reply_handler=(lambda _c, m, n=name: heard[n].append(m))
             if heard is not None else None)
        for name in names]


def test_rewritten_verkey_is_served_and_an_intruder_refused():
    """After a rewrite the node resolves the NEW form (uncommitted
    state included), the raw key is the same 32 bytes, and another
    identity's signature under the rewritten DID is refused: before
    the batch commits, after it, and after a later batch is reverted."""
    seed = 11
    _timer, (node,) = sim_nodes(["Alpha"], genesis(seed, 40))
    owner, other = traffic.identity(seed, 3), traffic.identity(seed, 4)
    whole, abbreviated = nym_rewrite_owners.verkey_forms(owner)
    did = owner.identifier
    store = {}
    executor = NodeBatchExecutor(node.write_manager, store.get,
                                 lanes=True, lane_min=1)

    def rewrite(req_id, verkey, signer=owner):
        req = {"identifier": did, "reqId": req_id, "protocolVersion": 2,
               "operation": {"type": "1", "dest": did, "verkey": verkey}}
        req["signature"] = signer.sign(req)
        return req

    def served(form, req_id):
        assert node._verkey_from_domain_state(did) == form
        assert node.authnr._raw_verkey(did) == owner.verkey
        assert node.authnr.authenticate(Request.from_dict(
            rewrite(req_id, abbreviated))) == [did]
        with pytest.raises(Exception):
            node.authnr.authenticate(Request.from_dict(
                rewrite(req_id + 1, abbreviated, signer=other)))
        # the PROPAGATE gate resolves through the same state
        gate = CoreAuthNr(verkey_provider=node._verkey_from_domain_state)
        with pytest.raises(Exception):
            gate.authenticate(Request.from_dict(
                rewrite(req_id + 2, whole, signer=other)))

    served(abbreviated, 100)                   # as the genesis holds it
    roots = apply_requests(executor, store, [rewrite(1, whole)], TS)
    served(whole, 200)                         # applied, not committed
    executor.commit_batch(ordered(1, roots, TS))
    served(whole, 300)                         # committed
    apply_requests(executor, store, [rewrite(2, abbreviated),
                                     rewrite(3, whole),
                                     rewrite(4, abbreviated)], TS + 1)
    served(abbreviated, 400)         # three writes of one key, one batch
    assert executor.revert_unordered_batches() == 1
    served(whole, 500)                         # reverted


# ------------------------------------------------- four nodes, in process

def test_late_propagate_of_an_ordered_rewrite_is_not_ordered_again():
    """Found on the chip host (PR 29's sweep, 1,280 writes/s offered:
    ten txns on every ledger that no client had sent twice). A relay's
    PROPAGATE that arrives after the request's batch committed here
    finds the request store freed; voting for it again finalised it,
    and an owner's rewrite — unlike the creation of a DID, which fails
    dynamic validation the second time — is valid whenever it is
    ordered, so it reached the ledger a second time."""
    seed = 2147483900
    timer, nodes = sim_nodes(
        NAMES, genesis(seed), Config(Max3PCBatchSize=10,
                                     Max3PCBatchWait=0.2))
    req = next(r for r, ok in made(seed, 5) if ok)
    submit_to_all(nodes, req, "client")
    pump(timer, nodes, 3)
    size = len(genesis(seed)) + 1
    assert [n.domain_ledger.size for n in nodes] == [size] * 4
    assert all(not n.propagator.requests for n in nodes)
    for node in nodes:
        relay = next(name for name in NAMES if name != node.name)
        node.propagator.process_propagate(
            Propagate(request=dict(req), senderClient="client"), relay)
    pump(timer, nodes, 3)
    assert [n.domain_ledger.size for n in nodes] == [size] * 4
    assert all(not n.propagator.requests for n in nodes)
    # a request nobody has ordered is still learned from a relay
    other = next(r for r, ok in made(seed, 9)[5:] if ok)
    for node in nodes[:2]:
        node.propagator.process_propagate(
            Propagate(request=dict(other), senderClient="client"),
            nodes[3].name)
    pump(timer, nodes, 3)
    assert [n.domain_ledger.size for n in nodes] == [size + 1] * 4



def test_four_nodes_order_the_stream_and_answer_as_the_reference():
    """400 writes of the stream through a four-node in-process pool,
    judged by the benchmark's own check.compare: every REPLY body is
    the reference's txn, every corrupted write refused by every node,
    and every node's ledger and state roots are Replay's."""
    seed, count = 2147483900, 400
    txns = genesis(seed)
    heard = {name: [] for name in NAMES}
    timer, nodes = sim_nodes(
        NAMES, txns, Config(Max3PCBatchSize=100, Max3PCBatchWait=0.3), heard)
    ops = [Op(req, b"", valid) for req, valid in made(seed, count)]
    for lo in range(0, count, 80):           # five bursts
        for op in ops[lo:lo + 80]:
            submit_to_all(nodes, op.request, "client")
        pump(timer, nodes, 3)
    pump(timer, nodes, 6)
    by_req_id = {op.request["reqId"]: op for op in ops}
    for name, messages in heard.items():
        for m in messages:
            if isinstance(m, Reply):
                op = by_req_id[m.result["txn"]["metadata"]["reqId"]]
                op.answers.setdefault(reply_body(m.result), []).append(name)
            elif isinstance(m, RequestNack):
                by_req_id[m.reqId].refused[name] = ("REQNACK", m.reason)
    for op in ops:
        if any(len(v) > 1 for v in op.answers.values()):
            op.done = 1.0
    reports = {n.name: {
        "Ledger_sizes": {"domain": n.domain_ledger.size},
        "Committed_ledger_root_hashes": {
            "domain": str(n.domain_ledger.root_hash)},
        "Committed_state_root_hashes": {
            "domain": n.domain_ledger.hashToStr(n.db_manager.get_state(
                DOMAIN_LEDGER_ID).committedHeadHash)},
        "Device_mesh": {}} for n in nodes}
    obs = check.Observed(NAMES, 1, ops, reports, test_control.READY,
                         test_control.STATS)
    got = check.compare(obs, txns)
    assert check.verdict(got["values"]), (got["values"], got["notes"])
    assert got["notes"]["reference"]["size"] == len(txns) + 392
    # the trie kept its leaves: rewrites add none
    leaves = nodes[0].db_manager.get_state(DOMAIN_LEDGER_ID).head
    assert sum(1 for _ in leaves.items()) == len(txns)
