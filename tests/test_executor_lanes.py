"""Shard-parallel deterministic execution (ISSUE 13): the conflict-lane
executor, its lane planner, the read-window/bulk-merge/merged-resolve
state machinery, and the lane-safety of handler read caches.

The load-bearing contract is BYTE-EQUALITY: whatever the lane planner
decides, the applied ledger/state/txn/audit roots must be identical to
the serial apply path on the identical digest stream — across
conflicting writes, read-your-own-lane-write chains, mixed ledgers,
interleaved rejects, commits and mid-stream view-change reverts.
micro_executor in bench.py asserts the same equivalence per batch, so
the bench gate and this file pin the contract from both sides.
"""
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from plenum_tpu.common.config import Config
from plenum_tpu.common.constants import (
    AUDIT_LEDGER_ID, CONFIG_LEDGER_ID, DATA, DOMAIN_LEDGER_ID, NODE, NYM,
    POOL_LEDGER_ID, ROLE, STEWARD, TARGET_NYM, TRUSTEE, VERKEY)
from plenum_tpu.common.request import Request
from plenum_tpu.common.state_codec import (
    decode_state_value, encode_state_value, nym_to_state_key)
from plenum_tpu.server.execution_lanes import (
    SERIAL_LANE, TouchedKeys, plan_lanes)
from plenum_tpu.server.executor import NodeBatchExecutor
from plenum_tpu.server.node import NodeBootstrap
from plenum_tpu.state.pruning_state import (
    PruningState, flush_states_merged)
from plenum_tpu.state.trie import BLANK_ROOT, Trie
from plenum_tpu.storage.kv_memory import KeyValueStorageInMemory

TS = 1700000000


# ----------------------------------------------------------- lane plan

def tk(reads=(), writes=()):
    return TouchedKeys(reads=[(1, k) for k in reads],
                       writes=[(1, k) for k in writes])


def test_plan_disjoint_requests_get_their_own_lanes():
    plan = plan_lanes([tk(reads=[b"a"], writes=[b"a"]),
                       tk(reads=[b"b"], writes=[b"b"]),
                       tk(reads=[b"c"], writes=[b"c"])])
    assert plan.n_lanes == 3
    assert len(set(plan.lanes)) == 3
    assert plan.serial_requests == 0
    assert plan.conflict_ratio == 0.0


def test_plan_read_read_sharing_never_merges():
    # every request reads the hot author key; none writes it
    plan = plan_lanes([tk(reads=[b"author", b"t%d" % i],
                          writes=[b"t%d" % i]) for i in range(5)])
    assert plan.n_lanes == 5
    assert plan.conflict_ratio == 0.0


def test_plan_write_involved_sharing_merges():
    # w/w, w-then-r and r-then-w all serialize into one lane
    plan = plan_lanes([
        tk(writes=[b"k"]),                    # writer
        tk(reads=[b"k"], writes=[b"x"]),      # reader after writer
        tk(writes=[b"k"]),                    # second writer
    ])
    assert plan.n_lanes == 1
    assert len(set(plan.lanes)) == 1
    assert plan.conflict_ratio == 1.0
    # reader BEFORE the writer of its key also joins the writer's lane
    plan = plan_lanes([tk(reads=[b"k"], writes=[b"a"]),
                       tk(writes=[b"k"])])
    assert plan.n_lanes == 1


def test_plan_transitive_chains_union():
    plan = plan_lanes([tk(writes=[b"a"]),
                       tk(reads=[b"a"], writes=[b"b"]),
                       tk(reads=[b"b"], writes=[b"c"]),
                       tk(writes=[b"z"])])
    assert plan.n_lanes == 2
    assert plan.lanes[0] == plan.lanes[1] == plan.lanes[2]
    assert plan.lanes[3] != plan.lanes[0]


def test_plan_undeclared_requests_take_the_serial_lane():
    plan = plan_lanes([tk(writes=[b"a"]), None, tk(writes=[b"b"]), None])
    assert plan.serial_requests == 2
    assert plan.lanes[1] == plan.lanes[3] == SERIAL_LANE
    assert plan.n_lanes == 3  # two declared singletons + serial
    assert plan.conflict_ratio == 0.5


def test_plan_is_deterministic_and_key_books_complete():
    touches = [tk(reads=[b"r%d" % (i % 3)], writes=[b"w%d" % (i % 4)])
               for i in range(20)]
    p1, p2 = plan_lanes(touches), plan_lanes(list(touches))
    assert p1.lanes == p2.lanes
    assert p1.n_lanes == p2.n_lanes
    assert sorted(p1.read_keys_by_ledger[1]) == sorted(
        {b"r0", b"r1", b"r2"})
    assert sorted(p1.write_keys_by_ledger[1]) == sorted(
        {b"w0", b"w1", b"w2", b"w3"})
    assert sum(p1.lane_sizes.values()) == 20


# --------------------------------------- bulk merge / merged resolve

def _rand_key(rng):
    kind = rng.randrange(3)
    if kind == 0:
        return ("did:sov:%s" % rng.randbytes(6).hex()).encode()
    if kind == 1:
        return rng.randbytes(rng.randrange(1, 5))
    return b"taa:" + rng.randbytes(rng.randrange(0, 3)).hex().encode()


@pytest.mark.parametrize("use_device", [False, True])
def test_begin_apply_resolve_byte_equal_to_host_trie(use_device):
    """Randomized batches (fresh keys, overwrites, deletes, inline and
    hashed nodes, extension splits) through begin_apply + the merged
    resolver produce roots byte-equal to per-key host Trie updates —
    on both the hashlib and the forced-device hash routes."""
    from plenum_tpu.state.device_state import (
        DeviceStateEngine, resolve_applies)
    seeds = range(40) if not use_device else range(6)
    for seed in seeds:
        rng = random.Random(seed)
        host = Trie(KeyValueStorageInMemory())
        eng = DeviceStateEngine(KeyValueStorageInMemory(), hash_floor=8)
        root = BLANK_ROOT
        for _ in range(3):
            batch = {}
            for _ in range(rng.randrange(1, 120)):
                batch[_rand_key(rng)] = (
                    b"" if rng.random() < 0.15
                    else rng.randbytes(rng.randrange(1, 60)))
            for k, v in batch.items():
                if v:
                    host.set(k, v)
                else:
                    host.delete(k)
            handle = eng.begin_apply(root, list(batch.items()))
            root = resolve_applies([handle],
                                   use_device=use_device)[0]
            assert root == host.root_hash, (use_device, seed)


def test_flush_states_merged_multi_state_byte_equal():
    """Three states' pending buffers resolve in ONE merged pass, each
    root byte-equal to its own host trie; states below the engine
    batch threshold flush through the host path inside the same
    call."""
    rng = random.Random(99)
    hosts, states = [], []
    for _ in range(3):
        hosts.append(Trie(KeyValueStorageInMemory()))
        st = PruningState(KeyValueStorageInMemory())
        st.attach_device_engine(batch_min=4)
        states.append(st)
    for _round in range(3):
        for i, (host, st) in enumerate(zip(hosts, states)):
            # state 2 stays tiny: below batch_min -> host flush path
            n = rng.randrange(0, 6) if i == 2 else rng.randrange(0, 40)
            for _ in range(n):
                k, v = _rand_key(rng), rng.randbytes(20)
                host.set(k, v)
                st.set(k, v)
        flush_states_merged(states, use_device=False)
        for host, st in zip(hosts, states):
            assert st.headHash == host.root_hash


def test_merged_resolve_failure_falls_back_to_host_path(monkeypatch):
    """A device failure inside the merged resolve costs the breaker a
    strike and serves the identical roots from the host trie."""
    from plenum_tpu.state import device_state
    host = Trie(KeyValueStorageInMemory())
    st = PruningState(KeyValueStorageInMemory())
    st.attach_device_engine(batch_min=2)
    monkeypatch.setattr(
        device_state, "_resolve_applies",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("device")))
    for i in range(8):
        k, v = b"k%d" % i, b"v%d" % i
        host.set(k, v)
        st.set(k, v)
    flush_states_merged([st], use_device=False)
    assert st.headHash == host.root_hash
    assert st._engine_breaker.fail_count == 1


# ------------------------------------------------------- read window

def test_read_window_serves_prebatch_values_and_pending_wins():
    st = PruningState(KeyValueStorageInMemory())
    st.set(b"a", b"1")
    st.set(b"b", b"2")
    st.commit()
    assert st.begin_read_window([b"a", b"b", b"absent"])
    # window hits: pre-batch values, absent stays None without a walk
    assert st.get(b"a", isCommitted=False) == b"1"
    assert st.get(b"absent", isCommitted=False) is None
    # a batch write goes pending-first and shadows the window
    st.set(b"a", b"9")
    assert st.get(b"a", isCommitted=False) == b"9"
    st.remove(b"b")
    assert st.get(b"b", isCommitted=False) is None
    st.end_read_window()
    assert st._read_window is None


def test_read_window_dropped_on_flush_and_revert():
    st = PruningState(KeyValueStorageInMemory())
    st.set(b"a", b"1")
    st.commit()
    st.begin_read_window([b"a"])
    st.set(b"a", b"2")
    _ = st.headHash  # flush: the pending-first shield is gone
    assert st._read_window is None
    # post-flush reads see the flushed write, not the stale window
    assert st.get(b"a", isCommitted=False) == b"2"
    st.begin_read_window([b"a"])
    st.revertToHead(st.committedHeadHash)
    assert st._read_window is None
    assert st.get(b"a", isCommitted=False) == b"1"


# --------------------------------------------------- executor stacks

def build_stack(lanes, n_base=60, lane_min=2, fused=True):
    dm = NodeBootstrap.init_storage()
    wm, _rm = NodeBootstrap.init_managers(dm)
    state = dm.get_state(DOMAIN_LEDGER_ID)
    state.set(nym_to_state_key("trustee1"),
              encode_state_value({"identifier": "genesis",
                                  ROLE: TRUSTEE, VERKEY: "~t"}, 1, TS))
    for i in range(n_base):
        state.set(nym_to_state_key("base%d" % i),
                  encode_state_value({"identifier": "gen",
                                      VERKEY: "~%d" % i}, i + 2, TS))
    state.commit()
    store = {}
    rejects = []
    executor = NodeBatchExecutor(
        wm, store.get, lanes=lanes, lane_min=lane_min, fused_dispatch=fused,
        on_request_rejected=lambda d, r, s: rejects.append((d, r, s)))
    return dm, wm, executor, store, rejects


def nym_req(req_id, dest, author="trustee1", role=None, verkey=None):
    op = {"type": NYM, TARGET_NYM: dest}
    if role is not None:
        op[ROLE] = role
    if verkey is not None:
        op[VERKEY] = verkey
    return Request(identifier=author, reqId=req_id, operation=op,
                   protocolVersion=2)


def node_req(req_id, alias, author="steward1"):
    return Request(identifier=author, reqId=req_id,
                   operation={"type": NODE, TARGET_NYM: "node" + alias,
                              DATA: {"alias": alias}},
                   protocolVersion=2)


def all_roots(dm):
    out = []
    for lid in (DOMAIN_LEDGER_ID, POOL_LEDGER_ID, CONFIG_LEDGER_ID,
                AUDIT_LEDGER_ID):
        ledger = dm.get_ledger(lid)
        out.append(ledger.hashToStr(ledger.uncommitted_root_hash))
        out.append(ledger.root_hash)
        state = dm.get_state(lid)
        if state is not None:
            out.append(state.headHash.hex())
            out.append(state.committedHeadHash.hex())
    return out


def _adversarial_batch(rng, i0):
    """One randomized adversarial batch: conflicting writes on hot
    nyms, read-your-own-lane-write chains (created-then-used author;
    created-then-rotated verkey), mixed ledgers (NODE in the serial
    lane), and interleaved rejects (unauthorized role grants, bad
    role values at dynamic stage, unknown authors granting roles)."""
    reqs = []
    n = rng.randrange(8, 26)
    for i in range(n):
        r = rng.random()
        rid = i0 + i
        if r < 0.25:
            reqs.append(nym_req(rid, "base%d" % rng.randrange(4)))
        elif r < 0.40:
            x = "lane%d" % rid
            reqs.append(nym_req(rid, x, role=TRUSTEE))
            reqs.append(nym_req(rid + 1000, "child%d" % rid,
                                author=x, role=STEWARD))
        elif r < 0.55:
            x = "rot%d" % rid
            reqs.append(nym_req(rid, x, verkey="~first"))
            reqs.append(nym_req(rid + 2000, x, author=x,
                                verkey="~second"))
        elif r < 0.65:
            reqs.append(nym_req(rid, "evil%d" % rid, author="nobody%d" % i,
                                role=TRUSTEE))  # reject: unknown author
        elif r < 0.75:
            reqs.append(node_req(rid, "Al%d" % rid,
                                 author="nobody"))  # reject: not steward
        else:
            reqs.append(nym_req(rid, "fresh%d" % rid,
                                verkey="~f%d" % rid))
    return reqs


@pytest.mark.parametrize("fused", [True, False])
def test_lanes_vs_serial_randomized_adversarial_equivalence(fused):
    """The headline gate: identical digest streams through the lane
    executor and the serial executor give byte-equal roots after every
    batch, commit, and mid-stream view-change revert — and identical
    reject streams (same digests, same seq numbers). In the fused
    window (the host trie) and through the engine's merged resolve."""
    from plenum_tpu.common.messages.node_messages import Ordered
    stacks = {mode: build_stack(mode, fused=fused) for mode in (True, False)}
    rng_master = random.Random(1234)
    pp_time = TS + 10
    applied = []
    for round_no in range(6):
        seed = rng_master.randrange(1 << 30)
        pp_time += 1
        outs = {}
        for mode in (True, False):
            dm, wm, executor, store, _rejects = stacks[mode]
            rng = random.Random(seed)
            batch = _adversarial_batch(rng, round_no * 10000)
            digests = []
            for req in batch:
                store[req.digest] = req
                digests.append(req.digest)
            outs[mode] = executor.apply_batch(
                digests, DOMAIN_LEDGER_ID, pp_time)
        assert outs[True] == outs[False], round_no
        assert all_roots(stacks[True][0]) == all_roots(stacks[False][0])
        applied.append(outs[True])
        if round_no == 2:
            # view change mid-stream: revert every staged batch
            for mode in (True, False):
                stacks[mode][2].revert_unordered_batches()
            assert all_roots(stacks[True][0]) == \
                all_roots(stacks[False][0])
            applied.clear()
    # commit the oldest staged batch on both sides
    for mode in (True, False):
        dm, wm, executor, store, _r = stacks[mode]
        state_root, txn_root, _ = applied[0]
        executor.commit_batch(Ordered(
            instId=0, viewNo=0, valid_reqIdr=["r"], invalid_reqIdr=[],
            ppSeqNo=1, ppTime=pp_time, ledgerId=DOMAIN_LEDGER_ID,
            stateRootHash=state_root, txnRootHash=txn_root,
            auditTxnRootHash=None, primaries=["P"]))
    assert all_roots(stacks[True][0]) == all_roots(stacks[False][0])
    # both modes rejected the same requests at the same seq numbers
    ra = [(r, s) for _, r, s in stacks[True][4]]
    rb = [(r, s) for _, r, s in stacks[False][4]]
    assert ra == rb and ra, "expected identical, non-empty rejects"


def test_multi_ledger_interleaved_seq_assignment():
    """Satellite: apply_request_deferred seq numbering when one batch
    interleaves ledgers — each ledger's txns get contiguous seq
    numbers from its own uncommitted_size, in batch order, and a
    second batch continues where the first left off."""
    dm, wm, executor, store, rejects = build_stack(lanes=True)
    # seed a steward for NODE txns
    st = dm.get_state(DOMAIN_LEDGER_ID)
    st.set(nym_to_state_key("steward1"),
           encode_state_value({"identifier": "genesis", ROLE: STEWARD,
                               VERKEY: "~s"}, 999, TS))
    st.commit()
    batch = [
        nym_req(1, "m1"), node_req(2, "AlphaNode"),
        nym_req(3, "m2"), nym_req(4, "m3"),
    ]
    digests = []
    for req in batch:
        store[req.digest] = req
        digests.append(req.digest)
    executor.apply_batch(digests, DOMAIN_LEDGER_ID, TS + 50)
    domain = dm.get_ledger(DOMAIN_LEDGER_ID)
    pool = dm.get_ledger(POOL_LEDGER_ID)
    from plenum_tpu.common.txn_util import get_seq_no
    assert not rejects
    assert [get_seq_no(t) for t in domain.uncommittedTxns] == [1, 2, 3]
    assert [get_seq_no(t) for t in pool.uncommittedTxns] == [1]
    # seq numbers embedded in the written STATE values match the txns
    val, lsn, _ = decode_state_value(
        st.get(nym_to_state_key("m2"), isCommitted=False))
    assert lsn == 2
    pool_state = dm.get_state(POOL_LEDGER_ID)
    _, node_lsn, _ = decode_state_value(pool_state.get(
        nym_to_state_key("nodeAlphaNode"), isCommitted=False))
    assert node_lsn == 1
    # a second interleaved batch continues each ledger's numbering
    batch2 = [node_req(5, "BetaNode", author="trustee1"), nym_req(6, "m4")]
    digests2 = []
    for req in batch2:
        store[req.digest] = req
        digests2.append(req.digest)
    executor.apply_batch(digests2, DOMAIN_LEDGER_ID, TS + 51)
    assert [get_seq_no(t) for t in domain.uncommittedTxns] == [1, 2, 3, 4]
    assert [get_seq_no(t) for t in pool.uncommittedTxns] == [1, 2]


def test_nym_cache_cannot_leak_stale_records_across_lanes():
    """Satellite: a role change applied earlier in the batch must be
    visible to every later author-role read, even when the author's
    record was cached from a PREVIOUS batch — the batch's declared
    writes are dropped from the cache before lane apply begins, and
    update_state pops what it writes."""
    dm, wm, executor, store, rejects = build_stack(lanes=True)
    nym_handler = wm.request_handlers[NYM]
    # batch 1: X exists with no role and is USED as an author (its
    # privileged grant rejects, which is exactly the author-role read
    # that populates the nym cache with X's roleless record)
    x = "cachedauthor"
    b1 = [nym_req(1, x, verkey="~x"),
          nym_req(2, "probe1", author=x, role=STEWARD)]
    digests = []
    for req in b1:
        store[req.digest] = req
        digests.append(req.digest)
    executor.apply_batch(digests, DOMAIN_LEDGER_ID, TS + 60)
    assert [s for _d, _r, s in rejects] == [1]  # the roleless grant
    rejects.clear()
    assert x in nym_handler._nym_cache
    assert (nym_handler._nym_cache[x] or {}).get(ROLE) is None
    # batch 2: a trustee promotes X, then X (now TRUSTEE) creates a
    # privileged nym LATER IN THE SAME BATCH — stale cache = reject
    b2 = [nym_req(10, x, role=TRUSTEE),
          nym_req(11, "privileged1", author=x, role=STEWARD)]
    digests = []
    for req in b2:
        store[req.digest] = req
        digests.append(req.digest)
    executor.apply_batch(digests, DOMAIN_LEDGER_ID, TS + 61)
    assert not rejects, rejects
    val, _, _ = decode_state_value(dm.get_state(DOMAIN_LEDGER_ID).get(
        nym_to_state_key("privileged1"), isCommitted=False))
    assert val.get(ROLE) == STEWARD
    # the pre-batch invalidation hook is what guarantees this shape
    # structurally: the declared write set empties the cached entry
    # before any lane read can resolve
    nym_handler._nym_cache["probe"] = {"r": 1}
    nym_handler.invalidate_for_writes([nym_to_state_key("probe")])
    assert "probe" not in nym_handler._nym_cache
    # undecodable keys clear wholesale instead of guessing
    nym_handler._nym_cache["q"] = {"r": 2}
    nym_handler.invalidate_for_writes([b"\xff\xfe"])
    assert nym_handler._nym_cache == {}


def test_touched_keys_declarations():
    dm, wm, executor, store, _r = build_stack(lanes=True)
    req = nym_req(1, "destX", author="authorY")
    tk_nym = wm.request_handlers[NYM].touched_keys(req)
    assert (DOMAIN_LEDGER_ID, nym_to_state_key("destX")) in tk_nym.reads
    assert (DOMAIN_LEDGER_ID, nym_to_state_key("authorY")) in tk_nym.reads
    assert tk_nym.writes == ((DOMAIN_LEDGER_ID,
                              nym_to_state_key("destX")),)
    # NODE is inherently dynamic -> undeclared
    assert wm.request_handlers[NODE].touched_keys(
        node_req(2, "A")) is None
    assert wm.touched_keys(node_req(2, "A")) is None
    # the write manager widens NYM with the TAA acceptance reads
    wide = wm.touched_keys(req)
    from plenum_tpu.server.taa_handlers import _path_digest, _path_latest
    assert (CONFIG_LEDGER_ID, _path_latest()) in wide.reads
    accepted = Request(identifier="authorY", reqId=3,
                       operation={"type": NYM, TARGET_NYM: "destX"},
                       protocolVersion=2,
                       taaAcceptance={"taaDigest": "d" * 8,
                                      "mechanism": "m", "time": TS})
    wide2 = wm.touched_keys(accepted)
    assert (CONFIG_LEDGER_ID, _path_digest("d" * 8)) in wide2.reads
    # malformed target -> handler opts out instead of guessing
    assert wm.request_handlers[NYM].touched_keys(Request(
        identifier="a", reqId=4, operation={"type": NYM},
        protocolVersion=2)) is None


def test_exec_substage_spans_and_lane_telemetry():
    """The executor's three sub-stages land in the flight recorder
    (feeding trace_budget's execute split) and the lane metrics land
    in the telemetry hub."""
    from plenum_tpu.observability.budget import budget_from_tracers
    from plenum_tpu.observability.telemetry import TM, TelemetryHub
    from plenum_tpu.observability.tracing import Tracer
    dm, wm, executor, store, _r = build_stack(lanes=True)
    executor.tracer = Tracer(name="X", capacity=4096)
    executor.telemetry = TelemetryHub(name="X")
    batch = [nym_req(i, "t%d" % (i % 3)) for i in range(6)]
    batch.append(node_req(9, "Z", author="trustee1"))
    digests = []
    for req in batch:
        store[req.digest] = req
        digests.append(req.digest)
    executor.apply_batch(digests, DOMAIN_LEDGER_ID, TS + 70)
    names = [name for _k, name, _c, _t0, _t1, _key, _a
             in executor.tracer.spans()]
    for expected in ("batch_apply", "exec_validate", "lane_apply",
                     "hash_resolve"):
        assert expected in names, names
    report = budget_from_tracers([executor.tracer])
    subs = report.get("execute_substages")
    assert subs and set(subs) == {"exec_validate", "lane_apply",
                                  "hash_resolve"}
    assert report["host_ms_per_ordered_req"]["execute"] > 0
    snap = executor.telemetry.snapshot()
    hists = snap["histograms"]
    assert hists[TM.EXEC_LANES_PER_BATCH]["count"] == 1
    assert hists[TM.EXEC_CONFLICT_PCT]["count"] == 1
    assert snap["counters"][TM.EXEC_SERIAL_FALLBACK] == 1  # the NODE txn


@pytest.mark.parametrize("accelerator,policy", [
    (False, "auto"), (True, "auto"), (True, True), (False, False)])
def test_fused_window_applies_on_the_host_on_every_platform(
        monkeypatch, accelerator, policy):
    """Whatever the platform and EXEC_MERGED_DEVICE_HASH, the fused
    window flushes every written state (domain and pool here) through
    flush_on_host and never reaches begin_apply, and fused_dispatch
    carries host_applied (the batch's valid writes) while armed; the
    roots are the engine's merged resolve's (the non-fused path, level
    hashes pinned to the device), which merges through begin_apply."""
    from plenum_tpu.observability.tracing import Tracer
    from plenum_tpu.ops import mesh
    from plenum_tpu.state.device_state import DeviceStateEngine
    monkeypatch.setattr(mesh, "is_accelerator", lambda: accelerator)
    calls = {"begin_apply": 0, "flush_on_host": 0}
    for cls, name in ((DeviceStateEngine, "begin_apply"),
                      (PruningState, "flush_on_host")):
        def counted(self, *args, _name=name, _fn=getattr(cls, name)):
            calls[_name] += 1
            return _fn(self, *args)
        monkeypatch.setattr(cls, name, counted)
    batch = [nym_req(i, "route%d" % i, verkey="~r%d" % i)
             for i in range(20)]
    batch += [node_req(100 + i, "N%d" % i, author="trustee1")
              for i in range(9)]
    batch.append(nym_req(200, "refused", author="nobody", role=TRUSTEE))
    roots = {}
    for fused in (True, False):
        monkeypatch.setattr(Config, "EXEC_MERGED_DEVICE_HASH",
                            policy if fused else True)
        dm, wm, executor, store, rejects = build_stack(lanes=True,
                                                       fused=fused)
        # the JAX leaf seam is attached only where the backend is an
        # accelerator: hashlib on every CPU-backend node
        assert (dm.get_ledger(DOMAIN_LEDGER_ID).hasher._batch_backend
                is not None) == accelerator
        executor.tracer = Tracer(name="X", capacity=4096)
        digests = []
        for req in batch:
            store[req.digest] = req
            digests.append(req.digest)
        for k in calls:
            calls[k] = 0
        executor.apply_batch(digests, DOMAIN_LEDGER_ID, TS + 60)
        roots[fused] = all_roots(dm)
        args = [a for _k, name, _c, _t0, _t1, _key, a
                in executor.tracer.spans() if name == "fused_dispatch"]
        assert len(rejects) == 1
        if fused:
            assert args == [dict(args[0], batch_size=29, host_applied=29)]
            assert calls == {"begin_apply": 0, "flush_on_host": 2}
        else:
            # the engine merges the domain state's 20 keys; the pool
            # state's one key is under STATE_DEVICE_BATCH_MIN and takes
            # the host trie inside the engine's flush
            assert args == []
            assert calls == {"begin_apply": 1, "flush_on_host": 0}
    assert roots[True] == roots[False]


def test_fused_dispatch_counts_the_trie_nodes_its_flush_framed(
        monkeypatch):
    """An armed fused_dispatch span carries trie_nodes, the nodes its
    state flush wrote to the store, and trie_nodes_framed, those of them
    that went out in batch frames: equal, and equal to what the stores
    were handed through setBatch in that window; unarmed, none."""
    from plenum_tpu.observability.tracing import Tracer
    pytest.importorskip("plenum_tpu.state.trie_native")
    handed = []

    def counted(self, batch, _fn=KeyValueStorageInMemory.setBatch):
        batch = list(batch)
        handed.append(len(batch))
        return _fn(self, batch)
    monkeypatch.setattr(KeyValueStorageInMemory, "setBatch", counted)
    for armed in (True, False):
        dm, wm, executor, store, _rejects = build_stack(lanes=True)
        executor.tracer = Tracer(name="X", capacity=4096, armed=armed)
        digests = []
        for i in range(40):
            req = nym_req(i, "framed%d" % i, verkey="~f%d" % i)
            store[req.digest] = req
            digests.append(req.digest)
        handed.clear()
        executor.apply_batch(digests, DOMAIN_LEDGER_ID, TS + 60)
        args = [a for _k, name, _c, _t0, _t1, _key, a
                in executor.tracer.spans() if name == "fused_dispatch"]
        if armed:
            assert len(args) == 1
            assert args[0]["trie_nodes"] == \
                args[0]["trie_nodes_framed"] == sum(handed) > 40
        else:
            assert args == [] and sum(handed) > 40


def test_lane_min_gates_planning():
    dm, wm, executor, store, _r = build_stack(lanes=True, lane_min=50)
    from plenum_tpu.observability.telemetry import TM, TelemetryHub
    executor.telemetry = TelemetryHub(name="X")
    batch = [nym_req(i, "small%d" % i) for i in range(4)]
    digests = []
    for req in batch:
        store[req.digest] = req
        digests.append(req.digest)
    executor.apply_batch(digests, DOMAIN_LEDGER_ID, TS + 80)
    snap = executor.telemetry.snapshot()
    assert TM.EXEC_LANES_PER_BATCH not in snap["histograms"]


def test_missing_request_raises_before_any_state_mutation():
    dm, wm, executor, store, _r = build_stack(lanes=True)
    good = nym_req(1, "ok1")
    store[good.digest] = good
    before = all_roots(dm)
    with pytest.raises(KeyError):
        executor.apply_batch([good.digest, "nonexistent-digest"],
                             DOMAIN_LEDGER_ID, TS + 90)
    assert all_roots(dm) == before
