"""A node's genesis load (ISSUE 30): NodeBootstrap.load_genesis hands
each ledger its genesis txns in bulk and commits each state once. What
it leaves in ledger, state and stores is what the per-transaction loop
it replaced left, on every store a node runs on, with and without the
device state engine; a second Node on the same stores skips the load;
a lost state root key is mended by Node._recover_from_storage.
"""
import json

import pytest

from plenum_tpu.bootstrap import node_genesis_txn, nym_genesis_txn
from plenum_tpu.common.config import Config
from plenum_tpu.common.constants import (
    DOMAIN_LEDGER_ID, NYM, POOL_LEDGER_ID, TRUSTEE)
from plenum_tpu.common.txn_util import get_type, init_empty_txn
from plenum_tpu.crypto.signer import DidSigner
from plenum_tpu.runtime.sim_random import DefaultSimRandom
from plenum_tpu.server.node import Node, NodeBootstrap
from plenum_tpu.server.validator_info import ValidatorNodeInfoTool
from plenum_tpu.state.pruning_state import PruningState
from plenum_tpu.storage import kv_native
from plenum_tpu.storage.kv_file import KeyValueStorageFile
from plenum_tpu.storage.kv_memory import KeyValueStorageInMemory
from plenum_tpu.testing.mock_timer import MockTimer
from plenum_tpu.testing.sim_network import SimNetwork

from tests.test_node_e2e import NAMES, SIM_EPOCH

NYMS = 3000
CASES = [(store, engine) for store in ("memory", "file", "native")
         for engine in (False, True)]
LEDGERS = (POOL_LEDGER_ID, DOMAIN_LEDGER_ID)


def signer(i):
    return DidSigner(seed=(b"%06d" % i).rjust(32, b"g"))


def genesis():
    """The pool's NODE txns (one node's record written twice), then
    NYMS role-less NYMs among which one DID comes three times (then
    with a role, then with its verkey in the other form), and a txn of
    a type no handler knows; through JSON, as a genesis file gives
    them."""
    steward = signer(0)
    pool = [node_genesis_txn(name, "node-key-" + name, "127.0.0.1",
                             9700 + 2 * i, "127.0.0.1", 9701 + 2 * i,
                             steward.identifier)
            for i, name in enumerate(NAMES)]
    moved = node_genesis_txn(NAMES[1], "node-key-" + NAMES[1], "127.0.0.2",
                             9800, "127.0.0.2", 9801, steward.identifier)
    domain = [nym_genesis_txn(steward.identifier, steward.verkey, TRUSTEE)]
    twice = signer(7)
    for i in range(1, NYMS):
        s = signer(i)
        domain.append(nym_genesis_txn(s.identifier, s.verkey))
        if i == NYMS // 3:
            domain.append(nym_genesis_txn(twice.identifier, twice.verkey,
                                          TRUSTEE))
        if i == 2 * NYMS // 3:
            domain.append(nym_genesis_txn(twice.identifier,
                                          twice.full_verkey))
    unknown = init_empty_txn("no-such-type")
    return json.loads(json.dumps(pool + [moved, unknown] + domain))


def load_per_txn(wm, txns):
    """The loop Node._load_genesis was before ISSUE 30."""
    for txn in txns:
        handler = wm.request_handlers.get(get_type(txn))
        if handler is None:
            continue
        handler.ledger.add(dict(txn))
        handler.update_state(txn, None, None, is_committed=True)
        if handler.state is not None:
            handler.state.commit()


class Stores:
    """A node's stores of one kind under one directory, by name: a
    second factory() over the same directory opens what the first
    wrote (the in-memory ones are simply handed out again)."""

    def __init__(self, kind, base_dir):
        if kind == "native" and not kv_native.available():
            pytest.skip("no native kv engine here (cc missing)")
        self.kind, self.dir = kind, str(base_dir)
        self.memory, self.opened = {}, []

    def factory(self):
        def make(name):
            if self.kind == "memory":
                return self.memory.setdefault(
                    name, KeyValueStorageInMemory())
            cls = KeyValueStorageFile if self.kind == "file" \
                else kv_native.KeyValueStorageNative
            store = cls(self.dir, name)
            self.opened.append(store)
            return store
        return make

    def close(self):
        for store in self.opened:
            store.close()
        self.opened = []


def managers(stores, engine):
    conf = Config(STATE_DEVICE_ENGINE=engine)
    dm = NodeBootstrap.init_storage(stores.factory(), conf)
    wm, _rm = NodeBootstrap.init_managers(dm, conf)
    return dm, wm


def seen(dm):
    """Everything of the two ledgers and states a reader could ask."""
    out = {}
    for lid in LEDGERS:
        ledger, state = dm.get_ledger(lid), dm.get_state(lid)
        size = ledger.size
        leaves = dict(state.committedHead.items())
        out[lid] = {
            "size": size,
            "root": ledger.root_hash,
            "txns": [ledger.getBySeqNo(s) for s in range(1, size + 1)],
            "stored": [bytes(v) for _, v in ledger._store.iterator()],
            "proofs": [ledger.merkleInfo(s)
                       for s in (1, (size + 1) // 2, size)],
            "committed_head": state.committedHeadHash,
            "head": state.headHash,
            "root_key": bytes(state._kv.get(PruningState.rootHashKey)),
            "leaves": leaves,
            "gets": {k: (state.get(k), state.get(k, isCommitted=False))
                     for k in leaves},
        }
    return out


@pytest.mark.parametrize("store,engine", CASES)
def test_bulk_load_leaves_what_the_per_txn_loop_left(tmp_path, store,
                                                     engine):
    bulk_dm, bulk_wm = managers(Stores(store, tmp_path / "bulk"), engine)
    loop_dm, loop_wm = managers(Stores(store, tmp_path / "loop"), engine)
    txns = genesis()
    loaded = NodeBootstrap.load_genesis(bulk_wm, txns)
    load_per_txn(loop_wm, genesis())
    assert loaded == len(txns) - 1          # the unknown type is skipped
    bulk, loop = seen(bulk_dm), seen(loop_dm)
    assert bulk[POOL_LEDGER_ID]["size"] == len(NAMES) + 1
    assert bulk[DOMAIN_LEDGER_ID]["size"] == NYMS + 2
    assert len(bulk[POOL_LEDGER_ID]["leaves"]) == len(NAMES)
    assert len(bulk[DOMAIN_LEDGER_ID]["leaves"]) == NYMS
    for lid in LEDGERS:
        for what, value in loop[lid].items():
            assert bulk[lid][what] == value, (lid, what)
        assert bulk[lid]["head"] == bulk[lid]["committed_head"] \
            == bulk[lid]["root_key"]
    assert not bulk_dm.get_state(DOMAIN_LEDGER_ID)._pending
    # the DID written three times: the last txn's verkey, the second's
    # role, the first's seqNo
    record, _seq, _time = bulk_wm.request_handlers[NYM].get_nym_details(
        signer(7).identifier)
    assert (record["verkey"], record["role"]) == (
        signer(7).full_verkey, TRUSTEE)
    assert record["seqNo"] == 8


def sim_node(stores, engine, txns):
    timer = MockTimer()
    timer.set_time(SIM_EPOCH)
    net = SimNetwork(timer, DefaultSimRandom(30))
    return Node(NAMES[0], NAMES, timer, net.create_peer(NAMES[0]),
                config=Config(STATE_DEVICE_ENGINE=engine),
                storage_factory=stores.factory(), genesis_txns=txns)


def roots(node):
    return {lid: (node.db_manager.get_ledger(lid).size,
                  node.db_manager.get_ledger(lid).root_hash,
                  node.db_manager.get_state(lid).committedHeadHash)
            for lid in LEDGERS}


@pytest.mark.parametrize("store,engine", CASES)
def test_second_node_on_the_same_stores_skips_the_load(tmp_path, store,
                                                       engine):
    stores = Stores(store, tmp_path)
    first = sim_node(stores, engine, genesis())
    assert first.genesis_load["txns"] == len(NAMES) + 1 + NYMS + 2
    assert first.genesis_load["seconds"] > 0
    info = ValidatorNodeInfoTool(first).info["Node_info"]
    assert info["Genesis_load"] == first.genesis_load
    want = roots(first)
    stores.close()
    second = sim_node(stores, engine, genesis())
    assert second.genesis_load is None
    assert "Genesis_load" not in ValidatorNodeInfoTool(second).info[
        "Node_info"]
    assert roots(second) == want
    record, _seq, _time = second.write_manager.request_handlers[
        NYM].get_nym_details(signer(7).identifier)
    assert record["role"] == TRUSTEE


@pytest.mark.parametrize("store,engine", CASES)
def test_lost_state_root_key_is_rebuilt_from_the_txn_log(tmp_path, store,
                                                         engine):
    stores = Stores(store, tmp_path)
    first = sim_node(stores, engine, genesis())
    want = roots(first)
    for lid in LEDGERS:
        first.db_manager.get_state(lid)._kv.remove(PruningState.rootHashKey)
    stores.close()
    second = sim_node(stores, engine, genesis())
    assert second.genesis_load is None
    assert roots(second) == want
    for lid in LEDGERS:
        state = second.db_manager.get_state(lid)
        assert bytes(state._kv.get(PruningState.rootHashKey)) \
            == want[lid][2]


def test_the_one_commit_goes_through_the_host_trie():
    """commit_bulk_load never asks an attached engine, whatever the
    buffer's size, and reaches the root a plain commit reaches; a
    serving flush of the same state still asks it first."""
    class Engine:
        def __init__(self):
            self.asked = []

        def __getattr__(self, name):
            def refuse(*_a, **_k):
                self.asked.append(name)
                raise RuntimeError(name)
            return refuse

    pairs = [(b"key-%d" % i, b"value-%d" % i) for i in range(200)]
    plain = PruningState(KeyValueStorageInMemory())
    state = PruningState(KeyValueStorageInMemory())
    engine = Engine()
    state.attach_device_engine(engine=engine, batch_min=8)
    for st in (plain, state):
        for k, v in pairs:
            st.set(k, v)
    plain.commit()
    state.commit_bulk_load()
    assert engine.asked == []
    assert state.committedHeadHash == state.headHash \
        == plain.committedHeadHash
    for k, v in pairs[:8]:
        state.set(k, v + b"'")
    state.commit()
    assert engine.asked == ["apply_batch"]
