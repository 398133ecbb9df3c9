"""Native log-structured KV engine (plenum_tpu/native/kvlog.c — SURVEY
§2.9 rocksdb/leveldb obligation) behind the KeyValueStorage ABC:
conformance, crash recovery (torn tail / torn batch), on-disk format
interop with the Python backend, compaction, and a full node restart
e2e on the native store.
"""
import os
import struct

import pytest

from plenum_tpu.storage import kv_native
from plenum_tpu.storage.kv_file import KeyValueStorageFile

if not kv_native.available():
    pytest.skip("no C compiler for the native kvlog engine",
                allow_module_level=True)

from plenum_tpu.storage.kv_native import KeyValueStorageNative


def test_basic_ops_and_iteration(tdir):
    kv = KeyValueStorageNative(tdir, "t1")
    kv.put(b"b", b"2")
    kv.put(b"a", b"1")
    kv.put(b"c", b"3" * 5000)         # > default read buffer
    assert kv.get(b"a") == b"1"
    assert kv.get(b"c") == b"3" * 5000
    assert len(kv) == 3
    assert [k for k, _ in kv.iterator()] == [b"a", b"b", b"c"]
    assert list(kv.iterator(start=b"b", include_value=False)) == [b"b", b"c"]
    kv.put(b"b", b"22")               # overwrite
    assert kv.get(b"b") == b"22"
    assert len(kv) == 3
    kv.remove(b"a")
    with pytest.raises(KeyError):
        kv.get(b"a")
    assert [k for k, _ in kv.iterator()] == [b"b", b"c"]
    kv.put(b"", b"empty-key")         # edge: empty key and value
    kv.put(b"z", b"")
    assert kv.get(b"") == b"empty-key"
    assert kv.get(b"z") == b""
    kv.close()
    assert kv.closed


def test_reopen_recovers_index(tdir):
    kv = KeyValueStorageNative(tdir, "t2")
    for i in range(500):
        kv.put(b"key-%04d" % i, b"val-%d" % i)
    kv.remove(b"key-0000")
    kv.setBatch([(b"batch-%d" % i, b"bv%d" % i) for i in range(10)])
    kv.close()
    kv2 = KeyValueStorageNative(tdir, "t2")
    assert len(kv2) == 509
    assert kv2.get(b"key-0499") == b"val-499"
    assert kv2.get(b"batch-7") == b"bv7"
    with pytest.raises(KeyError):
        kv2.get(b"key-0000")
    kv2.close()


def test_torn_tail_and_torn_batch_truncated(tdir):
    kv = KeyValueStorageNative(tdir, "t3")
    kv.put(b"good", b"value")
    kv.close()
    path = os.path.join(tdir, "t3.kvlog")
    # torn plain record
    with open(path, "ab") as f:
        f.write(struct.pack("<II", 4, 100) + b"torn")    # value missing
    kv2 = KeyValueStorageNative(tdir, "t3")
    assert len(kv2) == 1 and kv2.get(b"good") == b"value"
    kv2.close()
    # torn batch: header promises more than present
    with open(path, "ab") as f:
        f.write(struct.pack("<II", 0xFFFFFFFE, 1000) + b"short")
    kv3 = KeyValueStorageNative(tdir, "t3")
    assert len(kv3) == 1
    kv3.put(b"after", b"recovery")    # still writable after truncation
    assert kv3.get(b"after") == b"recovery"
    kv3.close()


def test_format_interop_with_python_backend(tdir):
    """The native engine opens files the Python backend wrote, and
    vice versa — same .kvlog format."""
    py = KeyValueStorageFile(tdir, "shared")
    py.put(b"from-python", b"pv")
    py.setBatch([(b"pb-%d" % i, b"x%d" % i) for i in range(3)])
    py.remove(b"pb-1")
    py.close()
    nat = KeyValueStorageNative(tdir, "shared")
    assert nat.get(b"from-python") == b"pv"
    assert nat.get(b"pb-0") == b"x0"
    with pytest.raises(KeyError):
        nat.get(b"pb-1")
    nat.put(b"from-native", b"nv")
    nat.close()
    py2 = KeyValueStorageFile(tdir, "shared")
    assert py2.get(b"from-native") == b"nv"
    assert py2.get(b"from-python") == b"pv"
    py2.close()


def test_compaction_drops_garbage_keeps_data(tdir):
    kv = KeyValueStorageNative(tdir, "t4")
    for i in range(100):
        kv.put(b"k-%03d" % i, os.urandom(64))
    for i in range(100):                  # overwrite all -> garbage
        kv.put(b"k-%03d" % i, b"final-%d" % i)
    for i in range(50, 100):
        kv.remove(b"k-%03d" % i)
    size_before = os.path.getsize(os.path.join(tdir, "t4.kvlog"))
    assert kv.garbage_bytes > 0
    kv.compact()
    size_after = os.path.getsize(os.path.join(tdir, "t4.kvlog"))
    assert size_after < size_before
    assert kv.garbage_bytes == 0
    assert len(kv) == 50
    assert kv.get(b"k-000") == b"final-0"     # reads after compaction
    kv.put(b"post", b"compact-write")
    assert kv.get(b"post") == b"compact-write"
    kv.close()
    kv2 = KeyValueStorageNative(tdir, "t4")   # reopen after compaction
    assert len(kv2) == 51
    assert kv2.get(b"k-049") == b"final-49"
    kv2.close()


def test_batch_remove_then_put_keeps_key_visible(tdir):
    """Key cache must apply batch ops IN ORDER: remove-then-put of the
    same key ends live in iteration, like the engine and file backend."""
    kv = KeyValueStorageNative(tdir, "t5")
    kv.put(b"k", b"old")
    kv.do_ops_in_batch([("remove", b"k"), ("put", b"k", b"new")])
    assert kv.get(b"k") == b"new"
    assert [k for k, _ in kv.iterator()] == [b"k"]
    kv.do_ops_in_batch([("put", b"k", b"x"), ("remove", b"k")])
    assert list(kv.iterator(include_value=False)) == []
    kv.close()


def test_closed_store_raises_instead_of_crashing(tdir):
    kv = KeyValueStorageNative(tdir, "t6")
    kv.put(b"k", b"v")
    kv.close()
    with pytest.raises(ValueError):
        kv.get(b"k")
    with pytest.raises(ValueError):
        kv.put(b"k2", b"v")
    with pytest.raises(ValueError):
        len(kv)


def test_remove_absent_key_is_noop_on_disk(tdir):
    kv = KeyValueStorageNative(tdir, "t7")
    kv.put(b"k", b"v")
    path = os.path.join(tdir, "t7.kvlog")
    size = os.path.getsize(path)
    for _ in range(50):
        kv.remove(b"missing")
    assert os.path.getsize(path) == size
    kv.close()


def test_iterator_snapshot_survives_mutation(tdir):
    kv = KeyValueStorageNative(tdir, "t8")
    kv.put(b"a", b"1")
    kv.put(b"b", b"2")
    it = kv.iterator()
    kv.remove(b"a")
    assert list(it) == [(b"a", b"1"), (b"b", b"2")]
    kv.close()


def test_node_restart_e2e_on_native_store(mock_timer, tmp_path):
    """The restart-from-durable-storage flow (tests/test_restart_e2e.py)
    with the NATIVE engine as every node's backing store."""
    from plenum_tpu.common.config import Config
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.sim_network import SimNetwork
    from tests.test_node_e2e import (
        ClientSink, NAMES, SIM_EPOCH, pump, signed_nym_request,
        submit_to_all)
    from plenum_tpu.crypto.signer import SimpleSigner

    conf = dict(Max3PCBatchSize=5, Max3PCBatchWait=0.2, CHK_FREQ=5,
                LOG_SIZE=15)

    def factory(node_name):
        return lambda store_name: KeyValueStorageNative(
            str(tmp_path / node_name), store_name)

    mock_timer.set_time(SIM_EPOCH)
    net = SimNetwork(mock_timer, DefaultSimRandom(505))
    sinks = {n: ClientSink() for n in NAMES}
    nodes = [Node(n, NAMES, mock_timer, net.create_peer(n),
                  config=Config(**conf), storage_factory=factory(n),
                  client_reply_handler=sinks[n])
             for n in NAMES]
    clients = [SimpleSigner(seed=bytes([110 + i]) * 32) for i in range(3)]
    for i, c in enumerate(clients):
        submit_to_all(nodes, signed_nym_request(c, req_id=i))
        pump(mock_timer, nodes, 1.5)
    pump(mock_timer, nodes, 5)
    assert all(n.domain_ledger.size == 3 for n in nodes)
    expected_root = nodes[0].domain_ledger.root_hash

    # stop Delta (drop the object; its native stores stay on disk)
    net.remove_peer("Delta")
    live = nodes[:3]
    submit_to_all(live, signed_nym_request(
        SimpleSigner(seed=bytes([120]) * 32), req_id=9))
    pump(mock_timer, live, 6)
    assert all(n.domain_ledger.size == 4 for n in live)

    # "restart": brand-new Node over the same on-disk native stores
    sink = ClientSink()
    delta2 = Node("Delta", NAMES, mock_timer, net.create_peer("Delta"),
                  config=Config(**conf), storage_factory=factory("Delta"),
                  client_reply_handler=sink)
    assert delta2.domain_ledger.size == 3       # recovered from disk
    assert delta2.domain_ledger.root_hash == expected_root
    delta2.start_catchup()
    pump(mock_timer, live + [delta2], 15)
    assert delta2.domain_ledger.size == 4       # caught up the suffix
    assert delta2.domain_ledger.root_hash == \
        nodes[0].domain_ledger.root_hash


def _mixed_history(kv, model):
    """Single puts, batch frames, removes and ordered batches, with an
    ordered read between them, so that live keys sit both in the sorted
    view and among the keys written since; `model` follows along."""
    def put(k, v):
        kv.put(k, v)
        model[k] = v

    def frame(pairs):
        kv.setBatch(pairs)
        model.update(pairs)

    for i in range(0, 40, 3):
        put(b"p-%03d" % i, b"single-%d" % i)
    frame([(b"f-%03d" % i, b"frame-%d" % i) for i in range(30)])
    assert [k for k, _ in kv.iterator()] == sorted(model)   # merge here
    frame([(b"f-%03d" % i, b"again-%d" % i) for i in range(20, 50)])
    frame([(b"p-003", b"from-a-frame")])
    for k in (b"f-000", b"p-006", b"f-045", b"absent"):
        kv.remove(k)
        model.pop(k, None)
    put(b"f-000", b"back")                  # removed, then put again
    kv.do_ops_in_batch([("remove", b"f-001"), ("put", b"f-001", b"new"),
                        ("put", b"f-002", b"gone"), ("remove", b"f-002"),
                        ("put", b"q-000", b"batch-put")])
    model[b"f-001"] = b"new"
    model.pop(b"f-002")
    model[b"q-000"] = b"batch-put"


@pytest.mark.parametrize("check", [
    "iteration", "get_or_none", "remove_then_put", "snapshot", "reopen"])
def test_key_index_after_frames_puts_and_removes(tdir, check):
    """The key cache after batch frames mixed with single puts and
    removes reads as the store's contents in every way a caller can
    look: ordered iteration and ranges, get_or_none, an ordered batch's
    last op per key, a materialised iterator, and a reopened store."""
    kv = KeyValueStorageNative(tdir, "k-" + check)
    model = {}
    _mixed_history(kv, model)
    live = sorted(model)
    if check == "iteration":
        assert list(kv.iterator()) == [(k, model[k]) for k in live]
        assert list(kv.iterator(start=b"f-020", end=b"p-999",
                                include_value=False)) == \
            [k for k in live if b"f-020" <= k <= b"p-999"]
        assert len(kv) == len(model)
    elif check == "get_or_none":
        for k in live:
            assert kv.get_or_none(k) == model[k]
        for k in (b"f-002", b"f-045", b"p-006", b"absent", b"f-999"):
            assert kv.get_or_none(k) is None
    elif check == "remove_then_put":
        assert kv.get(b"f-001") == b"new"
        assert kv.get(b"f-000") == b"back"
        with pytest.raises(KeyError):
            kv.get(b"f-002")
        kv.do_ops_in_batch([("remove", b"q-000"), ("put", b"q-000", b"x")])
        kv.setBatch([(b"q-001", b"y")])
        kv.do_ops_in_batch([("put", b"q-001", b"z"), ("remove", b"q-001")])
        assert list(kv.iterator(start=b"q", include_value=False)) == \
            [b"q-000"]
    elif check == "snapshot":
        it = kv.iterator()
        kv.setBatch([(b"a-new", b"1")])
        kv.remove(live[0])
        kv.put(b"z-new", b"2")
        assert list(it) == [(k, model[k]) for k in live]
    else:
        kv.close()
        kv2 = KeyValueStorageNative(tdir, "k-" + check)
        assert list(kv2.iterator()) == [(k, model[k]) for k in live]
        assert kv2.get_or_none(b"f-045") is None
        assert kv2.get_or_none(b"q-000") == b"batch-put"
        kv = kv2
    kv.close()


def test_frame_is_one_ffi_call_and_put_one_record(tdir):
    """A batch reaches the engine in one call that writes one frame; a
    single put stays one record written and flushed before it returns
    (another handle on the file reads it at once)."""
    kv = KeyValueStorageNative(tdir, "calls")
    calls = []

    class Counting:
        def __init__(self, lib):
            self._lib = lib

        def __getattr__(self, name):
            fn = getattr(self._lib, name)
            if not name.startswith(("kv_put", "kv_apply_packed")):
                return fn

            def counted(*args):
                calls.append(name)
                return fn(*args)
            return counted

    kv._lib = Counting(kv._lib)
    kv.setBatch([(b"n-%04d" % i, b"v" * 90) for i in range(1000)])
    assert calls == ["kv_apply_packed"]
    kv.put(b"one", b"record")
    assert calls == ["kv_apply_packed", "kv_put"]
    reader = KeyValueStorageFile(tdir, "calls", read_only=True)
    assert reader.get(b"one") == b"record"
    assert reader.size == 1001
    reader.close()
    kv.close()
