"""Cross-check the native C MPT (native/mpt_c.c via NativeTrie) against
the Python trie — roots are consensus state, so every operation must
produce bit-identical roots, proofs must verify under the Python
verifier, and reads must agree at every historical root.
"""
import hashlib
import random

import pytest

from plenum_tpu.state.trie import BLANK_ROOT, Trie, sha3, verify_proof
from plenum_tpu.storage.kv_memory import KeyValueStorageInMemory

trie_native = pytest.importorskip("plenum_tpu.state.trie_native")
NativeTrie = trie_native.NativeTrie


def make_pair():
    return (Trie(KeyValueStorageInMemory()),
            NativeTrie(KeyValueStorageInMemory()))


def test_blank_root_matches():
    assert trie_native.BLANK_ROOT == BLANK_ROOT


def test_sha3_matches_hashlib():
    # the C keccak is the root of all node hashes — spot-check widths
    rng = random.Random(3)
    for n in [0, 1, 135, 136, 137, 271, 272, 1000]:
        data = bytes(rng.randrange(256) for _ in range(n))
        got = NativeTrie(KeyValueStorageInMemory())
        # hash through a set: key="k", value=data → same root iff sha3 agrees
        py = Trie(KeyValueStorageInMemory())
        py.set(b"k", data or b"x")
        got.set(b"k", data or b"x")
        assert got.root_hash == py.root_hash, n


def test_roots_match_incremental():
    py, c = make_pair()
    rng = random.Random(5)
    keys = []
    for i in range(400):
        op = rng.random()
        if op < 0.75 or not keys:
            key = ("did:%d" % rng.randrange(200)).encode()
            val = ("v%d" % rng.randrange(10 ** 9)).encode()
            keys.append(key)
            py.set(key, val)
            c.set(key, val)
        else:
            key = rng.choice(keys)
            py.delete(key)
            c.delete(key)
        assert c.root_hash == py.root_hash, (i, key)


def test_get_and_historical_roots_match():
    py, c = make_pair()
    rng = random.Random(6)
    roots = []
    model = {}
    for i in range(150):
        key = ("k%d" % rng.randrange(60)).encode()
        val = ("val-%d" % i).encode()
        model[key] = val
        py.set(key, val)
        c.set(key, val)
        roots.append((c.root_hash, dict(model)))
    for root, snapshot in rng.sample(roots, 30):
        for key in rng.sample(list(snapshot), min(5, len(snapshot))):
            assert c.get_at_root(root, key) == snapshot[key]
            assert py.get_at_root(root, key) == snapshot[key]
    for key, val in model.items():
        assert c.get(key) == val


def test_proofs_verify_under_python_verifier():
    py, c = make_pair()
    for i in range(80):
        key = ("did:sov:%020d" % i).encode()
        c.set(key, b"value-%d" % i)
        py.set(key, b"value-%d" % i)
    root = c.root_hash
    for i in [0, 7, 42, 79]:
        key = ("did:sov:%020d" % i).encode()
        proof_c = c.produce_spv_proof(key)
        proof_py = py.produce_spv_proof(key)
        assert proof_c == proof_py
        assert verify_proof(root, key, b"value-%d" % i, proof_c)
        assert not verify_proof(root, key, b"wrong", proof_c)
    # non-membership
    absent = b"did:sov:absent"
    proof = c.produce_spv_proof(absent)
    assert verify_proof(root, absent, None, proof)


def test_items_match():
    py, c = make_pair()
    rng = random.Random(8)
    for i in range(120):
        key = ("it%d" % rng.randrange(80)).encode()
        val = ("x%d" % i).encode()
        py.set(key, val)
        c.set(key, val)
    assert list(c.items()) == list(py.items())


def test_durability_and_rehydration():
    """Nodes written through to the KV must let a FRESH NativeTrie (new
    C store, same KV) read everything back — the restart path."""
    kv = KeyValueStorageInMemory()
    c = NativeTrie(kv)
    for i in range(100):
        c.set(b"key-%d" % i, b"val-%d" % i)
    root = c.root_hash
    c2 = NativeTrie(kv, root)
    for i in range(100):
        assert c2.get(b"key-%d" % i) == b"val-%d" % i
    # and the Python trie over the same KV agrees completely
    py = Trie(kv, root)
    for i in range(100):
        assert py.get(b"key-%d" % i) == b"val-%d" % i
    # missing-node error on an empty store
    c3 = NativeTrie(KeyValueStorageInMemory(), root)
    with pytest.raises(KeyError):
        c3.get(b"key-0")


def test_set_empty_value_deletes():
    py, c = make_pair()
    for t in (py, c):
        t.set(b"a", b"1")
        t.set(b"b", b"2")
        t.set(b"a", b"")
    assert c.root_hash == py.root_hash
    assert c.get(b"a") is None
    assert c.get(b"b") == b"2"


def test_eviction_bounds_store_and_rehydrates():
    """With a tiny max_nodes cap the C store evicts drained nodes; reads
    of evicted nodes transparently rehydrate from the durable KV."""
    from plenum_tpu.native import load_ext
    mpt = load_ext("mpt_c")
    kv = KeyValueStorageInMemory()

    def miss(h):
        try:
            return bytes(kv.get(h))
        except KeyError:
            return None

    h = mpt.new(miss, 64)  # tiny cap to force constant eviction
    root = mpt.blank_root()
    model = {}
    for i in range(300):
        key = b"did:%03d" % i
        val = b"value-%d" % i
        root = mpt.set(h, root, key, val)
        for hsh, blob in mpt.drain(h):
            kv.put(hsh, blob)
        model[key] = val
    # everything still readable (current and historical roots hydrate back)
    for key, val in model.items():
        assert mpt.get(h, root, key) == val
    # items() still walks the full (partly evicted) trie
    assert dict(mpt.items(h, root)) == model


def test_deep_nesting_falls_back_to_python_paths():
    """Payloads deeper than the C guard must take the Python serializers
    on compiler-equipped nodes — digests/wire bytes stay identical to
    fallback nodes (the review's pool-split scenario)."""
    import json as _json
    import msgpack as _msgpack
    from plenum_tpu.common.serializers.serializers import (
        MsgPackSerializer, OrderedJsonSerializer, _sort_deep)
    from plenum_tpu.server.propagator import (
        _strict_deep_eq, _strict_deep_eq_py)
    deep = "leaf"
    for _ in range(150):
        deep = {"k": deep}
    assert MsgPackSerializer().serialize(deep) == _msgpack.packb(
        _sort_deep(deep), use_bin_type=True)
    assert OrderedJsonSerializer().serialize(deep) == _json.dumps(
        deep, sort_keys=True, separators=(",", ":")).encode()
    assert _strict_deep_eq(deep, deep) is True
    assert _strict_deep_eq_py(deep, deep) is True


def test_pruning_state_uses_native_backend():
    from plenum_tpu.state.pruning_state import PruningState, _TrieBackend
    assert _TrieBackend is NativeTrie
    st = PruningState(KeyValueStorageInMemory())
    st.set(b"did:x", b"{}")
    assert st.get(b"did:x", isCommitted=False) == b"{}"
    # committed/uncommitted split still works
    assert st.get(b"did:x", isCommitted=True) is None
    st.commit()
    assert st.get(b"did:x", isCommitted=True) == b"{}"


# ------------------------------------------ write-through as batch frames

def _nym_pairs(lo, hi):
    """NYM-shaped writes: 32-byte keys, values of about 190 bytes."""
    return [(hashlib.sha256(b"did-%d" % i).digest(),
             b'{"identifier":"%064d","verkey":"~%040d","seqNo":%d}'
             % (i, i, i) + b"x" * 70) for i in range(lo, hi)]


def _log_records(path):
    """The kvlog file, record by record: [('frame', n_records) |
    ('record', key)] in file order (tests/test_kv_native.py's format)."""
    import struct
    data = open(path, "rb").read()
    out, pos = [], 0
    while pos + 8 <= len(data):
        klen, vlen = struct.unpack_from("<II", data, pos)
        if klen == 0xFFFFFFFE:
            body, n, p = data[pos + 8:pos + 8 + vlen], 0, 0
            while p < len(body):
                kl, vl = struct.unpack_from("<II", body, p)
                p += 8 + kl + (0 if vl == 0xFFFFFFFF else vl)
                n += 1
            out.append(("frame", n))
            pos += 8 + vlen
        else:
            out.append(("record", data[pos + 8:pos + 8 + klen]))
            pos += 8 + klen + (0 if vlen == 0xFFFFFFFF else vlen)
    return out


@pytest.fixture
def native_kv():
    from plenum_tpu.storage import kv_native
    if not kv_native.available():
        pytest.skip("no C compiler for the native kvlog engine")
    return kv_native.KeyValueStorageNative


def test_set_many_reaches_the_store_as_one_frame(tdir, native_kv):
    """1,000 keys through set_many: every new node in ONE batch frame
    (the file's own framing says so), counted as written and framed;
    a fresh trie over the reopened store reads the same root and every
    value."""
    import os
    kv = native_kv(tdir, "state")
    trie = NativeTrie(kv)
    pairs = _nym_pairs(0, 1000)
    nodes, framed = trie.set_many(pairs)
    assert nodes == framed == len(kv) > 1000
    assert _log_records(os.path.join(tdir, "state.kvlog")) == \
        [("frame", nodes)]
    trie.set(b"one-more", b"value")          # a per-key set: one frame
    assert _log_records(os.path.join(tdir, "state.kvlog"))[1][0] == "frame"
    root = trie.root_hash
    kv.close()
    kv2 = native_kv(tdir, "state")
    fresh = NativeTrie(kv2, root)
    assert fresh.root_hash == root
    for k, v in pairs + [(b"one-more", b"value")]:
        assert fresh.get(k) == v
    kv2.close()


def test_flush_torn_inside_its_frame_is_lost_whole(tdir, native_kv):
    """A crash inside a flush's frame: on reopen the whole flush is
    gone (not one of its nodes is left) and the committed root, put
    after the earlier flush, still reads every key."""
    import os
    from plenum_tpu.state.pruning_state import PruningState
    path = os.path.join(tdir, "torn.kvlog")
    kv = native_kv(tdir, "torn")
    st = PruningState(kv)
    committed = _nym_pairs(0, 300)
    for k, v in committed:
        st.set(k, v)
    st.commit()
    root, size, count = st.committedHeadHash, os.path.getsize(path), len(kv)
    for k, v in _nym_pairs(300, 1300):
        st.set(k, v)
    head = st.headHash                        # the flush: one frame
    assert _log_records(path)[-1] == ("frame", len(kv) - count)
    kv.close()
    with open(path, "r+b") as f:              # torn half-way through it
        f.truncate(size + (os.path.getsize(path) - size) // 2)
    kv2 = native_kv(tdir, "torn")
    assert len(kv2) == count and os.path.getsize(path) == size
    assert kv2.get_or_none(head) is None
    st2 = PruningState(kv2)
    assert st2.committedHeadHash == root
    for k, v in committed:
        assert st2.get(k) == v
    kv2.close()


def _store(kind, tdir):
    from plenum_tpu.storage.kv_file import KeyValueStorageFile
    if kind == "memory":
        return KeyValueStorageInMemory()
    if kind == "file":
        return KeyValueStorageFile(tdir, "nodes")
    from plenum_tpu.storage import kv_native
    if not kv_native.available():
        pytest.skip("no C compiler for the native kvlog engine")
    return kv_native.KeyValueStorageNative(tdir, "nodes")


@pytest.mark.parametrize("kind", ["memory", "file", "native"])
def test_framed_write_through_equals_per_record(tdir, kind):
    """Per-key sets, deletes and set_many over each store: the root and
    every node the store holds are byte-equal to the per-record path
    (the C trie's drain put node by node)."""
    from plenum_tpu.native import load_ext
    mpt = load_ext("mpt_c")
    ref_kv = KeyValueStorageInMemory()
    ref_h, ref_root = mpt.new(None), mpt.blank_root()
    kv = _store(kind, tdir)
    trie = NativeTrie(kv)

    def per_record():
        for h, blob in mpt.drain(ref_h):
            ref_kv.put(h, blob)

    for k, v in _nym_pairs(0, 40):
        trie.set(k, v)
        ref_root = mpt.set(ref_h, ref_root, k, v)
        per_record()
    batch = _nym_pairs(40, 700) + [(k, b"") for k, _ in _nym_pairs(0, 10)]
    trie.set_many(batch)
    ref_root = mpt.set_many(ref_h, ref_root, batch)
    per_record()
    for k, _ in _nym_pairs(10, 15):
        trie.delete(k)
        ref_root = mpt.delete(ref_h, ref_root, k)
        per_record()
    assert trie.root_hash == ref_root
    assert dict(kv.iterator()) == dict(ref_kv.iterator())
    kv.close()


def test_drain_past_the_frame_cap_goes_out_in_consecutive_frames(
        tdir, native_kv, monkeypatch):
    """A drain larger than the cap is several frames, each within it,
    together every node; the reopened store reads every value."""
    import os
    monkeypatch.setattr(trie_native, "_FRAME_BYTES", 16 << 10)
    kv = native_kv(tdir, "capped")
    trie = NativeTrie(kv)
    pairs = _nym_pairs(0, 500)
    nodes, framed = trie.set_many(pairs)
    assert nodes == framed == len(kv)
    frames = _log_records(os.path.join(tdir, "capped.kvlog"))
    assert len(frames) > 3 and {f[0] for f in frames} == {"frame"}
    assert sum(n for _, n in frames) == nodes
    assert os.path.getsize(os.path.join(tdir, "capped.kvlog")) <= \
        len(frames) * (8 + (16 << 10))
    root = trie.root_hash
    kv.close()
    fresh = NativeTrie(native_kv(tdir, "capped"), root)
    assert all(fresh.get(k) == v for k, v in pairs)
