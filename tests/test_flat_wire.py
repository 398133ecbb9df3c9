"""Flat zero-copy wire codec — golden vectors, fuzzed round-trips,
adversarial envelopes, and columnar-intake equivalence.

The flat wire (common/serializers/flat_wire.py) is a pure dataflow
refactor of the per-message wire: for ANY valid vote stream the
receiver must end in the SAME observable state as a per-message
replay — equal vote stores and counters, equal
stashes, equal suspicions, byte-equal executor roots (the PR-8
equivalence methodology, extended to the byte level). Structurally
invalid envelopes (truncation, corruption, over-length, version skew)
must cost a per-sender suspicion — never a prod-loop crash, and never
partial state.
"""
import random

import pytest

from plenum_tpu.common.messages.message_factory import node_message_factory
from plenum_tpu.common.messages.node_messages import (
    Commit, FlatBatch, PrePrepare, Prepare, Propagate)
from plenum_tpu.common.serializers import flat_wire as fw
from plenum_tpu.common.serializers.serializers import MsgPackSerializer
from tests.test_columnar_3pc import (
    build_pair, feed_flat, feed_per_message, gen_stream, snapshot)

serializer = MsgPackSerializer()

B58_ROOT = "GKot5hBsd81kMupNCXHaqbhv3huEbxAFMLnpcX2hniwn"


def make_pp(seq=1, reqs=("req-a", "req-b"), inst=0, view=0):
    return PrePrepare(
        instId=inst, viewNo=view, ppSeqNo=seq, ppTime=1600000000,
        reqIdr=list(reqs), discarded="0", digest="0badc0de" * 8,
        ledgerId=1, stateRootHash=None, txnRootHash=None,
        sub_seq_no=0, final=False)


# ------------------------------------------------------------- golden

# byte-exact pin of the v1 envelope layout (docs/wire.md): little-
# endian columns, section order, flags, string table. If this breaks,
# the WIRE VERSION byte must be bumped — peers parse these bytes.
GOLDEN_HEX = (
    "505701030301000000300100000000000028010000de0012b061756469745478"
    "6e526f6f7448617368c0ab626c734d756c7469536967c0ac626c734d756c7469"
    "53696773c0a6646967657374d940306261646330646530626164633064653062"
    "6164633064653062616463306465306261646330646530626164633064653062"
    "6164633064653062616463306465a9646973636172646564a130a566696e616c"
    "c2a6696e7374496400a86c6564676572496401a26f70aa505245505245504152"
    "45ae6f726967696e616c566965774e6fc0b1706f6f6c5374617465526f6f7448"
    "617368c0a770705365714e6f01a6707054696d65ce5f5e1000a6726571496472"
    "92a57265712d61a57265712d62ad7374617465526f6f7448617368c0aa737562"
    "5f7365715f6e6f00ab74786e526f6f7448617368c0a6766965774e6f00010100"
    "00007d00000001000000020000000000000003000000000000000000100084d7"
    "d741abababababababababababababababababababababababababababababab"
    "abab01000000002c0000002c0000002c0000002c000000474b6f743568427364"
    "38316b4d75704e435848617162687633687545627841464d4c6e70635832686e"
    "69776e02010000002a0000000100000002000000000000000300000000000000"
    "0100000000090000000900000073686172652d78797a")


def golden_messages():
    pp = make_pp()
    p = Prepare(instId=1, viewNo=2, ppSeqNo=3, ppTime=1600000000.25,
                digest="ab" * 32, stateRootHash=B58_ROOT,
                txnRootHash=None)
    c = Commit(instId=1, viewNo=2, ppSeqNo=3, blsSig="share-xyz")
    return pp, p, c


def test_golden_vector_encode_is_byte_exact():
    pp, p, c = golden_messages()
    assert fw.encode_three_pc([pp], [p], [c]).hex() == GOLDEN_HEX


def test_golden_vector_decodes_to_the_original_messages():
    pp, p, c = golden_messages()
    msgs = fw.to_legacy_messages(bytes.fromhex(GOLDEN_HEX))
    assert msgs == [pp, p, c]
    # field types survive exactly: int ppTime stays int, float stays
    # float (canonical serialization distinguishes them)
    assert isinstance(msgs[0].ppTime, int)
    assert isinstance(msgs[1].ppTime, float)


def test_envelope_header_magic_and_version():
    env = bytes.fromhex(GOLDEN_HEX)
    assert env[:2] == b"PW"
    assert env[2] == fw.VERSION == 1


def test_flat_batch_survives_real_transport_serialization():
    """FLAT_WIRE over the socket path: msgpack wraps the payload as a
    single bin field (no canonical-sort recursion into the votes) and
    the factory hands back identical bytes."""
    env = bytes.fromhex(GOLDEN_HEX)
    wire = serializer.serialize(FlatBatch(payload=env).to_dict())
    back = node_message_factory.get_instance(
        **serializer.deserialize(wire))
    assert isinstance(back, FlatBatch)
    assert back.payload == env


# ---------------------------------------------------------- round trip

def _random_prepare(rng):
    digest = rng.choice([
        rng.getrandbits(256).to_bytes(32, "big").hex(),   # canonical
        "forged-" + "%x" % rng.getrandbits(64),           # odd digest
        "AB" * 32,                                        # non-canon hex
    ])
    return Prepare(
        instId=rng.randint(0, 5), viewNo=rng.randint(0, 2 ** 40),
        ppSeqNo=rng.randint(1, 2 ** 50),
        ppTime=rng.choice([1600000000, 1600000000.5,
                           1600000000 + rng.random() * 1e6]),
        digest=digest,
        stateRootHash=rng.choice([None, B58_ROOT]),
        txnRootHash=rng.choice([None, B58_ROOT]),
        auditTxnRootHash=rng.choice([None, B58_ROOT]))


def _random_commit(rng):
    return Commit(
        instId=rng.randint(0, 5), viewNo=rng.randint(0, 2 ** 40),
        ppSeqNo=rng.randint(1, 2 ** 50),
        blsSig=rng.choice([None, "sig-%x" % rng.getrandbits(80)]),
        blsSigs=rng.choice([None, {"0": "s0", "1": "s1"}]))


def _random_pp(rng):
    reqs = ["dig-%x" % rng.getrandbits(64)
            for _ in range(rng.randint(0, 7))]
    return PrePrepare(
        instId=rng.randint(0, 3), viewNo=rng.randint(0, 9),
        ppSeqNo=rng.randint(1, 10 ** 6), ppTime=1600000000 + rng.random(),
        reqIdr=reqs, discarded="0",
        digest="%064x" % rng.getrandbits(256), ledgerId=1,
        stateRootHash=rng.choice([None, B58_ROOT]),
        txnRootHash=rng.choice([None, B58_ROOT]),
        sub_seq_no=0, final=False)


@pytest.mark.parametrize("seed", range(8))
def test_fuzzed_roundtrip_matches_typed_serializer(seed):
    """Byte-exact encode/decode vs the typed-object path across fuzzed
    field values and ragged reqIdr shapes: the flat rematerialization
    must equal BOTH the original message and what the msgpack+factory
    wire would have delivered."""
    rng = random.Random(seed)
    pps = [_random_pp(rng) for _ in range(rng.randint(0, 3))]
    prepares = [_random_prepare(rng) for _ in range(rng.randint(0, 20))]
    commits = [_random_commit(rng) for _ in range(rng.randint(0, 20))]
    if not (pps or prepares or commits):
        prepares = [_random_prepare(rng)]
    env = fw.encode_three_pc(pps, prepares, commits)
    got = fw.to_legacy_messages(env)
    want = pps + prepares + commits
    assert got == want
    for m_got, m_want in zip(got, want):
        typed = node_message_factory.get_instance(**serializer.deserialize(
            serializer.serialize(m_want.to_dict())))
        assert m_got == typed
        assert m_got.as_dict() == typed.as_dict()
    # a second parse of the same bytes is bit-stable
    assert fw.to_legacy_messages(env) == got


def test_ragged_reqidr_shapes():
    """Empty, single and wide reqIdr (the freshness path sends EMPTY
    batches) ride the length-prefixed section byte-exactly."""
    pps = [make_pp(seq=1, reqs=()),
           make_pp(seq=2, reqs=("one",)),
           make_pp(seq=3, reqs=tuple("req-%03d" % i for i in range(64)))]
    assert fw.to_legacy_messages(fw.encode_three_pc(pps, [], [])) == pps


def test_propagate_roundtrip_and_lazy_unpack():
    reqs = [{"identifier": "idA", "reqId": 1,
             "operation": {"type": "1", "raw": "x" * 100}},
            {"identifier": "idB", "reqId": 2, "operation": {"type": "1"}}]
    env = fw.encode_propagate_envelope(
        [serializer.serialize(r) for r in reqs], ["cliA", ""])
    cols = fw.parse_envelope(env).sections[0]
    assert cols.n == 2
    assert cols.request(0) == reqs[0]
    assert cols.request(1) == reqs[1]
    assert cols.client(0) == "cliA" and cols.client(1) == ""
    # the legacy rematerialization for fault-injection taps
    legacy = fw.to_legacy_messages(env)
    assert legacy == [Propagate(request=reqs[0], senderClient="cliA"),
                      Propagate(request=reqs[1], senderClient=None)]
    single = fw.encode_propagate_envelope(
        [serializer.serialize(reqs[0])], ["cliA"])
    assert fw.to_legacy_messages(single) == [
        Propagate(request=reqs[0], senderClient="cliA")]


# ------------------------------------------------------ chunk boundary

def test_outbox_chunks_flat_envelopes_under_size_budget():
    """A tick of votes past the size budget leaves as MULTIPLE flat
    envelopes, FIFO order preserved phase-major, nothing dropped."""
    from plenum_tpu.server.three_pc_outbox import ThreePCOutbox

    sent = []

    class _Net:
        has_tap = False

        def send(self, msg, dst=None):
            sent.append(msg)

    # small budget: ~640B/prepare seed → a handful per envelope
    outbox = ThreePCOutbox(_Net(), msg_len_limit=8 * 1024 + 2048)
    votes = []
    for seq in range(1, 40):
        votes.append(Prepare(instId=0, viewNo=0, ppSeqNo=seq,
                             ppTime=1600000000, digest="ab" * 32,
                             stateRootHash=B58_ROOT, txnRootHash=B58_ROOT))
        votes.append(Commit(instId=0, viewNo=0, ppSeqNo=seq))
    for v in votes:
        outbox.queue(v)
    assert outbox.flush() == len(votes)
    assert len(sent) > 1
    assert all(isinstance(m, FlatBatch) for m in sent)
    got = []
    for m in sent:
        assert len(m.payload) <= outbox._size_budget
        got.extend(fw.to_legacy_messages(m.payload))
    # phase-major within each envelope, FIFO across envelopes: the
    # per-phase subsequences must match the queue order exactly
    for kind in (Prepare, Commit):
        assert [v for v in got if isinstance(v, kind)] \
            == [v for v in votes if isinstance(v, kind)]
    assert len(got) == len(votes)


def test_unencodable_chunk_leaves_per_message_in_order():
    """A vote the flat layout refuses (a ppSeqNo past u64) puts ITS
    chunk on the per-message wire, single votes in queue order; the
    chunks around it still leave as flat envelopes."""
    from plenum_tpu.server.three_pc_outbox import ThreePCOutbox

    sent = []

    class _Net:
        has_tap = False

        def send(self, msg, dst=None):
            sent.append(msg)

    outbox = ThreePCOutbox(_Net())
    outbox.BATCH_LIMIT = 3              # two chunks of three
    odd = [Prepare(instId=0, viewNo=0, ppSeqNo=1, ppTime=1600000000,
                   digest="ab" * 32, stateRootHash=B58_ROOT,
                   txnRootHash=B58_ROOT),
           Commit(instId=0, viewNo=0, ppSeqNo=1 << 64),
           Commit(instId=0, viewNo=0, ppSeqNo=1)]
    plain = [Commit(instId=0, viewNo=0, ppSeqNo=seq)
             for seq in (2, 3, 4)]
    with pytest.raises(fw.FlatWireUnencodable):
        fw.encode_three_pc([], [], [odd[1]])
    for v in odd + plain:
        outbox.queue(v)
    assert outbox.flush() == 6
    assert sent[:3] == odd              # single votes, queue order
    assert len(sent) == 4 and isinstance(sent[3], FlatBatch)
    assert fw.to_legacy_messages(sent[3].payload) == plain
    assert outbox.flushed_batches == 1


def test_outbox_size_model_tracks_measured_bytes():
    """Satellite: the hand-tuned byte constants are gone — after one
    flat flush the per-vote estimates are measured EWMAs, and the
    seam hub carries the per-vote-type byte histograms."""
    from plenum_tpu.observability.telemetry import (
        TM, TelemetryHub, set_seam_hub)
    from plenum_tpu.server.three_pc_outbox import ThreePCOutbox

    class _Net:
        has_tap = False

        def send(self, msg, dst=None):
            pass

    prev = set_seam_hub(TelemetryHub(name="test"))
    try:
        outbox = ThreePCOutbox(_Net())
        seed_prepare = outbox.size_model.prepare
        seed_commit = outbox.size_model.commit
        flushes = 20
        for _ in range(flushes):
            for seq in range(1, 9):
                outbox.queue(Commit(instId=0, viewNo=0, ppSeqNo=seq))
                outbox.queue(Prepare(instId=0, viewNo=0, ppSeqNo=seq,
                                     ppTime=1600000000, digest="ab" * 32,
                                     stateRootHash=None,
                                     txnRootHash=None))
            outbox.flush()
        # flat columns are far smaller than the legacy seeds — the
        # EWMA converged onto the measured sizes
        assert outbox.size_model.prepare < seed_prepare
        assert outbox.size_model.commit < seed_commit
        # measured per-vote flat bytes: tens, not hundreds
        assert outbox.size_model.commit < 100
        snap = set_seam_hub(prev).snapshot()
        hists = snap["histograms"]
        assert hists[TM.WIRE_VOTE_BYTES_PREPARE]["count"] == flushes
        assert hists[TM.WIRE_VOTE_BYTES_COMMIT]["count"] == flushes
        assert hists[TM.WIRE_ENV_BYTES_3PC]["count"] == flushes
        assert snap["counters"][TM.WIRE_BYTES_SENT] > 0
    finally:
        set_seam_hub(prev)


# --------------------------------------------------------- adversarial

def test_every_truncation_is_rejected():
    env = bytes.fromhex(GOLDEN_HEX)
    for cut in range(len(env)):
        with pytest.raises(fw.FlatWireError):
            fw.parse_envelope(env[:cut])


def test_over_length_and_version_skew_rejected():
    env = bytes.fromhex(GOLDEN_HEX)
    with pytest.raises(fw.FlatWireError):
        fw.parse_envelope(env + b"\x00")
    with pytest.raises(fw.FlatWireError):
        fw.parse_envelope(
            env[:2] + bytes([fw.VERSION_TRACE + 1]) + env[3:])
    with pytest.raises(fw.FlatWireError):
        fw.parse_envelope(b"XX" + env[2:])
    with pytest.raises(fw.FlatWireError):
        fw.parse_envelope(b"")
    with pytest.raises(fw.FlatWireError):
        fw.parse_envelope("not-bytes")


@pytest.mark.parametrize("seed", range(6))
def test_random_corruption_never_escapes_the_codec(seed):
    """Random byte flips either fail parsing with FlatWireError, fail
    entry materialization (dropped entry), or decode to different but
    VALID votes (content corruption is the digest/BLS layers' job) —
    never any other exception type."""
    rng = random.Random(seed)
    env = bytearray(bytes.fromhex(GOLDEN_HEX))
    for _ in range(40):
        i = rng.randrange(len(env))
        old = env[i]
        env[i] ^= 1 << rng.randrange(8)
        try:
            fw.to_legacy_messages(bytes(env))
        except fw.FlatWireError:
            pass
        env[i] = old


def test_malformed_envelope_raises_per_sender_suspicion_not_crash():
    """Node-level contract (acceptance): truncated / corrupted /
    over-length envelopes are rejected with a suspicion against the
    SENDER; the prod loop survives and keeps ordering."""
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork

    names = ["Alpha", "Beta", "Gamma", "Delta"]
    timer = MockTimer()
    timer.set_time(1600000000)
    net = SimNetwork(timer, DefaultSimRandom(3))
    node = Node("Alpha", names, timer, net.create_peer("Alpha"))
    env = bytes.fromhex(GOLDEN_HEX)
    # (an EMPTY payload cannot even be built: SerializedValueField
    # rejects it at FlatBatch construction on the typed layer)
    bad = [env[:17], env + b"junk", b"PW\x09\x01" + env[4:],
           b"\xff" * 64]
    for payload in bad:
        node._process_flat_batch(FlatBatch(payload=payload), "Beta")
    assert node.blacklister.suspicion_counts["Beta"] == len(bad)
    # suspicion is per-sender and non-destructive: a valid envelope
    # from an honest peer still processes afterwards
    pp = make_pp(seq=1, reqs=())
    prep = Prepare(instId=0, viewNo=0, ppSeqNo=1, ppTime=1600000000,
                   digest=pp.digest, stateRootHash=B58_ROOT,
                   txnRootHash=B58_ROOT)
    node._process_flat_batch(FlatBatch(
        payload=fw.encode_three_pc([], [prep], [])), "Gamma")
    assert "Gamma" in node.replica.ordering.prepares[(0, 1)]
    assert node.service() >= 0   # prod loop alive


def test_bad_entry_costs_one_entry_not_the_envelope():
    """A string-table root that fails schema validation drops ONE vote;
    the rest of the envelope lands (same blast radius as a bad entry
    inside a typed THREE_PC_BATCH)."""
    good = Prepare(instId=0, viewNo=0, ppSeqNo=1, ppTime=1600000000,
                   digest="ab" * 32, stateRootHash=None,
                   txnRootHash=None)
    bad = Prepare(instId=0, viewNo=0, ppSeqNo=2, ppTime=1600000000,
                  digest="cd" * 32, stateRootHash=B58_ROOT,
                  txnRootHash=None)
    env = bytearray(fw.encode_three_pc([], [bad, good], []))
    # corrupt the b58 root string in the table with an invalid char
    i = env.index(B58_ROOT.encode())
    env[i] = ord("0")   # '0' is outside the base58 alphabet
    got = fw.to_legacy_messages(bytes(env))
    assert got == [good]


# --------------------------------------------- columnar equivalence

@pytest.mark.parametrize("seed", range(12))
def test_flat_intake_equals_per_message_randomized(seed):
    """Acceptance: randomized adversarial envelope streams (stragglers,
    duplicates, conflicting digests, wrong instances, future views,
    watermark strays) keep vote stores, counters, stashes, suspicions,
    ordered log and executor roots byte-equal to a per-message replay
    of the identical stream."""
    rng = random.Random(seed)
    envelopes, known = gen_stream(rng)
    (ra, sus_a), (rb, sus_b) = build_pair(known)
    feed_flat(ra, envelopes)
    feed_per_message(rb, envelopes)
    assert snapshot(ra, sus_a) == snapshot(rb, sus_b)
    assert ra.ordering.ordered          # vacuous-equality guard


@pytest.mark.parametrize("seed", range(4))
def test_flat_intake_equals_per_message_across_view_change(seed):
    from plenum_tpu.common.messages.internal_messages import (
        NewViewAccepted, ViewChangeStarted)
    rng = random.Random(2000 + seed)
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


def test_duplicate_columns_across_sections_equal_per_message():
    """Acceptance: DUPLICATE vote columns — the same votes appearing in
    two sections of one envelope (and again in a second envelope) —
    leave state byte-equal to the per-message replay of the same
    duplicated stream."""
    rng = random.Random(99)
    envelopes, known = gen_stream(rng, n_batches=2)
    doubled = []
    for frm, msgs in envelopes:
        doubled.append((frm, msgs + msgs))      # dup within envelope
        doubled.append((frm, msgs))             # dup across envelopes
    (ra, sus_a), (rb, sus_b) = build_pair(known)
    feed_flat(ra, doubled)
    feed_per_message(rb, doubled)
    assert snapshot(ra, sus_a) == snapshot(rb, sus_b)


def test_mixed_version_stream_keeps_valid_envelopes():
    """Acceptance: a stream mixing current-version envelopes with
    future-version ones processes the valid envelopes normally and
    rejects each unknown-version one with a suspicion — state equals
    a replay that never saw the alien envelopes."""
    rng = random.Random(7)
    envelopes, known = gen_stream(rng, n_batches=2)
    (ra, sus_a), (rb, sus_b) = build_pair(known)
    from plenum_tpu.consensus.ordering_service import Suspicions
    alien_seen = 0
    for frm, msgs in envelopes:
        pps = [m for m in msgs if isinstance(m, PrePrepare)]
        prepares = [m for m in msgs if isinstance(m, Prepare)]
        commits = [m for m in msgs if isinstance(m, Commit)]
        env = fw.encode_three_pc(pps, prepares, commits)
        # interleave an alien-version copy before every real envelope
        # (VERSION_TRACE + 1: version 2 is merely v1 + a trailing
        # trace section, so it parses — the first UNKNOWN version is 3)
        alien = env[:2] + bytes([fw.VERSION_TRACE + 1]) + env[3:]
        with pytest.raises(fw.FlatWireError):
            fw.parse_envelope(alien)
        alien_seen += 1
        feed_flat(ra, [(frm, msgs)])
        feed_per_message(rb, [(frm, msgs)])
    assert alien_seen > 0
    assert snapshot(ra, sus_a) == snapshot(rb, sus_b)


def test_catching_up_replica_stashes_only_own_instance_once():
    """A flat section is handed WHOLE to every instance present in it;
    a replica in catchup must stash only ITS OWN instance's votes,
    exactly once each — never the other instances' rows (the bounded
    stash would multiply every vote by the instance count) and never
    junk-instance rows a byzantine sender padded in."""
    rng = random.Random(42)
    envelopes, known = gen_stream(rng, n_batches=2)
    (ra, sus_a), (rb, sus_b) = build_pair(known)
    for replica in (ra, rb):
        replica.data.node_mode_participating = False
    feed_flat(ra, envelopes)
    feed_per_message(rb, envelopes)
    assert snapshot(ra, sus_a) == snapshot(rb, sus_b)
    # the catch-up bucket actually filled (vacuous-equality guard)
    assert any(code == 3 for (_typ, code) in
               snapshot(ra, sus_a)["stashes"])


def test_propagator_flat_split_respects_size_budget():
    """Post-encode backstop: when the queue-time estimate lags the
    packed envelope size, the chunk splits instead of building a frame
    the transport would drop wholesale."""
    prop, sent, _ = _make_propagator()
    prop.BATCH_SIZE_BUDGET = 2048
    from plenum_tpu.common.request import Request
    for i, p in enumerate(_propagate_payloads(12)):
        p["operation"]["raw"] = "z" * 200
        prop.propagate(Request.from_dict(dict(p)), "cli-%d" % i)
    assert prop.flush() == 12
    assert len(sent) > 1
    total = 0
    for m in sent:
        assert isinstance(m, FlatBatch)
        assert len(m.payload) <= 2048
        total += fw.parse_envelope(m.payload).sections[0].n
    assert total == 12


def test_outbox_size_model_not_double_counted_on_split():
    """A chunk that must re-split feeds the size model / histograms
    only from the envelopes that actually SHIP — the oversize attempt
    is not measured twice."""
    from plenum_tpu.observability.telemetry import (
        TM, TelemetryHub, set_seam_hub)
    from plenum_tpu.server.three_pc_outbox import ThreePCOutbox

    sent = []

    class _Net:
        has_tap = False

        def send(self, msg, dst=None):
            sent.append(msg)

    prev = set_seam_hub(TelemetryHub(name="t"))
    try:
        outbox = ThreePCOutbox(_Net())
        outbox._size_budget = 2048      # force a split
        n_votes = 24
        for seq in range(1, n_votes + 1):
            outbox.queue(Prepare(instId=0, viewNo=0, ppSeqNo=seq,
                                 ppTime=1600000000, digest="ab" * 32,
                                 stateRootHash=B58_ROOT,
                                 txnRootHash=B58_ROOT))
        outbox.flush()
        assert len(sent) > 1
        # one histogram sample per SENT envelope's prepare section,
        # and the sample count's vote coverage equals the queue —
        # nothing counted twice
        snap = set_seam_hub(prev).snapshot()
        hist = snap["histograms"][TM.WIRE_VOTE_BYTES_PREPARE]
        assert hist["count"] == len(sent)
        assert sum(fw.parse_envelope(m.payload).sections[0].n
                   for m in sent) == n_votes
    finally:
        set_seam_hub(prev)


# ----------------------------------------------- propagate equivalence

def _make_propagator(name="Beta", tapped=False):
    from plenum_tpu.consensus.quorums import Quorums
    from plenum_tpu.server.propagator import Propagator

    sent, forwarded = [], []

    class _Net:
        has_tap = tapped

        def send(self, msg, dst=None):
            sent.append(msg)

    prop = Propagator(name, Quorums(4), _Net(),
                      forward_handler=forwarded.append,
                      forward_batch_handler=forwarded.extend)
    return prop, sent, forwarded


def _propagate_payloads(n=5):
    out = []
    for i in range(n):
        out.append({"identifier": "cli-id-%d" % i, "reqId": i + 1,
                    "protocolVersion": 2,
                    "operation": {"type": "1", "dest": "d%d" % i}})
    return out


def test_propagate_columns_equal_per_message_intake():
    payloads = _propagate_payloads()
    pa, _, fwd_a = _make_propagator()
    pb, _, fwd_b = _make_propagator()
    raws = [serializer.serialize(p) for p in payloads]
    clients = ["c%d" % i for i in range(len(payloads))]
    for frm in ("Alpha", "Gamma"):      # 2 peers + self echo = quorum
        cols = fw.parse_envelope(fw.encode_propagate_envelope(
            raws, clients)).sections[0]
        pa.process_propagate_columns(cols, frm)
        for p, client in zip(payloads, clients):
            pb.process_propagate(
                Propagate(request=dict(p), senderClient=client), frm)
    assert [r.key for r in fwd_a] == [r.key for r in fwd_b]
    assert len(fwd_a) == len(payloads)
    ka = {k: (s.propagates, s.finalised, s.forwarded)
          for k, s in pa.requests.items()}
    kb = {k: (s.propagates, s.finalised, s.forwarded)
          for k, s in pb.requests.items()}
    assert ka == kb


def test_propagate_bad_entry_skipped_per_item():
    payloads = _propagate_payloads(3)
    raws = [serializer.serialize(p) for p in payloads]
    raws[1] = b"\xc1garbage"            # undecodable msgpack
    prop, _, _ = _make_propagator()
    cols = fw.parse_envelope(fw.encode_propagate_envelope(
        raws, ["", "", ""])).sections[0]
    prop.process_propagate_columns(cols, "Alpha")
    # entries 0 and 2 collected a vote; entry 1 cost only itself
    assert len(prop.requests) == 2


def test_propagator_flat_flush_packs_once():
    prop, sent, _ = _make_propagator()
    from plenum_tpu.common.request import Request
    for p in _propagate_payloads(4):
        prop.propagate(Request.from_dict(dict(p)), "cli")
    assert prop.flush() == 4
    assert len(sent) == 1 and isinstance(sent[0], FlatBatch)
    cols = fw.parse_envelope(sent[0].payload).sections[0]
    assert cols.n == 4
    assert cols.request(0)["identifier"] == "cli-id-0"


# ------------------------------------------------------- tap interplay

def test_tapped_propagator_sends_single_propagates():
    """Send-side fault-injection contract: while the bus has a tap, n
    queued requests leave as n Propagates in queue order and no
    envelope of any kind."""
    prop, sent, _ = _make_propagator(tapped=True)
    from plenum_tpu.common.request import Request
    payloads = _propagate_payloads(4)
    for i, p in enumerate(payloads):
        prop.propagate(Request.from_dict(dict(p)), "cli-%d" % i)
    assert prop.flush() == 4
    assert sent == [Propagate(request=p, senderClient="cli-%d" % i)
                    for i, p in enumerate(payloads)]


def test_flat_envelopes_unwrap_before_bus_tap():
    """Receive-side fault-injection contract: a per-type tap on the
    bus sees the INNER typed votes of a flat envelope, never the
    envelope itself (the mirror of the outbox/propagator send-side
    degrade)."""
    from plenum_tpu.runtime.bus import ExternalBus

    seen = []

    class _Tap:
        def on_send(self, msg, dst):
            return None

        def on_incoming(self, msg, frm):
            seen.append(type(msg).__name__)
            return None

    bus = ExternalBus(send_handler=lambda m, d=None: None)
    handled = []
    bus.subscribe(Prepare, lambda m, f: handled.append((m, f)))
    bus.set_tap(_Tap())
    pp, p, c = golden_messages()
    bus.process_incoming(FlatBatch(
        payload=fw.encode_three_pc([pp], [p], [c])), "Gamma")
    assert "FlatBatch" not in seen
    assert seen == ["PrePrepare", "Prepare", "Commit"]
    assert handled == [(p, "Gamma")]


def test_sim_network_processors_unwrap_flat_envelopes():
    """Wire-level sim processors (drop/delay/tap) match per-type on the
    constituent votes of a flat envelope."""
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork, Tap

    timer = MockTimer()
    net = SimNetwork(timer, DefaultSimRandom(5))
    net.create_peer("A")
    bus_b = net.create_peer("B")
    got = []
    bus_b.subscribe(Commit, lambda m, f: got.append(m))
    tap = Tap(message_types=[Commit])
    net.add_processor(tap)
    pp, p, c = golden_messages()
    net._buses["A"]  # A exists
    # send from A: processors installed → envelope unwraps per vote
    netA_send = net._make_send_handler("A")
    netA_send(FlatBatch(payload=fw.encode_three_pc([pp], [p], [c])), "B")
    timer.run_for(1.0)
    assert [m for m in (x.message for x in tap.seen)] == [c]
    assert got == [c]


# ----------------------------------------------------- budget stages

def test_budget_has_serialize_and_parse_stages():
    from plenum_tpu.observability.budget import STAGES, stage_of
    assert "serialize" in STAGES and "parse" in STAGES
    assert stage_of("wire_pack", "3pc") == "serialize"
    assert stage_of("wire_pack", "propagate") == "serialize"
    assert stage_of("wire_parse", "3pc") == "parse"
    assert stage_of("prepare_batch", "3pc") == "3pc"


# ===================================================== trace context (v2)


def _stamp(origin="Alpha", seq=7, perf=1.5, wall=2.5):
    return fw.encode_trace_stamp(origin, seq, perf, wall)


def _prop_envelope(trace=None):
    import msgpack
    return fw.encode_propagate_envelope(
        [msgpack.packb({"reqId": 1}, use_bin_type=True)], ["c1"],
        trace=trace)


def test_trace_stamp_roundtrip():
    st = fw.decode_trace_stamp(_stamp())
    assert (st.origin, st.seq, st.perf_ts, st.wall_ts) \
        == ("Alpha", 7, 1.5, 2.5)


def test_trace_stamp_encode_is_total():
    """encode_trace_stamp clamps instead of raising: the stamp is
    advisory and must never fail the envelope it rides on."""
    payload = fw.encode_trace_stamp("x" * 200, -1, 0.25, 0.5)
    st = fw.decode_trace_stamp(payload)
    assert len(st.origin.encode()) == fw.TRACE_NAME_MAX
    assert st.seq == (1 << 64) - 1          # -1 wrapped into u64


def test_trace_stamp_decode_rejects_content_garbage():
    import struct
    good = _stamp()
    assert fw.decode_trace_stamp(b"") is None
    assert fw.decode_trace_stamp(good + b"x") is None       # bad length
    assert fw.decode_trace_stamp(good[:-1]) is None
    assert fw.decode_trace_stamp(bytes([255]) + good[1:]) is None
    for bad in (float("nan"), float("inf")):
        assert fw.decode_trace_stamp(
            good[:-8] + struct.pack("<d", bad)) is None
    assert fw.decode_trace_stamp(
        bytes([3]) + b"\xff\xfe\xfd" + good[6:]) is None    # bad utf-8


def test_envelope_version_bumps_only_with_stamp():
    plain = _prop_envelope()
    stamped = _prop_envelope(trace=_stamp())
    assert plain[2] == fw.VERSION
    assert stamped[2] == fw.VERSION_TRACE
    env = fw.parse_envelope(stamped)
    assert env.stamp is not None
    assert (env.stamp.origin, env.stamp.seq) == ("Alpha", 7)
    # the stamp never enters sections — consensus consumers cannot
    # see it by iterating
    assert len(env.sections) == 1
    assert env.sections[0].n == 1
    assert fw.parse_envelope(plain).stamp is None


def test_v1_envelope_rejects_trace_kind():
    """A version-1 envelope carrying a kind-5 section is structural
    garbage — the golden version-1 wire has no trace vocabulary."""
    raw = bytearray(_prop_envelope(trace=_stamp()))
    raw[2] = fw.VERSION
    with pytest.raises(fw.FlatWireError, match="unknown section kind 5"):
        fw.parse_envelope(bytes(raw))


def test_corrupt_stamp_yields_none_but_envelope_parses():
    import struct
    corrupt = _stamp()[:-8] + struct.pack("<d", float("inf"))
    env = fw.parse_envelope(_prop_envelope(trace=corrupt))
    assert env.stamp is None
    assert len(env.sections) == 1
    assert env.sections[0].request(0) == {"reqId": 1}


def test_duplicate_trace_sections_first_wins():
    s2 = _stamp("Beta", 9, 3.0, 4.0)
    raw = bytearray(_prop_envelope(trace=_stamp()))
    raw[3] += 1                                  # nsect
    raw += bytes((fw.KIND_TRACE,)) + (1).to_bytes(4, "little") \
        + len(s2).to_bytes(4, "little") + s2
    env = fw.parse_envelope(bytes(raw))
    assert env.stamp.origin == "Alpha"           # first stamp kept
    assert len(env.sections) == 1


def test_trace_section_payload_truncation_is_structural():
    """Cutting the envelope short INSIDE the trace section is a framing
    violation like any other truncation — attributable, rejected."""
    stamped = _prop_envelope(trace=_stamp())
    with pytest.raises(fw.FlatWireError):
        fw.parse_envelope(stamped[:-5])


def test_three_pc_envelope_carries_stamp_alongside_votes():
    pp, p, c = golden_messages()
    data = fw.encode_three_pc([pp], [p], [c],
                              trace=_stamp("Delta", 42, 0.5, 1.5))
    assert data[2] == fw.VERSION_TRACE
    env = fw.parse_envelope(data)
    assert env.stamp.origin == "Delta" and env.stamp.seq == 42
    kinds = {type(s).__name__ for s in env.sections}
    assert "PrepareColumns" in kinds and "CommitColumns" in kinds
