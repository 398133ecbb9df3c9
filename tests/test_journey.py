"""Pool-wide causal tracing e2e: wire-carried trace context joined
into per-request cross-node journeys (observability/journey.py).

The acceptance surface of the journey plane:

* a traced 4-node sim pool — flat wire AND the typed THREE_PC_BATCH /
  PROPAGATE fallback — yields COMPLETE journeys whose per-node phase
  chains are causally ordered, with the propagate-quorum closer and
  the per-batch critical path named;
* ledger/state roots are byte-equal with trace context on vs off (the
  stamp provably never steers consensus);
* a stamp-stripping tap (any installed processor unwraps envelopes to
  per-message sends, which carry no stamps) degrades the report to
  per-node-only records — no rejection, no crash;
* an equivocating primary leaves an evidence chain: conflicting
  PRE-PREPARE digests per (viewNo:ppSeqNo), observed by whom, when;
* a traced gateway's ``gateway_admit`` anchor joins the node-side
  journey on the same request digest.
"""
import json
import os
import subprocess
import sys

import pytest

from plenum_tpu.common.config import Config
from plenum_tpu.crypto.signer import SimpleSigner
from plenum_tpu.observability import journey
from plenum_tpu.observability.export import chrome_trace, pool_tracers
from plenum_tpu.testing.adversary import (
    AdversaryController, EquivocatingPrimary, Scenario)
from plenum_tpu.testing.sim_network import Processor

from tests.test_adversary import build_pool
from tests.test_node_e2e import pump, signed_nym_request, submit_to_all

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def traced_conf(**over):
    base = dict(Max3PCBatchSize=5, Max3PCBatchWait=0.2, CHK_FREQ=5,
                LOG_SIZE=15, TRACING_ENABLED=True,
                TRACE_CONTEXT_ENABLED=True)
    base.update(over)
    return Config(**base)


def run_traced_pool(n_reqs=3, net_seed=19, conf=None, net_hook=None):
    timer, net, nodes, sinks = build_pool(net_seed,
                                          conf=conf or traced_conf())
    if net_hook is not None:
        net_hook(net)
    for i in range(n_reqs):
        client = SimpleSigner(seed=bytes([0x41 + i]) * 32)
        submit_to_all(nodes, signed_nym_request(client, req_id=500 + i))
        pump(timer, nodes, 2)
    pump(timer, nodes, 6)
    assert all(n.domain_ledger.size == n_reqs for n in nodes), \
        [(n.name, n.domain_ledger.size) for n in nodes]
    return nodes, timer


def assert_complete_report(report, n_reqs):
    reqs = report["requests"]
    assert len(reqs) == n_reqs
    assert report["complete_requests"] == n_reqs
    assert journey.causal_violations(report) == []
    for r in reqs.values():
        assert r["intake"] is not None
        assert r["propagate_close"] is not None
        # the quorum-closing relay is NAMED, not just timed
        assert r["propagate_close"]["closer"]
        assert r["batch"] in report["batches"]
    for b in report["batches"].values():
        cp = b["critical_path"]
        assert cp is not None and cp["node"] and cp["phase"]
        bd = cp["breakdown"]
        assert bd is not None and bd["e2e_ms"] > 0
        assert abs(bd["wire_pct"] + bd["straggler_pct"]
                   + bd["local_pct"] - 100.0) < 0.1
        for n_rec in b["nodes"].values():
            assert n_rec.get("order") is not None


# ------------------------------------------------------------------ e2e


def test_journeys_complete_on_flat_wire():
    nodes, _ = run_traced_pool(n_reqs=3)
    report = journey.journeys_from_tracers(pool_tracers(nodes))
    assert_complete_report(report, 3)
    # stamps flowed: the clock/link model has per-link delay estimates
    assert not report["degraded"]
    assert report["links"]
    for link in report["links"].values():
        assert link["samples"] >= 1 and link["delay_ms"] >= 0.0


def test_roots_byte_equal_with_trace_context_on_and_off():
    """The whole plane is advisory: identical seeds must produce
    byte-identical ledger and state roots with stamps on vs off."""
    from plenum_tpu.common.constants import NYM

    def roots(conf):
        nodes, _ = run_traced_pool(n_reqs=2, net_seed=23, conf=conf)
        return [(n.name, n.domain_ledger.root_hash,
                 n.audit_ledger.root_hash,
                 n.write_manager.request_handlers[NYM]
                  .state.committedHeadHash)
                for n in nodes]

    on = roots(traced_conf())
    off = roots(traced_conf(TRACING_ENABLED=False,
                            TRACE_CONTEXT_ENABLED=False))
    assert on == off


def test_stamp_stripping_tap_degrades_to_per_node_records():
    """Any installed processor unwraps coalesced envelopes into
    per-message sends — which carry no stamps. The pool must order
    normally and the report must degrade gracefully: no link samples,
    but per-node phase records and causal ordering intact."""
    class PassThrough(Processor):
        def process(self, msg):
            return False

    nodes, _ = run_traced_pool(
        n_reqs=2, net_hook=lambda net: net.add_processor(PassThrough()))
    report = journey.journeys_from_tracers(pool_tracers(nodes))
    assert report["degraded"]
    assert report["links"] == {}
    assert journey.causal_violations(report) == []
    # per-node records survive stamp loss
    assert report["requests"]
    for b in report["batches"].values():
        assert b["nodes"]
        for rec in b["nodes"].values():
            assert rec.get("order") is not None


def test_corrupted_stamp_degrades_without_rejection():
    """A wire fault that CORRUPTS the trace section (valid envelope,
    non-finite stamp floats) must not cost a single ordered request —
    the flat parser decodes the stamp to None and the message
    proceeds."""
    from plenum_tpu.common.messages.node_messages import FlatBatch
    from plenum_tpu.testing.sim_network import PendingMessage

    timer, net, nodes, _sinks = build_pool(29, conf=traced_conf())
    orig_deliver = net._deliver

    def deliver(msg):
        m = msg.message
        if isinstance(m, FlatBatch) and m.payload[2:3] == b"\x02":
            # the version-2 envelope's advisory TRACE section rides
            # last; its final 8 bytes are the wall_ts f64 — forcing the
            # exponent to all-ones makes it non-finite, which the
            # decoder rejects into stamp=None without failing anything
            raw = bytearray(m.payload)
            raw[-1] = 0x7F
            raw[-2] = 0xF0
            msg = PendingMessage(FlatBatch(bytes(raw)), msg.frm, msg.dst)
        orig_deliver(msg)

    net._deliver = deliver
    client = SimpleSigner(seed=b"\x61" * 32)
    submit_to_all(nodes, signed_nym_request(client, req_id=700))
    pump(timer, nodes, 8)
    assert all(n.domain_ledger.size == 1 for n in nodes)
    report = journey.journeys_from_tracers(pool_tracers(nodes))
    assert report["degraded"]          # every stamp decoded to None
    assert journey.causal_violations(report) == []
    assert report["complete_requests"] == 1


# ------------------------------------------------- equivocation evidence


def test_equivocating_primary_leaves_evidence_chain():
    """An EquivocatingPrimary's conflicting PRE-PREPARE digests land in
    the journey report as an evidence chain: which digests for which
    (viewNo:ppSeqNo) slot, observed by whom, sent by whom, when."""
    timer, net, nodes, _ = build_pool(
        31, conf=traced_conf(ToleratePrimaryDisconnection=4,
                             NEW_VIEW_TIMEOUT=8,
                             STATE_FRESHNESS_UPDATE_INTERVAL=3))
    primary = next(n for n in nodes if n.replica.data.is_primary)
    adv = AdversaryController(timer, seed=7)
    adv.set_pool(nodes)
    adv.corrupt(primary, EquivocatingPrimary(real_count=1))
    sc = Scenario(timer, nodes, adversary=adv)
    for i in range(3):
        client = SimpleSigner(seed=bytes([0x30 + i]) * 32)
        submit_to_all(nodes, signed_nym_request(client, req_id=300 + i))
        sc.run(2)
    sc.run(6)
    report = journey.journeys_from_tracers(pool_tracers(nodes))
    eqs = report["equivocations"]
    assert eqs, "equivocating primary left no evidence"
    for eq in eqs:
        assert len(eq["digests"]) >= 2
        observers = {o["observed_by"] for d in eq["digests"]
                     for o in eq["evidence"][d]}
        senders = {o["frm"] for d in eq["digests"]
                   for o in eq["evidence"][d]}
        assert observers
        assert primary.name in senders
        for d in eq["digests"]:
            for o in eq["evidence"][d]:
                assert o["t"] is not None
    # the honest pool keeps a causally clean history regardless
    assert journey.causal_violations(report) == []


def test_scenario_dump_journey_writes_report_with_evidence(tmp_path):
    timer, net, nodes, _ = build_pool(31, conf=traced_conf())
    sc = Scenario(timer, nodes)
    client = SimpleSigner(seed=b"\x51" * 32)
    submit_to_all(nodes, signed_nym_request(client, req_id=600))
    sc.run(8)
    path, n_eq = sc.dump_journey(path=str(tmp_path / "j.json"))
    assert path and n_eq == 0
    doc = json.load(open(path))
    assert doc["causal_violations"] == []
    assert doc["complete_requests"] == 1
    assert "equivocations" in doc and "_clocks" not in doc


def test_untraced_pool_dumps_nothing():
    timer, net, nodes, _ = build_pool(31)   # tracing off
    sc = Scenario(timer, nodes)
    assert sc.dump_journey() == (None, 0)


# ------------------------------------------------------- gateway anchor


def test_gateway_admit_joins_node_side_journey():
    from plenum_tpu.crypto.batch_verifier import OpenSSLVerifier
    from plenum_tpu.gateway.intake import GatewayIntake
    from plenum_tpu.observability.tracing import Tracer

    client = SimpleSigner(seed=b"\x52" * 32)
    req = signed_nym_request(client, req_id=610)

    gw_tracer = Tracer("gateway")
    intake = GatewayIntake(verifier=OpenSSLVerifier(), tracer=gw_tracer)
    handle = intake.screen_dispatch([(req, "c1")])
    intake.screen_flush()
    assert len(intake.screen_conclude(handle)) == 1

    timer, net, nodes, _ = build_pool(37, conf=traced_conf())
    submit_to_all(nodes, req)
    pump(timer, nodes, 8)
    assert all(n.domain_ledger.size == 1 for n in nodes)
    report = journey.journeys_from_tracers(
        pool_tracers(nodes) + [gw_tracer])
    (digest, rec), = report["requests"].items()
    assert rec["gateway"] is not None
    assert rec["gateway"]["node"] == "gateway"
    assert rec["intake"] is not None
    assert rec["gateway"]["t"] is not None
    assert journey.causal_violations(report) == []


# ------------------------------------------------ chrome-dump round trip


def test_journeys_from_chrome_match_live_report():
    nodes, _ = run_traced_pool(n_reqs=2)
    tracers = pool_tracers(nodes)
    live = journey.journeys_from_tracers(tracers)
    doc = chrome_trace(tracers)
    from_file = journey.journeys_from_chrome(doc)
    assert from_file["complete_requests"] == live["complete_requests"]
    assert sorted(from_file["batches"]) == sorted(live["batches"])
    assert sorted(from_file["requests"]) == sorted(live["requests"])
    assert journey.causal_violations(from_file) == []
    # µs quantisation on export: link medians agree to ~10µs
    for link, l in live["links"].items():
        assert link in from_file["links"]
        assert abs(from_file["links"][link]["delay_ms"]
                   - l["delay_ms"]) < 0.05


def test_export_carries_flow_event_arrows():
    """wire_send/wire_recv pairs export as Perfetto flow events (ph
    s/f) with matching ids, so Perfetto draws arrows between node
    rows."""
    nodes, _ = run_traced_pool(n_reqs=2)
    doc = chrome_trace(pool_tracers(nodes))
    starts = [e for e in doc["traceEvents"] if e.get("ph") == "s"]
    ends = [e for e in doc["traceEvents"] if e.get("ph") == "f"]
    assert starts and ends
    start_ids = {e["id"] for e in starts}
    matched = [e for e in ends if e["id"] in start_ids]
    assert matched, "no flow end matches any flow start id"
    assert all(e.get("bp") == "e" for e in ends)


def test_to_json_report_is_json_serializable():
    nodes, _ = run_traced_pool(n_reqs=2)
    report = journey.journeys_from_tracers(pool_tracers(nodes))
    blob = json.dumps(journey.to_json(report))
    assert "batches" in json.loads(blob)


def test_format_table_names_closer_and_critical_path():
    nodes, _ = run_traced_pool(n_reqs=2)
    report = journey.journeys_from_tracers(pool_tracers(nodes))
    table = journey.format_table(report)
    assert "journeys: 2 request(s), 2 complete" in table
    assert "links (median one-way delay" in table
    assert "pool critical path" in table
    some_batch = next(iter(report["batches"].values()))
    assert some_batch["critical_path"]["node"] in table


# ---------------------------------------------------------------- CLIs


@pytest.mark.slow
def test_pool_journey_cli_sim_and_file_modes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "pool_journey"),
         "--sim", "--reqs", "2", "--json"],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc["causal_violations"] == []
    assert doc["complete_requests"] == 2


def test_pool_journey_cli_truncated_json_named_error(tmp_path):
    bad = tmp_path / "trunc.json"
    bad.write_text('{"traceEvents": [{"ph": "i", "pid"')
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "pool_journey"),
         str(bad)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1
    assert "MALFORMED trace JSON" in r.stderr


def test_trace_view_cli_truncated_json_named_error(tmp_path):
    bad = tmp_path / "trunc.json"
    bad.write_text('{"traceEvents": [{"ph": "X", "pid": 1, "ts"')
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_view"),
         str(bad)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1
    assert "MALFORMED trace JSON" in r.stderr


def test_trace_view_summary_includes_counter_tracks():
    from plenum_tpu.observability.export import summarize
    doc = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "Alpha"}},
        {"ph": "X", "name": "order", "cat": "3pc", "pid": 1, "tid": 1,
         "ts": 10, "dur": 5, "args": {}},
        {"ph": "C", "name": "backlog", "pid": 1, "tid": 0, "ts": 11,
         "args": {"backlog": 3}},
        {"ph": "C", "name": "backlog", "pid": 1, "tid": 0, "ts": 12,
         "args": {"backlog": 7}},
    ]}
    s = summarize(doc)
    assert s["counters"]["backlog"] == {
        "points": 2, "min": 3.0, "max": 7.0, "last": 7.0}
    # the CLI renderer shows them
    import importlib.machinery
    import importlib.util
    loader = importlib.machinery.SourceFileLoader(
        "trace_view_mod", os.path.join(REPO, "scripts", "trace_view"))
    spec = importlib.util.spec_from_loader("trace_view_mod", loader)
    tv = importlib.util.module_from_spec(spec)
    loader.exec_module(tv)
    out = tv.render_summary(s)
    assert "counter tracks:" in out and "backlog" in out
