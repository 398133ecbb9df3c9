"""Consensus flight recorder (observability/): ring tracer, Perfetto
export, pool-wide merged timeline, invariant-failure dumps."""
import json
import os

import pytest

from plenum_tpu.common.config import Config
from plenum_tpu.common.constants import NYM, TARGET_NYM, VERKEY
from plenum_tpu.crypto.signer import SimpleSigner
from plenum_tpu.observability.export import (
    chrome_trace, export_chrome_trace, pool_tracers, summarize,
    trace_events)
from plenum_tpu.observability.tracing import (
    CAT_3PC, CAT_DEVICE, NullTracer, Tracer)
from plenum_tpu.runtime.sim_random import DefaultSimRandom
from plenum_tpu.server.node import Node
from plenum_tpu.testing.sim_network import SimNetwork

NAMES = ["Alpha", "Beta", "Gamma", "Delta"]


# ------------------------------------------------------------- tracer


def _ticking_clock(step=0.001):
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]
    return clock


def test_disarmed_tracer_is_free_and_arms_without_reinjection():
    """Disarmed, a Tracer is what NullTracer is: the shared null
    context, nothing recorded, no ring. A component that was handed it
    once records after arm() with no second injection."""
    from plenum_tpu.observability import tracing
    from plenum_tpu.server.propagator import Propagator

    tracer = Tracer("n1", capacity=8, clock=_ticking_clock(), armed=False)
    assert not tracer.armed and not tracer.enabled
    assert tracer.span("s", CAT_3PC, key="k", n=1) is tracing._NULL_CTX
    assert tracer.span("s") is NullTracer().span("s")
    tracer.instant("i", CAT_3PC)
    tracer.counter("c", 3)
    tracer.complete("x", CAT_3PC, 0.0, 1.0)
    assert tracer.spans() == [] and tracer._buf == []
    assert tracer.stats() == {"enabled": False, "capacity": 8,
                              "recorded": 0, "buffered": 0, "dropped": 0}
    assert tracer.oldest_written_at() is None

    component = Propagator.__new__(Propagator)   # any holder will do
    component.tracer = tracer
    with component.tracer.span("before", CAT_3PC):
        pass
    tracer.arm()
    assert tracer.armed and tracer.enabled and len(tracer._buf) == 8
    with component.tracer.span("after", CAT_3PC):
        pass
    assert [r[1] for r in tracer.spans()] == ["after"]
    tracer.disarm()
    tracer.instant("late", CAT_3PC)
    assert [r[1] for r in tracer.spans()] == ["after"]   # kept, not grown
    tracer.arm()                                     # same ring
    tracer.instant("again", CAT_3PC)
    assert [r[1] for r in tracer.spans()] == ["after", "again"]


def test_complete_records_a_span_the_caller_measured():
    tracer = Tracer("n1", capacity=4, clock=_ticking_clock())
    t0 = tracer.now()
    t1 = tracer.now()
    tracer.complete("prod_tick", "transport", t0, t1, key="k", produced=3)
    assert tracer.spans() == [
        ("X", "prod_tick", "transport", t0, t1, "k", {"produced": 3})]
    assert tracer.oldest_written_at() == t1
    # after a wrap the oldest survivor moves on
    for i in range(5):
        tracer.complete("s%d" % i, "transport", 10.0 + i, 10.5 + i)
    assert tracer.oldest_written_at() == 11.5
    assert NullTracer().complete("x", "transport", 0.0, 1.0) is None
    assert NullTracer().now() == 0.0
    [ev] = [e for e in trace_events([tracer]) if e["name"] == "s4"]
    assert (ev["ph"], ev["ts"], ev["dur"]) == ("X", 14000000, 500000)


def test_ring_buffer_wraparound_keeps_newest():
    tracer = Tracer("n1", capacity=8, clock=_ticking_clock())
    for i in range(20):
        tracer.instant("e%d" % i)
    recs = tracer.spans()
    assert len(recs) == 8
    # flight-recorder semantics: the NEWEST records survive, in order
    assert [r[1] for r in recs] == ["e%d" % i for i in range(12, 20)]
    stats = tracer.stats()
    assert stats["recorded"] == 20
    assert stats["buffered"] == 8
    assert stats["dropped"] == 12


def test_span_context_manager_records_payload_and_times():
    tracer = Tracer("n1", capacity=4, clock=_ticking_clock())
    with tracer.span("work", CAT_3PC, key="0:1", batch=3) as sp:
        sp.add(extra=7)
    (kind, name, cat, t0, t1, key, args), = tracer.spans()
    assert (kind, name, cat, key) == ("X", "work", CAT_3PC, "0:1")
    assert t1 > t0
    assert args == {"batch": 3, "extra": 7}


def test_counter_and_instant_records():
    tracer = Tracer("n1", capacity=4, clock=_ticking_clock())
    tracer.counter("depth", 5)
    tracer.instant("mark", CAT_DEVICE, key="d1", hits=1)
    counter, instant = tracer.spans()
    assert counter[0] == "C" and counter[6] == {"depth": 5}
    assert instant[0] == "i" and instant[5] == "d1"


def test_tracer_clear_resets_stats():
    tracer = Tracer("n1", capacity=4, clock=_ticking_clock())
    tracer.instant("a")
    tracer.clear()
    assert tracer.spans() == []
    assert tracer.stats()["recorded"] == 0


def test_null_tracer_emits_nothing_and_is_reusable():
    tracer = NullTracer("n")
    with tracer.span("x", CAT_3PC, key="k", a=1) as sp:
        sp.add(b=2)   # the shared null ctx must absorb payload calls
    tracer.instant("i")
    tracer.counter("c", 1)
    assert tracer.spans() == []
    assert tracer.stats()["enabled"] is False
    assert tracer.enabled is False


# ------------------------------------------------------------ exporter


def _fixed_trace():
    tracer = Tracer("Alpha", capacity=16, clock=_ticking_clock())
    with tracer.span("pp_process", CAT_3PC, key="0:1", batch_size=2):
        pass
    tracer.counter("auth_batch_size", 3)
    tracer.instant("prepared", CAT_3PC, key="0:1")
    with tracer.span("auth_dispatch", CAT_DEVICE, n=3):
        pass
    return tracer


def test_exporter_deterministic_under_fixed_clock():
    a = chrome_trace([_fixed_trace()])
    b = chrome_trace([_fixed_trace()])
    assert a == b
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_exporter_event_shapes():
    events = trace_events([_fixed_trace()])
    by_ph = {}
    for e in events:
        by_ph.setdefault(e["ph"], []).append(e)
    # process_name + one thread_name per category
    meta_names = {e["name"] for e in by_ph["M"]}
    assert meta_names == {"process_name", "thread_name"}
    x = next(e for e in by_ph["X"] if e["name"] == "pp_process")
    assert x["ts"] >= 0 and x["dur"] > 0
    assert x["args"]["key"] == "0:1" and x["args"]["batch_size"] == 2
    c, = by_ph["C"]
    assert c["args"] == {"auth_batch_size": 3}
    i, = by_ph["i"]
    assert i["s"] == "t" and i["args"]["key"] == "0:1"
    # categories become distinct tracks within the node's pid
    pid = x["pid"]
    device = next(e for e in by_ph["X"] if e["name"] == "auth_dispatch")
    assert device["pid"] == pid and device["tid"] != x["tid"]


def test_exporter_skips_empty_and_null_tracers():
    doc = chrome_trace([NullTracer("a"), Tracer("b", capacity=4)])
    assert doc["traceEvents"] == []


# ---------------------------------------------------------- pool merge


@pytest.fixture
def traced_pool(mock_timer):
    mock_timer.set_time(1600000000)
    net = SimNetwork(mock_timer, DefaultSimRandom(11))
    conf = Config(TRACING_ENABLED=True, Max3PCBatchSize=10,
                  Max3PCBatchWait=0.2, CHK_FREQ=5, LOG_SIZE=15)
    nodes = [Node(n, NAMES, mock_timer, net.create_peer(n), config=conf,
                  client_reply_handler=lambda c, m: None)
             for n in NAMES]
    return nodes, mock_timer


def _order_one_batched(nodes, timer):
    client = SimpleSigner(seed=b"\x55" * 32)
    req = {"identifier": client.identifier, "reqId": 1,
           "protocolVersion": 2,
           "operation": {"type": NYM, TARGET_NYM: client.identifier,
                         VERKEY: client.verkey}}
    req["signature"] = client.sign(dict(req))
    for n in nodes:
        n.process_client_batch([(dict(req), "c1")])
    end = timer.get_current_time() + 8.0
    while timer.get_current_time() < end:
        for n in nodes:
            n.service()
        timer.run_for(0.05)
        if all(n.domain_ledger.size >= 1 for n in nodes):
            break


def test_sim_pool_merged_timeline_has_every_3pc_phase(traced_pool, tdir):
    nodes, timer = traced_pool
    _order_one_batched(nodes, timer)
    assert all(n.domain_ledger.size >= 1 for n in nodes)
    doc = chrome_trace(pool_tracers(nodes))
    summary = summarize(doc)
    assert sorted(summary["nodes"]) == sorted(NAMES)
    for name in NAMES:
        spans = summary["span_counts"][name]
        # the batch lifecycle, per node: intake -> propagate quorum ->
        # PP -> prepare -> commit -> order -> apply -> commit -> reply
        assert spans.get("request_accepted", 0) >= 1, (name, spans)
        assert spans.get("propagate_quorum", 0) >= 1, (name, spans)
        assert spans.get("pp_create", 0) + spans.get("pp_process", 0) \
            >= 1, (name, spans)
        # inbound votes arrive per-message OR as flat/typed envelopes
        # (the columnar intake spans carry the same phase evidence)
        assert spans.get("prepare_process", 0) \
            + spans.get("prepare_batch", 0) >= 1, (name, spans)
        assert spans.get("prepared", 0) >= 1, (name, spans)
        assert spans.get("commit_process", 0) \
            + spans.get("commit_batch", 0) >= 1, (name, spans)
        assert spans.get("order", 0) >= 1, (name, spans)
        assert spans.get("batch_apply", 0) >= 1, (name, spans)
        assert spans.get("batch_commit", 0) >= 1, (name, spans)
        assert spans.get("reply", 0) >= 1, (name, spans)
        # device-dispatch seam + its queue-depth counter
        assert spans.get("auth_dispatch", 0) >= 1, (name, spans)
        assert spans.get("auth_conclude", 0) >= 1, (name, spans)
        assert spans.get("auth_batch_size", 0) >= 1, (name, spans)
    # exactly one primary created the batch; all correlate by 3PC key
    assert sum(summary["span_counts"][n].get("pp_create", 0)
               for n in NAMES) >= 1
    keys = {e["args"]["key"] for e in doc["traceEvents"]
            if e.get("name") == "order"}
    assert len(keys) >= 1
    # the file round-trips as valid JSON
    path = export_chrome_trace(pool_tracers(nodes),
                               os.path.join(tdir, "trace.json"))
    with open(path) as f:
        assert json.load(f)["traceEvents"]


def test_tracing_disabled_pool_records_nothing(mock_timer):
    mock_timer.set_time(1600000000)
    net = SimNetwork(mock_timer, DefaultSimRandom(12))
    conf = Config(Max3PCBatchSize=10, Max3PCBatchWait=0.2, CHK_FREQ=5,
                  LOG_SIZE=15)   # TRACING_ENABLED defaults off
    nodes = [Node(n, NAMES, mock_timer, net.create_peer(n), config=conf,
                  client_reply_handler=lambda c, m: None)
             for n in NAMES]
    _order_one_batched(nodes, mock_timer)
    assert all(not t.enabled and t.spans() == []
               for t in pool_tracers(nodes))
    assert chrome_trace(pool_tracers(nodes))["traceEvents"] == []


def test_node_arms_on_a_trace_session_and_dumps_only_when_told(
        mock_timer, tdir):
    """A node whose config leaves tracing off holds a disarmed Tracer.
    Told of a session (the verify daemon's id-0 frame) it records spans
    through the references injected at construction, never stamps the
    wire, touches no file while it runs, and writes exactly
    node_<Name>_spans.json when told to stop."""
    mock_timer.set_time(1600000000)
    net = SimNetwork(mock_timer, DefaultSimRandom(13))
    conf = Config(Max3PCBatchSize=10, Max3PCBatchWait=0.2, CHK_FREQ=5,
                  LOG_SIZE=15)
    nodes = [Node(n, NAMES, mock_timer, net.create_peer(n), config=conf,
                  client_reply_handler=lambda c, m: None)
             for n in NAMES]
    alpha, beta = nodes[0], nodes[1]
    assert isinstance(alpha.tracer, Tracer) and not alpha.tracer.armed
    assert alpha.write_trace_dump() is None          # no session yet
    session = os.path.join(tdir, "session")
    os.mkdir(session)
    alpha._on_verifier_control({"trace": {"dir": session}})
    beta._on_verifier_control({"trace": {"dir": session + "-gone"}})
    for junk in (None, "trace", {"trace": 1}, {"trace": {"dir": 7}}):
        nodes[2]._on_verifier_control(junk)
    assert alpha.tracer.armed and beta.tracer.armed
    assert not nodes[2].tracer.armed
    assert alpha.propagator.tracer is alpha.tracer
    _order_one_batched(nodes, mock_timer)
    assert all(n.domain_ledger.size >= 1 for n in nodes)
    assert os.listdir(session) == []                 # nothing before stop
    names = {r[1] for r in alpha.tracer.spans()}
    assert {"auth_dispatch", "propagate_flush", "batch_apply", "order",
            "reply"} <= names
    # spans only: the stamps follow the config at start, and with them
    # the per-request instants only the journey join reads
    assert not alpha.propagator.trace_context
    assert not names & {"request_accepted", "propagate_quorum",
                        "wire_send", "wire_recv"}
    assert nodes[2].tracer.spans() == []
    path = alpha.write_trace_dump()
    assert path == os.path.join(session, "node_Alpha_spans.json")
    assert os.listdir(session) == ["node_Alpha_spans.json"]
    assert beta.write_trace_dump() is None           # no such directory
    assert not os.path.exists(session + "-gone")
    with open(path) as f:
        doc = json.load(f)
    meta = doc["metadata"]["Alpha"]
    assert meta["stats"]["recorded"] == len(alpha.tracer.spans())
    assert meta["stats"]["dropped"] == 0
    assert meta["clock"]["name"] == "perf_counter"
    assert meta["oldest_ts"] == min(
        e["ts"] + e.get("dur", 0) for e in doc["traceEvents"]
        if e["ph"] != "M")


def test_run_node_hands_out_the_dump_in_its_finally():
    """bootstrap.run_node writes the dump after the stacks stop, also
    when the loop is cancelled (what SIGINT does under asyncio.run)."""
    import asyncio
    from plenum_tpu.bootstrap import run_node
    calls = []

    class _Stack:
        def __init__(self, name):
            self.name = name

        async def stop(self):
            calls.append("stop " + self.name)

    class _Core:
        def write_trace_dump(self):
            calls.append("dump")

    class _Node:
        name = "Alpha"
        node = _Core()
        nodestack, clientstack = _Stack("nodes"), _Stack("clients")

        async def start_async(self):
            calls.append("start")

        async def prod(self):
            return 0

    async def main():
        task = asyncio.ensure_future(run_node(_Node()))
        await asyncio.sleep(0.05)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task

    asyncio.run(main())
    assert calls == ["start", "stop nodes", "stop clients", "dump"]


def test_validator_info_reports_tracing_stats(traced_pool):
    from plenum_tpu.server.validator_info import ValidatorNodeInfoTool
    nodes, timer = traced_pool
    _order_one_batched(nodes, timer)
    info = ValidatorNodeInfoTool(nodes[0]).info
    tr = info["Tracing"]
    assert tr["enabled"] is True
    assert tr["recorded"] >= 1
    assert tr["capacity"] == nodes[0].config.TRACING_BUFFER_SPANS


# ------------------------------------------------- invariant-dump hook


class _Boom:
    def __init__(self):
        self.calls = 0

    def check(self):
        self.calls += 1
        if self.calls >= 2:
            raise AssertionError("agreement violated (test)")


class _StubNode:
    def __init__(self, name, tracer):
        self.name = name
        self.tracer = tracer

    def service(self):
        self.tracer.instant("tick", CAT_3PC)


def test_scenario_dumps_flight_recorder_on_invariant_failure(
        mock_timer, tdir, monkeypatch):
    from plenum_tpu.testing.adversary.scenario import Scenario
    monkeypatch.setenv("PLENUM_TPU_TRACE_DIR", tdir)
    nodes = [_StubNode("A", Tracer("A", capacity=16)),
             _StubNode("B", Tracer("B", capacity=16))]
    scenario = Scenario(mock_timer, nodes, honest=["A", "B"],
                        checker=_Boom())
    with pytest.raises(AssertionError) as exc:
        scenario.run(5.0)
    assert "flight recorder" in str(exc.value)
    dumps = [f for f in os.listdir(tdir)
             if f.startswith("invariant_failure_trace")]
    assert len(dumps) == 1
    with open(os.path.join(tdir, dumps[0])) as f:
        doc = json.load(f)
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"A", "B"}


def test_scenario_without_tracing_raises_plain(mock_timer):
    from plenum_tpu.testing.adversary.scenario import Scenario
    nodes = [_StubNode("A", NullTracer("A"))]
    scenario = Scenario(mock_timer, nodes, honest=["A"], checker=_Boom())
    with pytest.raises(AssertionError) as exc:
        scenario.run(5.0)
    assert "flight recorder" not in str(exc.value)


# ------------------------------------------------- per-stage budget

def _manual_tracer(name="Alpha"):
    """Tracer with a controllable clock for deterministic spans."""
    t = [0.0]

    def clock():
        return t[0]
    tracer = Tracer(name, clock=clock)
    return tracer, t


def _span(tracer, t, name, cat, t0, t1, **args):
    t[0] = t0
    ctx = tracer.span(name, cat, **args)
    ctx.__enter__()
    t[0] = t1
    ctx.__exit__(None, None, None)


def test_budget_exclusive_time_and_per_request_math():
    """A device window nested inside an apply is charged to
    dispatch_wait ONLY; stages sum to real host time."""
    from plenum_tpu.observability.budget import budget_from_tracers
    tracer, t = _manual_tracer()
    # 100ms apply containing a 40ms fused device window
    _span(tracer, t, "fused_dispatch", "device", 0.02, 0.06)
    _span(tracer, t, "batch_apply", "execute", 0.0, 0.1,
          batch_size=10)
    # 10ms of columnar intake + 5ms reply
    _span(tracer, t, "prepare_batch", "3pc", 0.2, 0.21)
    _span(tracer, t, "reply", "reply", 0.3, 0.305)
    # intake seam is device-cat but belongs to the intake stage
    _span(tracer, t, "auth_dispatch", "device", 0.4, 0.42)
    report = budget_from_tracers([tracer])
    assert report["ordered_reqs"] == 10
    ms = report["stage_ms_per_node"]
    assert ms["execute"] == pytest.approx(60.0, abs=0.1)
    assert ms["dispatch_wait"] == pytest.approx(40.0, abs=0.1)
    assert ms["3pc"] == pytest.approx(10.0, abs=0.1)
    assert ms["reply"] == pytest.approx(5.0, abs=0.1)
    assert ms["intake"] == pytest.approx(20.0, abs=0.1)
    per_req = report["host_ms_per_ordered_req"]
    assert per_req["execute"] == pytest.approx(6.0, abs=0.01)
    assert per_req["total"] == pytest.approx(13.5, abs=0.01)


def test_budget_files_transport_and_the_tick_envelope():
    """The served node's socket seams are the transport stage; what is
    left of a productive tick once every span inside it is taken off is
    `untraced`; a blocking wait on the daemon inside an inline
    authentication is dispatch_wait, not propagate."""
    from plenum_tpu.observability.budget import (
        STAGES, budget_from_chrome, budget_from_tracers, stage_of)
    assert "transport" in STAGES and "untraced" in STAGES
    assert stage_of("node_rx", "transport") == "transport"
    assert stage_of("prod_tick", "transport") == "untraced"
    assert stage_of("verify_wait", "device") == "dispatch_wait"
    tracer, t = _manual_tracer()
    tracer.complete("prod_tick", "transport", 0.0, 0.1, produced=5)
    tracer.complete("node_rx", "transport", 0.01, 0.03, messages=4)
    tracer.complete("client_rx", "transport", 0.03, 0.04, messages=1)
    _span(tracer, t, "propagate_process", "propagate", 0.012, 0.028,
          n=3, frm="Beta")
    _span(tracer, t, "propagate_auth_single", "propagate", 0.014, 0.02)
    _span(tracer, t, "verify_wait", "device", 0.015, 0.019, n=1)
    _span(tracer, t, "batch_apply", "execute", 0.05, 0.07, batch_size=5)
    tracer.complete("transport_flush", "transport", 0.09, 0.1, frames=2)
    for report in (budget_from_tracers([tracer]),
                   budget_from_chrome(chrome_trace([tracer]))):
        ms = report["stage_ms_per_node"]
        assert ms["transport"] == pytest.approx(4 + 10 + 10, abs=0.01)
        assert ms["propagate"] == pytest.approx(10 + 2, abs=0.01)
        assert ms["dispatch_wait"] == pytest.approx(4, abs=0.01)
        assert ms["execute"] == pytest.approx(20, abs=0.01)
        assert ms["untraced"] == pytest.approx(100 - 60, abs=0.01)
        assert sum(ms.values()) == pytest.approx(100, abs=0.01)


def test_budget_from_chrome_matches_live_tracers(tdir):
    """The exported-file path (scripts/trace_budget) and the live
    path (bench.py) agree on the same spans."""
    from plenum_tpu.observability.budget import (
        budget_from_chrome, budget_from_tracers)
    tracer, t = _manual_tracer()
    _span(tracer, t, "fused_dispatch", "device", 0.01, 0.02)
    _span(tracer, t, "batch_apply", "execute", 0.0, 0.05, batch_size=4)
    _span(tracer, t, "commit_batch", "3pc", 0.1, 0.12)
    live = budget_from_tracers([tracer])
    doc = chrome_trace([tracer])
    from_file = budget_from_chrome(doc)
    assert from_file == live


def test_trace_budget_cli(tdir):
    """scripts/trace_budget on an exported dump: table mode, --json
    mode, and the metrics_stats missing-file convention."""
    import subprocess
    import sys as _sys
    tracer, t = _manual_tracer()
    _span(tracer, t, "batch_apply", "execute", 0.0, 0.05, batch_size=4)
    path = export_chrome_trace([tracer], os.path.join(tdir, "t.json"))
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "trace_budget")
    out = subprocess.run([_sys.executable, script, path],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "execute" in out.stdout and "ordered_reqs=4" in out.stdout
    outj = subprocess.run([_sys.executable, script, path, "--json"],
                          capture_output=True, text=True)
    assert outj.returncode == 0
    report = json.loads(outj.stdout)
    assert report["ordered_reqs"] == 4
    assert report["host_ms_per_ordered_req"]["execute"] > 0
    # missing file: clean exit with a message (metrics_stats convention)
    miss = subprocess.run(
        [_sys.executable, script, os.path.join(tdir, "nope.json"),
         "--json"], capture_output=True, text=True)
    assert miss.returncode == 0
    assert "error" in json.loads(miss.stdout)


def test_trace_budget_merges_a_session_directory(tdir):
    """A host trace session leaves one dump per node beside the
    daemon's: given the directory, trace_budget merges the node dumps
    (each numbered its own pid 1) and leaves the daemon's out."""
    import subprocess
    import sys as _sys
    from plenum_tpu.observability.export import merge_trace_documents
    docs = []
    for name, apply_s in (("Alpha", 0.05), ("Beta", 0.03)):
        tracer, t = _manual_tracer(name)
        _span(tracer, t, "batch_apply", "execute", 0.0, apply_s,
              batch_size=4)
        tracer.complete("node_rx", "transport", 0.1, 0.11, messages=2)
        path = export_chrome_trace([tracer], os.path.join(
            tdir, "node_%s_spans.json" % name))
        with open(path) as f:
            docs.append(json.load(f))
    daemon, t = _manual_tracer("verify-daemon")
    _span(daemon, t, "device_verify", "device", 0.0, 9.0, unique=600)
    export_chrome_trace([daemon], os.path.join(tdir, "daemon_spans.json"))
    merged = merge_trace_documents(docs)
    assert sorted(merged["metadata"]) == ["Alpha", "Beta"]
    assert {e["pid"] for e in merged["traceEvents"]} == {1, 2}
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "trace_budget")
    out = subprocess.run([_sys.executable, script, tdir, "--json"],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert (report["nodes"], report["ordered_reqs"]) == (2, 4)
    ms = report["stage_ms_per_node"]
    assert ms["execute"] == pytest.approx(40.0, abs=0.1)
    assert ms["transport"] == pytest.approx(10.0, abs=0.1)
    assert ms["dispatch_wait"] == 0.0


# ------------------------------------------------------- dual clocks


def test_clock_pair_samples_both_injected_clocks():
    perf = [10.0]
    wall = [1600000000.0]
    tracer = Tracer("n1", clock=lambda: perf[0],
                    wall_clock=lambda: wall[0])
    assert tracer.clock_pair() == (10.0, 1600000000.0)
    perf[0], wall[0] = 11.5, 1600000001.5
    p, w = tracer.clock_pair()
    assert (p, w) == (11.5, 1600000001.5)
    assert isinstance(p, float) and isinstance(w, float)


def test_clock_pair_defaults_to_perf_and_wall_time():
    p, w = Tracer("n1").clock_pair()
    # perf_counter is process-relative, wall is epoch-scale — the pair
    # is exactly what lets file-mode consumers re-anchor timelines
    assert w > 1e9 > p >= 0.0


def test_null_tracer_clock_pair_is_free_and_zero():
    assert NullTracer().clock_pair() == (0.0, 0.0)
