"""readers/node_cpu.py and the six metrics of the node's CPU account on
hand-made node dumps (fixtures/node_spans_small.json, with `cpu`
counter samples, `cpu_us` arguments and `wire_rx` spans added): the
interpolation of the samples at the window's edges, the loop thread's
residual, the off-thread and off-CPU times, the account closing on the
process's own CPU, nothing read from a dump without them, and wire_rx
spans that leave every older reading as it was.

    python -m pytest benchmark/tests/test_node_cpu.py -q
"""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from readers import node_cpu, node_spans, span_args  # noqa: E402


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


FIXTURE = load(HERE, "fixtures", "node_spans_small.json")
WRITES = FIXTURE["window"]["confirmed_writes"]          # 5, in [1 s, 2 s]
NEW = ("node_wire_rx_ms_per_write", "node_rx_sigs_per_write",
       "node_loop_cpu_ms_per_write", "node_offthread_cpu_ms_per_write",
       "node_offcpu_ms_per_write", "device_idle_nodes_rx_pct")
CPU = ("node_loop_cpu_ms_per_write", "node_offthread_cpu_ms_per_write",
       "node_offcpu_ms_per_write")


def spec_of(metric):
    return load(HERE, "metrics", metric + ".json")


def read(metric, run):
    spec = spec_of(metric)
    reader = {"node_cpu": node_cpu, "node_spans": node_spans,
              "span_args": span_args}[spec["reader"]]
    return reader.read(spec, run)


class Op:
    def __init__(self, valid, done):
        self.valid, self.done = valid, done


def run_on(tmp_path, docs):
    for name, doc in docs.items():
        with open(tmp_path / ("node_%s_spans.json" % name), "w") as f:
            json.dump(doc, f)
    win = FIXTURE["window"]
    released = [Op(True, 1.5)] * WRITES + [Op(True, None),
                                           Op(False, 1.2)]
    return {"t0": win["t0"], "t1": win["t1"], "released": released,
            "spans_file": str(tmp_path / "daemon_spans.json"),
            "side": {}, "profile_dir": str(tmp_path / "none"),
            "cache": {}}


def sample(ts, thread_s, process_s):
    return {"name": "cpu", "ph": "C", "pid": 1, "tid": 1, "ts": ts,
            "args": {"thread_s": thread_s, "process_s": process_s}}


def span(name, cat, ts, dur, **args):
    return {"name": name, "cat": cat, "ph": "X", "pid": 1, "tid": 1,
            "ts": ts, "dur": dur, "args": args}


# samples around both edges of the window [1 s, 2 s]: at t0 thread_s
# 1.1 and process_s 1.7, at t1 2.1 and 3.7 (halfway in each case)
SAMPLES = [sample(900000, 1.0, 1.5), sample(1100000, 1.2, 1.9),
           sample(1500000, 1.6, 2.7), sample(1900000, 2.0, 3.5),
           sample(2100000, 2.2, 3.9)]


def cpu_dump(samples=True, args=True):
    """A dump of ticks and frames with CPU times: in the window a tick of
    400 ms with 300 ms on a core and a frame of 60 ms with 50 ms; a tick
    before and one after the window, and a span of another name that
    carries cpu_us, count for nothing."""
    doc = copy.deepcopy(FIXTURE)
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if e.get("ph") in ("M", "C")]
    cpu = (lambda us: {"cpu_us": us}) if args else (lambda us: {})
    doc["traceEvents"] += [
        span("prod_tick", "transport", 950000, 20000, produced=1,
             **cpu(20000)),
        span("prod_tick", "transport", 1100000, 400000, produced=9,
             **cpu(300000)),
        span("node_rx", "transport", 1100100, 1000, messages=1,
             **cpu(900)),
        span("wire_rx", "wire", 1600000, 60000, messages=1, sigs=0,
             bytes=300, **cpu(50000)),
        span("prod_tick", "transport", 2050000, 20000, produced=1,
             **cpu(20000)),
    ]
    if samples:
        doc["traceEvents"] += copy.deepcopy(SAMPLES)
    return doc


def test_samples_are_read_at_the_window_edges_between_two_samples():
    series = sorted((s["ts"], s["args"]["thread_s"], s["args"]["process_s"])
                    for s in SAMPLES)
    assert node_cpu.at(series, 1000000, 1) == pytest.approx(1.1)
    assert node_cpu.at(series, 1000000, 2) == pytest.approx(1.7)
    assert node_cpu.at(series, 2000000, 1) == pytest.approx(2.1)
    assert node_cpu.at(series, 2000000, 2) == pytest.approx(3.7)
    assert node_cpu.at(series, 1500000, 1) == 1.6        # on a sample
    assert node_cpu.at(series, 1200000, 1) == pytest.approx(1.3)
    with pytest.raises(ValueError, match="do not reach"):
        node_cpu.at(series, 800000, 1)
    with pytest.raises(ValueError, match="do not reach"):
        node_cpu.at(series, 2200000, 2)


def test_a_window_the_samples_do_not_cover_fails_the_run(tmp_path):
    doc = cpu_dump()
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if not (e["name"] == "cpu" and e["ts"] > 2e6)]
    run = run_on(tmp_path, {"Alpha": doc})
    with pytest.raises(ValueError, match="do not reach"):
        read("node_loop_cpu_ms_per_write", run)


def test_loop_residual_offthread_and_offcpu(tmp_path):
    run = run_on(tmp_path, {"Alpha": cpu_dump()})
    # thread_s 2.1 - 1.1 = 1 s; in the window's tick and frame 0.35 s
    assert read("node_loop_cpu_ms_per_write", run) \
        == pytest.approx((1.0 - 0.35) * 1e3 / WRITES)
    # (3.7 - 2.1) - (1.7 - 1.1) = 1 s on the other threads
    assert read("node_offthread_cpu_ms_per_write", run) \
        == pytest.approx(1.0 * 1e3 / WRITES)
    # 100 ms of the tick and 10 ms of the frame off a core
    assert read("node_offcpu_ms_per_write", run) \
        == pytest.approx(110.0 / WRITES)


def test_nodes_are_summed(tmp_path):
    beta = cpu_dump()
    beta["metadata"] = {"Beta": beta["metadata"].pop("Alpha")}
    for e in beta["traceEvents"]:
        if e["name"] == "cpu":                  # Beta's thread: half
            e["args"]["thread_s"] /= 2
    run = run_on(tmp_path, {"Alpha": cpu_dump(), "Beta": beta})
    assert read("node_loop_cpu_ms_per_write", run) \
        == pytest.approx((1.0 + 0.5 - 0.7) * 1e3 / WRITES)
    assert read("node_offthread_cpu_ms_per_write", run) \
        == pytest.approx((1.0 + 1.5) * 1e3 / WRITES)
    assert read("node_offcpu_ms_per_write", run) \
        == pytest.approx(220.0 / WRITES)


def test_the_account_closes_on_the_process_cpu(tmp_path):
    """cpu_us of the ticks and frames, the loop's residual and the other
    threads add up to the processes' own CPU over the window."""
    run = run_on(tmp_path, {"Alpha": cpu_dump()})
    in_spans_ms = (300000 + 50000) / 1e3 / WRITES
    total = in_spans_ms + read("node_loop_cpu_ms_per_write", run) \
        + read("node_offthread_cpu_ms_per_write", run)
    assert total == pytest.approx((3.7 - 1.7) * 1e3 / WRITES)


@pytest.mark.parametrize("samples, args, reads", [
    (False, False, set()),            # the parent's dump
    (True, False, {"node_offthread_cpu_ms_per_write"}),
    (False, True, {"node_offcpu_ms_per_write"}),
    (True, True, set(CPU)),
])
def test_nothing_is_read_without_samples_or_arguments(tmp_path, samples,
                                                      args, reads):
    run = run_on(tmp_path, {"Alpha": cpu_dump(samples, args)})
    assert {m for m in CPU if read(m, run) is not None} == reads


def test_no_dump_and_the_parents_dump_read_none_of_the_six(tmp_path):
    assert all(read(m, run_on(tmp_path, {})) is None for m in NEW)
    run = run_on(tmp_path, {"Alpha": FIXTURE})
    run["side"] = {"profile": [{"start": 1.0, "stop": 1.02}]}
    run["cache"]["device_trace"] = {
        "anchor_ns": 5, "busy_s": 0.001, "window_s": 0.02,
        "intervals_ns": [[5 + 2000000, 5 + 3000000]]}
    assert {m: read(m, run) for m in NEW} == dict.fromkeys(NEW)
    # what node_spans would read for the two guarded ones: a number
    assert node_spans.read(spec_of("node_wire_rx_ms_per_write"), run) == 0
    assert node_spans.read(spec_of("device_idle_nodes_rx_pct"), run) == 0


def with_frames(doc):
    """The fixture with frames taken in between its ticks (before,
    inside and after the window): the read loop runs between ticks."""
    doc = copy.deepcopy(doc)
    doc["traceEvents"] += [
        span("wire_rx", "wire", 990000, 300, messages=3, sigs=1,
             bytes=900, cpu_us=250.0),
        span("wire_rx", "wire", 1012000, 2000, messages=40, sigs=1,
             bytes=9000, cpu_us=1800.0),
        span("wire_rx", "wire", 1400000, 150, messages=1, sigs=0,
             bytes=400, cpu_us=120.5),
        span("wire_rx", "wire", 1600000, 350, messages=2, sigs=1,
             bytes=700, cpu_us=300.0),
        span("wire_rx", "wire", 2050000, 500, messages=5, sigs=1,
             bytes=1500, cpu_us=400.0),
    ]
    return doc


def test_frames_leave_every_older_reading_as_it_was(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    before = run_on(tmp_path / "a", {"Alpha": FIXTURE})
    after = run_on(tmp_path / "b", {"Alpha": with_frames(FIXTURE)})
    for metric, want in FIXTURE["expected"]["metrics"].items():
        spec = spec_of(metric)
        assert node_spans.read(spec, after) == node_spans.read(
            spec, before) == pytest.approx(want), metric
    for metric in ("node_transport_ms_per_write",
                   "node_untraced_ms_per_write"):
        assert read(metric, after) == read(metric, before)
    # the frames' own readings: the three that start in the window
    assert read("node_wire_rx_ms_per_write", after) \
        == pytest.approx((2000 + 150 + 350) / 1e3 / WRITES)
    assert read("node_rx_sigs_per_write", after) == pytest.approx(2 / WRITES)


def test_device_idle_share_while_a_node_takes_in_frames(tmp_path):
    run = run_on(tmp_path, {"Alpha": with_frames(FIXTURE)})
    spec = spec_of("device_idle_nodes_rx_pct")
    assert read("device_idle_nodes_rx_pct", run) is None   # no trace
    anchor = 77000000000
    run["side"] = {"profile": [{"start": 1.0, "stop": 1.02}]}
    run["cache"]["device_trace"] = {
        "anchor_ns": anchor, "busy_s": 0.001, "window_s": 0.02,
        "intervals_ns": [[anchor + 2000000, anchor + 3000000]]}
    # idle: 19 ms of the 20 ms bracket; the frame at 1.012-1.014 s is
    # 2 ms of it
    assert node_cpu.read(spec, run) == pytest.approx(100.0 * 2 / 19)


def test_declared_as_six_new_per_layer_metrics():
    bench = load(os.path.dirname(HERE), "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(NEW)
    layers = {
        "node_wire_rx_ms_per_write": ("node transport (network/stack.py)",
                                      "program_span", "ms/req"),
        "node_rx_sigs_per_write": ("node transport (network/stack.py)",
                                   "program_counter", "sigs/req"),
        "node_loop_cpu_ms_per_write": (entries["node_cpu_ms_per_write"][
            "layer"], "program_counter", "ms/req"),
        "node_offthread_cpu_ms_per_write": (entries[
            "node_cpu_ms_per_write"]["layer"], "program_counter", "ms/req"),
        "node_offcpu_ms_per_write": (entries["node_cpu_ms_per_write"][
            "layer"], "program_span", "ms/req"),
        "device_idle_nodes_rx_pct": (entries["device_idle_nodes_busy_pct"][
            "layer"], "program_span", "%"),
    }
    for name, (layer, source, unit) in layers.items():
        m = entries[name]
        assert (m["layer"], m["source"], m["unit"], m["moves"],
                m["better"]) == (layer, source, unit, "write_p95_ms",
                                 "lower")
        assert "workloads" not in m
