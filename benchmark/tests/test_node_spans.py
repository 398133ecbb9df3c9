"""readers/node_spans.py on a small hand-made dump
(fixtures/node_spans_small.json: nested and sibling spans, ticks before
and after the window): the self times and metrics written beside it,
agreement with the program's own budget on the same spans, and the two
refusals (a ring that wrapped inside the window, a foreign clock).

    python -m pytest benchmark/tests/test_node_spans.py -q
"""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from readers import node_spans  # noqa: E402


class Op:
    def __init__(self, valid, done):
        self.valid, self.done = valid, done


with open(os.path.join(HERE, "fixtures", "node_spans_small.json")) as f:
    FIXTURE = json.load(f)
EXPECTED = FIXTURE["expected"]


def spec_of(metric):
    with open(os.path.join(HERE, "metrics", metric + ".json")) as f:
        return json.load(f)


def run_on(tmp_path, docs):
    """A traced run's record as the harness hands it to a reader, with
    `docs` ({node name: dump}) written beside the daemon's span file."""
    for name, doc in docs.items():
        with open(tmp_path / ("node_%s_spans.json" % name), "w") as f:
            json.dump(doc, f)
    win = FIXTURE["window"]
    released = [Op(True, 1.5)] * win["confirmed_writes"] + [
        Op(True, None), Op(True, 2.5), Op(False, 1.2)]
    return {"t0": win["t0"], "t1": win["t1"], "released": released,
            "spans_file": str(tmp_path / "daemon_spans.json"),
            "side": {}, "profile_dir": str(tmp_path / "none"),
            "cache": {}}


def renamed(doc, name):
    other = copy.deepcopy(doc)
    other["metadata"] = {name: other["metadata"].pop("Alpha")}
    return other


def test_no_dump_reads_nothing(tmp_path):
    run = run_on(tmp_path, {})
    for metric in EXPECTED["metrics"]:
        assert node_spans.read(spec_of(metric), run) is None


def test_self_times_of_the_hand_made_dump(tmp_path):
    run = run_on(tmp_path, {"Alpha": FIXTURE})
    by_stage = {}
    for (stage, _cat, _name), us in node_spans.window_self_times(
            run).items():
        by_stage[stage] = by_stage.get(stage, 0) + us
    assert by_stage == EXPECTED["self_us_by_stage"]
    for metric, want in EXPECTED["metrics"].items():
        assert node_spans.read(spec_of(metric), run) \
            == pytest.approx(want, rel=1e-9), metric


def test_nodes_are_summed_and_the_busiest_one_is_the_busy_share(tmp_path):
    quiet = renamed(FIXTURE, "Beta")
    quiet["traceEvents"] = [e for e in quiet["traceEvents"]
                            if not 1400000 <= e.get("ts", 0) <= 1600000]
    run = run_on(tmp_path, {"Alpha": FIXTURE, "Beta": quiet})
    want = EXPECTED["metrics"]
    # Beta is Alpha less tick B (2,000 us: 300 propagate, 800 transport,
    # 900 untraced, one inline authentication)
    assert node_spans.read(spec_of("node_intake_ms_per_write"), run) \
        == pytest.approx(2 * want["node_intake_ms_per_write"])
    assert node_spans.read(spec_of("node_propagate_ms_per_write"), run) \
        == pytest.approx(2 * want["node_propagate_ms_per_write"] - 0.06)
    assert node_spans.read(spec_of("node_single_auth_per_write"), run) \
        == pytest.approx(3 / 5)
    assert node_spans.read(spec_of("node_busy_pct"), run) \
        == pytest.approx(want["node_busy_pct"])


def test_agrees_with_the_programs_budget_on_the_same_spans(tmp_path):
    from plenum_tpu.observability.budget import STAGES, budget_from_chrome
    run = run_on(tmp_path, {"Alpha": FIXTURE})
    mine = {}
    for (stage, _cat, _name), us in node_spans.window_self_times(
            run).items():
        mine[stage] = mine.get(stage, 0) + us
    inside = dict(FIXTURE, traceEvents=[
        e for e in FIXTURE["traceEvents"]
        if e["ph"] != "X" or 1000000 <= e["ts"] <= 2000000])
    theirs = budget_from_chrome(inside)["stage_ms_per_node"]
    assert set(mine) <= set(STAGES)
    for stage in STAGES:
        assert theirs[stage] == pytest.approx(mine.get(stage, 0) / 1e3), \
            stage


def test_device_idle_share_while_a_node_works(tmp_path):
    want = EXPECTED["device_idle_nodes_busy_pct"]
    run = run_on(tmp_path, {"Alpha": FIXTURE})
    spec = spec_of("device_idle_nodes_busy_pct")
    assert node_spans.read(spec, run) is None        # no device trace
    anchor = 77000000000
    run["side"] = {"profile": [want["bracket"]]}
    run["cache"]["device_trace"] = {
        "anchor_ns": anchor, "busy_s": 0.001, "window_s": 0.02,
        "intervals_ns": [[anchor + a, anchor + b]
                         for a, b in want["busy_after_anchor_ns"]]}
    assert node_spans.read(spec, run) == pytest.approx(want["value"])


@pytest.mark.parametrize("fault, message", [
    ("wrapped", "wrapped inside the window"),
    ("foreign_clock", "the harness reads"),
    ("no_metadata", "no metadata"),
])
def test_a_dump_that_cannot_be_trusted_fails_the_run(tmp_path, fault,
                                                     message):
    doc = copy.deepcopy(FIXTURE)
    if fault == "wrapped":
        doc["metadata"]["Alpha"]["oldest_ts"] = 1000050
        doc["metadata"]["Alpha"]["stats"]["dropped"] = 12
    elif fault == "foreign_clock":
        doc["metadata"]["Alpha"]["clock"] = {
            "name": "injected", "implementation": "injected"}
    else:
        del doc["metadata"]
    run = run_on(tmp_path, {"Alpha": doc})
    with pytest.raises(ValueError, match=message):
        node_spans.read(spec_of("node_intake_ms_per_write"), run)
