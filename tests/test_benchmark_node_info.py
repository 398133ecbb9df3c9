"""benchmark/readers/node_info.py (ISSUE 30) on hand-made validator-info
dumps: the median over the nodes whose `Node_info` holds the field,
None where no node's does (a program older than `Genesis_load`, or a
pool of restarted nodes), and the metric that reads it as
BENCHMARK.json declares it.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from readers import node_info  # noqa: E402

with open(os.path.join(BENCH, "metrics", "node_genesis_load_s.json")) as f:
    SPEC = json.load(f)


def report(seconds=None, txns=100009):
    out = {"Name": "N", "Ledger_sizes": {"domain": txns}, "Device_mesh": {}}
    if seconds is not None:
        out["Genesis_load"] = {"txns": txns, "seconds": seconds}
    return out


@pytest.mark.parametrize("seconds,want", [
    ([9.5, 8.25, 10.0, 9.0], 9.25),         # on every node
    ([9.5, None, 10.0, None], 9.75),        # on some: two restarted
    ([None, 0.0021, None, None], 0.0021),
    ([None, None, None, None], None),       # on none: an older program
    ([], None)])
def test_median_over_the_nodes_that_state_it(seconds, want):
    run = {"reports_before": {"Node%d" % i: report(s)
                              for i, s in enumerate(seconds)}}
    assert node_info.read(SPEC, run) == want


@pytest.mark.parametrize("run", [{}, {"reports_before": None},
                                 {"reports_before": {"A": {}}},
                                 {"reports_before": {
                                     "A": {"Genesis_load": None}}}])
def test_nothing_to_read_is_none(run):
    assert node_info.read(SPEC, run) is None


def test_another_key_of_the_same_field():
    run = {"reports_before": {"A": report(1.0, 15), "B": report(2.0, 15)}}
    assert node_info.read({"field": "Genesis_load", "key": "txns"},
                          run) == 15


def test_the_metric_is_declared_and_found_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {m["name"]: m for m in bench["per_layer"]}[
        "node_genesis_load_s"]
    assert entry == {
        "name": "node_genesis_load_s", "unit": "s", "better": "lower",
        "source": "program_counter", "layer": "node host path: start",
        "moves": "setup_s"}
    assert SPEC["reader"] == "node_info"
    assert (SPEC["field"], SPEC["key"]) == ("Genesis_load", "seconds")
