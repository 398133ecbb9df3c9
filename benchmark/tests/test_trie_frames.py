"""metrics/exec_trie_nodes_per_write.json and exec_trie_framed_pct.json
through readers/span_args.py on hand-made node dumps: the arguments
`trie_nodes` and `trie_nodes_framed` of `fused_dispatch` spans that
start in the window, summed over every node per confirmed write and,
on the busiest node, framed over written; a dump whose spans lack the
arguments (a program older than them) reads nothing.

    python -m pytest benchmark/tests/test_trie_frames.py -q
"""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from readers import span_args  # noqa: E402


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


FIXTURE = load(HERE, "fixtures", "node_spans_small.json")
NODES = load(HERE, "metrics", "exec_trie_nodes_per_write.json")
FRAMED = load(HERE, "metrics", "exec_trie_framed_pct.json")


class Op:
    def __init__(self, valid, done):
        self.valid, self.done = valid, done


def dump(name, tick_us, windows):
    """One node's dump: a `prod_tick` of `tick_us` inside the window
    (1 s to 2 s) and a `fused_dispatch` span for each (start us, args)."""
    meta = copy.deepcopy(FIXTURE["metadata"]["Alpha"])
    events = [{"name": "prod_tick", "cat": "transport", "ph": "X",
               "pid": 1, "tid": 1, "ts": 1100000, "dur": tick_us,
               "args": {"produced": 1}}]
    for ts, args in windows:
        events.append({"name": "fused_dispatch", "cat": "device",
                       "ph": "X", "pid": 1, "tid": 1, "ts": ts,
                       "dur": 900, "args": dict(args, groups=1,
                                                batch_size=1000)})
    return {"traceEvents": events, "metadata": {name: meta}}


def run_with(tmp_path, docs, confirmed=2000):
    for name, doc in docs.items():
        with open(tmp_path / ("node_%s_spans.json" % name), "w") as f:
            json.dump(doc, f)
    released = [Op(True, 1.5)] * confirmed + [Op(True, None),
                                              Op(False, 1.2)]
    return {"t0": 1.0, "t1": 2.0, "released": released,
            "spans_file": str(tmp_path / "daemon_spans.json"),
            "side": {}, "cache": {}}


def flush(nodes, framed):
    return {"host_applied": 1000, "trie_nodes": nodes,
            "trie_nodes_framed": framed}


@pytest.mark.parametrize("alpha, beta, per_write, pct", [
    # every node wrote every node of its flushes in frames
    ([flush(2400, 2400), flush(2600, 2600)],
     [flush(2400, 2400), flush(2600, 2600)], 5.0, 100.0),
    # the busiest node (Alpha) framed half; Beta's share is not read
    ([flush(2000, 1000), flush(2000, 1000)],
     [flush(3000, 3000)], 3.5, 50.0),
    ([flush(1500, 0)], [flush(1500, 0)], 1.5, 0.0),
    # a program older than the arguments: both metrics left out
    ([{"host_applied": 1000}], [{"host_applied": 1000}], None, None),
])
def test_trie_metrics_on_a_hand_made_dump(tmp_path, alpha, beta,
                                          per_write, pct):
    # spans before and after the window do not count
    outside = [(950000, flush(99999, 0)), (2100000, flush(99999, 0))]
    docs = {"Alpha": dump("Alpha", 400000, outside + [
                (1200000 + 100000 * i, a) for i, a in enumerate(alpha)]),
            "Beta": dump("Beta", 100000, [
                (1300000 + 100000 * i, a) for i, a in enumerate(beta)])}
    run = run_with(tmp_path, docs)
    assert span_args.read(NODES, run) == per_write
    assert span_args.read(FRAMED, run) == pct


def test_without_dumps_neither_reads(tmp_path):
    run = run_with(tmp_path, {})
    assert span_args.read(NODES, run) is None
    assert span_args.read(FRAMED, run) is None


def test_declared_beside_exec_host_apply_pct():
    bench = load(os.path.dirname(HERE), "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, unit in (("exec_trie_nodes_per_write", "nodes"),
                       ("exec_trie_framed_pct", "%")):
        m = entries[name]
        assert m["unit"] == unit and "workloads" not in m
        assert (m["layer"], m["source"], m["moves"]) == (
            "node host path: execute", "program_counter", "write_p95_ms")
