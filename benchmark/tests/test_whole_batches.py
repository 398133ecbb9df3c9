"""Whole-batch traffic (workloads/write-whole.json, owners-whole.json):
a burst is exactly two whole 3PC batches, so no write waits out
Max3PCBatchWait and write_p95_ms is the pool's own time. What the two
cells rest on, held here:

  - the makers' arithmetic: with corrupted_every 101 ANY 2,020
    consecutive writes of a stream hold exactly 20 corrupted ones, the
    two kinds alternating, and 2,000 valid ones;
  - the schedule: 9 bursts of 2,020 at 0, 2.9 ... 23.2 s of a 30 s
    window, 18,000 valid writes after the probe and one warm-up burst
    (run.warm_bursts, asked for by the traffic file's `warm_up_bursts`)
    are taken off the front; the older traffic files ask for none;
  - BENCHMARK.json names only cells and files that exist;
  - `busiest_min` of readers/span_args.py on a hand-made dump;
  - the program's batching at small size: a four-node in-process pool
    with Max3PCBatchSize 10 cuts a burst of 20 valid writes into two
    batches of 10 and orders both before Max3PCBatchWait has passed on
    its clock, and holds the 21st of a burst of 21 until it has.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_whole_batches.py -q
"""
import copy
import functools
import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(HERE, "tests"))
sys.path.insert(2, ROOT)

import generators  # noqa: E402
import operations  # noqa: E402
import run as bench_run  # noqa: E402
import test_control  # noqa: E402
import traffic  # noqa: E402
from readers import span_args  # noqa: E402
from reference import pool as ref  # noqa: E402

from tests.test_node_e2e import SIM_EPOCH, pump  # noqa: E402

KINDS = ("nym_write", "nym_write_authors", "nym_rewrite_owners")
WHOLE = ("write-whole", "owners-whole")
IDENTITIES = 40
BURST, CORRUPTED, VALID = 2020, 20, 2000


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


BENCH = load(ROOT, "BENCHMARK.json")


def mix_of(kind, every=101):
    return {"kind": kind, "zipf_constant": 0.99, "corrupted_every": every}


@functools.lru_cache(maxsize=None)
def stream(kind, seed=2147483900, count=2 * BURST + 150):
    return operations.make(seed, count, mix_of(kind),
                           {"identities": IDENTITIES})


# ------------------------------------------------------------ the makers

@pytest.mark.parametrize("offset", [0, 1, 57, 100, BURST + 149])
@pytest.mark.parametrize("kind", KINDS)
def test_any_stretch_of_2020_is_two_whole_batches(kind, offset):
    """Offset 1 is the window's own: run.make_ops takes the first valid
    write off the front as the probe."""
    stretch = stream(kind)[offset:offset + BURST]
    assert len(stretch) == BURST
    assert sum(1 for _req, valid in stretch if valid) == VALID
    assert sum(1 for _req, valid in stretch if not valid) == CORRUPTED


@pytest.mark.parametrize("kind", KINDS)
def test_two_kinds_of_corruption_alternate(kind):
    seed = 2147483900
    if kind == "nym_write":
        keys = {"intruder": traffic.Signer(hashlib.sha256(
            b"%d-intruder" % seed).digest()).verkey}
        own = lambda req: traffic.Signer(  # noqa: E731
            traffic.trustee_seed(seed)).verkey
    else:
        signers = [traffic.identity(seed, i) for i in range(IDENTITIES)]
        keys = {s.identifier: s.verkey for s in signers}
        own = lambda req: keys[req["identifier"]]  # noqa: E731
    bad = [req for req, valid in stream(kind) if not valid]
    assert len(bad) == 2 * CORRUPTED + 1
    for n, req in enumerate(bad):
        assert not ref.signature_valid(req, own(req))
        signed_by = [name for name, vk in keys.items()
                     if name != req["identifier"]
                     and ref.signature_valid(req, vk)]
        # even: a flipped byte, nobody's signature; odd: another key's
        assert len(signed_by) == n % 2, (n, signed_by)
    assert all(ref.signature_valid(req, own(req))
               for req, valid in stream(kind)[:300] if valid)


# ---------------------------------------------------------- the schedule

@pytest.mark.parametrize("name", WHOLE)
def test_plan_is_nine_bursts_of_2020(name):
    mix = load(HERE, "workloads", name + ".json")
    assert mix["params"] == {"burst": BURST, "period_s": 2.9,
                             "quiet_tail_s": 6.0}
    assert mix["operations"]["corrupted_every"] == 101
    for seed in (1, 2 ** 31 + 5):
        due = generators.plan(mix, seed, 30.0)["due"]
        assert due == [k * 2.9 for k in range(9) for _ in range(BURST)]
    assert round(due[-1], 6) == 23.2


@pytest.mark.parametrize("name", WHOLE)
def test_a_window_is_18000_valid_writes_after_probe_and_warm_up(name):
    """run.make_ops as a run calls it, at a tenth of the size of a
    burst: the stream is the probe, one warm-up burst and the plan; the
    first valid write goes to the probe, the next burst's worth to
    set-up, and every burst of what is left is whole batches, as the
    warm-up burst is (it leaves nothing in the primary's queue)."""
    mix = load(HERE, "workloads", name + ".json")
    assert mix["warm_up_bursts"] == 1
    assert bench_run.warm_count(mix) == BURST
    mix["params"]["burst"] = 202           # 2 x 101: 200 valid, 2 corrupted
    plan = generators.plan(mix, 5, 30.0)
    ops = bench_run.make_ops(5, plan, mix, {"identities": IDENTITIES})
    assert len(ops) == 1 + 202 + 9 * 202
    probe = next(op for op in ops if op.valid)
    rest = [op for op in ops if op is not probe]
    warm, window = rest[:202], rest[202:]
    assert sum(op.valid for op in warm) == 200
    for b in range(9):
        burst = window[b * 202:(b + 1) * 202]
        assert sum(op.valid for op in burst) == 200
    assert 9 * VALID == 18000 and 9 * VALID / 30.0 == 600.0


@pytest.mark.parametrize("name", ["write-burst", "authors-burst",
                                  "owners-burst", "write-sat"])
def test_older_traffic_asks_for_no_warm_up(name):
    mix = load(HERE, "workloads", name + ".json")
    assert bench_run.warm_count(mix) == 0
    if mix["generator"] == "burst":
        plan = generators.plan(mix, 5, 30.0)
        assert len(plan["due"]) == 9 * 2048


class StubClient:
    """Answers every operation on the second drain after it was sent."""

    def __init__(self):
        self.sent, self.drains, self.seen = [], 0, {}

    def send(self, op):
        self.sent.append(op)
        self.seen[id(op)] = self.drains

    def drain(self):
        self.drains += 1
        return []

    def settled(self, op):
        return self.drains - self.seen[id(op)] >= 2


def test_warm_bursts_release_a_burst_at_once_and_wait_for_it():
    import asyncio
    import time
    from client import Op
    ops = [Op({"reqId": i}, b"", True) for i in range(7)]
    client = StubClient()
    asyncio.run(bench_run.warm_bursts(client, ops, 3,
                                      time.monotonic() + 30))
    assert client.sent == ops
    # three bursts (3, 3, 1), each waited for before the next goes out
    assert [client.seen[id(op)] for op in ops] == [0, 0, 0, 2, 2, 2, 4]
    assert len({op.due for op in ops[:3]}) == 1 and ops[3].due > ops[0].due
    with pytest.raises(RuntimeError, match="never settled"):
        asyncio.run(bench_run.warm_bursts(StubClient(), ops, 3,
                                          time.monotonic() - 1))


@pytest.mark.parametrize("metric, want", [
    ("daemon_device_share_pct", 100.0 * 600 / 1000),
    ("daemon_items_per_launch", 600 / 2)])
def test_daemon_counters_start_at_the_window(metric, want):
    """After a warm-up burst `drive` puts the daemon's counters as they
    then stand (run.daemon_counters) into `warm`, and the reader takes
    them off the final line: the 4,096 warm-up launches, the probe and
    the warm-up burst are in neither metric."""
    from readers import daemon_stats
    run = {"daemon_stats": {"device_items": 2 * 4096 + 300 + 600,
                            "device_launches": 2 + 1 + 2,
                            "host_items": 1 + 100 + 400},
           "warm": {"first_launch_s": 2.2, "device_items": 2 * 4096 + 300,
                    "device_launches": 3, "host_items": 101}}
    assert daemon_stats.read(load(HERE, "metrics", metric + ".json"),
                             run) == pytest.approx(want)


# ------------------------------------------------------- BENCHMARK.json

@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_has_its_files(cell):
    entry = {w["name"]: w for w in BENCH["workloads"]}[cell]
    config = {c["name"]: c for c in BENCH["configs"]}[entry["config"]]
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    mix = load(HERE, "workloads", entry["traffic"] + ".json")
    assert os.path.isfile(os.path.join(
        HERE, "generators", mix["generator"] + ".py"))
    assert os.path.isfile(os.path.join(
        HERE, "operations", mix["operations"]["kind"] + ".py"))
    if operations.uses_genesis(mix["operations"]):
        assert load(ROOT, config["file"])["genesis"]["identities"] > 1
    assert entry["chips"] == 1 and len(entry["why"]) <= 200


def test_metrics_name_cells_and_files_that_exist():
    cells = {w["name"] for w in BENCH["workloads"]}
    assert len(cells) == len(BENCH["workloads"]) == 6
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    for group in ("end_to_end", "per_layer"):
        for m in BENCH[group]:
            assert set(m.get("workloads", ())) <= cells, m["name"]
            spec = load(HERE, "metrics", m["name"] + ".json")
            assert os.path.isfile(os.path.join(
                HERE, "readers", spec["reader"] + ".py")), m["name"]
            if group == "per_layer":
                assert m["moves"] in end_to_end
    by_name = {m["name"]: m for g in ("end_to_end", "per_layer")
               for m in BENCH[g]}
    whole = ["pool7-write-whole", "pool4-owners-whole"]
    burst = ["pool7-write-burst", "pool4-write-burst",
             "pool4-authors-burst", "pool4-owners-burst"]
    # the tail and what moves it: one name and one bound in all six
    # cells (the same readers read the same thing in both kinds)
    for name in ("write_p95_ms", "gen_late_p95_ms", "write_p50_ms"):
        assert by_name[name]["workloads"] == burst + whole
    assert by_name["smallest_3pc_batch"]["workloads"] == whole
    assert by_name["write_p95_ms"]["bound"] == 0.15
    assert by_name["smallest_3pc_batch"]["moves"] == "write_p95_ms"
    # a per-layer metric restricted to some cells moves an end-to-end
    # metric that each of them reports
    for m in BENCH["per_layer"]:
        moved = by_name[m["moves"]]
        if "workloads" in moved:
            assert set(m.get("workloads", cells)) <= set(
                moved["workloads"]), m["name"]


@pytest.mark.parametrize("cell, has", [
    ("pool7-write-whole", True), ("pool4-owners-whole", True),
    ("pool7-write-burst", False), ("pool4-owners-burst", False)])
def test_smallest_3pc_batch_is_read_in_the_whole_cells_only(cell, has):
    names = [m["name"] for m in bench_run.Cell(cell).metrics("per_layer")]
    assert ("smallest_3pc_batch" in names) is has
    sibling = cell.replace("whole", "burst")
    # a -whole cell reports what its sibling reports, and this beside
    assert set(names) - {"smallest_3pc_batch"} == {
        m["name"] for m in bench_run.Cell(sibling).metrics("per_layer")}
    assert [m["name"] for m in bench_run.Cell(cell).metrics(
        "end_to_end")] == ["write_tput", "write_p95_ms", "setup_s"]


# ------------------------------------------- the reader on a hand-made dump

FIXTURE = load(HERE, "fixtures", "node_spans_small.json")
SPEC = load(HERE, "metrics", "smallest_3pc_batch.json")


def dump(name, tick_us, batches):
    """One node's dump: a `prod_tick` of `tick_us` inside the window
    (1 s to 2 s) and `exec_validate` spans with the given start (us)
    and batch_size."""
    meta = copy.deepcopy(FIXTURE["metadata"]["Alpha"])
    events = [{"name": "prod_tick", "cat": "transport", "ph": "X",
               "pid": 1, "tid": 1, "ts": 1100000, "dur": tick_us,
               "args": {"produced": 1}}]
    for ts, size in batches:
        args = {} if size is None else {"batch_size": size, "lanes": 3}
        events.append({"name": "exec_validate", "cat": "execute",
                       "ph": "X", "pid": 1, "tid": 1, "ts": ts,
                       "dur": 50, "args": args})
    return {"traceEvents": events, "metadata": {name: meta}}


def run_with(tmp_path, docs):
    for name, doc in docs.items():
        with open(tmp_path / ("node_%s_spans.json" % name), "w") as f:
            json.dump(doc, f)
    return {"t0": 1.0, "t1": 2.0, "released": [],
            "spans_file": str(tmp_path / "daemon_spans.json"),
            "side": {}, "cache": {}}


@pytest.mark.parametrize("inside, want", [
    ([1000, 1000, 1000], 1000),      # the premise holds
    ([1000, 1000, 28], 28),          # a leftover was cut on the timer
    ([999, 1000], 999),
    ([], None),                      # nothing to read: the metric is left out
])
def test_busiest_min_is_the_busiest_nodes_smallest_batch(tmp_path, inside,
                                                         want):
    # Alpha is the busiest; its spans before and after the window, those
    # without the argument and every span of Beta's do not count
    alpha = dump("Alpha", 400000, [(950000, 5), (2100000, 3),
                                   (1500000, None)]
                 + [(1200000 + 1000 * i, n) for i, n in enumerate(inside)])
    beta = dump("Beta", 100000, [(1300000, 7)])
    run = run_with(tmp_path, {"Alpha": alpha, "Beta": beta})
    assert span_args.read(SPEC, run) == want
    median = dict(SPEC, quantity="busiest_median")
    if inside:
        assert span_args.read(median, run) >= want


def test_busiest_min_without_dumps_reads_nothing(tmp_path):
    assert span_args.read(SPEC, run_with(tmp_path, {})) is None


# ------------------------------ the batching both kinds of cell rest on

def pool_of_four(seed, kind):
    from plenum_tpu.common.config import Config
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork
    names = test_control.NAMES[:4]
    timer = MockTimer()
    timer.set_time(SIM_EPOCH)
    net = SimNetwork(timer, DefaultSimRandom(29))
    txns = test_control.genesis(
        seed, 0 if kind == "nym_write" else IDENTITIES)
    conf = Config(Max3PCBatchSize=10, Max3PCBatchWait=3)
    return timer, [Node(name, names, timer, net.create_peer(name),
                        config=conf, genesis_txns=txns)
                   for name in names], len(txns)


def sizes(nodes):
    from plenum_tpu.common.constants import AUDIT_LEDGER_ID
    return ([n.domain_ledger.size for n in nodes],
            [n.db_manager.get_ledger(AUDIT_LEDGER_ID).size for n in nodes])


def queued(nodes):
    """Requests waiting in the primary's proposal queue."""
    primary, = [n for n in nodes if n.replica.ordering._is_primary()]
    return sum(len(q) for q in
               primary.replica.ordering.requestQueues.values())


@pytest.mark.parametrize("kind", ["nym_write", "nym_rewrite_owners"])
@pytest.mark.parametrize("every, left_over", [(21, 0), (22, 1)])
def test_whole_batches_are_ordered_without_the_timer(kind, every,
                                                     left_over):
    """The cells' arithmetic at a hundredth: Max3PCBatchSize 10, bursts
    of `every` writes of which one is corrupted. 21: 20 valid, two
    whole batches, ordered with Max3PCBatchWait (3 s) never reached on
    the pool's clock. 22: 21 valid, and the 21st sits in the primary's
    queue until the timer (write-burst.json's 28)."""
    seed = 2147483900
    timer, nodes, genesis_size = pool_of_four(seed, kind)
    made = operations.make(seed, 2 * every, mix_of(kind, every),
                           {"identities": IDENTITIES})
    pump(timer, nodes, 1.0)                       # a primary is there
    _domain, audit0 = sizes(nodes)
    for burst in (made[:every], made[every:]):
        assert sum(valid for _req, valid in burst) == 20 + left_over
        start = timer.get_current_time()
        before = sizes(nodes)
        for req, _valid in burst:
            for n in nodes:
                n.process_client_request(dict(req), "client")
        pump(timer, nodes, 2.5)
        assert timer.get_current_time() - start < 3
        domain, audit = sizes(nodes)
        # two batches of exactly ten, on every node, before the timer
        assert domain == [s + 20 for s in before[0]]
        assert audit == [s + 2 for s in before[1]]
        assert queued(nodes) == left_over
        pump(timer, nodes, 3.0)                   # past Max3PCBatchWait
        domain, audit = sizes(nodes)
        assert domain == [s + 20 + left_over for s in before[0]]
        assert audit == [s + 2 + left_over for s in before[1]]
        assert queued(nodes) == 0
    domain, audit = sizes(nodes)
    assert domain == [genesis_size + 2 * (20 + left_over)] * 4
    assert audit == [audit0[0] + 2 * (2 + left_over)] * 4
