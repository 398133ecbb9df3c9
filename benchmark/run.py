#!/usr/bin/env python3
"""One cell, once:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the deployment the cell's configuration names (verify daemon on
the chip, n node processes, this process as the client), warms up,
drives the cell's traffic for --seconds, waits for every answer due,
stops everything, checks the answers against the plain reference and
prints one JSON line last. See README.md for the layout and for the
builder-only modes (--tiny, --sweep, --seeds).

This process never initialises a JAX backend: the daemon owns the chip.
"""
import time
T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import check  # noqa: E402
import generators  # noqa: E402
import operations  # noqa: E402
import trace_reduce  # noqa: E402
import window  # noqa: E402
from client import Client, Op  # noqa: E402
from pool import (  # noqa: E402
    Daemon, Pool, Ports, Procs, ephemeral_range, in_thread,
    jax_backend_untouched, log, last_line, native_modules)

SETUP_BUDGET_S = 1100     # a first run compiles: 1200 s in all
DRAIN_BUDGET_S = 60
SECOND_TRACE_BY_S = 150   # a traced run this young may be made again


class NoResult(Exception):
    """The run cannot give a result line (no chip, no program, a
    process that died in set-up): its one line is the run's last word."""


class DiedInSetup(Exception):
    """A process the run started ended before the window opened."""

    def __init__(self, name: str, word: str, port=None):
        super().__init__("%s died in set-up: %s" % (
            name, word or "no last word"))
        self.name = name
        self.port = port      # the port it could not bind, if that is why


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json's workloads, with its files."""

    def __init__(self, name: str):
        self.bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise NoResult("no cell %r in BENCHMARK.json (have %s)"
                           % (name, sorted(cells)))
        self.entry = cells[name]
        self.name = name
        self.chips = self.entry["chips"]
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(ROOT, conf["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "workloads", self.entry["traffic"] + ".json"))

    def metrics(self, group: str):
        """The metrics of `end_to_end` or `per_layer` this cell reports:
        those without a `workloads` key, and those that list it."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


# ------------------------------------------------------------ one run

def warm_count(traffic_file: dict) -> int:
    """Operations a traffic file asks set-up to put through the pool
    before the window: `warm_up_bursts` bursts of its own `burst`
    (write-whole.json, owners-whole.json; the older files ask for none
    and run as they always did)."""
    return int(traffic_file.get("warm_up_bursts", 0)) \
        * int(traffic_file["params"].get("burst", 0))


def make_ops(seed: int, plan: dict, traffic_file: dict, genesis=None):
    """The stream in the order it is used: the probe (the first valid
    write), the warm-up bursts, the window's operations."""
    count = plan["max_ops"] if plan["kind"] == "closed" \
        else len(plan["due"])
    made = operations.make(seed, count + 1 + warm_count(traffic_file),
                           traffic_file["operations"], genesis)
    return [Op(req, Client.wire(req), valid) for req, valid in made]


async def warm_bursts(client, ops, burst: int, deadline: float) -> None:
    """Set-up: each burst released at once, as the window releases one,
    and waited for until every node has said its last word on every
    operation of it, so that the window starts on a pool that has
    applied, ordered and answered batches of the window's own size and
    whose primary's queue holds what the traffic's arithmetic leaves
    there (nothing, for whole-batch traffic)."""
    for lo in range(0, len(ops), burst):
        released = ops[lo:lo + burst]
        now = time.perf_counter()
        for op in released:
            op.due = now
            client.send(op)
        await window.drain(client, released, deadline)
        still = sum(1 for op in released if not client.settled(op))
        if still:
            raise RuntimeError("the pool never settled the warm-up "
                               "burst (%d open)" % still)


def daemon_counters(daemon) -> dict:
    """The running daemon's counters that readers/daemon_stats.py takes
    off its final line, as they stand now."""
    stats = daemon.stats_now()
    return {k: stats[k] for k in ("device_items", "device_launches",
                                  "host_items")}


async def watch(pool, daemon) -> None:
    """Set-up's watcher: twice a second, has a node or the daemon
    ended? The loops it runs beside (Client.connect, window.probe,
    warm_bursts) wait on the set-up budget alone."""
    while True:
        dead = pool.dead_nodes()
        if dead:
            raise DiedInSetup(dead[0], pool.last_word(dead[0]),
                              pool.failed_bind(dead[0]))
        if daemon.proc.poll() is not None:
            raise DiedInSetup("the daemon", last_line(
                os.path.join(daemon.dir, "daemon.err")))
        await asyncio.sleep(0.5)


async def watched(setup, pool, daemon):
    """Set-up raced against the watcher → set-up's result. The watcher
    is gone before this returns: nothing runs beside the window."""
    tasks = [asyncio.ensure_future(setup),
             asyncio.ensure_future(watch(pool, daemon))]
    try:
        await asyncio.wait(tasks, return_when=asyncio.FIRST_COMPLETED)
    finally:
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    # the watcher ends only by raising; a death wins over whatever the
    # set-up made of it
    for task in reversed(tasks):
        if not task.cancelled() and task.exception() is not None:
            raise task.exception()
    return tasks[0].result()


async def set_up(client, pool, daemon, ops, deadline, traffic_file):
    """Connect, probe, warm-up bursts, the nodes' reports → what the
    window starts from."""
    await client.connect(pool.base_dir, deadline)
    log("client connected to %d nodes" % len(pool.names))
    # the first valid write is the probe; the window's start there
    probe_op = next(op for op in ops if op.valid)
    await window.probe(client, probe_op, deadline)
    log("probe write ordered")
    rest = [op for op in ops if op is not probe_op]
    n_warm = warm_count(traffic_file)
    warm, rest = rest[:n_warm], rest[n_warm:]
    if warm:
        await warm_bursts(client, warm,
                          int(traffic_file["params"]["burst"]), deadline)
        log("%d warm-up operations settled" % len(warm))
        # the daemon's counter metrics start at the window, as every
        # other reading does: what it has verified so far is warm-up
        daemon.warm.update(daemon_counters(daemon))
    before = {"reports": pool.wait_reports(
        len(pool.genesis_domain_txns()) + 1 + sum(
            1 for op in warm if op.valid and op.answers),
        time.monotonic() + 10),
        "cpu_s": pool.cpu_seconds()}
    return probe_op, warm, rest, before


async def drive(pool, daemon, ops, plan, seconds, traced, marks_out,
                deadline, traffic_file):
    """Connect, probe, warm-up bursts, window, drain → the window's
    record."""
    client = Client(pool.names, pool.f)
    for op in ops:
        client.register(op)
    try:
        probe_op, warm, rest, before = await watched(
            set_up(client, pool, daemon, ops, deadline, traffic_file),
            pool, daemon)
        marks = []
        if traced:
            half = min(2.5, seconds / 4.0)
            marks = [(seconds / 2.0 - half, "profile_start"),
                     (seconds / 2.0 + half, "profile_stop")]

        def on_mark(label):
            marks_out[label] = time.perf_counter()
            daemon.signal_profile(label == "profile_start")

        setup_s = time.perf_counter() - T_PROCESS
        rec = await window.run_window(client, rest, plan, seconds,
                                      on_mark, marks)
        rec["cpu_s"] = (before["cpu_s"], pool.cpu_seconds())
        rec["setup_s"] = setup_s
        rec["reports_before"] = before["reports"]
        rec["drain_s"] = await window.drain(
            client, rec["released"], time.monotonic() + DRAIN_BUDGET_S)
        rec["probe_op"] = probe_op
        rec["warm_ops"] = warm
        rec["stray"] = client.stray
        rec["dead_links"] = client.dead_links()
        return rec
    finally:
        client.close()


def new_pool(cell, procs, workdir, seed, tiny, ports):
    """A fresh pool's files: keys, and the two genesis files with the
    configuration's identities, on 2n ports that `ports` has just shown
    free. They need no daemon, so `single` makes them while the daemon
    starts."""
    base_dir = tempfile.mkdtemp(prefix="pool_", dir=workdir)
    pool = Pool(procs, base_dir, cell.config, tiny, ports.base())
    pool.generate(seed)
    log("pool generated: %d domain genesis txns" % len(
        pool.genesis_domain_txns()))
    return pool


def start_pool(cell, daemon, procs, workdir, seed, seconds, tiny, ports,
               pool=None, made=None):
    """A fresh pool beside the daemon (which may still be warming up):
    generated unless it is handed in, configured, its nodes started,
    and the window's operations signed (or, handed in as `made`, the
    same requests with nothing sent yet) → (pool, plan, ops)."""
    pool = pool or new_pool(cell, procs, workdir, seed, tiny, ports)
    pool.write_config(daemon.info["port"])
    pool.start_nodes()
    if made:
        plan, ops = made[0], [Op(op.request, op.wire, op.valid)
                              for op in made[1]]
    else:
        plan = generators.plan(cell.traffic, seed, seconds)
        ops = make_ops(seed, plan, cell.traffic, cell.config.get("genesis"))
        log("%d operations signed" % len(ops))
    return pool, plan, ops


def finish_pool(pool, daemon, plan, ops, seconds, traced, deadline,
                traffic_file):
    """Warm-up bursts if the traffic file asks for them, window, drain,
    the nodes' last reports, nodes stopped → everything the readers and
    the check need. The daemon is left running."""
    marks = {}
    try:
        rec = asyncio.run(drive(pool, daemon, ops, plan, seconds, traced,
                                marks, deadline, traffic_file))
    except DiedInSetup:
        raise               # the caller logs the one tail that matters
    except BaseException:
        pool.log_tails()
        raise
    rec["marks"] = marks
    released = rec["released"]
    valid = [op for op in rec["warm_ops"] + released if op.valid]
    want = len(pool.genesis_domain_txns()) + 1 + sum(
        1 for op in valid if op.answers)
    rec["reports_after"] = pool.wait_reports(want, time.monotonic() + 30)
    rec["dead_nodes"] = pool.dead_nodes()
    pool.stop()
    rec["pool"] = pool
    rec["plan"] = plan
    rec["seconds"] = seconds
    return rec


def read_metrics(cell, group, run) -> dict:
    """Every metric of `end_to_end` or `per_layer` that this cell
    reports is a data file (metrics/<name>.json) naming a reader
    (readers/<reader>.py). A reader that finds nothing to read returns
    None and the metric is left out of the line; a reader that fails
    fails the run (a device kind that peaks.json does not hold must not
    pass as a run without a roofline)."""
    out = {}
    for m in cell.metrics(group):
        spec = load_json(os.path.join(HERE, "metrics", m["name"] + ".json"))
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(spec, run)
        if value is not None:
            out[m["name"]] = value
    return out


def gathered(rec, daemon_stats, side, daemon) -> dict:
    """What a traced run hands its readers, beside the window's record."""
    return dict(rec, daemon_stats=daemon_stats, side=side,
                warm=daemon.warm, spans_file=daemon.trace_file,
                profile_dir=daemon.profile_dir, ready=daemon.info,
                peaks_file=os.path.join(HERE, "peaks.json"), cache={})


def judge(rec, daemon, daemon_stats, tiny):
    pool = rec["pool"]
    ops = [rec["probe_op"]] + rec["warm_ops"] + rec["released"]
    obs = check.Observed(pool.names, pool.f, ops, rec["reports_after"],
                         daemon.info, daemon_stats,
                         ran_dry=rec["ran_dry"], tiny=tiny)
    genesis = pool.genesis_domain_txns()
    t = time.perf_counter()
    got = check.compare(obs, genesis)
    got["seconds"] = time.perf_counter() - t
    got["obs"] = obs
    got["genesis"] = genesis
    if rec["dead_nodes"] or rec["dead_links"]:
        got["values"]["unanswered_by_a_node"] += 1
        got["notes"]["dead"] = {"nodes": rec["dead_nodes"],
                                "links": rec["dead_links"]}
    if not jax_backend_untouched():
        got["values"]["daemon_faults"] += 1
        got["notes"]["parent"] = "the harness initialised a JAX backend"
    return got


def result_line(rec, metrics, units, daemon, side, got, device_extra,
                breakdown=None, notes=None) -> dict:
    released = rec["released"]
    valid = [op for op in released if op.valid]
    device = dict((daemon.info or {}).get("device") or {})
    line = {
        "correct": check.verdict(got["values"]),
        "attempted": len(released),
        "failed": sum(1 for op in valid if op.done is None),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in metrics.items()},
        "device": {"platform": device.get("platform"),
                   "kind": device.get("kind"),
                   "count": device.get("count"),
                   "memory_peak_bytes": side.get("memory_peak_bytes")},
        "host": {"cores": os.cpu_count()},
        "window": {"seconds": rec["t1"] - rec["t0"],
                   "drain_s": rec["drain_s"],
                   "refused_as_expected": sum(
                       1 for op in released if not op.valid and op.done),
                   "valid_also_refused": sum(
                       1 for op in valid if op.refused),
                   "check_s": got["seconds"],
                   "warm": daemon.warm},
    }
    line["device"].update(device_extra or {})
    if breakdown:
        line["breakdown"] = breakdown
    if notes:
        # what the harness had to put right on the way (README.md); a
        # run with nothing to say has no such key
        line["notes"] = notes
    line["compared"] = check.table(got["values"])
    return line


def print_checks(got) -> None:
    if got["notes"].get("daemon_problems") or not check.verdict(
            got["values"]):
        log("notes: %s" % json.dumps(got["notes"], default=str)[:6000])
    for name, (value, limit) in check.table(got["values"]).items():
        print("compared %-22s %8s  limit %s" % (name, value, limit),
              file=sys.stderr, flush=True)


def units_of(cell) -> dict:
    return {m["name"]: m["unit"]
            for g in ("end_to_end", "per_layer") for m in cell.bench[g]}


def launch_daemon(cell, procs, workdir, tiny, traced):
    daemon = Daemon(procs, workdir, cell.config, tiny, traced)
    daemon.start()
    return daemon


def start_daemon(cell, procs, workdir, tiny, traced):
    return daemon_ready(
        launch_daemon(cell, procs, workdir, tiny, traced), cell, tiny)


def daemon_ready(daemon, cell, tiny):
    """Wait for a launched daemon; it has to hold the chip(s) the cell
    asks for."""
    try:
        info = daemon.wait_ready(timeout=300)
    except RuntimeError:
        daemon.log_tail(15)
        raise NoResult("the verify daemon did not start: no accelerator "
                       "here, or another process holds it")
    device = info.get("device") or {}
    log("host cores %s; local ports %s; daemon device %s; compile cache %s"
        % (os.cpu_count(), ephemeral_range(), json.dumps(device),
           info.get("compile_cache")))
    if not tiny and (device.get("platform") != "tpu"
                     or (device.get("count") or 0) < cell.chips):
        raise NoResult("the cell asks for %d tpu chip(s), the daemon got "
                       "%s" % (cell.chips, device))
    return daemon


def pool_through_setup(cell, daemon, procs, workdir, args, ports, notes,
                       traced, deadline, pool=None, between=None):
    """start_pool, `between` (once: `attempt` joins the daemon's warm-up
    there, which ran beside the node starts) and finish_pool, with the
    one fault of set-up that is the harness's own put right once: a node
    whose last word is a failed bind (its port was free when `ports`
    tried it and taken when the node did) is started again with all the
    others on a fresh base dir and fresh ports, the same seed, requests
    and plan. Any other death before the window, or a second failed
    bind, ends the run."""
    made = None
    while True:
        pool, plan, ops = start_pool(
            cell, daemon, procs, workdir, args.seed, args.seconds,
            args.tiny, ports, pool, made)
        if between is not None:
            between()
            between = None
        try:
            return finish_pool(pool, daemon, plan, ops, args.seconds,
                               traced, deadline, cell.traffic)
        except DiedInSetup as death:
            if death.name in pool.names:
                pool.log_tails([death.name])
            else:
                daemon.log_tail()
            pool.stop()
            if death.port is None or "setup_restarts" in notes:
                raise NoResult(str(death))
            log("%s could not bind port %d: one fresh start of the pool"
                % (death.name, death.port))
            notes["setup_restarts"] = 1
            notes["bind_failed_port"] = death.port
            made, pool = (plan, ops), None


def trace_is_missing(run, tiny) -> bool:
    """A traced run has to have read a device trace. The CPU rehearsal
    has no device plane to read: there the closed bracket and the
    profiler's file are what a second attempt is decided on."""
    if not tiny:
        return not run["cache"].get("device_trace")
    from readers import device_trace
    return not ("stop" in device_trace.bracket_of(run)
                and trace_reduce.newest_xplane(run["profile_dir"]))


def attempt(args, cell, procs, workdir, notes):
    """Daemon, pool, window, stop, check → the result line, or None
    where a traced run has read no device trace."""
    traced = bool(args.trace)
    deadline = time.monotonic() + SETUP_BUDGET_S
    ports = Ports(2 * cell.config["nodes"])
    daemon = launch_daemon(cell, procs, workdir, args.tiny, traced)
    pool = new_pool(cell, procs, workdir, args.seed, args.tiny, ports)
    daemon_ready(daemon, cell, args.tiny)
    warm_thread, warm_box = in_thread(daemon.warm_up, args.seed,
                                      SETUP_BUDGET_S)

    def warm_joined():
        warm_thread.join()
        if "error" in warm_box:
            daemon.log_tail()
            raise warm_box["error"]
        log("daemon warm: first launch %.1fs, steady %.3fs" % (
            daemon.warm["first_launch_s"], daemon.warm["steady_launch_s"]))

    rec = pool_through_setup(cell, daemon, procs, workdir, args, ports,
                             notes, traced, deadline, pool, warm_joined)
    daemon_stats, side = daemon.stop()
    if args.keep:
        keep(args.keep, workdir, rec["pool"], daemon)
    units = units_of(cell)
    device_extra, breakdown = {}, None
    if traced:
        run = gathered(rec, daemon_stats, side, daemon)
        metrics = read_metrics(cell, "per_layer", run)
        if trace_is_missing(run, args.tiny):
            log("no device trace: bracket %s, profiler's file %s" % (
                side.get("profile"),
                trace_reduce.newest_xplane(daemon.profile_dir)))
            daemon.log_tail(40)
            return None
        trace = run["cache"].get("device_trace")
        if trace:
            device_extra = {"busy_s": trace["busy_s"],
                            "window_s": trace["window_s"]}
            breakdown = trace.get("breakdown")
    else:
        metrics = read_metrics(cell, "end_to_end", rec)
    got = judge(rec, daemon, daemon_stats, args.tiny)
    print_checks(got)
    if ports.stepped:
        notes["ports_busy"] = ports.stepped
    if procs.killed:
        notes["killed"] = procs.killed
        if traced:
            notes["not_read"] = sorted(
                m["name"] for m in cell.metrics("per_layer")
                if m["name"] not in metrics)
    return result_line(rec, metrics, units, daemon, side, got, device_extra,
                       breakdown, notes)


def single(args, cell, procs, workdir) -> int:
    """One attempt; a second, in a traced run that read no device trace
    while the run is young enough for two to end inside the run's limit
    (a fresh daemon, a fresh pool, the same seed)."""
    natives = native_modules()
    log("native modules %s" % json.dumps(natives))
    notes = {}
    for n in (1, 2):
        adir = os.path.join(workdir, "attempt%d" % n)
        os.mkdir(adir)
        line = attempt(args, cell, procs, adir, notes)
        if line is not None:
            print(json.dumps(line), flush=True)
            return 0
        age = time.perf_counter() - T_PROCESS
        if n == 2 or age > SECOND_TRACE_BY_S:
            raise NoResult(
                "the traced run read no device trace (attempt %d, %.0f s "
                "after the start%s)" % (n, age, "" if n == 2 else
                                        ": too late for a second"))
        log("the traced run read no device trace: a second attempt")
        procs.stop()
        notes["traced_attempts"] = 2
        shutil.rmtree(adir, ignore_errors=True)


def keep(dest, workdir, pool, daemon) -> None:
    """Builder only: copy logs, spans and the trace out of the temp dir
    (chiprun_out/ comes back from the chip)."""
    os.makedirs(dest, exist_ok=True)
    for name in os.listdir(workdir):
        src = os.path.join(workdir, name)
        if os.path.isfile(src):
            shutil.copy(src, dest)
    for name in pool.names:
        out = os.path.join(pool.base_dir, name + ".out")
        if os.path.exists(out):
            shutil.copy(out, dest)
    xplane = trace_reduce.newest_xplane(daemon.profile_dir)
    if xplane:
        shutil.copy(xplane, dest)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--tiny", action="store_true",
                    help="builder: CPU rehearsal at a tiny size; the "
                         "last line says correct false (no tpu)")
    ap.add_argument("--seeds", default=None,
                    help="builder: several seeds on ONE warmed daemon, "
                         "a fresh pool each, with the controls")
    ap.add_argument("--sweep", default=None,
                    help="builder: comma-separated period_s values, one "
                         "warmed daemon, a fresh pool each")
    ap.add_argument("--keep", default=None, metavar="DIR",
                    help="builder: copy logs and traces here")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "plenum_tpu")):
        print("no result: plenum_tpu/ is not beside benchmark/: nothing to "
              "measure", file=sys.stderr)
        return 2
    procs = Procs()
    signal.signal(signal.SIGTERM, lambda s, f: sys.exit(143))
    workdir = tempfile.mkdtemp(prefix="plenum_bench_")
    code, why = 3, None
    try:
        code = run_cell(args, procs, workdir)
    except (NoResult, RuntimeError) as e:
        # RuntimeError: the harness's own word for a set-up that did not
        # get there (a node never came up, a burst never settled); the
        # pool's log tails are on standard error by now
        why = " ".join(str(e).split())
    except Exception as e:
        traceback.print_exc()
        why = "uncaught %s: %s" % (type(e).__name__,
                                   " ".join(str(e).split()))
    except SystemExit as e:      # the SIGTERM handler's
        code, why = e.code, "ended by SIGTERM %.0f s after the start" % (
            time.perf_counter() - T_PROCESS)
    finally:
        # everything stopped and logged before the last word is said
        procs.stop()
        shutil.rmtree(workdir, ignore_errors=True)
        if why is not None:
            print("no result: %s" % why, file=sys.stderr, flush=True)
    return code


def run_cell(args, procs, workdir) -> int:
    cell = Cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell.bench["run_seconds"])
    if args.tiny:
        for key, value in cell.traffic.get("tiny", {}).items():
            cell.traffic["params"][key] = value
        if "genesis" in cell.config["tiny"]:
            cell.config["genesis"] = cell.config["tiny"]["genesis"]
    if operations.uses_genesis(cell.traffic["operations"]) \
            and not cell.config.get("genesis"):
        raise NoResult(
            "traffic %r signs with the deployment's identities and "
            "configuration %r states no genesis" % (
                cell.entry["traffic"], cell.entry["config"]))
    if args.seeds or args.sweep:
        import builder
        return builder.main(args, cell, procs, workdir)
    return single(args, cell, procs, workdir)


if __name__ == "__main__":
    sys.exit(main())
