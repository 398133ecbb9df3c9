"""Builder-only modes of run.py. Set-up is most of a run (the daemon's
first launch traces and lowers the unrolled kernel for a minute and a
half), so both keep ONE warmed daemon and start a fresh pool per item:

  --seeds a,b,c   the cell as it is, once per seed, each judged by
                  check.compare, and after each the controls (after the
                  first three: the reference in the pool's place with one guarantee
                  broken) judged the same way: the readings the limits
                  in PERF.md rest on
  --sweep p,q,r   the cell's traffic with period_s set to each value in
                  turn: rate met, tail, per-burst completion and what
                  was still open when the window closed; the share of
                  signatures that took the device, from the daemon's
                  spans

Neither prints a result line; each prints one JSON line per item."""
import copy
import json
import os
import shutil
import time

import check
import controls
import stats
from pool import Ports, in_thread, log, native_modules, tail


def burst_times(rec):
    """Per burst: [due, unconfirmed, seconds until 95% of it was
    confirmed, seconds until the last was]."""
    by_due = {}
    for op in rec["released"]:
        if op.valid:
            by_due.setdefault(round(op.due - rec["t0"], 3), []).append(op)
    out = []
    for due in sorted(by_due):
        ops = by_due[due]
        done = [op.done for op in ops if op.done is not None]
        done.sort()
        out.append([due, len(ops) - len(done),
                    round(done[int(0.95 * len(ops)) - 1] - ops[0].due, 3)
                    if len(done) >= 0.95 * len(ops) else None,
                    round(done[-1] - ops[0].due, 3) if done else None])
    return out


def keep_logs(dest, pool, tag):
    os.makedirs(os.path.join(dest, tag), exist_ok=True)
    for name in pool.names:
        for src in (os.path.join(pool.base_dir, name + ".out"),
                    os.path.join(pool.base_dir, name, "logs",
                                 name + ".log")):
            if os.path.exists(src):
                shutil.copy(src, os.path.join(dest, tag))


def device_share(spans, t0, t1, floor):
    """Unique items a pool's window sent to the device and to the host,
    and how many of the daemon's batches there held one or two items
    (a PROPAGATE authenticated singly, from a node or two at once)."""
    dev = host = small = 0
    for e in spans:
        if e.get("name") == "device_verify" and e.get("ph") == "X" \
                and t0 * 1e6 <= e["ts"] <= t1 * 1e6:
            unique = (e.get("args") or {}).get("unique", 0)
            if unique >= floor:
                dev += unique
            else:
                host += unique
            small += unique <= 2
    return dev, host, small


def main(args, cell, procs, workdir) -> int:
    import run
    log("native modules %s" % json.dumps(native_modules()))
    daemon = run.start_daemon(cell, procs, workdir, args.tiny, True)
    thread, box = in_thread(daemon.warm_up, args.seed, run.SETUP_BUDGET_S)
    thread.join()
    if "error" in box:
        log(tail(os.path.join(workdir, "daemon.err"), 30))
        raise box["error"]
    log("daemon warm: %s" % json.dumps(daemon.warm))
    if args.seeds:
        items = [("seed", int(s)) for s in args.seeds.split(",")]
    else:
        items = [("period_s", float(p)) for p in args.sweep.split(",")]
    ports = Ports(2 * cell.config["nodes"])
    records = []
    all_ok = True
    for i, (kind, value) in enumerate(items):
        this = copy.copy(cell)
        this.traffic = copy.deepcopy(cell.traffic)
        seed = value if kind == "seed" else args.seed + i
        if kind == "period_s":
            this.traffic["params"]["period_s"] = value
        deadline = time.monotonic() + 300
        pool, plan, ops = run.start_pool(
            this, daemon, procs, workdir, seed, args.seconds, args.tiny,
            ports)
        rec = run.finish_pool(pool, daemon, plan, ops, args.seconds, False,
                              deadline, this.traffic)
        got = run.judge(rec, daemon, None, args.tiny)
        values = dict(got["values"], daemon_faults=0)
        line = {kind: value, "seed": seed,
                "correct_but_for_the_daemon": check.verdict(values),
                "compared": {k: v for k, v in values.items() if v},
                "check_s": round(got["seconds"], 2),
                "drain_s": round(rec["drain_s"], 2)}
        line.update({k: round(v, 3) for k, v in run.read_metrics(
            this, "end_to_end", rec).items() if k != "setup_s"})
        valid = [op for op in rec["released"] if op.valid]
        line["released"] = len(rec["released"])
        line["open_at_close"] = sum(
            1 for op in valid if op.done is None or op.done > rec["t1"])
        line["node_cpu_s"] = round(rec["cpu_s"][1] - rec["cpu_s"][0], 2)
        if plan["kind"] == "open":
            line["bursts"] = burst_times(rec)
            line["write_p50_ms"] = round(stats.percentile(
                stats.latencies_ms(valid), 50), 1)
            late = [(op.sent - op.due) * 1e3 for op in rec["released"]]
            line["gen_late_p95_ms"] = round(stats.percentile(late, 95), 1)
        if rec["drain_s"] > 8 and args.keep:
            keep_logs(args.keep, pool, "%s_%s" % (kind, value))
        if kind == "seed" and i < 3:
            obs, genesis = got["obs"], got["genesis"]
            line["controls"] = {}
            for name in controls.CONTROLS:
                t = time.perf_counter()
                ctl = controls.reference_pool(
                    obs.names, obs.f, obs.ops, genesis, daemon.info,
                    None, args.tiny, break_guarantee=name)
                cv = check.compare(ctl, genesis)["values"]
                cv["daemon_faults"] = 0
                line["controls"][name] = {
                    "correct": check.verdict(cv),
                    "fails": {k: v for k, v in cv.items() if v},
                    "s": round(time.perf_counter() - t, 2)}
            if i == 0:
                sound = controls.reference_pool(
                    obs.names, obs.f, obs.ops, genesis, daemon.info, None,
                    args.tiny)
                sv = check.compare(sound, genesis)["values"]
                sv["daemon_faults"] = 0
                line["controls"]["reference_unbroken"] = {
                    "correct": check.verdict(sv)}
        line["window"] = [rec["t0"], rec["t1"]]
        if not line["correct_but_for_the_daemon"]:
            all_ok = False
            log("notes: %s" % json.dumps(got["notes"], default=str)[:4000])
            pool.log_tails()
        records.append(line)
        print(json.dumps(line), flush=True)
        shutil.rmtree(pool.base_dir, ignore_errors=True)
    daemon_stats, side = daemon.stop()
    try:
        with open(daemon.trace_file) as f:
            spans = json.load(f).get("traceEvents", [])
    except (OSError, ValueError):
        spans = []
    floor = (daemon_stats or {}).get("cpu_floor", 512)
    for line in records:
        dev, host, small = device_share(spans, line["window"][0],
                                        line["window"][1], floor)
        print(json.dumps({k: line[k] for k in ("seed", "drain_s")
                          if k in line}
                         | {"device_items": dev, "host_items": host,
                            "batches_of_1_or_2": small}), flush=True)
    obs = check.Observed([], 0, [], {}, daemon.info, daemon_stats,
                         tiny=args.tiny)
    problems = check.daemon_problems(obs, {})
    print(json.dumps({"daemon_stats": daemon_stats, "side": side,
                      "daemon_problems": problems, "all_ok": all_ok}),
          flush=True)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        with open(os.path.join(args.keep, "builder_records.json"),
                  "w") as f:
            json.dump(records, f)
    return 0 if all_ok and not problems else 1
