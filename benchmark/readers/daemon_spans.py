"""Spans of the daemon's own tracer (its --trace-file, Chrome JSON; ts
in microseconds of perf_counter, which on Linux is the system-wide
monotonic clock the harness reads too). spec: {"span": "device_verify",
"statistic": "p50", "min_unique": 0}: spans that start inside the
window."""
import json

import stats


def spans(run):
    cache = run["cache"]
    if "daemon_spans" not in cache:
        try:
            with open(run["spans_file"]) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {"traceEvents": []}
        cache["daemon_spans"] = [
            e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    return cache["daemon_spans"]


def in_window(run, name, t0=None, t1=None):
    t0 = run["t0"] if t0 is None else t0
    t1 = run["t1"] if t1 is None else t1
    return [e for e in spans(run) if e.get("name") == name
            and t0 * 1e6 <= e["ts"] <= t1 * 1e6]


def read(spec, run):
    picked = [e["dur"] / 1e3 for e in in_window(run, spec["span"])
              if (e.get("args") or {}).get("unique", 0)
              >= spec.get("min_unique", 0)]
    if not picked:
        return None
    q = {"p50": 50, "p95": 95}[spec["statistic"]]
    return stats.percentile(picked, q)
