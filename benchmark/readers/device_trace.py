"""The profiler's trace of the chip, taken by the daemon over a few
seconds in the middle of the window, reduced by trace_reduce.py.
spec["quantity"]:

  idle_pct      1 - union of device-op intervals over the traced window
  kernel_ms     mean device duration of the events matching
                spec["event_pattern"]
  roofline_pct  least time for the REAL (deduplicated, unpadded) items
                the daemon sent to the device inside the bracket
                (kernel_work.py over peaks.json) over the matching
                events' device time there
"""
import json
import re

import kernel_work
import trace_reduce
from readers import daemon_spans


def bracket_of(run) -> dict:
    """perf_counter readings of the profiler's bracket, from the daemon."""
    return (run["side"].get("profile") or [{}])[-1]


def reduced(run):
    cache = run["cache"]
    if "device_trace" not in cache:
        path = trace_reduce.newest_xplane(run["profile_dir"])
        bracket = bracket_of(run)
        cache["device_trace"] = None
        if path and "stop" in bracket:
            red = trace_reduce.reduce(
                path, window_s=bracket["stop"] - bracket["start"])
            if red:
                red["breakdown"] = trace_reduce.breakdown(
                    red, daemon_spans.spans(run), bracket)
                cache["device_trace"] = red
    return cache["device_trace"]


def read(spec, run):
    red = reduced(run)
    if not red or red["busy_s"] <= 0:
        return None
    what = spec["quantity"]
    if what == "idle_pct":
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    pattern = re.compile(spec["event_pattern"])
    events = [(n, d) for n, d in red["op_events"] if pattern.search(n)]
    if not events:
        return None
    kernel_s = sum(d for _n, d in events)
    if what == "kernel_ms":
        return 1e3 * kernel_s / len(events)
    if what == "roofline_pct":
        bracket = bracket_of(run)
        floor = (run["daemon_stats"] or {}).get("cpu_floor", 0)
        items = sum((e.get("args") or {}).get("unique", 0)
                    for e in daemon_spans.in_window(
                        run, spec["span"], bracket["start"],
                        bracket["stop"])
                    if (e.get("args") or {}).get("unique", 0) >= floor)
        if not items:
            return None
        with open(run["peaks_file"]) as f:
            peaks = json.load(f)
        kind = (run["ready"].get("device") or {}).get("kind")
        if kind not in peaks:
            raise KeyError("device kind %r is not in peaks.json" % kind)
        least_s = items * kernel_work.ed25519_verify_madds() \
            / peaks[kind][spec["peak"]]["value"]
        return 100.0 * least_s / (kernel_s / red["devices"])
    raise ValueError("unknown quantity %r" % what)
