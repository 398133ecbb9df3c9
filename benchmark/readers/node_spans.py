"""Spans the node processes handed out: a traced run's daemon opens a
trace session for the host, and every node that took part in it writes
node_<Name>_spans.json beside the daemon's own file when it stops
(Chrome JSON; ts in microseconds of perf_counter, the host-wide
monotonic clock the harness's t0/t1 and the profiler's bracket are
readings of). No dump there (a parent commit whose nodes cannot arm):
None, and the metric is left out. A dump on another clock, or one whose
ring wrapped inside the window (its oldest surviving record was written
after t0), raises: a per-stage number from half a window is not a
smaller number, it is a wrong one.

Only complete ("X") spans that START inside [t0, t1] count. Self time
is a span's duration less the part of it that its child spans cover,
computed here (a node is one thread: spans nest or follow one
another). A span's stage is its category, but for the names the
program's own budget files by name.

spec["quantity"]:

  self_ms_per_write   self time, summed over nodes, of the spans that
                      spec["select"] picks (each selector gives some of
                      stage, cat, name; a span is picked if one selector
                      matches), in ms per valid write confirmed in the
                      window
  total_ms_per_write  whole duration of the spans named spec["span"],
                      summed over nodes, per confirmed write (wall time
                      the node's one thread stood blocked, not CPU)
  count_per_write     number of spans named spec["span"], all nodes, per
                      confirmed write
  busiest_union_pct   of the window, the share the busiest node spent
                      inside a spec["span"] span
  device_idle_overlap_pct
                      of the device's idle time inside the profiler's
                      bracket, the share during which at least one node
                      was inside a spec["span"] span
"""
import glob
import json
import os
import time

import trace_reduce
from readers import device_trace

_INTAKE_NAMES = ("auth_dispatch", "auth_conclude", "read_batch")
_STAGE_BY_NAME = {"wire_pack": "serialize", "wire_parse": "parse",
                  "queue_wait": "queue_wait", "prod_tick": "untraced"}
_STAGE_BY_CAT = {"device": "dispatch_wait", "bls": "dispatch_wait"}


def stage_of(name, cat):
    if name in _INTAKE_NAMES:
        return "intake"
    return _STAGE_BY_NAME.get(name) or _STAGE_BY_CAT.get(cat, cat)


def check_dump(path, doc, t0_us):
    """Raise unless every tracer of the dump is on the harness's clock
    and still holds everything written since the window's start."""
    mine = time.get_clock_info("perf_counter")
    meta = doc.get("metadata")
    if not meta:
        raise ValueError("%s: no metadata, so neither its clock nor its "
                         "ring can be checked" % path)
    for name, m in meta.items():
        clock = m.get("clock") or {}
        if clock.get("implementation") != mine.implementation \
                or not mine.monotonic:
            raise ValueError(
                "%s: %s stamped its spans with %r, the harness reads %r"
                % (path, name, clock.get("implementation"),
                   mine.implementation))
        oldest = m.get("oldest_ts")
        if oldest is None or oldest > t0_us:
            raise ValueError(
                "%s: %s's ring wrapped inside the window (oldest "
                "surviving record %s us, window from %d us, dropped %s)"
                % (path, name, oldest, t0_us,
                   (m.get("stats") or {}).get("dropped")))


def self_times(spans):
    """[(start, end, key)] of one thread → {key: self time}, in the
    unit of start and end. A child is a span that starts inside the
    span open before it; its part inside the parent is taken off the
    parent."""
    out = {}
    stack = []   # [start, end, key, covered by children]

    def close(entry):
        out[entry[2]] = out.get(entry[2], 0) + max(
            0, entry[1] - entry[0] - entry[3])

    for start, end, key in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and start >= stack[-1][1]:
            close(stack.pop())
        if stack:
            end = min(end, stack[-1][1])
            stack[-1][3] += end - start
        stack.append([start, end, key, 0])
    while stack:
        close(stack.pop())
    return out


def overlap(xs, ys):
    """Total length of the intersection of two sorted disjoint lists."""
    total, i, j = 0, 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0, hi - lo)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def nodes(run):
    """→ {dump path: [X events]} or None when no node handed out a
    dump; every event as the dump has it, whatever its start."""
    cache = run["cache"]
    if "node_spans" not in cache:
        found = {}
        pattern = os.path.join(os.path.dirname(run["spans_file"]),
                               "node_*_spans.json")
        for path in sorted(glob.glob(pattern)):
            with open(path) as f:
                doc = json.load(f)
            check_dump(path, doc, run["t0"] * 1e6)
            found[path] = [e for e in doc.get("traceEvents", [])
                           if e.get("ph") == "X"]
        cache["node_spans"] = found or None
    return cache["node_spans"]


def window_self_times(run):
    """{(stage, cat, name): self time in us, all nodes} of the spans
    that start in the window."""
    cache = run["cache"]
    if "node_self_us" not in cache:
        total = {}
        for events in nodes(run).values():
            spans = [(e["ts"], e["ts"] + e.get("dur", 0),
                      (stage_of(e["name"], e.get("cat", "")),
                       e.get("cat", ""), e["name"]))
                     for e in in_window(events, run["t0"] * 1e6,
                                        run["t1"] * 1e6)]
            for key, v in self_times(spans).items():
                total[key] = total.get(key, 0) + v
        cache["node_self_us"] = total
    return cache["node_self_us"]


def in_window(events, t0_us, t1_us):
    return [e for e in events if t0_us <= e["ts"] <= t1_us]


def confirmed_writes(run):
    t0, t1 = run["t0"], run["t1"]
    return sum(1 for op in run["released"] if op.valid
               and op.done is not None and t0 <= op.done <= t1)


def picked(selectors, stage, cat, name):
    have = {"stage": stage, "cat": cat, "name": name}
    return any(all(have[k] == v for k, v in sel.items())
               for sel in selectors)


def intervals_of(events, name, lo, hi):
    """Union of the named spans of one node, cut to [lo, hi]."""
    return trace_reduce.merge((max(e["ts"], lo), min(e["ts"] + e.get("dur", 0), hi))
                 for e in events if e.get("name") == name
                 and e["ts"] < hi and e["ts"] + e.get("dur", 0) > lo)


def read(spec, run):
    dumps = nodes(run)
    if not dumps:
        return None
    t0, t1 = run["t0"] * 1e6, run["t1"] * 1e6
    what = spec["quantity"]
    if what == "busiest_union_pct":
        busy = [sum(b - a for a, b in intervals_of(ev, spec["span"], t0, t1))
                for ev in dumps.values()]
        return 100.0 * max(busy) / (t1 - t0)
    if what == "device_idle_overlap_pct":
        red = device_trace.reduced(run)
        bracket = device_trace.bracket_of(run)
        if not red or red.get("anchor_ns") is None or "stop" not in bracket:
            return None
        lo, hi = bracket["start"] * 1e6, bracket["stop"] * 1e6
        # trace ns → microseconds of perf_counter, by the anchor
        busy = [[lo + (a - red["anchor_ns"]) / 1e3,
                 lo + (b - red["anchor_ns"]) / 1e3]
                for a, b in red["intervals_ns"]]
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        idle = [[max(a, lo), min(b, hi)]
                for a, b in zip(edges[0::2], edges[1::2])
                if min(b, hi) > max(a, lo)]
        idle_us = sum(b - a for a, b in idle)
        if idle_us <= 0:
            return None
        at_work = trace_reduce.merge(iv for ev in dumps.values()
                        for iv in intervals_of(ev, spec["span"], lo, hi))
        return 100.0 * overlap(idle, at_work) / idle_us
    writes = confirmed_writes(run)
    if not writes:
        return None
    if what == "self_ms_per_write":
        return sum(v for key, v in window_self_times(run).items()
                   if picked(spec["select"], *key)) / 1e3 / writes
    named = [e for events in dumps.values()
             for e in in_window(events, t0, t1)
             if e.get("name") == spec["span"]]
    if what == "total_ms_per_write":
        return sum(e.get("dur", 0) for e in named) / 1e3 / writes
    if what == "count_per_write":
        return len(named) / writes
    raise ValueError("unknown quantity %r" % what)
