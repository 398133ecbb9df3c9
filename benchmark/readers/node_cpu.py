"""CPU time of the node processes as their own dumps state it (the dumps
of readers/node_spans.py, which finds and checks them): the `cpu`
counter samples (`thread_s`, the CPU seconds of the thread that runs a
node's loop; `process_s`, of all its threads; at most ten a second),
and the argument `cpu_us` of the spans that carry it (`prod_tick`,
`wire_rx`: the loop thread's CPU time across the span). Where the
span's wall time was the question, this is the CPU behind it.

A window's difference of a sample series is read at t0 and at t1, each
interpolated between the two samples around it; samples that do not
reach both ends fail the run (the node's loop stood still for the
whole drain, or the dump is not a whole run's). Spans count if they
START inside [t0, t1], as in node_spans. A run without dumps, or a
dump without samples or without the argument (a program older than
them), gives None and the metric is left out.

spec["quantity"], all nodes summed, in ms per valid write confirmed in
the window:

  loop_cpu_ms_per_write       the window's thread_s less the cpu_us of
                              the spec["spans"] spans: the loop's
                              thread on a core outside them (idle
                              turns, the selector, stream buffering)
  offthread_cpu_ms_per_write  the window's process_s - thread_s: every
                              other thread of the node processes
  offcpu_ms_per_write         the sum of duration - cpu_us over the
                              spec["spans"] spans: time inside them
                              that the thread was not on a core

and, for a span a program older than it does not record, node_spans'
own quantities behind a guard: `self_ms_per_write` and
`device_idle_overlap_pct` are read by node_spans.read(spec) only where
some dump holds a span named in spec["spans"], and are None where none
does (node_spans would read 0 there: no such span is not no such work).
"""
import bisect
import json

from readers import node_spans

_GUARDED = ("self_ms_per_write", "device_idle_overlap_pct")


def samples(run):
    """{dump path: [(ts us, thread_s, process_s)], sorted} of every
    dump node_spans found (and checked); None without dumps."""
    cache = run["cache"]
    if "node_cpu_samples" not in cache:
        dumps = node_spans.nodes(run)
        found = None
        if dumps:
            found = {}
            for path in dumps:
                with open(path) as f:
                    doc = json.load(f)
                found[path] = sorted(
                    (e["ts"], e["args"]["thread_s"], e["args"]["process_s"])
                    for e in doc.get("traceEvents", [])
                    if e.get("ph") == "C" and e.get("name") == "cpu")
        cache["node_cpu_samples"] = found
    return cache["node_cpu_samples"]


def at(series, t_us, i):
    """Value i (1: thread_s, 2: process_s) of a sample series at t_us,
    interpolated between the samples on either side."""
    k = bisect.bisect_left(series, (t_us,))
    if k < len(series) and series[k][0] == t_us:
        return series[k][i]
    if k == 0 or k == len(series):
        raise ValueError(
            "the cpu samples (%s us to %s us) do not reach %d us"
            % (series[0][0], series[-1][0], t_us))
    a, b = series[k - 1], series[k]
    return a[i] + (b[i] - a[i]) * (t_us - a[0]) / (b[0] - a[0])


def spans_with_cpu(events, names, t0_us, t1_us):
    """[(duration us, cpu_us)] of the named spans that start in the
    window and carry cpu_us."""
    return [(e.get("dur", 0), e["args"]["cpu_us"])
            for e in node_spans.in_window(events, t0_us, t1_us)
            if e.get("name") in names
            and "cpu_us" in (e.get("args") or {})]


def read(spec, run):
    dumps = node_spans.nodes(run)
    if not dumps:
        return None
    what = spec["quantity"]
    if what in _GUARDED:
        names = set(spec["spans"])
        if not any(e.get("name") in names
                   for events in dumps.values() for e in events):
            return None
        return node_spans.read(spec, run)
    writes = node_spans.confirmed_writes(run)
    if not writes:
        return None
    t0, t1 = run["t0"] * 1e6, run["t1"] * 1e6
    spans = {path: spans_with_cpu(events, spec["spans"], t0, t1)
             for path, events in dumps.items()}
    if what == "offcpu_ms_per_write":
        if not any(spans.values()):
            return None
        return sum(dur - cpu for found in spans.values()
                   for dur, cpu in found) / 1e3 / writes
    series = samples(run)
    if not all(series.values()):
        return None
    if what == "offthread_cpu_ms_per_write":
        total_s = sum((at(s, t1, 2) - at(s, t1, 1))
                      - (at(s, t0, 2) - at(s, t0, 1))
                      for s in series.values())
        return total_s * 1e3 / writes
    if what == "loop_cpu_ms_per_write":
        if not any(spans.values()):
            return None
        total_s = sum(at(s, t1, 1) - at(s, t0, 1) for s in series.values())
        in_spans_s = sum(cpu for found in spans.values()
                         for _dur, cpu in found) / 1e6
        return (total_s - in_spans_s) * 1e3 / writes
    raise ValueError("unknown quantity %r" % what)
