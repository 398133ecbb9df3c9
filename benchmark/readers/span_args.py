"""Numbers a node's spans carry as arguments (the node dumps of
readers/node_spans.py, which finds and checks them): what a stage
counted while it ran, as against how long it took. A dump whose spans
lack the argument (a program older than the argument) gives None and
the metric is left out, as does a run without dumps.

Only spans that START inside [t0, t1] count. spec["spans"] names them,
spec["arg"] the argument, spec["quantity"] what is made of it:

  busiest_ratio_pct  100 x the sum of the argument over the sum of
                     spec["over"] (another argument of the same spans),
                     in the busiest node's spans: every node plans the
                     same 3PC batches, so one node's are the pool's
  busiest_median     the median of the argument over those spans
  busiest_min        the smallest of the argument over those spans
  sum_per_write      the sum of the argument, all nodes, per valid
                     write confirmed in the window

The busiest node is node_busy_pct's: the largest union of `prod_tick`
spans over the window.
"""
import statistics

from readers import node_spans


def carrying(events, spec, t0_us, t1_us):
    """The named spans of one node that start in the window and carry
    the argument → their `args`."""
    return [e["args"] for e in node_spans.in_window(events, t0_us, t1_us)
            if e.get("name") in spec["spans"]
            and spec["arg"] in (e.get("args") or {})]


def read(spec, run):
    dumps = node_spans.nodes(run)
    if not dumps:
        return None
    t0, t1 = run["t0"] * 1e6, run["t1"] * 1e6
    what = spec["quantity"]
    if what == "sum_per_write":
        found = [a for ev in dumps.values()
                 for a in carrying(ev, spec, t0, t1)]
        writes = node_spans.confirmed_writes(run)
        if not found or not writes:
            return None
        return sum(a[spec["arg"]] for a in found) / writes
    busiest = max(dumps.values(), key=lambda ev: sum(
        b - a for a, b in node_spans.intervals_of(ev, "prod_tick", t0, t1)))
    found = carrying(busiest, spec, t0, t1)
    if not found:
        return None
    if what == "busiest_median":
        return statistics.median(a[spec["arg"]] for a in found)
    if what == "busiest_min":
        return min(a[spec["arg"]] for a in found)
    if what == "busiest_ratio_pct":
        base = sum(a[spec["over"]] for a in found)
        return 100.0 * sum(a[spec["arg"]] for a in found) / base \
            if base else None
    raise ValueError("unknown quantity %r" % what)
