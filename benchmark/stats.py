"""The arithmetic of the end-to-end metrics, over the whole window and
over all requests due in it."""
import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; `inf` entries (never answered) sort
    last, so they are beyond any percentile they do not reach."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def whole_window_rate(done_times, t0: float, t1: float) -> float:
    """Completions inside [t0, t1] over the window's whole length: a
    stall counts as the time it took."""
    inside = sum(1 for t in done_times if t is not None and t0 <= t <= t1)
    return inside / (t1 - t0)


def latencies_ms(ops):
    """Due time to confirmation, in ms; inf where none came."""
    return [(op.done - op.due) * 1e3 if op.done is not None else math.inf
            for op in ops]
