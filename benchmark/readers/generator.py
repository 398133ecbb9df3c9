"""The load generator's own record: how late it sent, against when each
operation was due. spec: {"field": "late_ms", "percentile": 95}."""
import stats


def read(spec, run):
    late = [(op.sent - op.due) * 1e3 for op in run["released"]
            if op.sent is not None and op.due is not None]
    if not late:
        return None
    return stats.percentile(late, spec["percentile"])
