"""Due time to confirmation over ALL valid operations due in an
open-loop window; one never confirmed counts as infinite, beyond any
percentile it does not reach. spec: {"percentile": 95}. A closed loop
has no due times of its own, so nothing to read there."""
import stats


def read(spec, run):
    if run["plan"]["kind"] != "open":
        return None
    valid = [op for op in run["released"] if op.valid]
    if not valid:
        return None
    return stats.percentile(stats.latencies_ms(valid), spec["percentile"])
