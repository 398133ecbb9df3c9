"""The verify daemon's final stats line (verify_daemon.stats()), with
the harness's own warm-up launches taken off. spec: {"numerator":
[fields], "denominator": [fields], "scale": 100}. The daemon prints its
counters only when it stops, so they cover set-up's probe write too (a
handful of host items)."""


def read(spec, run):
    stats = run["daemon_stats"]
    if not stats:
        return None
    warm = run.get("warm") or {}

    def total(fields):
        return sum((stats.get(f) or 0) - (warm.get(f) or 0)
                   for f in fields)

    den = total(spec["denominator"])
    if den <= 0:
        return None
    return total(spec["numerator"]) * spec.get("scale", 1.0) / den
