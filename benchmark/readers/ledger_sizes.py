"""Growth of one ledger over the growth of another, from the nodes'
validator-info dumps read just before the window's first request and
after the drain (dumps come every 2 s, so the readings bracket window
and drain together; every write due in the window is ordered between
them). spec: {"numerator": "domain", "denominator": "audit"}; the
median over the nodes."""
import statistics


def read(spec, run):
    ratios = []
    for name, after in run["reports_after"].items():
        before = run["reports_before"].get(name)
        if before is None:
            continue
        a, b = after["Ledger_sizes"], before["Ledger_sizes"]
        num = (a.get(spec["numerator"]) or 0) - (b.get(spec["numerator"]) or 0)
        den = (a.get(spec["denominator"]) or 0) - (
            b.get(spec["denominator"]) or 0)
        if den > 0 and num > 0:
            ratios.append(num / den)
    return statistics.median(ratios) if ratios else None
