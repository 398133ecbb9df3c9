"""CPU seconds (user + system, /proc/<pid>/stat) that all node
processes spent between the window's start and its close, over the
valid writes confirmed in the window. spec: {"per": "confirmed_write",
"scale": 1000} → ms per write."""


def read(spec, run):
    before, after = run["cpu_s"]
    t0, t1 = run["t0"], run["t1"]
    confirmed = sum(1 for op in run["released"] if op.valid
                    and op.done is not None and t0 <= op.done <= t1)
    if not confirmed or after <= before:
        return None
    return (after - before) * spec.get("scale", 1.0) / confirmed
