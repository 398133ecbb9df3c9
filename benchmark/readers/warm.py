"""What the harness's own warm-up of the daemon took (pool.py
Daemon.warm_up, before the window: two launches of the one shape the
defaults launch, each timed on the harness's clock from the frame sent
to the verdicts back). spec: {"field": "first_launch_s"}. The first
launch is where a daemon builds its kernel, or loads it from the
built-kernel store (ops/kernel_store.py); a run that took no warm-up
has nothing to read."""


def read(spec, run):
    return (run.get("warm") or {}).get(spec["field"])
