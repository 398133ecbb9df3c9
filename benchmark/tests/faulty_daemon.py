#!/usr/bin/env python3
"""The verify daemon with the timed path broken underneath, or one the
harness has trouble with, for test_faults.py and test_no_lost_run.py:
the benchmark's daemon entry, run unchanged after one method of the
program (or of the profiler) is replaced. A new fault is one more value
here.

  BENCH_DAEMON_FAULT=accept_all   an answer altered where it is
      produced: every signature passes
  BENCH_DAEMON_FAULT=stop_hangs   a daemon that does not end on SIGTERM:
      VerifyDaemon.stop never returns, so no final stats line, no span
      dump, and the harness has to kill it (after the bracket, in a
      traced run: the side file and the profiler's trace are there)
  BENCH_DAEMON_FAULT=no_xplane_once   the first daemon started
      (BENCH_FAULT_MARK names a file that says one has) closes its
      bracket over a profiler that wrote nothing
"""
import asyncio
import os
import runpy
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))

fault = os.environ.get("BENCH_DAEMON_FAULT")
if fault == "accept_all":
    from plenum_tpu.server import verify_daemon
    real = verify_daemon.VerifyDaemon._verify_bucketed

    def accept_all(self, items):
        return [True] * len(real(self, items))
    verify_daemon.VerifyDaemon._verify_bucketed = accept_all
elif fault == "stop_hangs":
    from plenum_tpu.server import verify_daemon

    async def stop(self):
        await asyncio.sleep(3600)
    verify_daemon.VerifyDaemon.stop = stop
elif fault == "no_xplane_once":
    try:
        os.close(os.open(os.environ["BENCH_FAULT_MARK"],
                         os.O_CREAT | os.O_EXCL | os.O_WRONLY))
        import jax
        jax.profiler.start_trace = lambda *a, **k: None
        jax.profiler.stop_trace = lambda *a, **k: None
    except FileExistsError:
        pass
elif fault:
    raise SystemExit("unknown BENCH_DAEMON_FAULT %r" % fault)

entry = os.path.join(HERE, "daemon_entry.py")
sys.argv[0] = entry
runpy.run_path(entry, run_name="__main__")
