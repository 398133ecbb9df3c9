#!/usr/bin/env python3
"""The verify daemon with the timed path broken underneath, for
test_faults.py: the benchmark's daemon entry, run unchanged after one
method of the program is replaced.

  BENCH_DAEMON_FAULT=accept_all   an answer altered where it is
      produced: every signature passes
"""
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
elif fault:
    raise SystemExit("unknown BENCH_DAEMON_FAULT %r" % fault)

entry = os.path.join(HERE, "daemon_entry.py")
sys.argv[0] = entry
runpy.run_path(entry, run_name="__main__")
