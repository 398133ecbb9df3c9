#!/usr/bin/env python3
"""run.py with one of the processes it starts swapped for a faulty one
(faulty_daemon.py, faulty_node.py), or with a fault of the host planted
in front of it, for test_faults.py and test_no_lost_run.py. The harness
itself has no switch for this: the entries are wrapped from here.

  BENCH_DAEMON_FAULT=...   the daemon starts through faulty_daemon.py
  BENCH_NODE_FAULT=...     node Delta starts through faulty_node.py
  BENCH_RUN_FAULT=port_taken   a listener sits on the fourth port of the
                           first base the harness tries
  BENCH_RUN_FAULT=uncaught     set-up raises what nothing expects
  BENCH_STOP_WAIT_S=n      Procs.stop waits n seconds, not 20
"""
import os
import socket
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TESTS))

import pool  # noqa: E402
import run  # noqa: E402

if os.environ.get("BENCH_DAEMON_FAULT"):
    pool.Daemon.entry = os.path.join(TESTS, "faulty_daemon.py")
if os.environ.get("BENCH_NODE_FAULT"):
    pool.Pool.entries = {"Delta": os.path.join(TESTS, "faulty_node.py")}
if os.environ.get("BENCH_STOP_WAIT_S"):
    pool.Procs.STOP_WAIT_S = float(os.environ["BENCH_STOP_WAIT_S"])

fault = os.environ.get("BENCH_RUN_FAULT")
if fault == "port_taken":
    first_base = pool.Ports.base
    HOLDERS = []

    def base(self):
        if not HOLDERS:
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.bind(("127.0.0.1", self.next + 3))
            s.listen(1)
            HOLDERS.append(s)
        return first_base(self)
    pool.Ports.base = base
elif fault == "uncaught":
    def native_modules():
        raise ValueError("planted: nothing\nexpects this")
    run.native_modules = native_modules
elif fault:
    raise SystemExit("unknown BENCH_RUN_FAULT %r" % fault)
sys.exit(run.main())
