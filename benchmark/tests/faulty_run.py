#!/usr/bin/env python3
"""run.py with one of the processes it starts swapped for a faulty one
(faulty_daemon.py, faulty_node.py), for test_faults.py. The harness
itself has no switch for this: the entries are wrapped from here.

  BENCH_DAEMON_FAULT=...   the daemon starts through faulty_daemon.py
  BENCH_NODE_FAULT=...     node Delta starts through faulty_node.py
"""
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TESTS))

import pool  # noqa: E402
import run  # noqa: E402

if os.environ.get("BENCH_DAEMON_FAULT"):
    pool.Daemon.entry = os.path.join(TESTS, "faulty_daemon.py")
if os.environ.get("BENCH_NODE_FAULT"):
    pool.Pool.entries = {"Delta": os.path.join(TESTS, "faulty_node.py")}
sys.exit(run.main())
