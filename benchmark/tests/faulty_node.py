#!/usr/bin/env python3
"""A node with the timed path broken underneath, for test_faults.py: the
operator's start script, run unchanged after one method is replaced.

  BENCH_NODE_FAULT=state_unchanged   a step that returns its state
      unchanged: NYM writes are ordered but leave no state leaf
"""
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

fault = os.environ.get("BENCH_NODE_FAULT")
if fault == "state_unchanged":
    from plenum_tpu.server import request_handlers

    def update_state(self, txn, prev_result, request, is_committed=False):
        return None
    request_handlers.NymHandler.update_state = update_state
elif fault:
    raise SystemExit("unknown BENCH_NODE_FAULT %r" % fault)

script = os.path.join(ROOT, "scripts", "start_plenum_tpu_node")
sys.argv[0] = script
runpy.run_path(script, run_name="__main__")
