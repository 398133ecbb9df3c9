#!/usr/bin/env python3
"""A node with the timed path broken underneath, or one that dies in
set-up, for test_faults.py and test_no_lost_run.py: the operator's start
script, run unchanged after one method is replaced or one port is taken.
A new fault is one more value here.

  BENCH_NODE_FAULT=state_unchanged   a step that returns its state
      unchanged: NYM writes are ordered but leave no state leaf
  BENCH_NODE_FAULT=bind_busy_once    a listener put on the node's own
      port between the harness's probe and the node's bind, the first
      time this node starts (BENCH_FAULT_MARK names a file that says it
      has): the start script ends on the program's own Errno 98
  BENCH_NODE_FAULT=bind_busy_always  the same at every start
  BENCH_NODE_FAULT=exit_1_once       the first start ends with code 1
      and another last word
"""
import os
import runpy
import socket
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def first_start() -> bool:
    """True once: the mark outlives the pool's base dir, which a fresh
    start replaces."""
    mark = os.environ["BENCH_FAULT_MARK"]
    try:
        os.close(os.open(mark, os.O_CREAT | os.O_EXCL | os.O_WRONLY))
        return True
    except FileExistsError:
        return False


def take_own_port():
    from plenum_tpu.bootstrap import pool_genesis_txns, registry_from_txns
    argv = sys.argv
    name = argv[argv.index("--name") + 1]
    base_dir = argv[argv.index("--base-dir") + 1]
    ha = registry_from_txns(pool_genesis_txns(base_dir))[name].ha
    holder = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    # as a node binds: over what an earlier pool left in TIME_WAIT
    holder.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    holder.bind((ha[0], ha[1]))
    holder.listen(1)
    return holder


fault = os.environ.get("BENCH_NODE_FAULT")
if fault == "state_unchanged":
    from plenum_tpu.server import request_handlers

    def update_state(self, txn, prev_result, request, is_committed=False):
        return None
    request_handlers.NymHandler.update_state = update_state
elif fault == "bind_busy_always" \
        or (fault == "bind_busy_once" and first_start()):
    HOLDER = take_own_port()
elif fault == "exit_1_once":
    if first_start():
        print("planted: this node ends before it binds anything")
        sys.exit(1)
elif fault and fault != "bind_busy_once":
    raise SystemExit("unknown BENCH_NODE_FAULT %r" % fault)

script = os.path.join(ROOT, "scripts", "start_plenum_tpu_node")
sys.argv[0] = script
runpy.run_path(script, run_name="__main__")
