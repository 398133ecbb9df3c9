"""The rest of a run with the timed path broken underneath: skips the
harness's look for a chip (--tiny: daemon on the CPU backend, tiny
sizes) and drives run.py end to end, once sound and once per fault a
write cell can have, and reads the numbers compared off the last line.

  accept_all       an answer altered where it is produced: the daemon
                   passes every signature → corrupted writes are ordered
  state_unchanged  a step that returns its state unchanged: one node
                   orders writes and sets no state leaf
  (the exchange between chips and half a batch left out have no
   counterpart here: one chip, and a 3PC batch is all or nothing)

Each run starts a daemon and four node processes: one to five minutes each
(the run with the faulty node waits long for its probe write).

    python -m pytest benchmark/tests/test_faults.py -q
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def run_tiny(env_extra=None, seed=5, cell="pool4-write-burst"):
    """run.py's rehearsal; with a fault, through tests/faulty_run.py,
    which swaps one process's entry and changes nothing else."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    entry = os.path.join(HERE, "tests", "faulty_run.py") if env_extra \
        else os.path.join(HERE, "run.py")
    proc = subprocess.run(
        [sys.executable, entry, "--workload",
         cell, "--seed", str(seed), "--seconds", "8",
         "--trace", "0", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    return line, {k: v[0] for k, v in line["compared"].items()}


CELLS = pytest.mark.parametrize("cell", ["pool4-write-burst",
                                         "pool4-authors-burst"])


@CELLS
def test_sound_run_fails_only_for_want_of_a_tpu(cell):
    line, got = run_tiny(cell=cell)
    assert line["correct"] is False
    assert got.pop("daemon_faults") == 1
    assert not any(got.values()), got


@CELLS
def test_daemon_that_passes_every_signature(cell):
    line, got = run_tiny({"BENCH_DAEMON_FAULT": "accept_all"}, cell=cell)
    assert line["correct"] is False
    # some node let a corrupted write stand; whether it is also ordered
    # before the drain ends depends on how the copies were batched
    assert got["corrupted_not_refused"] + got["corrupted_ordered"] > 0, got


@CELLS
def test_node_that_leaves_its_state_unchanged(cell):
    line, got = run_tiny({"BENCH_NODE_FAULT": "state_unchanged"}, cell=cell)
    assert line["correct"] is False
    assert got["nodes_off_state"] + got["nodes_off_ledger"] \
        + got["unanswered_by_a_node"] > 0, got
