"""The cell pool4-owners-burst at a size a test run can hold: the
controls come out not correct and the unbroken reference correct when
the traffic is owners rewriting their own NYMs (every write an update
of a leaf that exists, hot DIDs written many times), and a rehearsal of
the whole run under --tiny is sound but for the want of a tpu. No pool
is started for the controls: the reference stands in the pool's place.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_owners.py -q
"""
import collections
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(HERE, "tests"))

import check  # noqa: E402
import controls  # noqa: E402
import operations  # noqa: E402
import test_control  # noqa: E402
from client import Op  # noqa: E402
from reference import pool as ref  # noqa: E402

NAMES = test_control.NAMES[:4]
READY, STATS = test_control.READY, test_control.STATS
IDENTITIES = test_control.IDENTITIES
SEEDS = [3, 2147483900, 77]


def genesis(seed):
    return test_control.genesis(seed, IDENTITIES)


def ops_for(seed, count=400):
    mix = {"kind": "nym_rewrite_owners", "zipf_constant": 0.99,
           "corrupted_every": 50}
    ops = [Op(req, b"", valid) for req, valid in operations.make(
        seed, count, mix, {"identities": IDENTITIES})]
    for i, op in enumerate(ops):
        op.due = op.sent = float(i)
        op.done = float(i) + 0.5
    return ops


def judged(seed, control=None):
    obs = controls.reference_pool(NAMES, 1, ops_for(seed), genesis(seed),
                                  READY, STATS, tiny=False,
                                  break_guarantee=control)
    return check.compare(obs, genesis(seed))


@pytest.mark.parametrize("seed", SEEDS)
def test_unbroken_reference_is_correct(seed):
    got = judged(seed)
    assert check.verdict(got["values"]), got["values"]
    # the traffic is what the cell is for: rewrites of leaves that exist
    assert got["notes"]["reference"]["size"] == 1 + IDENTITIES + 392
    replay = ref.Replay(genesis(seed))
    assert len(replay.state) == 1 + IDENTITIES
    rewritten = collections.Counter(
        op.request["operation"]["dest"] for op in ops_for(seed) if op.valid)
    assert set(rewritten) <= set(replay.records)
    assert rewritten.most_common(1)[0][1] > 40


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", controls.CONTROLS)
def test_control_is_not_correct(seed, control):
    values = judged(seed, control)["values"]
    assert not check.verdict(values), values


def test_a_record_left_at_an_earlier_write_is_off_state():
    """last_write_wins: a pool whose state holds, for one hot DID, what
    an EARLIER write left (same ledger, same leaf count) is not
    correct, by nodes_off_state alone."""
    seed = SEEDS[0]
    obs = controls.reference_pool(NAMES, 1, ops_for(seed), genesis(seed),
                                  READY, STATS, tiny=False)
    replay = ref.Replay(genesis(seed))
    hot = collections.Counter(
        op.request["identifier"] for op in obs.ops if op.valid
    ).most_common(1)[0][0]
    writes = [op for op in obs.ops
              if op.valid and op.request["identifier"] == hot]
    for op in obs.ops:
        if not op.valid:
            continue
        (body, _nodes), = op.answers.items()
        if op is writes[-1]:
            # on the ledger, not in the state
            replay.leaf_hashes.append(ref.merkle.leaf_hash(
                ref.canonical_msgpack(json.loads(body))))
        else:
            replay.append(json.loads(body))
    stale = dict(obs.reports["Delta"])
    assert stale["Committed_ledger_root_hashes"]["domain"] \
        == replay.ledger_root()
    stale["Committed_state_root_hashes"] = {"domain": replay.state_root()}
    obs.reports["Delta"] = stale
    values = check.compare(obs, genesis(seed))["values"]
    assert values.pop("nodes_off_state") == 1
    assert not any(values.values()), values


def test_sound_rehearsal_fails_only_for_want_of_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "pool4-owners-burst", "--seed", "2147483777", "--seconds", "8",
         "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = {k: v[0] for k, v in line["compared"].items()}
    assert line["correct"] is False
    assert got.pop("daemon_faults") == 1
    assert not any(got.values()), got
    assert line["failed"] == 0 and line["attempted"] > 90
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # the traffic that ran conflicts inside its 3PC batches
    assert 20 < metrics["exec_conflict_pct"] < 90
    assert metrics["exec_largest_lane"] >= 2
    assert metrics["node_nym_miss_per_write"] > 0
    assert metrics["authors_per_window"] < line["attempted"]
