"""The controls come out as not correct, the unbroken reference as
correct, at a size a test run can hold. No pool is started: the
reference stands in the pool's place (controls.reference_pool) and
check.compare judges it as it judges a run.

    python -m pytest benchmark/tests/test_control.py -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import check  # noqa: E402
import controls  # noqa: E402
import operations  # noqa: E402
import traffic  # noqa: E402
from client import Op  # noqa: E402

NAMES = ["Alpha", "Beta", "Gamma", "Delta", "Epsilon", "Zeta", "Eta"]
READY = {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
STATS = {"device_launches": 3, "failed_batches": 0, "step_downs": {},
         "mesh": {"dispatches": 3}, "kernel_backends": {"ed25519": True}}


IDENTITIES = 300     # of the configuration with a `genesis`


def genesis(seed, identities=0):
    """A domain genesis as Pool.generate leaves it, without the
    stewards: the trustee, then the configuration's identities."""
    trustee = traffic.Signer(traffic.trustee_seed(seed))
    return [trustee.genesis_nym("0")] + [
        traffic.identity(seed, i).genesis_nym() for i in range(identities)]


def ops_for(seed, count=400, identities=0):
    mix = {"kind": "nym_write_authors" if identities else "nym_write",
           "zipf_constant": 0.99, "corrupted_every": 50}
    ops = [Op(req, b"", valid)
           for req, valid in operations.make(
               seed, count, mix, {"identities": identities})]
    for i, op in enumerate(ops):
        op.due = op.sent = float(i)
        op.done = float(i) + 0.5
    return ops


@pytest.mark.parametrize("identities", [0, IDENTITIES])
@pytest.mark.parametrize("seed", [3, 2147483900, 77])
@pytest.mark.parametrize("n", [4, 7])
def test_unbroken_reference_is_correct(seed, n, identities):
    obs = controls.reference_pool(
        NAMES[:n], (n - 1) // 3, ops_for(seed, identities=identities),
        genesis(seed, identities), READY, STATS, tiny=False)
    values = check.compare(obs, genesis(seed, identities))["values"]
    assert check.verdict(values), values


@pytest.mark.parametrize("identities", [0, IDENTITIES])
@pytest.mark.parametrize("seed", [3, 2147483900, 77])
@pytest.mark.parametrize("control", controls.CONTROLS)
def test_control_is_not_correct(seed, control, identities):
    obs = controls.reference_pool(
        NAMES[:4], 1, ops_for(seed, identities=identities),
        genesis(seed, identities), READY, STATS, tiny=False,
        break_guarantee=control)
    values = check.compare(obs, genesis(seed, identities))["values"]
    assert not check.verdict(values), values


@pytest.mark.parametrize("stats,ready", [
    (dict(STATS, failed_batches=1), READY),
    (dict(STATS, step_downs={"ed25519": 1}), READY),
    (dict(STATS, kernel_backends={"ed25519": False}), READY),
    (None, READY),
    (STATS, {"device": {"platform": "cpu", "kind": "cpu", "count": 1}}),
])
def test_daemon_off_the_device_is_not_correct(stats, ready):
    obs = controls.reference_pool(NAMES[:4], 1, ops_for(5, 60), genesis(5),
                                  ready, stats, tiny=False)
    values = check.compare(obs, genesis(5))["values"]
    assert values["daemon_faults"] > 0 and not check.verdict(values)
