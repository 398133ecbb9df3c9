"""readers/warm.py on a hand-made run: a field of the warm-up's record,
None where the run took no warm-up or the field is not in it.

    python -m pytest benchmark/tests/test_warm.py -q
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from readers import warm  # noqa: E402

with open(os.path.join(HERE, "metrics", "daemon_first_launch_s.json")) as f:
    SPEC = json.load(f)


def test_reads_the_first_launch_of_the_warm_up():
    run = {"warm": {"first_launch_s": 4.25, "steady_launch_s": 0.05,
                    "device_items": 8192, "device_launches": 2}}
    assert warm.read(SPEC, run) == 4.25
    assert warm.read({"field": "steady_launch_s"}, run) == 0.05


@pytest.mark.parametrize("run", [
    {}, {"warm": {}}, {"warm": None}, {"warm": {"steady_launch_s": 0.05}}])
def test_nothing_to_read_is_none(run):
    assert warm.read(SPEC, run) is None


def test_the_metric_is_declared_and_found_by_name():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {m["name"]: m for m in bench["per_layer"]}[
        "daemon_first_launch_s"]
    assert entry == {
        "name": "daemon_first_launch_s", "unit": "s", "better": "lower",
        "source": "host_clock",
        "layer": "verify daemon (server/verify_daemon.py)",
        "moves": "setup_s"}
    assert SPEC["reader"] == "warm"
