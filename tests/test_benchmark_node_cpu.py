"""Tier-1's window on benchmark/tests/test_node_cpu.py: the cases live
with the harness they test and are not collected by `pytest tests/`,
so they are imported here, unchanged — the node's CPU account (the
`cpu` counter samples and the `cpu_us` of `prod_tick` and `wire_rx`)
read from hand-made node dumps, nothing read where a program's dumps
lack them, and `wire_rx` spans that move no older reading.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))

from test_node_cpu import *  # noqa: E402,F401,F403
