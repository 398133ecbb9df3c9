"""Tier-1's window on benchmark/tests/test_trie_frames.py: the cases
live with the harness they test and are not collected by `pytest
tests/`, so they are imported here, unchanged — the two metrics of the
trie's framed write-through read the arguments of `fused_dispatch`
spans on hand-made node dumps, and read nothing where a program's
spans lack them.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))

from test_trie_frames import *  # noqa: E402,F401,F403
