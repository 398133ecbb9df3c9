"""Tier-1's window on benchmark/tests/test_genesis.py (ISSUE 28's
missing file): the cases live with the harness they test and are not
collected by `pytest tests/`, so they are imported here, unchanged —
the identities a configuration's `genesis` states are the same in every
process, a node built from the files Pool.generate wrote loads them
into the ledger and state the reference's Replay reaches and resolves
their verkeys, and the maker that signs with them draws its authors by
the law its traffic file states.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))

from test_genesis import *  # noqa: E402,F401,F403
