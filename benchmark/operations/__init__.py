"""Operation makers, one module each, found by `operations.kind` in a
traffic file. Each has ``make(seed, count, mix) -> [(request, valid)]``:
`count` signed requests made from the seed alone, in the order they
will be released, each with whether the pool must accept it. `mix` is
the traffic file's `operations` object (the maker's own parameters).

What a maker cannot bring by itself: client.py confirms an operation by
f+1 matching REPLYs and check.py judges NYM writes, so a kind that is
confirmed or judged otherwise (a read with a state proof) is a
`benchmark` PR, not a data file (README.md)."""
import importlib


def make(seed: int, count: int, mix: dict):
    mod = importlib.import_module("operations." + mix["kind"])
    return mod.make(seed, count, mix)
