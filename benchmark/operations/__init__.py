"""Operation makers, one module each, found by `operations.kind` in a
traffic file. Each has ``make(seed, count, mix) -> [(request, valid)]``:
`count` signed requests made from the seed alone, in the order they
will be released, each with whether the pool must accept it. `mix` is
the traffic file's `operations` object (the maker's own parameters).
Authors other than the trustee have to be in the domain genesis (the
reference resolves an author's verkey there): see uses_genesis.

What a maker cannot bring by itself: client.py confirms an operation by
f+1 matching REPLYs and check.py judges NYM writes, so a kind that is
confirmed or judged otherwise (a read with a state proof) is a
`benchmark` PR, not a data file (README.md)."""
import importlib


def _maker(mix: dict):
    return importlib.import_module("operations." + mix["kind"])


def uses_genesis(mix: dict) -> bool:
    """A maker that signs with the deployment's own identities says so
    (`USES_GENESIS = True`) and takes the configuration's `genesis`
    object ({"identities": K}; traffic.identity derives each) as a
    fourth argument; the others are called as they always were."""
    return getattr(_maker(mix), "USES_GENESIS", False)


def make(seed: int, count: int, mix: dict, genesis=None):
    if uses_genesis(mix):
        return _maker(mix).make(seed, count, mix, genesis)
    return _maker(mix).make(seed, count, mix)
