"""A configuration's `genesis`: the identities the harness writes into
the domain genesis are the same in every process, the program's own
genesis path loads them into the ledger and state the reference's
Replay reaches, the node resolves their verkeys, and the maker that
signs with them draws its authors by the law its traffic file states.
No pool is started: one node is built in this process from the files
Pool.generate wrote.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_genesis.py -q
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
sys.path.insert(1, ROOT)

import check  # noqa: E402
import controls  # noqa: E402
import operations  # noqa: E402
import traffic  # noqa: E402
from client import Op  # noqa: E402
from pool import Pool, Procs  # noqa: E402
from reference import pool as ref  # noqa: E402
from reference.codec import b58encode  # noqa: E402

MIX = {"kind": "nym_write_authors", "zipf_constant": 0.99,
       "corrupted_every": 50}
CONFIG = {"nodes": 4, "genesis": {"identities": 1000}}


def test_identities_of_a_seed_are_the_same_in_another_process():
    code = ("import sys; sys.path.insert(0, %r); import traffic; "
            "print([traffic.identity(2147483900, i).identifier "
            "for i in (0, 7, 99999)])" % HERE)
    there = subprocess.run([sys.executable, "-c", code], check=True,
                           capture_output=True, text=True).stdout
    here = [traffic.identity(2147483900, i).identifier
            for i in (0, 7, 99999)]
    assert there.strip() == str(here)
    assert len(set(here)) == 3
    assert traffic.identity(2147483901, 0).identifier != here[0]


@pytest.fixture(scope="module")
def booted(tmp_path_factory):
    """Pool.generate's files with 1,000 identities, and node Alpha built
    from them as the start script builds it."""
    from plenum_tpu.bootstrap import build_networked_node
    from plenum_tpu.common.config import Config
    base = str(tmp_path_factory.mktemp("pool"))
    pool = Pool(Procs(), base, CONFIG, False, 23400)
    pool.generate(77)
    node = build_networked_node("Alpha", base, config=Config())
    return pool, node.node


def test_genesis_lines_have_the_shape_of_the_stewards(booted):
    pool, _node = booted
    txns = pool.genesis_domain_txns()
    assert len(txns) == 1 + 4 + 1000
    steward, first = txns[4], txns[5]
    assert "role" in steward["txn"]["data"]
    assert "role" not in first["txn"]["data"]
    strip = lambda t: {k: (sorted(v) if isinstance(v, dict) else v)  # noqa
                       for k, v in t.items() if k != "txn"}
    assert strip(steward) == strip(first)
    assert first["txn"]["data"] == {
        "dest": traffic.identity(77, 0).identifier,
        "verkey": "~" + b58encode(traffic.identity(77, 0).verkey[16:])}


def test_node_loads_them_and_replay_reaches_the_same_roots(booted):
    from plenum_tpu.common.serializers.base58 import b58encode as b58
    pool, node = booted
    ledger = node.db_manager.get_ledger(1)
    state = node.db_manager.get_state(1)
    replay = ref.Replay(pool.genesis_domain_txns())
    assert ledger.size == replay.size == 1005
    assert str(ledger.root_hash) == replay.ledger_root()
    assert b58(state.committedHeadHash) == replay.state_root()


@pytest.mark.parametrize("index", [0, 500, 999])
def test_node_resolves_an_identitys_verkey(booted, index):
    pool, node = booted
    signer = traffic.identity(77, index)
    verkey = node._verkey_from_domain_state(signer.identifier)
    assert ref.full_verkey(signer.identifier, verkey) == signer.verkey
    assert ref.Replay(pool.genesis_domain_txns()).verkey_of(
        signer.identifier) == signer.verkey


def test_configuration_without_genesis_adds_nothing(tmp_path):
    pool = Pool(Procs(), str(tmp_path), {"nodes": 4}, False, 23500)
    pool.generate(77)
    assert len(pool.genesis_domain_txns()) == 1 + 4


def test_authors_follow_the_zipfian():
    from operations import nym_write_authors
    k, draws = 1000, 20000
    got = collections.Counter(
        nym_write_authors.authors(5, draws, k, 0.99))
    h = sum(r ** -0.99 for r in range(1, k + 1))
    top = got.most_common(2)
    assert abs(top[0][1] / draws - 1 / h) < 0.1 / h
    assert abs(top[1][1] / draws - 2 ** -0.99 / h) < 0.15 * 2 ** -0.99 / h
    assert nym_write_authors.authors(5, 50, k, 0.99) \
        == nym_write_authors.authors(5, 50, k, 0.99)
    # another seed, another permutation: the hot author moves
    other = collections.Counter(
        nym_write_authors.authors(6, draws, k, 0.99))
    assert other.most_common(1)[0][0] != top[0][0]


def made(seed, count=400, identities=1000):
    return operations.make(seed, count, MIX, {"identities": identities})


def test_corrupted_writes_of_both_kinds():
    seed = 2147483900
    signers = [traffic.identity(seed, i) for i in range(1000)]
    by_did = {s.identifier: s.verkey for s in signers}
    bad = [req for req, valid in made(seed) if not valid]
    assert len(bad) == 8
    for n, req in enumerate(bad):
        own = by_did[req["identifier"]]
        assert not ref.signature_valid(req, own)
        signed_by = [did for did, vk in by_did.items()
                     if ref.signature_valid(req, vk)]
        if n % 2:
            # a valid signature, by ANOTHER identity of the genesis
            assert len(signed_by) == 1
            assert signed_by[0] != req["identifier"]
        else:
            assert signed_by == []
    for req, valid in made(seed):
        if valid:
            assert ref.signature_valid(req, by_did[req["identifier"]])


def test_maker_without_identities_is_refused_before_the_pool():
    assert operations.uses_genesis(MIX)
    assert not operations.uses_genesis({"kind": "nym_write"})
    # nym_write takes no genesis and is called as it always was
    assert operations.make(3, 2, {"kind": "nym_write",
                                  "corrupted_every": 100},
                           {"identities": 9}) \
        == operations.make(3, 2, {"kind": "nym_write",
                                  "corrupted_every": 100})
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["workloads"].append({"name": "x", "config": "pool4",
                               "traffic": "authors-burst", "chips": 1})
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = ("import sys, json; sys.argv = ['run.py', '--workload', 'x', "
            "'--tiny']; sys.path.insert(0, %r); import run; "
            "run.load_json = lambda p, _l=run.load_json: "
            "json.loads(%r) if p.endswith('BENCHMARK.json') else _l(p); "
            "sys.exit(run.main())" % (HERE, json.dumps(bench)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "states no genesis" in proc.stderr and not proc.stdout.strip()


def test_author_outside_the_genesis_is_a_disagreement():
    """One valid-marked write signed by an identity that the genesis
    does not hold: the reference cannot resolve its author."""
    seed, names = 11, ["Alpha", "Beta", "Gamma", "Delta"]
    genesis = [traffic.Signer(traffic.trustee_seed(seed)).genesis_nym("0")]
    genesis += [traffic.identity(seed, i).genesis_nym() for i in range(50)]
    ops = [Op(req, b"", valid) for req, valid in made(seed, 60, 50)]
    outsider = traffic.identity(seed, 50)
    req = dict(ops[7].request, identifier=outsider.identifier)
    del req["signature"]
    req["signature"] = outsider.sign(req)
    ops[7] = Op(req, b"", True)
    for i, op in enumerate(ops):
        op.due = op.sent = float(i)
        op.done = float(i) + 0.5
    ready = {"device": {"platform": "tpu", "kind": "TPU v5 lite",
                        "count": 1}}
    stats = {"device_launches": 3, "failed_batches": 0, "step_downs": {},
             "mesh": {"dispatches": 3},
             "kernel_backends": {"ed25519": True}}
    obs = controls.reference_pool(names, 1, ops, genesis, ready, stats,
                                  tiny=False)
    values = check.compare(obs, genesis)["values"]
    assert values["reference_disagrees"] == 1
    assert not check.verdict(values)
