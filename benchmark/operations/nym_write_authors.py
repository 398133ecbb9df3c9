"""Signed NYM writes for fresh DIDs, each authored by one of the K
role-less identities of the configuration's genesis: the authors follow
YCSB's zipfian request distribution (ZipfianGenerator's law: rank r of
1..K is drawn with probability proportional to 1 / r**zipf_constant),
and a permutation made from the seed maps ranks to identity indices, so
that the hot authors are not neighbours in the genesis file.
mix: {"zipf_constant": c, "corrupted_every": k}: every k-th write is
corrupted, starting in the middle of the first stretch: alternately a
flipped signature byte, and a valid signature by ANOTHER genesis
identity under the author's identifier. No author has a role, so every
valid write is the creation of a role-less NYM, which anyone on the
ledger may make."""
import hashlib
import itertools
import random

from reference.codec import b58decode, b58encode
from traffic import identity

USES_GENESIS = True


def authors(seed: int, count: int, identities: int, constant: float):
    """Identity index of each of `count` writes."""
    rng = random.Random("%d-authors" % seed)
    by_rank = list(range(identities))
    rng.shuffle(by_rank)
    cumulative = list(itertools.accumulate(
        (r + 1) ** -constant for r in range(identities)))
    ranks = rng.choices(range(identities), cum_weights=cumulative, k=count)
    return [by_rank[r] for r in ranks]


def make(seed: int, count: int, mix: dict, genesis: dict, first_req_id=1):
    identities = genesis["identities"]
    if identities < 2:
        raise ValueError("a write signed by ANOTHER identity needs two")
    bad_every = mix["corrupted_every"]
    signers = {}

    def signer(index):
        if index not in signers:
            signers[index] = identity(seed, index)
        return signers[index]

    out = []
    n_bad = 0
    for i, index in enumerate(
            authors(seed, count, identities, mix["zipf_constant"])):
        author = signer(index)
        dest = b58encode(hashlib.sha256(
            b"%d-nym-%d" % (seed, first_req_id + i)).digest()[:16])
        req = {"identifier": author.identifier, "reqId": first_req_id + i,
               "protocolVersion": 2,
               "operation": {"type": "1", "dest": dest,
                             "verkey": "~" + dest}}
        bad = bad_every and i % bad_every == bad_every // 2
        if not bad:
            req["signature"] = author.sign(req)
        elif n_bad % 2:
            other = signer((index + 1 + i % (identities - 1)) % identities)
            req["signature"] = other.sign(req)
        else:
            sig = bytearray(b58decode(author.sign(req)))
            sig[(i * 7) % 64] ^= 0x20
            req["signature"] = b58encode(bytes(sig))
        n_bad += bool(bad)
        out.append((req, not bad))
    return out
