"""Signed NYM writes in which a DID's owner rewrites its own record: the
operation of indy-sdk's how-to "Rotate a key" (a NYM whose submitter is
its target, carrying a verkey, signed by the key the ledger holds; from
memory, no network here), which upstream's nym_handler.py handles as
the update of an existing nym. Which of the K genesis identities is
rewritten follows YCSB core workload A's update operation:
requestdistribution=zipfian, constant 0.99, over recordcount records.
The draw and the permutation of ranks are nym_write_authors.authors',
so write i of a seed has the author it has in authors-burst: what
differs between the two traffic mixes is what is written.

mix: {"zipf_constant": c, "corrupted_every": k}. Write i is

    {"identifier": d, "operation": {"type": "1", "dest": d, "verkey": v}}

signed by d's genesis key. v is d's WHOLE verkey (base58 of its 32
bytes) if an even number of valid writes of d came earlier in the
stream, else the abbreviated form the genesis holds ("~" + the last 16
bytes): in stream order every rewrite changes the string the ledger
stores and none changes the key, so check.compare, which judges every
signature against the verkey the genesis holds, can judge every write
whatever order the pool gives those in flight. A write with new key
material needs a reference whose verdict depends on that order: a
`benchmark` PR (PERF.md, Open questions). Every k-th write is
corrupted, starting in the middle of the first stretch: alternately a
flipped signature byte, and a valid signature by ANOTHER genesis
identity under d's identifier (somebody else rewriting d's record).
A corrupted write is refused and counts as no write of d."""
import collections

from operations.nym_write_authors import authors
from reference.codec import b58decode, b58encode
from traffic import identity

USES_GENESIS = True


def verkey_forms(signer):
    """(whole, abbreviated): the two strings a ledger may hold for
    this signer's one key."""
    return (b58encode(signer.verkey),
            "~" + b58encode(signer.verkey[16:]))


def make(seed: int, count: int, mix: dict, genesis: dict, first_req_id=1):
    identities = genesis["identities"]
    if identities < 2:
        raise ValueError("a write signed by ANOTHER identity needs two")
    bad_every = mix["corrupted_every"]
    signers = {}

    def signer(index):
        """(the identity's signer, its verkey's two forms)"""
        if index not in signers:
            made = identity(seed, index)
            signers[index] = (made, verkey_forms(made))
        return signers[index]

    out = []
    n_bad = 0
    rewrites = collections.Counter()    # valid writes so far, per DID
    for i, index in enumerate(
            authors(seed, count, identities, mix["zipf_constant"])):
        owner, forms = signer(index)
        req = {"identifier": owner.identifier, "reqId": first_req_id + i,
               "protocolVersion": 2,
               "operation": {"type": "1", "dest": owner.identifier,
                             "verkey": forms[rewrites[index] % 2]}}
        bad = bad_every and i % bad_every == bad_every // 2
        if not bad:
            req["signature"] = owner.sign(req)
            rewrites[index] += 1
        elif n_bad % 2:
            other, _ = signer(
                (index + 1 + i % (identities - 1)) % identities)
            req["signature"] = other.sign(req)
        else:
            sig = bytearray(b58decode(owner.sign(req)))
            sig[(i * 7) % 64] ^= 0x20
            req["signature"] = b58encode(bytes(sig))
        n_bad += bool(bad)
        out.append((req, not bad))
    return out
