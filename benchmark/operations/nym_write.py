"""Signed NYM writes for fresh DIDs authored by the pool trustee: one
author, as indy-node's scripts/performance/perf_processes.py signs every
request with the one DID of its `-s` seed (from memory; PERF.md).
mix: {"corrupted_every": k}: every k-th write is corrupted (alternately
a flipped signature byte and another key's signature under the
trustee's identifier), starting in the middle of the first stretch, so
they are spread through the stream."""
import hashlib

from reference.codec import b58decode, b58encode
from traffic import Signer, trustee_seed


def make(seed: int, count: int, mix: dict, first_req_id=1):
    bad_every = mix["corrupted_every"]
    signer = Signer(trustee_seed(seed))
    intruder = Signer(hashlib.sha256(b"%d-intruder" % seed).digest())
    out = []
    n_bad = 0
    for i in range(count):
        dest = b58encode(hashlib.sha256(
            b"%d-nym-%d" % (seed, first_req_id + i)).digest()[:16])
        req = {"identifier": signer.identifier, "reqId": first_req_id + i,
               "protocolVersion": 2,
               "operation": {"type": "1", "dest": dest,
                             "verkey": "~" + dest}}
        bad = bad_every and i % bad_every == bad_every // 2
        if not bad:
            req["signature"] = signer.sign(req)
        elif n_bad % 2:
            req["signature"] = intruder.sign(req)
        else:
            sig = bytearray(b58decode(signer.sign(req)))
            sig[(i * 7) % 64] ^= 0x20
            req["signature"] = b58encode(bytes(sig))
        n_bad += bool(bad)
        out.append((req, not bad))
    return out
