"""Deterministic signature fixtures shared by bench.py, __graft_entry__,
and tests — one generator so every harness exercises the same data path.
"""
from typing import List, Tuple

import numpy as np


def make_signed_batch(count: int, seed: int = 0, unique: int = None,
                      msg_prefix: bytes = b"fixture"
                      ) -> Tuple[List[bytes], List[bytes], List[bytes]]:
    """→ (msgs, sigs, verkeys), `unique` distinct keypairs tiled to
    `count` entries. Keygen+signing ride OpenSSL when available (RFC
    8032 Ed25519 is deterministic, so outputs are bit-identical to the
    pure-Python reference path) — at count=10k+ the pure-Python path
    costs minutes, the OpenSSL one milliseconds."""
    from plenum_tpu.crypto.signer import SimpleSigner

    unique = min(count, unique or count)
    rng = np.random.RandomState(seed)
    msgs, sigs, vks = [], [], []
    for i in range(unique):
        kseed = bytes(rng.randint(0, 256, 32, dtype=np.uint8))
        signer = SimpleSigner(seed=kseed)   # OpenSSL path w/ py fallback
        msg = msg_prefix + b"-%d" % i
        msgs.append(msg)
        sigs.append(signer.sign_bytes(msg))
        vks.append(signer.verraw)
    reps = (count + unique - 1) // unique
    return ((msgs * reps)[:count], (sigs * reps)[:count],
            (vks * reps)[:count])


_L = 2 ** 252 + 27742317777372353535851937790883648493   # group order


def make_known_answer_batch(valid: int = 16, seed: int = 0
                            ) -> Tuple[List[bytes], List[bytes], List[bytes]]:
    """→ (msgs, sigs, verkeys): `valid` valid signatures, then each of
    them corrupted three ways: a flipped bit in R, ``s + L`` (the same
    point, not canonical: RFC 8032 refuses it), another message. What a
    kernel taken from the store (ops/kernel_store.py) has to judge as
    the host reference does before it serves."""
    msgs, sigs, vks = make_signed_batch(valid, seed=seed,
                                        msg_prefix=b"known-answer")
    flipped = [bytes([sig[0] ^ 1]) + sig[1:] for sig in sigs]
    plus_l = [sig[:32] + (int.from_bytes(sig[32:], "little") + _L)
              .to_bytes(32, "little") for sig in sigs]
    return (msgs * 3 + [m + b"!" for m in msgs],
            sigs + flipped + plus_l + sigs, vks * 4)
