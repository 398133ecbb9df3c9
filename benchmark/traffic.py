"""The signer of a window's operations and the seeds they come from.
Signing is done here, with OpenSSL through `cryptography`, not by the
program's signer; the operations themselves are made by the modules of
operations/."""
import hashlib

from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey)

from reference.codec import b58encode, canonical_json


class Signer:
    def __init__(self, seed: bytes):
        self._key = Ed25519PrivateKey.from_private_bytes(seed)
        self.verkey = self._key.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw)
        self.identifier = b58encode(self.verkey[:16])

    def sign(self, request: dict) -> str:
        return b58encode(self._key.sign(canonical_json(request)))

    def genesis_nym(self, role=None) -> dict:
        """This key's NYM as a domain genesis file holds it: the DID is
        the first half of the public key, the verkey abbreviated to the
        second."""
        data = {"dest": self.identifier,
                "verkey": "~" + b58encode(self.verkey[16:])}
        if role is not None:
            data["role"] = role
        return {"reqSignature": {}, "txn": {"data": data, "metadata": {},
                                            "type": "1"},
                "txnMetadata": {}, "ver": "1"}


def trustee_seed(seed: int) -> bytes:
    return hashlib.sha256(b"%d-trustee" % seed).digest()


def identity(seed: int, index: int) -> Signer:
    """Identity `index` of a configuration's `genesis.identities`, for
    the run's seed: pool.py writes its role-less NYM into the domain
    genesis, a maker that is told the genesis signs with it."""
    return Signer(hashlib.sha256(
        b"%d-identity-%d" % (seed, index)).digest())
