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


def trustee_seed(seed: int) -> bytes:
    return hashlib.sha256(b"%d-trustee" % seed).digest()
