"""What a pool of validators must do with a stream of NYM writes, said
plainly: check each write's ed25519 signature against the author's
verkey on the ledger, append the valid ones to the domain ledger in the
order the pool chose, set one state leaf per DID. From that: the txn a
REPLY must carry, the ledger's RFC 6962 root and the state trie's root.

Inputs are the deployment's genesis file and the requests the generator
made; nothing here imports or reads anything the pool computed, except
the two numbers only the pool can choose (seqNo and txnTime of a
write), which arrive inside the answers being checked."""
import hashlib

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey)

from . import merkle, mpt
from .codec import (
    b58decode, b58encode, canonical_json, canonical_json_ascii,
    canonical_msgpack)

NYM = "1"


def full_verkey(did: str, verkey: str) -> bytes:
    """A verkey as the ledger stores it: abbreviated ("~" + the last 16
    bytes, the DID being the first 16) or whole."""
    if verkey.startswith("~"):
        return b58decode(did) + b58decode(verkey[1:])
    return b58decode(verkey)


def signing_bytes(request: dict) -> bytes:
    return canonical_json({k: v for k, v in request.items()
                           if k not in ("signature", "signatures")})


def signature_valid(request: dict, verkey: bytes) -> bool:
    try:
        sig = b58decode(request["signature"])
        Ed25519PublicKey.from_public_bytes(verkey).verify(
            sig, signing_bytes(request))
        return True
    except (InvalidSignature, ValueError, KeyError):
        return False


def expected_txn(request: dict, seq_no: int, txn_time: int) -> dict:
    """The ledger entry of one ordered write request."""
    op = dict(request["operation"])
    txn_type = op.pop("type")
    payload_state = {k: v for k, v in request.items()
                     if k not in ("signature", "signatures")}
    meta = {"digest": hashlib.sha256(canonical_json(request)).hexdigest(),
            "payloadDigest": hashlib.sha256(
                canonical_json(payload_state)).hexdigest(),
            "from": request["identifier"], "reqId": request["reqId"]}
    payload = {"type": txn_type, "data": op, "metadata": meta}
    if request.get("protocolVersion") is not None:
        payload["protocolVersion"] = request["protocolVersion"]
    return {"txn": payload,
            "txnMetadata": {"seqNo": seq_no, "txnTime": txn_time},
            "reqSignature": {"type": "ED25519", "values": [
                {"from": request["identifier"],
                 "value": request["signature"]}]},
            "ver": "1"}


def state_leaf(txn: dict, existing=None):
    """(key, value) the domain state holds after a NYM txn."""
    data = txn["txn"]["data"]
    md = txn.get("txnMetadata") or {}
    value = dict(existing or {})
    value["identifier"] = txn["txn"]["metadata"].get("from")
    for field in ("role", "verkey"):
        if field in data:
            value[field] = data[field]
    value.setdefault("seqNo", md.get("seqNo"))
    leaf = {"val": value, "lsn": md.get("seqNo"), "lut": md.get("txnTime")}
    return data["dest"].encode(), canonical_json_ascii(leaf), value


class Replay:
    """Domain ledger and state after genesis plus the given txns."""

    def __init__(self, genesis_domain_txns):
        self.leaf_hashes = []
        self.state = {}
        self.records = {}
        for i, txn in enumerate(genesis_domain_txns):
            txn = dict(txn, txnMetadata=dict(txn.get("txnMetadata") or {},
                                             seqNo=i + 1))
            self.append(txn)

    def append(self, txn: dict) -> None:
        self.leaf_hashes.append(merkle.leaf_hash(canonical_msgpack(txn)))
        if txn["txn"]["type"] == NYM:
            did = txn["txn"]["data"]["dest"]
            key, value, record = state_leaf(txn, self.records.get(did))
            self.state[key] = value
            self.records[did] = record

    def verkey_of(self, did: str):
        record = self.records.get(did)
        if not record or not record.get("verkey"):
            return None
        return full_verkey(did, record["verkey"])

    @property
    def size(self) -> int:
        return len(self.leaf_hashes)

    def ledger_root(self) -> str:
        return b58encode(merkle.root_of_hashes(self.leaf_hashes))

    def state_root(self) -> str:
        return b58encode(mpt.root(self.state))
