"""Base58, canonical JSON and canonical msgpack as the pool's wire and
ledger formats define them (indy-plenum: base58 bitcoin alphabet, JSON
with sorted keys and no whitespace, msgpack with keys sorted at every
level)."""
import json

import msgpack

ALPHABET = "123456789ABCDEFGHJKLMNPQRSTUVWXYZabcdefghijkmnopqrstuvwxyz"
_INDEX = {c: i for i, c in enumerate(ALPHABET)}


def b58encode(data: bytes) -> str:
    n = int.from_bytes(data, "big")
    out = []
    while n:
        n, r = divmod(n, 58)
        out.append(ALPHABET[r])
    pad = len(data) - len(data.lstrip(b"\0"))
    return "1" * pad + "".join(reversed(out))


def b58decode(text: str) -> bytes:
    n = 0
    for c in text:
        n = n * 58 + _INDEX[c]
    pad = len(text) - len(text.lstrip("1"))
    return b"\0" * pad + n.to_bytes((n.bit_length() + 7) // 8, "big")


def canonical_json(obj) -> bytes:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"),
                      ensure_ascii=False).encode("utf-8")


def canonical_json_ascii(obj) -> bytes:
    return json.dumps(obj, sort_keys=True, separators=(",", ":")).encode()


def _sorted_deep(obj):
    if isinstance(obj, dict):
        return {k: _sorted_deep(obj[k]) for k in sorted(obj)}
    if isinstance(obj, (list, tuple)):
        return [_sorted_deep(v) for v in obj]
    return obj


def canonical_msgpack(obj) -> bytes:
    return msgpack.packb(_sorted_deep(obj), use_bin_type=True)
