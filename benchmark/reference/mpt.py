"""Hexary Merkle Patricia Trie root of a key → value map (Ethereum
yellow paper appendix D, with SHA3-256 as indy-plenum's state uses):
built top-down from the sorted keys, no store, no updates."""
import hashlib


def rlp(item) -> bytes:
    if isinstance(item, (bytes, bytearray)):
        b = bytes(item)
        if len(b) == 1 and b[0] < 0x80:
            return b
        return _length(len(b), 0x80) + b
    body = b"".join(rlp(x) for x in item)
    return _length(len(body), 0xC0) + body


def _length(n: int, offset: int) -> bytes:
    if n < 56:
        return bytes([offset + n])
    digits = n.to_bytes((n.bit_length() + 7) // 8, "big")
    return bytes([offset + 55 + len(digits)]) + digits


def _hex_prefix(nibbles, terminal: bool) -> bytes:
    flag = 2 if terminal else 0
    if len(nibbles) % 2:
        nibbles = [flag | 1] + list(nibbles)
    else:
        nibbles = [flag, 0] + list(nibbles)
    return bytes((nibbles[i] << 4) | nibbles[i + 1]
                 for i in range(0, len(nibbles), 2))


def _ref(node):
    """A node as its parent holds it: itself if its encoding is short,
    else the hash of the encoding."""
    enc = rlp(node)
    return node if len(enc) < 32 else hashlib.sha3_256(enc).digest()


def _build(pairs, depth):
    """pairs: sorted (nibbles, value), all sharing nibbles[:depth]."""
    if len(pairs) == 1:
        nib, value = pairs[0]
        return [_hex_prefix(nib[depth:], True), value]
    first, last = pairs[0][0], pairs[-1][0]
    common = depth
    while common < len(first) and common < len(last) \
            and first[common] == last[common]:
        common += 1
    if common > depth:
        return [_hex_prefix(first[depth:common], False),
                _ref(_build(pairs, common))]
    slots = [b""] * 17
    lo = 0
    if len(pairs[0][0]) == depth:
        slots[16] = pairs[0][1]
        lo = 1
    while lo < len(pairs):
        nibble = pairs[lo][0][depth]
        hi = lo
        while hi < len(pairs) and pairs[hi][0][depth] == nibble:
            hi += 1
        slots[nibble] = _ref(_build(pairs[lo:hi], depth + 1))
        lo = hi
    return slots


def root(mapping) -> bytes:
    """State root of {key bytes: value bytes}; empty values are absent."""
    pairs = sorted(([int(c, 16) for c in k.hex()], v)
                   for k, v in mapping.items() if v)
    if not pairs:
        return hashlib.sha3_256(rlp(b"")).digest()
    return hashlib.sha3_256(rlp(_build(pairs, 0))).digest()
