"""RFC 6962 Merkle tree hash over a list of leaves (SHA-256, 0x00 leaf
prefix, 0x01 node prefix, split at the largest power of two below n)."""
import hashlib


def leaf_hash(data: bytes) -> bytes:
    return hashlib.sha256(b"\x00" + data).digest()


def root_of_hashes(hashes) -> bytes:
    """Root over leaf hashes, by the stack form of the recursion: equal
    to MTH(D[n]) of RFC 6962 section 2.1."""
    if not hashes:
        return hashlib.sha256().digest()
    stack = []  # (height, hash): complete subtrees, left to right
    for h in hashes:
        height = 0
        while stack and stack[-1][0] == height:
            _, left = stack.pop()
            h = hashlib.sha256(b"\x01" + left + h).digest()
            height += 1
        stack.append((height, h))
    _, acc = stack.pop()
    while stack:
        _, left = stack.pop()
        acc = hashlib.sha256(b"\x01" + left + acc).digest()
    return acc
