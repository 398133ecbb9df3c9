"""Native-trie wrapper: the C MPT (native/mpt_c.c) behind the same
interface as state/trie.py's Trie, so PruningState can swap backends.

The C module owns the node blobs (sha3 → RLP) and does all per-node
work; this wrapper keeps the durable-KV contract identical to the
Python trie: every node created by an operation is written through to
the KV store before the call returns, and on a node miss (fresh process
over an existing store) the C side hydrates lazily through a callback
into the same KV. The nodes of one operation reach the store in one
`setBatch`, which a durable store writes as one atomic frame with one
flush; a frame torn by a crash is dropped whole on reopen, and that
loses nothing a committed root names, because the trie is
content-addressed and the committed root is stored only at commit
(`PruningState.commit`). Roots are bit-identical to the Python
implementation (cross-checked in tests/test_mpt_native.py) — they are
consensus state.
"""
from __future__ import annotations

from bisect import bisect_right
from itertools import accumulate
from typing import List, Optional, Tuple

from plenum_tpu.native import try_load_ext

_mpt = try_load_ext("mpt_c")
if _mpt is None:
    # honor the PLENUM_TPU_NO_NATIVE kill-switch (and missing-compiler
    # environments): PruningState catches this import failure and falls
    # back to the Python trie
    raise ImportError("native MPT unavailable or disabled")

BLANK_ROOT = _mpt.blank_root()

# The most bytes of kvlog records (8-byte header, key, blob) one frame
# carries. A drain past it goes out as consecutive frames: a genesis
# load drains every node of its trie at once (about 150,000 for 100,005
# NYMs, ten times that at 1,000,000), and the cap bounds the packed
# buffer a frame is built in, far under the 4 GiB a frame's u32 length
# can state.
_FRAME_BYTES = 64 << 20


def _frames(nodes) -> list:
    """`nodes` [(hash, blob)] cut into consecutive slices of at most
    _FRAME_BYTES of records each (a single larger node goes alone)."""
    ends = list(accumulate(8 + len(h) + len(b) for h, b in nodes))
    frames, start, base = [], 0, 0
    while start < len(nodes):
        stop = max(bisect_right(ends, base + _FRAME_BYTES), start + 1)
        frames.append(nodes[start:stop])
        start, base = stop, ends[stop - 1]
    return frames


class NativeTrie:
    """Drop-in for state/trie.py's Trie over a KeyValueStorage."""

    def __init__(self, store, root_hash: Optional[bytes] = None):
        self._store = store

        def _miss(h: bytes):
            try:
                return bytes(store.get(h))
            except KeyError:
                return None

        self._h = _mpt.new(_miss)
        self.root_hash = bytes(root_hash) if root_hash is not None \
            else BLANK_ROOT

    # ---------------------------------------------------------- write

    def _flush(self) -> Tuple[int, int]:
        """Write the operation's new nodes through, one store batch per
        frame → (nodes written, of them in batch frames)."""
        nodes = _mpt.drain(self._h)
        framed = 0
        for frame in _frames(nodes):
            self._store.setBatch(frame)
            framed += len(frame)
        return len(nodes), framed

    def set(self, key: bytes, value: bytes):
        self.root_hash = _mpt.set(self._h, self.root_hash, bytes(key),
                                  bytes(value))
        self._flush()

    def set_many(self, pairs) -> Tuple[int, int]:
        """Batched set (empty value deletes): one deferred-hash C pass —
        path nodes shared by the batch hash once, not once per key.
        Only the final root is a readable snapshot. → (nodes written,
        of them in batch frames)."""
        self.root_hash = _mpt.set_many(self._h, self.root_hash,
                                       list(pairs))
        return self._flush()

    def delete(self, key: bytes):
        self.root_hash = _mpt.delete(self._h, self.root_hash, bytes(key))
        self._flush()

    # ----------------------------------------------------------- read

    def get(self, key: bytes) -> Optional[bytes]:
        return _mpt.get(self._h, self.root_hash, bytes(key))

    def get_at_root(self, root_hash: bytes, key: bytes) -> Optional[bytes]:
        return _mpt.get(self._h, bytes(root_hash), bytes(key))

    def produce_spv_proof(self, key: bytes,
                          root_hash: Optional[bytes] = None) -> List[bytes]:
        root = root_hash if root_hash is not None else self.root_hash
        return _mpt.proof(self._h, bytes(root), bytes(key))

    def items(self, root_hash: Optional[bytes] = None):
        root = root_hash if root_hash is not None else self.root_hash
        return iter(_mpt.items(self._h, bytes(root)))
