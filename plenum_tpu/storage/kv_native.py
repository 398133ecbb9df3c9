"""Native log-structured KV store — ctypes bridge to
plenum_tpu/native/kvlog.c (the framework's RocksDB-equivalent,
reference storage/kv_store_rocksdb.py:15).

Same .kvlog on-disk format as KeyValueStorageFile, so the two backends
open each other's files; unlike the Python backend, VALUES STAY ON
DISK — only the C index (key bytes + offsets) is resident. A key cache
on the Python side answers membership and ordered iteration: keys
written since the last ordered read wait in a plain set (one C-level
add per put, one bulk update per batch frame) and join the sorted view
at the next `iterator()`; on open every key of the C index snapshot
starts there.
"""
from __future__ import annotations

import ctypes
import os
import struct
from typing import Iterable, Iterator, Tuple

try:
    from sortedcontainers import SortedSet
except ImportError:            # soft dep: stdlib fallback
    from plenum_tpu.utils.sorted_fallback import SortedSet

from plenum_tpu.storage.kv_store import KeyValueStorage, to_bytes

_REC = struct.Struct("<II")     # record header: key length, value length

_lib = None


def _get_lib():
    global _lib
    if _lib is None:
        from plenum_tpu.native import build_and_load
        lib = build_and_load("kvlog")
        lib.kv_open.argtypes = [ctypes.c_char_p]
        lib.kv_open.restype = ctypes.c_void_p
        lib.kv_close.argtypes = [ctypes.c_void_p]
        lib.kv_flush.argtypes = [ctypes.c_void_p]
        lib.kv_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                               ctypes.c_uint32, ctypes.c_char_p,
                               ctypes.c_uint32]
        lib.kv_put.restype = ctypes.c_int
        lib.kv_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                               ctypes.c_uint32, ctypes.c_char_p,
                               ctypes.c_uint64]
        lib.kv_get.restype = ctypes.c_long
        lib.kv_remove.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_uint32]
        lib.kv_remove.restype = ctypes.c_int
        lib.kv_batch_begin.argtypes = [ctypes.c_void_p]
        lib.kv_batch_begin.restype = ctypes.c_int
        lib.kv_batch_end.argtypes = [ctypes.c_void_p]
        lib.kv_batch_end.restype = ctypes.c_int
        lib.kv_apply_packed.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_uint64]
        lib.kv_apply_packed.restype = ctypes.c_int
        lib.kv_count.argtypes = [ctypes.c_void_p]
        lib.kv_count.restype = ctypes.c_uint64
        lib.kv_garbage.argtypes = [ctypes.c_void_p]
        lib.kv_garbage.restype = ctypes.c_uint64
        lib.kv_keys_size.argtypes = [ctypes.c_void_p]
        lib.kv_keys_size.restype = ctypes.c_uint64
        lib.kv_keys.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.kv_compact.argtypes = [ctypes.c_void_p]
        lib.kv_compact.restype = ctypes.c_int
        _lib = lib
    return _lib


def available() -> bool:
    try:
        _get_lib()
        return True
    except Exception:
        return False


class KeyValueStorageNative(KeyValueStorage):
    def __init__(self, db_dir: str, db_name: str):
        os.makedirs(db_dir, exist_ok=True)
        self._path = os.path.join(db_dir, db_name + ".kvlog")
        self._lib = _get_lib()
        self._db = self._lib.kv_open(self._path.encode())
        if not self._db:
            raise IOError("kvlog open failed: {}".format(self._path))
        self._closed = False
        # live keys = _keys (sorted view) | _fresh (written since the
        # last ordered read); a key may sit in both until the merge
        self._keys = SortedSet()
        self._fresh = set(self._snapshot_keys())

    def _handle(self):
        """The C engine dereferences the handle unchecked — a NULL from
        a closed store would segfault the process, so guard here."""
        if self._closed or not self._db:
            raise ValueError("operation on closed kvlog store {}".format(
                self._path))
        return self._db

    def _snapshot_keys(self):
        size = self._lib.kv_keys_size(self._handle())
        if size == 0:
            return []
        buf = ctypes.create_string_buffer(size)
        self._lib.kv_keys(self._handle(), buf)
        keys, pos, raw = [], 0, buf.raw
        while pos + 4 <= size:
            (klen,) = struct.unpack_from("<I", raw, pos)
            keys.append(raw[pos + 4:pos + 4 + klen])
            pos += 4 + klen
        return keys

    # ------------------------------------------------------------- ops

    def put(self, key, value):
        key, value = to_bytes(key), to_bytes(value)
        if self._lib.kv_put(self._handle(), key, len(key), value,
                            len(value)) != 0:
            raise IOError("kvlog put failed")
        self._fresh.add(key)

    def get(self, key) -> bytes:
        key = to_bytes(key)
        cap = 4096
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = self._lib.kv_get(self._handle(), key, len(key), buf, cap)
            if n < 0:
                if n == -2:
                    raise IOError("kvlog read failed")
                raise KeyError(key)
            if n <= cap:
                return buf.raw[:n]
            cap = n

    def get_or_none(self, key):
        key = to_bytes(key)
        if key not in self._fresh and key not in self._keys:
            return None
        return self.get(key)

    def remove(self, key):
        key = to_bytes(key)
        if self._lib.kv_remove(self._handle(), key, len(key)) != 0:
            raise IOError("kvlog remove failed")
        self._forget(key)

    def _forget(self, key: bytes):
        self._fresh.discard(key)
        self._keys.discard(key)

    def _write_frame(self, parts):
        """One FFI call and one flush: the packed records applied by the
        engine as a single atomic batch frame."""
        packed = b"".join(parts)
        if self._lib.kv_apply_packed(self._handle(), packed,
                                     len(packed)) != 0:
            raise IOError("kvlog batch failed")

    def setBatch(self, batch: Iterable[Tuple]):
        """Records packed host-side into the wire format and written as
        one frame; its keys join the key cache in one bulk update."""
        pairs = [(to_bytes(k), to_bytes(v)) for k, v in batch]
        pack = _REC.pack
        self._write_frame([pack(len(k), len(v)) + k + v for k, v in pairs])
        self._fresh.update([k for k, _ in pairs])

    def do_ops_in_batch(self, batch: Iterable[Tuple]):
        """batch of ('put', key, value) / ('remove', key) — one atomic
        on-disk frame, like setBatch. The key cache sees the ops IN
        BATCH ORDER, so remove-then-put of one key ends live, like the
        engine."""
        parts, ops = [], []
        for op, key, *rest in batch:
            key = to_bytes(key)
            if op == "put":
                value = to_bytes(rest[0])
                parts.append(struct.pack("<II", len(key), len(value)))
                parts.append(key)
                parts.append(value)
                ops.append((key, True))
            elif op == "remove":
                parts.append(struct.pack("<II", len(key), 0xFFFFFFFF))
                parts.append(key)
                ops.append((key, False))
            else:
                raise ValueError("unknown batch op {}".format(op))
        self._write_frame(parts)
        for key, is_put in ops:
            if is_put:
                self._fresh.add(key)
            else:
                self._forget(key)

    def iterator(self, start=None, end=None,
                 include_value=True) -> Iterator:
        start = to_bytes(start) if start is not None else None
        end = to_bytes(end) if end is not None else None
        if self._fresh:
            self._keys.update(self._fresh)
            self._fresh = set()
        keys = list(self._keys.irange(start, end))
        if include_value:
            # materialized snapshot, like the file backend: mutations
            # during consumption must not change what the iterator yields
            return iter([(k, self.get(k)) for k in keys])
        return iter(keys)

    # ------------------------------------------------------ maintenance

    def compact(self):
        if self._lib.kv_compact(self._handle()) != 0:
            raise IOError("kvlog compact failed")

    @property
    def garbage_bytes(self) -> int:
        return self._lib.kv_garbage(self._handle())

    def __len__(self):
        return self._lib.kv_count(self._handle())

    @property
    def size(self) -> int:
        return self._lib.kv_count(self._handle())

    def drop(self):
        self._lib.kv_close(self._db)
        if os.path.exists(self._path):
            os.unlink(self._path)
        self._db = self._lib.kv_open(self._path.encode())
        self._keys = SortedSet()
        self._fresh = set()

    def close(self):
        if not self._closed:
            self._lib.kv_close(self._db)
            self._db = None
            self._closed = True

    @property
    def closed(self) -> bool:
        return self._closed
