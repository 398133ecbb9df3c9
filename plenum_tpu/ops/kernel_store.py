"""Store of BUILT kernels beside the persistent compilation cache.

Why: XLA's persistent cache is keyed by the lowered program, so it can
save the compile and nothing in front of it. For the ed25519 Pallas
kernel (ops/ed25519_pallas.py) what is in front is the larger part: its
body is Python-unrolled field arithmetic, and tracing it and lowering it
to Mosaic took 90-125 s in every process on the chip host, warm cache or
not, before the cache's key even existed. The work is the same in every
process and its result only changes with the kernel's source, the JAX
and libtpu versions and the chip. So the first process that builds a
kernel writes the compiled executable here
(``jax.experimental.serialize_executable``), and every later one loads
it: no trace, no lowering, no compile.

One file per (kernel, shape): ``<compile cache dir>/kernels/<name>.bin``,
a JSON header line (the key's digest and its parts, the payload's length
and SHA-256) and the payload. Written to a temporary file and renamed, so
a reader never sees half a file and two writers cannot corrupt it.

A stale kernel is never served: the header's key is compared before the
payload is touched, and a loaded kernel runs its owner's known-answer
launch before it serves. A key that differs, a file that cannot be read,
a load that raises, a wrong answer: each means build as before, overwrite
the file, and count it under ``rebuilt`` with that reason. What runs is
always the kernel the caller asked for, loaded or built; nothing here
chooses another.

Import of this module never imports JAX.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Callable, Optional, Sequence

logger = logging.getLogger(__name__)

FORMAT = 1     # of the file; inside the key, so another format is another key

REASON_KEY = "key"                    # written for another key
REASON_UNREADABLE = "unreadable"      # truncated, foreign, not a header
REASON_LOAD_FAILED = "load_failed"    # the runtime refused the payload
REASON_WRONG_ANSWER = "wrong_answer"  # the known-answer launch disagreed


def runtime_versions() -> dict:
    """What a compiled executable is tied to besides the chip: the jax,
    jaxlib and libtpu distributions (None where one is not installed)
    and the runtime the backend actually loaded."""
    from importlib import metadata
    import jax
    import jaxlib
    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        out["libtpu"] = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        out["libtpu"] = None
    from plenum_tpu.ops import mesh as mesh_mod
    out["runtime"] = mesh_mod.default_device().client.platform_version
    return out


def kernel_key(sources: Sequence[str], params: dict, versions: dict,
               device_kind: str) -> dict:
    """→ the parts a stored kernel is valid for and their digest.
    `sources` are the files whose BYTES decide the kernel (its body and
    constant tables); they enter by base name and content, never by
    path, so checkouts that share a cache directory share the store."""
    files = {}
    for path in sources:
        with open(path, "rb") as f:
            files[os.path.basename(path)] = hashlib.sha256(
                f.read()).hexdigest()
    parts = {"format": FORMAT, "sources": files, "params": params,
             "versions": versions, "device_kind": device_kind}
    digest = hashlib.sha256(
        json.dumps(parts, sort_keys=True).encode()).hexdigest()
    return {"digest": digest, "parts": parts}


class _Unusable(Exception):
    """The stored file cannot serve; .reason says why."""

    def __init__(self, reason: str, detail: str):
        super().__init__(detail)
        self.reason = reason


def _zero_counts() -> dict:
    return {"loaded": 0, "built": 0, "rebuilt": {}, "load_s": 0.0,
            "build_s": 0.0}


class KernelStore:
    def __init__(self, directory: str):
        self.directory = directory
        self._lock = threading.Lock()
        self._counts = _zero_counts()

    def counts(self) -> dict:
        """loaded / built: kernels this process took from the store /
        traced, lowered and compiled itself. rebuilt: of the built, those
        that replaced a stored file, by reason. load_s / build_s: wall
        time of each, the known-answer launch inside load_s."""
        with self._lock:
            return dict(self._counts, rebuilt=dict(self._counts["rebuilt"]))

    def path(self, name: str) -> str:
        return os.path.join(self.directory, name + ".bin")

    def load_or_build(self, name: str, key: dict,
                      build: Callable[[], object],
                      known_answer: Callable[[Callable], bool]):
        """→ the compiled kernel `name` (a ``jax.stages.Compiled``):
        loaded from the store when its file was written for `key` and
        passes `known_answer`, else from `build()` (trace, lower,
        compile), which is then written for the next process."""
        t0 = time.perf_counter()
        reason = None
        try:
            fn = self._load(name, key, known_answer)
        except FileNotFoundError:
            fn = None
        except _Unusable as e:
            fn, reason = None, e.reason
            logger.warning("kernel store: %s not usable (%s: %s); "
                           "building", self.path(name), reason, e)
        if fn is not None:
            took = time.perf_counter() - t0
            with self._lock:
                self._counts["loaded"] += 1
                self._counts["load_s"] += took
            logger.info("kernel store: loaded %s in %.2fs (key %s)",
                        self.path(name), took, key["digest"][:16])
            return fn
        t0 = time.perf_counter()
        compiled = build()
        self._write(name, key, compiled)
        took = time.perf_counter() - t0
        with self._lock:
            self._counts["built"] += 1
            self._counts["build_s"] += took
            if reason is not None:
                rebuilt = self._counts["rebuilt"]
                rebuilt[reason] = rebuilt.get(reason, 0) + 1
        logger.info("kernel store: built %s in %.2fs (key %s%s)",
                    self.path(name), took, key["digest"][:16],
                    "" if reason is None else ", rebuilt: " + reason)
        return compiled

    # ---------------------------------------------------------- the file

    def _load(self, name: str, key: dict, known_answer):
        try:
            f = open(self.path(name), "rb")
        except FileNotFoundError:
            raise
        except OSError as e:
            raise _Unusable(REASON_UNREADABLE, repr(e))
        with f:
            head = f.readline(1 << 20)
            try:
                header = json.loads(head)
                stored, length, sha = (header["key"]["digest"],
                                       header["length"], header["sha256"])
            except (ValueError, KeyError, TypeError):
                raise _Unusable(REASON_UNREADABLE, "no header")
            if stored != key["digest"]:
                theirs = header["key"].get("parts") or {}
                differ = sorted(k for k in key["parts"]
                                if theirs.get(k) != key["parts"][k])
                raise _Unusable(REASON_KEY, "written for another %s"
                                % (", ".join(differ) or "key"))
            payload = f.read()
        if len(payload) != length \
                or hashlib.sha256(payload).hexdigest() != sha:
            raise _Unusable(REASON_UNREADABLE,
                            "payload of %d bytes, header says %s"
                            % (len(payload), length))
        try:
            fn = _load_executable(payload)
        except Exception as e:  # plenum-lint: disable=PT006 — whatever
            # the runtime raises on a payload it cannot take means the
            # same thing here: build the kernel instead
            raise _Unusable(REASON_LOAD_FAILED, repr(e))
        if not known_answer(fn):
            raise _Unusable(REASON_WRONG_ANSWER,
                            "the loaded kernel's verdicts differ from "
                            "the host reference")
        return fn

    def _write(self, name: str, key: dict, compiled) -> None:
        """After a successful build; a store that cannot be written
        costs the next process its load, not this one its kernel."""
        try:
            payload = _dump_executable(compiled)
            header = json.dumps({
                "kernel": name, "key": key, "length": len(payload),
                "sha256": hashlib.sha256(payload).hexdigest()})
            os.makedirs(self.directory, exist_ok=True)
            tmp = "%s.%d.tmp" % (self.path(name), os.getpid())
            with open(tmp, "wb") as f:
                f.write(header.encode() + b"\n")
                f.write(payload)
            os.replace(tmp, self.path(name))
        except Exception:  # plenum-lint: disable=PT006 — see docstring
            logger.warning("kernel store: could not write %s",
                           self.path(name), exc_info=True)


def _dump_executable(compiled) -> bytes:
    from jax.experimental import serialize_executable
    return pickle.dumps(serialize_executable.serialize(compiled))


def _load_executable(payload: bytes):
    """The payload is this program's own pickle out of the compile
    cache's directory (as trusted as the executables XLA loads from
    there) and is unpickled only after its SHA-256 matched the header."""
    from jax.experimental import serialize_executable
    from plenum_tpu.ops import mesh as mesh_mod
    serialized, in_tree, out_tree = pickle.loads(payload)
    device = mesh_mod.default_device()
    return serialize_executable.deserialize_and_load(
        serialized, in_tree, out_tree, backend=device.client,
        execution_devices=[device])


# ------------------------------------------------- the process's own store

_DEFAULT_LOCK = threading.Lock()
_DEFAULT: Optional[KernelStore] = None


def default_store() -> KernelStore:
    """The store beside this process's compilation cache:
    ``<ops.enable_persistent_compilation_cache()>/kernels``."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            from plenum_tpu.ops import enable_persistent_compilation_cache
            _DEFAULT = KernelStore(os.path.join(
                enable_persistent_compilation_cache(), "kernels"))
        return _DEFAULT


def counts() -> dict:
    """The default store's counters; zeros in a process that never
    reached it (CPU backend, interpret mode: see ed25519_pallas)."""
    with _DEFAULT_LOCK:
        store = _DEFAULT
    return store.counts() if store is not None else _zero_counts()
