"""TPU-accelerated batch primitives (JAX/XLA).

The framework's hot data paths — merkle SHA-256 hashing, ed25519 signature
verification, BLS12-381 aggregation — are expressed as pure batched JAX
functions in this package, dispatched from the host-side consensus loop
behind pluggable provider seams (SURVEY.md §2.9).
"""
import os

import numpy as np


def pow2_at_least(n: int) -> int:
    """Smallest power of two >= n — the shared bucket-rounding rule for
    batch padding (ops/mesh.py) and tree capacity (ops/merkle.py)."""
    p = 1
    while p < n:
        p *= 2
    return p


def scatter_ragged_rows(msgs, width: int):
    """Scatter variable-length messages into a zero-filled
    ``[len(msgs), width]`` uint8 buffer with ONE flat vectorized
    scatter — the shared core of the mixed-length host padding in
    ``ops/sha256.pad_messages`` and ``ops/sha3.pad_sha3_messages``
    (a per-message Python loop was the host bottleneck for large
    mixed batches in both).

    Returns ``(out, lens)``: the row buffer and the per-message byte
    lengths as int64 — each hash pads its own domain/length markers on
    top (SHA-2: 0x80 + 64-bit big-endian bit length; SHA-3: 0x06 +
    final-byte 0x80 XOR).
    """
    n = len(msgs)
    out = np.zeros((n, width), dtype=np.uint8)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.int64, count=n)
    joined = np.frombuffer(b"".join(msgs), dtype=np.uint8)
    if joined.shape[0]:
        flat = out.reshape(-1)
        starts = np.zeros(n, dtype=np.int64)
        np.cumsum(lens[:-1], out=starts[1:])
        rows = np.arange(n, dtype=np.int64)
        dst = np.repeat(rows * width, lens) \
            + (np.arange(joined.shape[0], dtype=np.int64)
               - np.repeat(starts, lens))
        flat[dst] = joined
    return out, lens


def enable_persistent_compilation_cache() -> str:
    """Turn on XLA's persistent compilation cache — THE one setter:
    every entry point that can compile (verify daemon, node start,
    bench.py, chip_smoke.py, the test suite, __graft_entry__) calls
    this and nothing else places the cache. The directory comes from
    outside: ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<checkout>/.jax_cache`` — never a temporary, pid- or time-derived
    path (the path is part of the cache key, so a directory that moves
    never hits). The ed25519 buckets take minutes to compile (the
    4,096-signature Pallas block ~3.5 min); with the cache every process
    after the first loads them in seconds. Goes through jax.config
    because the env var alone does not activate the cache on every
    backend. → the directory in use.

    Also keeps the CALLER's stack out of op locations: a Pallas
    kernel's Mosaic payload embeds its ops' source locations inside an
    opaque string the cache-key canonicalisation cannot strip, and with
    full tracebacks those locations carry the frames of whoever called
    the kernel — so the verify daemon, a node and chip_smoke.py each
    got a different key for the SAME ed25519 kernel and each paid its
    ~4 min compile (seen on the chip, PR 22). With the innermost frame
    only, the key depends on the kernel's own source."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return path
