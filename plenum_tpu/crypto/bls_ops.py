"""Backend dispatch for BLS12-381 curve operations.

The hot operations (scalar mults, pairing checks) route to the native C
module (plenum_tpu/native/bls12_381.c — the framework's ursa equivalent,
~100-300x the pure-Python speed) when a C compiler is available, and
fall back to the pure-Python reference implementation otherwise. Select
explicitly with PLENUM_TPU_BLS=python|native.

Serialization, constants and the Fq towers always come from the Python
module — they are not hot and keep a single source of truth for the
wire format.
"""
from __future__ import annotations

import os
from typing import Sequence, Tuple

from plenum_tpu.crypto import bls12_381 as _py
from plenum_tpu.crypto.bls12_381 import (  # noqa: F401  (re-exports)
    FQ12_ONE, G1Point, G2Point, G1_GEN, G2_GEN, Q, R, X_ABS,
    g1_compress, g1_decompress, g1_is_on_curve, g1_neg,
    g2_compress, g2_decompress, g2_is_on_curve, g2_neg)


def _pick_backend():
    import logging
    log = logging.getLogger(__name__)
    mode = os.environ.get("PLENUM_TPU_BLS", "auto")
    if mode not in ("auto", "native", "python"):
        log.warning("unrecognized PLENUM_TPU_BLS=%r; using auto", mode)
        mode = "auto"
    if mode == "python":
        return None
    try:
        from plenum_tpu.crypto import bls_native
        if bls_native.available():
            return bls_native
        err = bls_native.build_error()
    except (ImportError, OSError, AttributeError) as e:
        # pragma: no cover - import failure path, narrowed (PT006):
        # available() already absorbs build/load errors, so only a
        # broken import of the bridge module itself lands here
        log.debug("BLS native bridge import failed: %s", e)
        err = e
    if mode == "native":
        raise RuntimeError(
            "PLENUM_TPU_BLS=native but the C backend failed to build: %s"
            % (err,))
    log.warning("native BLS backend unavailable (%s); falling back to the "
                "~100-300x slower pure-Python pairing", err)
    return None


_native = _pick_backend()
BACKEND = "native" if _native is not None else "python"

if _native is not None:
    g1_add = _native.g1_add
    g1_mul = _native.g1_mul
    g2_add = _native.g2_add
    g2_mul = _native.g2_mul
    multi_pairing_is_one = _native.multi_pairing_is_one
    g1_decompress = _native.g1_decompress  # noqa: F811 (hot override)
    # prepared pairings: precomputed line coefficients for fixed G2
    # arguments (verifiers pair against the same generator/pool-key on
    # every verify); None on the Python backend — callers fall back
    miller_precompute = _native.miller_precompute
    multi_pairing_is_one_prepared = _native.multi_pairing_is_one_prepared
    g1_aggregate_compressed = _native.g1_aggregate_compressed
    g1_aggregate_points = _native.g1_aggregate_points
else:
    g1_add = _py.g1_add
    g1_mul = _py.g1_mul
    g2_add = _py.g2_add
    g2_mul = _py.g2_mul
    miller_precompute = None
    multi_pairing_is_one_prepared = None

    def multi_pairing_is_one(
            pairs: Sequence[Tuple[G1Point, G2Point]]) -> bool:
        return _py.multi_pairing(pairs) == _py.FQ12_ONE

    def g1_aggregate_compressed(sigs: Sequence[bytes]) -> G1Point:
        agg = None
        for s in sigs:
            agg = _py.g1_add(agg, _py.g1_decompress(s))
        return agg

    def g1_aggregate_points(points) -> G1Point:
        agg = None
        for p in points:
            agg = _py.g1_add(agg, p)
        return agg


# ---------------------------------------------------------------- device
# Batched pairing / MSM (ops/bls381_pairing.py). Jobs of compressed
# (G1, G2) byte pairs run as one bucketed Miller-loop launch with a
# single shared final exponentiation; the host path below implements
# the SAME verdict semantics pair-for-pair, so a device step-down is
# invisible to callers. The heavy ops/ imports stay lazy — this module
# loads on every node, jax only on the first batch above threshold.

# env knob shared with ops/bls381_pairing: "native"/"off" pins the host
# path; runtime failures step the family down permanently through the
# same mesh registry as the Pallas kernels
BLS_TOWER_ENV = "PLENUM_TPU_BLS_TOWER"


def _step_down(what: str, exc: Exception) -> None:
    import logging
    from plenum_tpu.ops import mesh
    mesh.disable_pallas_backend(BLS_TOWER_ENV)
    logging.getLogger(__name__).warning(
        "device BLS %s failed at run time (%s); stepped down to the "
        "host path permanently", what, exc)


def pairing_device_ready(n_jobs: int) -> bool:
    """True when a batch of ``n_jobs`` pairing-product checks should
    take the device kernel: batch clears Config.BLS_PAIRING_DEVICE_MIN,
    the feature is on, and the tower backend has not been pinned off or
    stepped down."""
    from plenum_tpu.common.config import Config
    if not getattr(Config, "BLS_DEVICE_PAIRING", True):
        return False
    if n_jobs < int(getattr(Config, "BLS_PAIRING_DEVICE_MIN", 4)):
        return False
    try:
        from plenum_tpu.ops import mesh
    except ImportError:  # pragma: no cover - jax-less deployment
        return False
    return mesh.xla_backend_enabled(BLS_TOWER_ENV)


def pairing_job_host(pairs) -> bool:
    """Host reference semantics for ONE pairing-product job — the
    contract the device kernel is pinned byte-equal to: a both-infinity
    pair is neutral (skipped), a one-sided infinity fails the job, any
    undecodable / off-curve point fails the job, else the product over
    the decoded pairs must be exactly 1. NO subgroup checks — callers
    (crypto/bls.py) gate those before building jobs, identically on
    both paths."""
    try:
        decoded = []
        for s1, s2 in pairs:
            p = g1_decompress(bytes(s1))
            q = _py.g2_decompress(bytes(s2))
            if (p is None) != (q is None):
                return False
            if p is None:
                continue
            decoded.append((p, q))
        if not decoded:
            return True
        return multi_pairing_is_one(decoded)
    except (ValueError, KeyError, TypeError, ZeroDivisionError):
        # undecodable bytes, or a degenerate inversion inside the
        # Python Miller loop on an adversarial (e.g. 2-torsion) point
        return False


def multi_pairing_is_one_jobs(jobs) -> list:
    """Batch of independent pairing-product checks → verdict per job.
    Each job is a sequence of (compressed G1, compressed G2) byte
    pairs. One device launch for the whole batch above the threshold;
    per-job host evaluation (``pairing_job_host``) otherwise, and as
    the permanent step-down after a device failure."""
    jobs = [list(j) for j in jobs]
    if not jobs:
        return []
    if pairing_device_ready(len(jobs)):
        from plenum_tpu.ops import bls381_pairing as _bp
        # trace/lowering/compile failures raise from the dispatch:
        # program bugs, not something to serve around
        handles = _bp.pairing_dispatch(jobs)
        try:
            verdict, _ok = _bp.pairing_collect(handles)
            return [bool(v) for v in verdict]
        except Exception as e:  # pragma: no cover  # plenum-lint: disable=PT006
            # serving-path robustness: a launched kernel that dies on
            # the device (OOM, runtime) steps the family down and this
            # batch is served by the host — COUNTED
            # (mesh.step_down_counts), never crash a verify path; same
            # contract as the sha256/ed25519 Pallas step-downs
            _step_down("pairing", e)
    return [pairing_job_host(j) for j in jobs]


def g1_msm(points: Sequence[bytes], scalars: Sequence[int]):
    """Σ sᵢ·Pᵢ over G1 — windowed multi-scalar multiplication. Device
    kernel (shared doubling chain across the whole batch) above
    Config.BLS_MSM_DEVICE_MIN when the tower backend is up; host
    double-and-add per point otherwise. ``points`` are compressed
    bytes; scalars are reduced mod r on both paths. Returns an affine
    point, or None for the identity; raises ValueError on undecodable
    input (both paths)."""
    if len(points) != len(scalars):
        raise ValueError("points/scalars length mismatch")
    if not points:
        return None
    from plenum_tpu.common.config import Config
    n_min = int(getattr(Config, "BLS_MSM_DEVICE_MIN", 8))
    use_device = len(points) >= n_min \
        and getattr(Config, "BLS_DEVICE_PAIRING", True)
    if use_device:
        try:
            from plenum_tpu.ops import mesh
            use_device = mesh.xla_backend_enabled(BLS_TOWER_ENV)
        except ImportError:  # pragma: no cover - jax-less deployment
            use_device = False
    if use_device:
        from plenum_tpu.ops import bls381_pairing as _bp
        handles = _bp.msm_dispatch(points, scalars)   # compile raises
        try:
            point, ok = _bp.msm_result(handles)
        except Exception as e:  # pragma: no cover  # plenum-lint: disable=PT006
            # counted step-down, not crash: the host double-and-add
            # below serves every MSM the device path would have
            _step_down("MSM", e)
        else:
            if not ok:
                raise ValueError("undecodable point in MSM input")
            return point
    agg = None
    for raw, s in zip(points, scalars):
        p = g1_decompress(bytes(raw))
        if p is None:
            continue
        agg = g1_add(agg, g1_mul(p, s % R))
    return agg


def hash_to_g1(msg: bytes, dst: bytes = b"PLENUM_TPU_BLS_G1") -> G1Point:
    """The single shared try-and-increment construction from bls12_381;
    fully native when the C backend is up (sha256 + sqrt + cofactor in
    one call), else the Python construction with the fast scalar mul."""
    if _native is not None:
        return _native.hash_to_g1(msg, dst)
    return _py.hash_to_g1(msg, dst, g1_mul_fn=g1_mul)


def g1_in_subgroup(p: G1Point) -> bool:
    """The single shared check from bls12_381, with the scalar mult
    running on the fast backend."""
    return _py.g1_in_subgroup(p, g1_mul_fn=g1_mul)


def g2_in_subgroup(p: G2Point) -> bool:
    return _py.g2_in_subgroup(p, g2_mul_fn=g2_mul)
