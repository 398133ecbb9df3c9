#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that plenum_tpu starts and serves
on one attached TPU chip, through the entry points an operator uses.

    python chip_smoke.py                 # one chip: pool + kernels
    python chip_smoke.py --four-chips    # four chips: the mesh path only

Phases (one chip):

  pool     the deployment shape: ``generate_pool``, ONE verify daemon
           (``python -m plenum_tpu.server.verify_daemon``) owning the
           chip, four ``scripts/start_plenum_tpu_node`` processes with
           VERIFIER_PROVIDER="remote", and a client over the real
           TCP+AEAD stack: signed NYM writes with corrupted ones spread
           through the stream, then GET_NYM reads whose state proof and
           BLS multi-signature the client verifies.
  kernels  one child process owning the chip: every device kernel a
           node can route to, at the size a deployment holds, each
           compared with an independent host reference, each asserting
           afterwards that the DEVICE path served it.

``--four-chips`` runs only the mesh path (ops/mesh.get_mesh over four
chips) and the same inputs with the mesh capped to one device.

This process never initialises a JAX backend: a chip belongs to one
process at a time, so every phase that needs it is a child that owns it
alone and exits before the next starts. Chip-owning children run with
the platform pinned (``JAX_PLATFORMS`` as given, else "tpu"), so a chip
that cannot be initialised is an error, not a CPU run. Node processes
get no such variable from here: they land on the CPU backend through
their own start path (bootstrap.settle_device_ownership).

Exit code 0 only if every phase passed on a TPU. The last stdout line is
exactly ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": 1}}`` with the device as the chip-owning children reported it;
on any failure it is ``{"ok": false, ...}`` and the exit code is not 0.
Earlier lines (one JSON object each) carry per-phase seconds, compile
seconds, the backend each kernel took, daemon launches and batch sizes,
native modules and versions — for orientation only, no number here is a
measurement claim.

``--tiny`` is the rehearsal size (CPU sandbox, tier-1 test): every phase
runs, and the run still fails because the platform is not "tpu".
"""
import argparse
import asyncio
import hashlib
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

NAMES = ["Alpha", "Beta", "Gamma", "Delta"]
# the whole run must end inside the driver's 1200 s, compilation included
RUN_BUDGET_S = 1150
# reaching the chip takes ~15 s; a child that waits this long for it is
# waiting on a chip another process holds
PREFLIGHT_BUDGET_S = 180


class Sizes:
    """Per-phase sizes: what a deployment holds, or the rehearsal cut."""

    def __init__(self, tiny: bool):
        self.tiny = tiny
        # pool phase: BENCH_POOL_REQS has always been 4,000
        self.writes = 64 if tiny else 4000
        self.bad_writes = 8 if tiny else 32
        self.reads = 16 if tiny else 256
        # ed25519: the daemon's bucket, so pool and kernels share ONE
        # compiled shape (one cache entry); plus one sub-block batch
        self.bucket = 16 if tiny else 4096
        # the daemon's OpenSSL floor: its default (512 items) at full
        # size; scaled with the bucket for the rehearsal
        self.cpu_floor = 2 if tiny else None
        self.ed_launches = 4
        self.ed_sub = 8 if tiny else 1000
        # merkle: BASELINE.json config 4
        self.leaves = 1 << (12 if tiny else 20)
        self.proofs = 64 if tiny else 4096
        self.append = 100 if tiny else 1000
        # state: Max3PCBatchSize writes onto a >= 100k-key trie
        self.state_keys = 2000 if tiny else 100000
        self.state_build_batch = 500 if tiny else 10000
        self.state_batch = 100 if tiny else 1000
        self.state_batches = 2 if tiny else 5
        self.state_proofs = 64 if tiny else 1000
        # BLS: committee sizes x jobs; pairing at the 8x2 bucket
        self.bls_signers = (4,) if tiny else (4, 25, 100)
        self.bls_jobs = 8 if tiny else 256
        self.pair_jobs = 8


def emit(obj) -> None:
    print(json.dumps(obj, sort_keys=True, default=str), flush=True)


def log(*a) -> None:
    print("[chip_smoke]", *a, file=sys.stderr, flush=True)


# ===================================================================
# child side: code below this line may initialise JAX
# ===================================================================

class CompileMonitor:
    """Sums, from JAX's own monitoring events, what a cold start pays
    before a first launch — trace, lowering and backend compile seconds
    (``compile_s``; the backend part alone is ``xla_compile_s``, near
    zero on a persistent-cache hit) — and the cache's hits and misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.xla_compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event.startswith("/jax/core/compile/"):
            self.compile_s += duration
            if event.endswith("/backend_compile_duration"):
                self.xla_compile_s += duration

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.compile_s, self.xla_compile_s, self.hits, self.misses)

    def since(self, mark=(0.0, 0.0, 0, 0)) -> dict:
        return {"compile_s": round(self.compile_s - mark[0], 2),
                "xla_compile_s": round(self.xla_compile_s - mark[1], 2),
                "cache_hits": self.hits - mark[2],
                "cache_misses": self.misses - mark[3]}


def steer_for_rehearsal() -> None:
    """--tiny only: the routing thresholds are sized for deployment
    batches, so at rehearsal sizes the mesh gate and the device proof
    gather would never engage. Steered here, in the harness, not
    through an option of the program."""
    from plenum_tpu.ops import mesh as mesh_mod
    from plenum_tpu.ops.merkle import DeviceMerkleTree
    mesh_mod.configure(shard_min=16)
    DeviceMerkleTree._TOP_CACHE = 64


def child_begin(sz: "Sizes" = None):
    """Common start of a chip-owning child: compile cache (the one
    setter), compile monitor, device facts (raises if the pinned
    platform cannot be initialised)."""
    from plenum_tpu.ops import enable_persistent_compilation_cache
    from plenum_tpu.ops import mesh as mesh_mod
    cache_dir = enable_persistent_compilation_cache()
    monitor = CompileMonitor()
    device = mesh_mod.device_facts()
    if sz is not None and sz.tiny:
        steer_for_rehearsal()
    import jax
    import jaxlib
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        from importlib.metadata import version
        versions["libtpu"] = version("libtpu")
    except Exception:
        versions["libtpu"] = None
    emit({"event": "child_start", "device": device,
          "compile_cache": cache_dir, "versions": versions,
          "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")})
    return monitor, device


def device_path_report() -> dict:
    """What every kernel check asserts afterwards: no family stepped
    down, the Pallas registry as decided, the ed25519 block size; and
    whether the ed25519 kernel was loaded from the built-kernel store."""
    from plenum_tpu.ops import ed25519_pallas as edp
    from plenum_tpu.ops import kernel_store
    from plenum_tpu.ops import mesh as mesh_mod
    return {"step_downs": mesh_mod.step_down_counts(),
            "kernel_backends": mesh_mod.kernel_backends(),
            "ed25519_block_r": edp.BLOCK_R,
            "kernel_store": kernel_store.counts()}


def run_checks(checks, monitor) -> list:
    """Run each (name, fn) → result dict with seconds and compile
    seconds. A failing check is recorded with its traceback and the
    rest still run (a chip call is too dear to stop at the first
    fault); the caller exits non-zero if any failed."""
    results = []
    for name, fn in checks:
        mark = monitor.mark()
        t0 = time.perf_counter()
        try:
            res = fn()
            res.setdefault("ok", True)
        except Exception:
            res = {"ok": False, "error": traceback.format_exc()}
            log("check %s FAILED:\n%s" % (name, res["error"]))
        res["check"] = name
        res["seconds"] = round(time.perf_counter() - t0, 2)
        res.update(monitor.since(mark))
        emit(res)
        results.append(res)
    return results


def require(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ----------------------------------------------------------- ed25519

def mixed_signed_items(n: int, seed: int):
    """n (msg, sig, verkey) items, distinct keys, with invalid
    signatures, non-canonical s, bad point encodings, wrong messages
    and wrong lengths mixed in at seeded positions."""
    from plenum_tpu.crypto.fixtures import make_signed_batch
    from plenum_tpu.ops.ed25519_jax import L, P
    msgs, sigs, vks = make_signed_batch(n, seed=seed, msg_prefix=b"smoke")
    msgs, sigs, vks = list(msgs), list(sigs), list(vks)
    rng = random.Random(seed)
    kinds = ("flip_sig", "noncanonical_s", "bad_vk_y", "bad_r",
             "wrong_msg", "short_sig", "long_vk")
    corrupted = {}
    for i in rng.sample(range(n), max(len(kinds), n // 8)):
        kind = kinds[len(corrupted) % len(kinds)]
        corrupted[i] = kind
        sig = bytearray(sigs[i])
        if kind == "flip_sig":
            sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            sigs[i] = bytes(sig)
        elif kind == "noncanonical_s":
            s = int.from_bytes(sig[32:], "little") + L
            sigs[i] = bytes(sig[:32]) + s.to_bytes(32, "little")
        elif kind == "bad_vk_y":       # y >= p: not a field element
            vks[i] = (P + rng.randrange(19)).to_bytes(32, "little")
        elif kind == "bad_r":          # R.y >= p
            sigs[i] = (P + rng.randrange(19)).to_bytes(32, "little") \
                + bytes(sig[32:])
        elif kind == "wrong_msg":
            msgs[i] = msgs[i] + b"!"
        elif kind == "short_sig":
            sigs[i] = bytes(sig[:63])
        elif kind == "long_vk":
            vks[i] = vks[i] + b"\x00"
    return list(zip(msgs, sigs, vks)), corrupted


def check_ed25519(sz: Sizes, seed: int) -> dict:
    from plenum_tpu.crypto.batch_verifier import OpenSSLVerifier, HAVE_OPENSSL
    from plenum_tpu.ops import ed25519_jax as edj
    from plenum_tpu.ops import ed25519_pallas as edp
    from plenum_tpu.ops import mesh as mesh_mod
    from plenum_tpu.server.verify_daemon import VerifyDaemon
    require(HAVE_OPENSSL, "the OpenSSL reference (cryptography) is missing")
    n = sz.bucket * sz.ed_launches
    items, corrupted = mixed_signed_items(n, seed)
    want = OpenSSLVerifier().verify_batch(items)
    require(sum(want) == n - len(corrupted),
            "reference: %d valid of %d, %d corrupted"
            % (sum(want), n, len(corrupted)))
    # the daemon's own fixed-bucket path, in this process: one compiled
    # shape, launches pipelined through the device queue
    daemon = VerifyDaemon(backend="tpu_batch", bucket=sz.bucket,
                          cpu_floor=0)
    t0 = time.perf_counter()
    first = daemon._verify_bucketed(items[:sz.bucket])
    first_launch_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = daemon._verify_bucketed(items)
    steady_s = time.perf_counter() - t0
    got = [bool(x) for x in got]
    require([bool(x) for x in first] == want[:sz.bucket],
            "first launch verdicts differ from OpenSSL")
    bad = [i for i in range(n) if got[i] != want[i]]
    require(not bad, "verdicts differ from OpenSSL at %s (kinds %s)"
            % (bad[:8], [corrupted.get(i) for i in bad[:8]]))
    require(daemon.device_launches == 1 + sz.ed_launches
            and daemon.host_items == 0,
            "daemon path: %s" % daemon.stats())
    pallas = bool(mesh_mod.kernel_backends().get(edj._ED25519_PALLAS_ENV))
    backend = "pallas" if pallas and sz.bucket >= edp.BLOCK else "xla"
    # one sub-block batch through the XLA kernel
    sub_items = items[:sz.ed_sub]
    m, s, v = zip(*sub_items)
    t0 = time.perf_counter()
    sub = [bool(x) for x in edj.verify_batch(list(m), list(s), list(v))]
    sub_s = time.perf_counter() - t0
    require(sub == want[:sz.ed_sub], "sub-block verdicts differ")
    require(edj.launch_lanes(sz.ed_sub) < edp.BLOCK,
            "sub-block batch fills a Pallas block")
    return {"items": n, "corrupted": len(corrupted),
            "bucket": sz.bucket, "backend": backend,
            "sub_block": {"items": sz.ed_sub, "backend": "xla",
                          "lanes": edj.launch_lanes(sz.ed_sub),
                          "seconds": round(sub_s, 2)},
            "first_launch_s": round(first_launch_s, 2),
            "steady_s_for_all": round(steady_s, 3),
            "device_launches": daemon.device_launches}


# ------------------------------------------------------------ merkle

def host_merkle_root(leaf_hashes):
    """RFC 6962 MTH by hashlib alone (any size)."""
    def mth(lo, hi):
        if hi - lo == 1:
            return leaf_hashes[lo]
        k = 1 << ((hi - lo - 1).bit_length() - 1)
        return hashlib.sha256(
            b"\x01" + mth(lo, lo + k) + mth(lo + k, hi)).digest()
    n = len(leaf_hashes)
    if n & (n - 1) == 0:
        # power of two: fold level by level (no deep recursion cost)
        level = leaf_hashes
        while len(level) > 1:
            level = [hashlib.sha256(b"\x01" + level[i] + level[i + 1])
                     .digest() for i in range(0, len(level), 2)]
        return level[0]
    return mth(0, n)


def smoke_leaves(n: int, seed: int, start: int = 0):
    return [b"txn-%08x-%020d" % (seed, i) for i in range(start, start + n)]


def check_merkle(sz: Sizes, seed: int) -> dict:
    from plenum_tpu.ledger.merkle_verifier import MerkleVerifier
    from plenum_tpu.ops import mesh as mesh_mod
    from plenum_tpu.ops import sha256_pallas as sp
    from plenum_tpu.ops.merkle import DeviceMerkleTree
    from plenum_tpu.ops.sha256 import select_backend
    n = sz.leaves
    leaves = smoke_leaves(n, seed)
    leaf_hashes = [hashlib.sha256(b"\x00" + d).digest() for d in leaves]
    want_root = host_merkle_root(leaf_hashes)
    dev = DeviceMerkleTree()
    t0 = time.perf_counter()
    root = dev.build(leaves)
    build_first_s = time.perf_counter() - t0
    require(root == want_root, "device root differs from hashlib")
    t0 = time.perf_counter()
    require(dev.build(leaves) == want_root, "second build root differs")
    build_s = time.perf_counter() - t0
    # audit paths, served as the node serves them (any-size path)
    rng = random.Random(seed + 1)
    idx = rng.sample(range(n), sz.proofs)
    t0 = time.perf_counter()
    paths = dev.inclusion_proofs(idx)
    proofs_first_s = time.perf_counter() - t0
    verifier = MerkleVerifier()
    for i, path in zip(idx, paths):
        verifier.verify_leaf_hash_inclusion(leaf_hashes[i], i, path, n,
                                            want_root)
    # incremental append, checked against the host tree of n + b leaves
    extra = smoke_leaves(sz.append, seed, start=n)
    extra_hashes = [hashlib.sha256(b"\x00" + d).digest() for d in extra]
    t0 = time.perf_counter()
    dev.append_leaf_hashes(extra_hashes)
    append_s = time.perf_counter() - t0
    n1 = n + sz.append
    want_root1 = hashlib.sha256(
        b"\x01" + want_root + host_merkle_root(extra_hashes)).digest()
    require(dev.tree_size == n1 and dev.root_hash == want_root1,
            "root after append differs from the host tree")
    all_hashes = leaf_hashes + extra_hashes
    idx1 = rng.sample(range(n), sz.proofs // 2) \
        + rng.sample(range(n, n1), min(sz.append, sz.proofs // 2))
    for i, path in zip(idx1, dev.inclusion_proofs(idx1)):
        verifier.verify_leaf_hash_inclusion(all_hashes[i], i, path, n1,
                                            want_root1)
    st = dict(dev.dispatch_stats)
    require(st["build_dispatches"] >= 2 and st["gather_dispatches"] >= 2
            and st["append_dispatches"] >= 2,
            "device tree counters: %s" % st)
    return {"leaves": n, "proofs": len(idx), "appended": sz.append,
            "backend": select_backend(n),
            "sha256_pallas": bool(
                mesh_mod.kernel_backends().get(sp.PALLAS_ENV)),
            "build_first_s": round(build_first_s, 2),
            "build_s": round(build_s, 3),
            "proofs_first_s": round(proofs_first_s, 2),
            "append_s": round(append_s, 2), "dispatch_stats": st}


# ------------------------------------------------------------- state

def check_state(sz: Sizes, seed: int) -> dict:
    """DeviceStateEngine behind PruningState vs a PruningState with no
    engine (the host trie): same writes, byte-equal roots, values and
    proofs."""
    from plenum_tpu.state.pruning_state import PruningState
    from plenum_tpu.storage.kv_memory import KeyValueStorageInMemory

    def kv(i):
        k = hashlib.sha256(b"%d-key-%d" % (seed, i)).digest()
        v = hashlib.sha512(b"%d-val-%d" % (seed, i)).digest() \
            + b"%016d" % i
        return k, v

    host = PruningState(KeyValueStorageInMemory())
    dev = PruningState(KeyValueStorageInMemory())
    engine = dev.attach_device_engine(warm=True)

    def apply(pairs):
        for st in (host, dev):
            for k, v in pairs:
                st.set(k, v)
        hr, dr = host.headHash, dev.headHash      # both flush here
        require(hr == dr, "state root differs from the host trie")
        for st in (host, dev):
            st.commit()
        return dr

    t0 = time.perf_counter()
    for lo in range(0, sz.state_keys, sz.state_build_batch):
        apply([kv(i) for i in range(lo, lo + sz.state_build_batch)])
    build_s = time.perf_counter() - t0
    # Max3PCBatchSize-shaped batches onto the standing trie: new keys,
    # overwrites and deletes mixed
    rng = random.Random(seed + 2)
    nxt = sz.state_keys
    t0 = time.perf_counter()
    for _ in range(sz.state_batches):
        pairs = []
        for j in range(sz.state_batch):
            r = j % 4
            if r == 0:                          # overwrite
                k, _v = kv(rng.randrange(sz.state_keys))
                pairs.append((k, b"upd-%d" % nxt + bytes(64)))
            elif r == 1 and j % 16 == 1:        # delete
                pairs.append((kv(rng.randrange(sz.state_keys))[0], b""))
            else:                               # insert
                pairs.append(kv(nxt))
                nxt += 1
        root = apply(pairs)
    batches_s = time.perf_counter() - t0
    keys = [kv(rng.randrange(nxt))[0] for _ in range(sz.state_proofs)]
    t0 = time.perf_counter()
    vals, proofs = dev.get_with_proofs_batch(keys, root=root)
    proofs_s = time.perf_counter() - t0
    hvals, hproofs = host.get_with_proofs_batch(keys, root=root)
    require(vals == hvals, "batched values differ from the host trie")
    require(proofs == hproofs, "batched proofs differ from the host trie")
    require(dev.get_batch(keys) == hvals, "get_batch differs")
    for k, v, p in zip(keys[:64], vals, proofs):
        require(PruningState.verify_state_proof(root, k, v, p),
                "a device proof does not verify against the root")
    br = dev._engine_breaker
    est = engine.stats()
    require(br.failures == 0 and br.trips == 0,
            "state breaker: failures=%d trips=%d" % (br.failures, br.trips))
    require(est["device_dispatches"] > 0, "no device dispatch: %s" % est)
    return {"keys": nxt, "build_batch": sz.state_build_batch,
            "batch": sz.state_batch, "batches": sz.state_batches,
            "proof_keys": len(keys), "backend": "xla(sha3)",
            "build_s": round(build_s, 2),
            "batches_s": round(batches_s, 2),
            "proofs_s": round(proofs_s, 2), "engine": est,
            "breaker": {"failures": br.failures, "trips": br.trips}}


# --------------------------------------------------------------- BLS

def check_bls_aggregate(sz: Sizes, seed: int) -> dict:
    """G1 aggregation: n signers x jobs on device vs the native C
    aggregate (native/bls12_381.c), undecodable shares mixed in."""
    from plenum_tpu.crypto import bls_ops
    from plenum_tpu.ops import bls381_jax as bj
    require(bls_ops.BACKEND == "native", "native BLS reference missing")
    rng = random.Random(seed + 3)
    nmax = max(sz.bls_signers)
    pool = [bls_ops.g1_compress(bls_ops.g1_mul(
        bls_ops.G1_GEN, rng.randrange(1, bls_ops.R)))
        for _ in range(sz.bls_jobs * nmax)]
    out = {"jobs": sz.bls_jobs, "by_n": {}, "backend": "xla"}
    for n in sz.bls_signers:
        jobs = [pool[j * nmax:j * nmax + n] for j in range(sz.bls_jobs)]
        bad_jobs = set(rng.sample(range(sz.bls_jobs),
                                  max(1, sz.bls_jobs // 32)))
        for j in bad_jobs:                # x >= q: undecodable share
            jobs[j] = list(jobs[j])
            jobs[j][rng.randrange(n)] = bytes([0x9f]) + b"\xff" * 47
        t0 = time.perf_counter()
        pts, ok = bj.aggregate_g1_jobs(jobs)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        pts, ok = bj.aggregate_g1_jobs(jobs)
        steady_s = time.perf_counter() - t0
        for j, job in enumerate(jobs):
            if j in bad_jobs:
                require(not ok[j], "undecodable share accepted (job %d)" % j)
                continue
            require(bool(ok[j]), "valid job %d flagged undecodable" % j)
            want = bls_ops.g1_aggregate_compressed(job)
            require(pts[j] == (want if want is None else
                               (int(want[0]), int(want[1]))),
                    "aggregate differs from native at n=%d job %d" % (n, j))
        out["by_n"][str(n)] = {"first_s": round(first_s, 2),
                               "steady_s": round(steady_s, 3),
                               "undecodable_jobs": len(bad_jobs)}
    return out


def check_bls_pairing(sz: Sizes, seed: int) -> dict:
    """The device pairing path through
    BlsCryptoVerifier.verify_multi_sigs_batch, one bad share, against
    the scalar (native C) verify."""
    from plenum_tpu.crypto import bls_ops
    from plenum_tpu.crypto.bls import (
        BlsCryptoSignerPlenum, BlsCryptoVerifierPlenum)
    from plenum_tpu.observability import telemetry as tmy
    require(bls_ops.pairing_device_ready(sz.pair_jobs),
            "the device pairing path is not enabled "
            "(PLENUM_TPU_BLS_TOWER=%r)" % os.environ.get(
                bls_ops.BLS_TOWER_ENV))
    signers = [BlsCryptoSignerPlenum.generate(
        hashlib.sha256(b"%d-bls-%d" % (seed, i)).digest())[0]
        for i in range(4)]
    pks = [s.pk for s in signers]
    verifier = BlsCryptoVerifierPlenum()
    checks = []
    for j in range(sz.pair_jobs):
        msg = b"smoke-root-%d-%d" % (seed, j)
        shares = [s.sign(msg) for s in signers]
        if j == sz.pair_jobs // 2:      # one bad share: wrong message
            shares[1] = signers[1].sign(msg + b"x")
        checks.append((verifier.create_multi_sig(shares), msg, pks))
    want = [verifier.verify_multi_sig(s, m, k) for s, m, k in checks]
    require(want == [j != sz.pair_jobs // 2
                     for j in range(sz.pair_jobs)],
            "native reference verdicts: %s" % want)
    launches0 = _seam_launches(tmy.SEAM_BLS_PAIR)
    t0 = time.perf_counter()
    got = verifier.verify_multi_sigs_batch(checks)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = verifier.verify_multi_sigs_batch(checks)
    steady_s = time.perf_counter() - t0
    require(got == want and again == want,
            "device pairing verdicts %s differ from native %s" % (got, want))
    require(_seam_launches(tmy.SEAM_BLS_PAIR) - launches0 == 2,
            "the pairing batches did not launch on the device seam")
    return {"jobs": sz.pair_jobs, "pairs": 2, "backend": "xla",
            "first_s": round(first_s, 2), "steady_s": round(steady_s, 3)}


def _seam_launches(seam: str) -> int:
    from plenum_tpu.observability import telemetry as tmy
    return tmy.get_seam_hub().snapshot()["seams"].get(
        seam, {}).get("launches", 0)


def seam_summary() -> dict:
    from plenum_tpu.observability import telemetry as tmy
    return {k: {"launches": s["launches"], "shapes": s["shapes"],
                "lane_occupancy": s["lane_occupancy"]}
            for k, s in tmy.get_seam_hub().snapshot()["seams"].items()}


def child_kernels(sz: Sizes, seed: int) -> int:
    monitor, device = child_begin(sz)
    results = run_checks([
        ("ed25519", lambda: check_ed25519(sz, seed)),
        ("merkle", lambda: check_merkle(sz, seed)),
        ("state", lambda: check_state(sz, seed)),
        ("bls_aggregate", lambda: check_bls_aggregate(sz, seed)),
        ("bls_pairing", lambda: check_bls_pairing(sz, seed)),
    ], monitor)
    report = device_path_report()
    ok = all(r["ok"] for r in results)
    problems = []
    if report["step_downs"]:
        problems.append("kernel families stepped down: %s"
                        % report["step_downs"])
    if report["ed25519_block_r"] != 32:
        problems.append("ed25519 BLOCK_R is %d" % report["ed25519_block_r"])
    if device["platform"] == "tpu":
        off = [k for k, v in report["kernel_backends"].items() if not v]
        if off:
            problems.append("kernel families off their device path: %s"
                            % off)
    emit({"event": "child_result", "phase": "kernels",
          "ok": ok and not problems, "problems": problems,
          "device": device, "device_path": report,
          "seams": seam_summary(),
          "failed": [r["check"] for r in results if not r["ok"]],
          **monitor.since()})
    return 0 if ok and not problems else 1


# ----------------------------------------------------- four chips

def _shard_devices(arr) -> list:
    return sorted(str(s.device) for s in arr.addressable_shards)


def check_mesh_ed25519(sz: Sizes, seed: int) -> dict:
    from plenum_tpu.crypto.batch_verifier import OpenSSLVerifier
    from plenum_tpu.ops import ed25519_jax as edj
    from plenum_tpu.ops import mesh as mesh_mod
    from plenum_tpu.server.verify_daemon import VerifyDaemon
    m = mesh_mod.get_mesh()
    d = m.n_devices
    items, corrupted = mixed_signed_items(d * sz.bucket, seed)
    want = OpenSSLVerifier().verify_batch(items)
    daemon = VerifyDaemon(backend="tpu_batch", bucket=sz.bucket,
                          cpu_floor=0)
    sharded0 = m.sharded_dispatches
    t0 = time.perf_counter()
    got = [bool(x) for x in daemon._verify_bucketed(items)]
    mesh_first_s = time.perf_counter() - t0
    require(got == want, "sharded verdicts differ from OpenSSL")
    require(m.sharded_dispatches - sharded0 == 1
            and daemon.device_launches == 1,
            "expected ONE launch sharded over %d devices: %s / %s"
            % (d, m.stats(), daemon.stats()))
    # where the shards live: the same placement call dispatch makes,
    # and the un-awaited output of the production async entry
    msgs, sigs, vks = zip(*items)
    arrays, _valid = edj.host_pack(list(msgs), list(sigs), list(vks))
    in_devs = _shard_devices(m.put_sharded(arrays)[0])
    ok_dev, _valid, _n = edj.verify_batch_async(
        list(msgs), list(sigs), list(vks))
    out_devs = _shard_devices(ok_dev)
    require(len(set(in_devs)) == d and len(set(out_devs)) == d,
            "shards not on %d distinct devices: in=%s out=%s"
            % (d, in_devs, out_devs))
    # the same inputs with the mesh capped to one device
    mesh_mod.configure(max_devices=1)
    try:
        single = VerifyDaemon(backend="tpu_batch", bucket=sz.bucket,
                              cpu_floor=0)
        t0 = time.perf_counter()
        one = [bool(x) for x in single._verify_bucketed(items)]
        single_first_s = time.perf_counter() - t0
        require(single.device_launches == d and m.n_devices == 1,
                "capped run: %s" % single.stats())
    finally:
        mesh_mod.configure(max_devices=0)
    require(one == got, "one-device verdicts differ from the sharded run")
    return {"items": len(items), "corrupted": len(corrupted),
            "devices": d, "per_device": sz.bucket,
            "input_shard_devices": in_devs,
            "output_shard_devices": out_devs,
            "backend": {"mesh": "xla", "one_device": "pallas" if
                        mesh_mod.kernel_backends().get(
                            edj._ED25519_PALLAS_ENV) else "xla"},
            "mesh_first_s": round(mesh_first_s, 2),
            "one_device_first_s": round(single_first_s, 2)}


def check_mesh_merkle(sz: Sizes, seed: int) -> dict:
    from plenum_tpu.ledger.merkle_verifier import MerkleVerifier
    from plenum_tpu.ops import mesh as mesh_mod
    from plenum_tpu.ops.merkle import DeviceMerkleTree
    m = mesh_mod.get_mesh()
    d = m.n_devices
    n = sz.leaves
    leaves = smoke_leaves(n, seed)
    leaf_hashes = [hashlib.sha256(b"\x00" + x).digest() for x in leaves]
    want_root = host_merkle_root(leaf_hashes)
    idx = random.Random(seed + 1).sample(range(n), sz.proofs)

    def run():
        dev = DeviceMerkleTree()
        t0 = time.perf_counter()
        root = dev.build(leaves)
        build_s = time.perf_counter() - t0
        handle = dev.dispatch_proof_batch(idx)
        low = handle[1]
        devs = _shard_devices(low) if low is not None else []
        paths = dev.collect_proof_batch(handle)
        return dev, root, paths, devs, build_s

    sharded0 = m.sharded_dispatches
    dev, root, paths, gather_devs, mesh_build_s = run()
    n_sharded = m.sharded_dispatches - sharded0
    require(root == want_root, "sharded build root differs from hashlib")
    require(n_sharded >= 2, "build and gather did not shard: %s" % m.stats())
    require(len(set(gather_devs)) == d,
            "gather output not on %d devices: %s" % (d, gather_devs))
    repl_devs = sorted({str(x) for rep, _rows, _sh in
                        dev._repl_cache.values()
                        for x in rep.sharding.device_set})
    require(len(repl_devs) == d, "replicated levels on %s" % repl_devs)
    verifier = MerkleVerifier()
    for i, path in zip(idx, paths):
        verifier.verify_leaf_hash_inclusion(leaf_hashes[i], i, path, n,
                                            want_root)
    mesh_mod.configure(max_devices=1)
    try:
        _dev1, root1, paths1, _devs1, one_build_s = run()
    finally:
        mesh_mod.configure(max_devices=0)
    require(root1 == root and paths1 == paths,
            "one-device root/paths differ from the sharded run")
    return {"leaves": n, "proofs": len(idx), "devices": d,
            "sharded_dispatches": n_sharded,
            "gather_shard_devices": gather_devs,
            "replicated_level_devices": repl_devs,
            "backend": {"mesh": "xla", "one_device": "pallas/xla by size"},
            "mesh_build_first_s": round(mesh_build_s, 2),
            "one_device_build_first_s": round(one_build_s, 2)}


def child_mesh4(sz: Sizes, seed: int) -> int:
    monitor, device = child_begin(sz)
    from plenum_tpu.ops import mesh as mesh_mod
    problems = []
    if mesh_mod.get_mesh().n_devices != 4:
        problems.append("the mesh spans %d devices, not 4"
                        % mesh_mod.get_mesh().n_devices)
    results = run_checks([
        ("mesh_ed25519", lambda: check_mesh_ed25519(sz, seed)),
        ("mesh_merkle", lambda: check_mesh_merkle(sz, seed)),
    ], monitor)
    report = device_path_report()
    if report["step_downs"]:
        problems.append("kernel families stepped down: %s"
                        % report["step_downs"])
    ok = all(r["ok"] for r in results) and not problems
    emit({"event": "child_result", "phase": "four_chips", "ok": ok,
          "problems": problems, "device": device, "device_path": report,
          "mesh": mesh_mod.mesh_stats(),
          "failed": [r["check"] for r in results if not r["ok"]],
          **monitor.since()})
    return 0 if ok else 1


def child_device() -> int:
    """Preflight: initialise the pinned platform, say what it is."""
    _monitor, device = child_begin()
    emit({"event": "child_result", "phase": "device", "ok": True,
          "device": device})
    return 0


# ===================================================================
# parent side: never initialises a JAX backend
# ===================================================================

class Procs:
    """Every process the run starts, so that all of them are stopped
    whatever happens."""

    def __init__(self):
        self.items = []

    def add(self, proc, sig=signal.SIGTERM):
        self.items.append((proc, sig))
        return proc

    def stop_all(self):
        for proc, sig in self.items:
            if proc.poll() is None:
                proc.send_signal(sig)
        for proc, _sig in self.items:
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        self.items = []


def chip_env() -> dict:
    """Environment of a child that owns the chip: platform pinned, so a
    chip that cannot be initialised raises instead of landing on the
    CPU backend."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = os.environ.get("JAX_PLATFORMS") or "tpu"
    return env


def run_child(mode: str, args, procs: Procs, deadline: float,
              extra_env=None):
    """Run `chip_smoke.py --child mode`, echo its stdout lines, → (rc,
    last parsed child_result or None)."""
    env = chip_env()
    env.update(extra_env or {})
    cmd = [sys.executable, os.path.abspath(__file__), "--child", mode,
           "--seed", str(args.seed)] + (["--tiny"] if args.tiny else [])
    proc = procs.add(subprocess.Popen(cmd, cwd=ROOT, env=env,
                                      stdout=subprocess.PIPE, text=True))
    killer = threading.Timer(max(1.0, deadline - time.monotonic()),
                             proc.kill)
    killer.start()
    result = None
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            print(line, flush=True)
            if line.startswith("{"):
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if obj.get("event") == "child_result":
                    result = obj
        rc = proc.wait()
    finally:
        killer.cancel()
    return rc, result


def native_modules() -> dict:
    """Build/load every native module here, once (the node processes
    then load the built libraries), and say which came up."""
    from plenum_tpu.crypto import bls_ops
    from plenum_tpu.native import try_load_ext
    from plenum_tpu.state import rlp, trie_native
    from plenum_tpu.storage import kv_native
    return {"bls12_381": bls_ops.BACKEND == "native",
            "kvlog": bool(kv_native.available()),
            "mpt_c": trie_native._mpt is not None,
            "fastpath": try_load_ext("fastpath") is not None,
            "rlp_c": rlp._c is not None}


def jax_backend_untouched() -> bool:
    jax = sys.modules.get("jax")
    if jax is None:
        return True
    from jax._src import xla_bridge
    return not xla_bridge.backends_are_initialized()


# -------------------------------------------------------- pool phase

def make_writes(signer, n_valid: int, n_bad: int, seed: int):
    """→ (stream, valid_ids, bad_ids): signed NYM writes authored by the
    pool trustee (as bench.py's make_mp_requests), with n_bad corrupted
    ones spread evenly through the stream — half with a flipped
    signature byte, half signed by another key under the trustee's
    identifier."""
    from plenum_tpu.common.constants import NYM, TARGET_NYM, VERKEY
    from plenum_tpu.common.serializers.base58 import b58decode, b58encode
    from plenum_tpu.crypto.signer import DidSigner
    intruder = DidSigner(seed=hashlib.sha256(b"%d-intruder" % seed).digest())
    total = n_valid + n_bad
    bad_at = {int((k + 0.5) * total / n_bad) for k in range(n_bad)} \
        if n_bad else set()
    stream, valid_ids, bad_ids = [], [], []
    for i in range(total):
        dest = b58encode(hashlib.sha256(b"%d-nym-%d" % (seed, i))
                         .digest()[:16])
        req = {"identifier": signer.identifier, "reqId": i + 1,
               "protocolVersion": 2,
               "operation": {"type": NYM, TARGET_NYM: dest,
                             VERKEY: "~" + dest}}
        if i in bad_at:
            if len(bad_ids) % 2:
                req["signature"] = intruder.sign(dict(req))
            else:
                sig = bytearray(b58decode(signer.sign(dict(req))))
                sig[(i * 7) % 64] ^= 0x20
                req["signature"] = b58encode(bytes(sig))
            bad_ids.append(i + 1)
        else:
            req["signature"] = signer.sign(dict(req))
            valid_ids.append(i + 1)
        stream.append(req)
    return stream, valid_ids, bad_ids


async def drive_client(base_dir, stream, valid_ids, bad_ids, n_reads,
                       seed, deadline):
    """One encrypted connection per node; every request goes to every
    node. → dict of what came back (checked by the caller)."""
    from plenum_tpu.bootstrap import (
        client_ha_from_txns, pool_genesis_txns, registry_from_txns)
    from plenum_tpu.client.client import PoolClient
    from plenum_tpu.client.wallet import Wallet
    from plenum_tpu.common.constants import (
        ALIAS, BLS_KEY, DATA, TARGET_NYM)
    from plenum_tpu.common.txn_util import get_payload_data
    from plenum_tpu.crypto.bls import BlsCryptoVerifierPlenum
    from plenum_tpu.network.stack import ClientConnection

    pool_txns = pool_genesis_txns(base_dir)
    registry = registry_from_txns(pool_txns)
    bls_keys = {get_payload_data(t)[DATA][ALIAS]:
                get_payload_data(t)[DATA].get(BLS_KEY) for t in pool_txns}

    def left():
        return deadline - time.monotonic()

    conns = {}
    for name in NAMES:
        ha = client_ha_from_txns(pool_txns, name)
        while True:
            conn = ClientConnection(ha,
                                    expected_verkey=registry[name].verkey)
            try:
                await conn.connect()
                conns[name] = conn
                break
            except OSError:
                if left() < 0:
                    raise RuntimeError("node %s never came up" % name)
                await asyncio.sleep(0.5)
    log("client connected to all nodes")

    # reqId -> the committed txn a REPLY carries (seqNo and txnTime
    # included), WITHOUT its rootHash/auditPath: a node that ordered a
    # request before its own client copy arrived answers from the
    # ledger later (Node._committed_reply) and proves the same txn
    # against a later tree, so the proof fields legitimately differ
    replies = {n: {} for n in NAMES}
    proof_roots = {n: {} for n in NAMES}  # reqId -> rootHash it proved to
    refused = {n: {} for n in NAMES}      # reqId -> (op, reason)
    read_results = {n: {} for n in NAMES}

    def drain():
        for name, conn in conns.items():
            while conn.rx:
                m = conn.rx.popleft()
                op = m.get("op")
                if op == "REPLY":
                    result = m.get("result") or {}
                    rid = result.get("txn", {}).get(
                        "metadata", {}).get("reqId")
                    if rid is not None:
                        proof_roots[name][rid] = result.get("rootHash")
                        replies[name][rid] = json.dumps(
                            {k: v for k, v in result.items()
                             if k not in ("rootHash", "auditPath")},
                            sort_keys=True, default=str)
                    elif result.get("reqId") is not None:
                        read_results[name][result["reqId"]] = result
                elif op in ("REQNACK", "REJECT"):
                    refused[name][m.get("reqId")] = (op, m.get("reason"))

    # the pool needs a primary before it orders: resend the first valid
    # write until every node has replied to it
    by_id = {r["reqId"]: r for r in stream}
    probe = by_id[valid_ids[0]]
    while True:
        for conn in conns.values():
            conn.send(dict(probe))
        await asyncio.sleep(1.0)
        drain()
        if all(probe["reqId"] in replies[n] for n in NAMES):
            break
        if left() < 0:
            raise RuntimeError("the pool never ordered the probe write")
    log("probe write ordered")

    # request by request to every node, as PoolClient broadcasts: a
    # node that gets a request from a peer's PROPAGATE long before its
    # own client copy authenticates it singly (server/propagator.py:455)
    # — one blocking daemon round trip each
    t0 = time.perf_counter()
    for req in stream:
        if req is not probe:
            for conn in conns.values():
                conn.send(req)
    valid, bad = set(valid_ids), set(bad_ids)
    while True:
        drain()
        if all(valid <= set(replies[n]) and bad <= set(refused[n])
               for n in NAMES):
            break
        if left() < 0:
            break
        await asyncio.sleep(0.02)
    writes_s = time.perf_counter() - t0
    log("writes done in %.1fs" % writes_s)

    # proof-bearing reads: written NYMs, plus the corrupted writes'
    # targets (must be provably absent)
    rng = random.Random(seed + 4)
    read_ids = {}
    picks = rng.sample(valid_ids, min(n_reads, len(valid_ids))) \
        + bad_ids[:max(1, n_reads // 8)]
    for k, wid in enumerate(picks):
        rid = 10 ** 6 + k
        read_ids[rid] = wid
        req = {"identifier": by_id[wid]["identifier"], "reqId": rid,
               "operation": {"type": "105", TARGET_NYM:
                             by_id[wid]["operation"][TARGET_NYM]}}
        for conn in conns.values():
            conn.send(req)
    t0 = time.perf_counter()
    while True:
        drain()
        if all(set(read_ids) <= set(read_results[n]) for n in NAMES):
            break
        if left() < 0:
            break
        await asyncio.sleep(0.02)
    reads_s = time.perf_counter() - t0
    for conn in conns.values():
        conn.close()

    wallet = Wallet("smoke-reader")
    wallet.add_identifier(seed=hashlib.sha256(b"smoke-reader").digest())
    checker = PoolClient(wallet, NAMES, send_fn=lambda n, m: None,
                         bls_verifier=BlsCryptoVerifierPlenum(),
                         bls_key_provider=bls_keys.get)
    proofs_ok = proofs_total = 0
    read_problems = []
    for name in NAMES:
        for rid, wid in read_ids.items():
            result = read_results[name].get(rid)
            proofs_total += 1
            if result is None:
                read_problems.append("%s: no reply to read %d" % (name, rid))
                continue
            written = wid in valid
            data = result.get("data")
            want_vk = by_id[wid]["operation"]["verkey"]
            if written != (data is not None) \
                    or (written and data.get("verkey") != want_vk):
                read_problems.append(
                    "%s: read %d returned %r" % (name, rid, data))
            elif not checker.verify_state_proof(result):
                read_problems.append(
                    "%s: proof of read %d does not verify" % (name, rid))
            else:
                proofs_ok += 1
    return {"replies": replies, "refused": refused,
            "writes_proved_against_a_later_tree": sum(
                len({proof_roots[n].get(rid) for n in NAMES}) > 1
                for rid in valid_ids),
            # a node that finishes authenticating its client copy while
            # the request is ordered-but-uncommitted proposes it again
            # and REJECTs the duplicate: reported, not a failure here
            "valid_writes_also_refused": sum(
                any(rid in refused[n] for n in NAMES) for rid in valid_ids),
            "reads": len(read_ids), "proofs_ok": proofs_ok,
            "proofs_total": proofs_total,
            "read_problems": read_problems[:10],
            "writes_s": round(writes_s, 2), "reads_s": round(reads_s, 2)}


def wait_node_reports(base_dir, want_domain_size, deadline):
    """Each node's own validator-info report, once all four show the
    expected domain ledger size."""
    reports = {}
    while True:
        for name in NAMES:
            path = os.path.join(base_dir, name,
                                "%s_info.json" % name.lower())
            try:
                with open(path) as f:
                    info = json.load(f)
                reports[name] = dict(info["Node_info"],
                                     Device_mesh=info.get("Device_mesh", {}))
            except (OSError, ValueError, KeyError):
                continue
        if len(reports) == len(NAMES) and all(
                r["Ledger_sizes"].get("domain") == want_domain_size
                for r in reports.values()):
            return reports
        if time.monotonic() > deadline:
            return reports
        time.sleep(0.5)


def tail(path, n=40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def phase_pool(args, sz: Sizes, procs: Procs, deadline: float) -> dict:
    from plenum_tpu.bootstrap import generate_pool
    from plenum_tpu.crypto.fixtures import make_signed_batch
    from plenum_tpu.crypto.remote_verifier import RemoteVerifier
    from plenum_tpu.crypto.signer import DidSigner
    from plenum_tpu.server.verify_daemon import wait_ready

    t_phase = time.perf_counter()
    base_dir = tempfile.mkdtemp(prefix="plenum_tpu_smoke_")
    out = {"phase": "pool", "ok": False, "problems": []}
    problems = out["problems"]
    try:
        base_port = 19000 + (os.getpid() % 400) * 10
        trustee_seed = hashlib.sha256(b"%d-trustee" % args.seed).digest()
        generate_pool(base_dir, NAMES, base_port=base_port,
                      trustee_seed=trustee_seed)

        # ---- the verify daemon owns the chip, with the operator's
        # defaults: 4,096 bucket, and a coalesced batch under the
        # 512-item floor takes OpenSSL (a 4,096-lane launch costs the
        # same for 2 items as for 4,096). The client-intake batches are
        # hundreds to thousands deep and take the device, which is what
        # this phase counts; `host_items` says what the floor took
        ready = os.path.join(base_dir, "daemon_ready.json")
        daemon_cmd = [sys.executable, "-m",
                      "plenum_tpu.server.verify_daemon", "--port", "0",
                      "--backend", "tpu_batch", "--bucket", str(sz.bucket),
                      "--ready-file", ready]
        if sz.cpu_floor is not None:
            daemon_cmd += ["--cpu-floor", str(sz.cpu_floor)]
        dout = open(os.path.join(base_dir, "daemon.out"), "w")
        derr = open(os.path.join(base_dir, "daemon.err"), "w")
        daemon = procs.add(subprocess.Popen(
            daemon_cmd, cwd=ROOT, env=chip_env(), stdout=dout,
            stderr=derr))
        dout.close()
        derr.close()
        info = wait_ready(ready, daemon,
                          timeout=max(1.0, deadline - time.monotonic()))
        out["daemon_ready"] = info
        emit({"event": "daemon_ready", **info})
        device = info.get("device") or {}
        if device.get("platform") != "tpu" and not sz.tiny:
            problems.append("the daemon got %s, not a tpu" % device)
            return out

        # ---- first launch (cold compile of the bucket) and a steady one
        rv = RemoteVerifier(("127.0.0.1", info["port"]), timeout=900)
        wm, ws, wv = make_signed_batch(sz.bucket, seed=args.seed + 5)
        warm = list(zip(wm, ws, wv))
        t0 = time.perf_counter()
        first_ok = all(rv.verify_batch(warm))
        out["daemon_first_launch_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        second_ok = all(rv.verify_batch(warm))
        out["daemon_steady_launch_s"] = round(time.perf_counter() - t0, 3)
        rv.close()
        if not (first_ok and second_ok):
            problems.append("the daemon rejected valid warm-up signatures")
            return out

        # ---- four node processes through the operator's start script.
        # No JAX variable is set for them: VERIFIER_PROVIDER="remote"
        # makes the start path pin the CPU backend by itself
        with open(os.path.join(base_dir, "plenum_tpu_config.py"), "w") as f:
            f.write("Max3PCBatchSize = 1000\n"
                    "Max3PCBatchWait = 0.05\n"
                    "CHK_FREQ = 10\n"
                    "LOG_SIZE = 30\n"
                    "CLIENT_TO_NODE_STACK_QUOTA = 4000\n"
                    "NODE_TO_NODE_STACK_QUOTA = 4096\n"
                    "NODE_TO_NODE_STACK_SIZE = %d\n"
                    "CLIENT_TO_NODE_STACK_SIZE = %d\n"
                    "VERIFIER_PROVIDER = 'remote'\n"
                    "VERIFIER_DAEMON_PORT = %d\n"
                    "VALIDATOR_INFO_DUMP_INTERVAL = 2\n"
                    % (16 << 20, 16 << 20, info["port"]))
        script = os.path.join(ROOT, "scripts", "start_plenum_tpu_node")
        for name in NAMES:
            nout = open(os.path.join(base_dir, "%s.out" % name), "w")
            procs.add(subprocess.Popen(
                [sys.executable, script, "--name", name,
                 "--base-dir", base_dir],
                cwd=ROOT, stdout=nout, stderr=subprocess.STDOUT),
                sig=signal.SIGINT)
            nout.close()

        signer = DidSigner(seed=trustee_seed)
        stream, valid_ids, bad_ids = make_writes(
            signer, sz.writes, sz.bad_writes, args.seed)
        got = asyncio.run(drive_client(
            base_dir, stream, valid_ids, bad_ids, sz.reads, args.seed,
            deadline))

        # ---- every valid write: matching REPLYs from all four nodes
        replies, refused = got.pop("replies"), got.pop("refused")
        unmatched = [rid for rid in valid_ids
                     if len({replies[n].get(rid) for n in NAMES}) != 1
                     or replies[NAMES[0]].get(rid) is None]
        if unmatched:
            problems.append("%d valid writes without four matching "
                            "REPLYs (first %s)"
                            % (len(unmatched), unmatched[:5]))
        # ---- every corrupted write: refused by all, ordered by none
        leaked = [rid for rid in bad_ids
                  if any(rid in replies[n] for n in NAMES)]
        unrefused = [rid for rid in bad_ids
                     if not all(rid in refused[n] for n in NAMES)]
        if leaked or unrefused:
            problems.append("corrupted writes ordered %s / not refused "
                            "by every node %s" % (leaked[:5], unrefused[:5]))
        if got["proofs_ok"] != got["proofs_total"] or not got["proofs_ok"]:
            problems.append("read proofs: %d of %d verified (%s)"
                            % (got["proofs_ok"], got["proofs_total"],
                               got["read_problems"]))
        out.update(got)
        out["writes"] = {"valid": len(valid_ids), "corrupted": len(bad_ids)}

        # ---- four nodes, one ledger and one state
        genesis_domain = 1 + len(NAMES)       # trustee + one steward each
        want_size = genesis_domain + len(valid_ids)
        reports = wait_node_reports(
            base_dir, want_size, min(deadline, time.monotonic() + 90))
        sizes = {n: r["Ledger_sizes"].get("domain")
                 for n, r in reports.items()}
        lroots = {r["Committed_ledger_root_hashes"].get("domain")
                  for r in reports.values()}
        sroots = {r["Committed_state_root_hashes"].get("domain")
                  for r in reports.values()}
        out["domain_ledger"] = {"sizes": sizes, "roots": sorted(lroots),
                                "state_roots": sorted(sroots)}
        if len(reports) != len(NAMES) \
                or set(sizes.values()) != {want_size} \
                or len(lroots) != 1 or len(sroots) != 1:
            problems.append("nodes disagree or are short of %d domain "
                            "txns: %s" % (want_size, out["domain_ledger"]))
        node_platforms = {n: r["Device_mesh"].get("platform")
                          for n, r in reports.items()}
        out["node_platforms"] = node_platforms
        if any(p not in (None, "cpu") for p in node_platforms.values()):
            problems.append("a node process opened an accelerator: %s"
                            % node_platforms)

        # ---- clean stop; the daemon's last stdout line is its counters
        procs.stop_all()
        stats = None
        for line in reversed(tail(os.path.join(base_dir, "daemon.out"),
                                  5).splitlines()):
            if line.startswith("{"):
                stats = json.loads(line)
                break
        out["daemon_stats"] = stats
        if stats is None:
            problems.append("the daemon printed no final stats line")
        else:
            if stats["device_launches"] < 1 \
                    or stats["device_items"] < len(valid_ids):
                problems.append(
                    "the device verified %d items in %d launches; the "
                    "pool ordered %d valid writes"
                    % (stats["device_items"], stats["device_launches"],
                       len(valid_ids)))
            if stats["failed_batches"] or stats.get("step_downs") \
                    or stats["mesh"]["dispatches"] < stats["device_launches"]:
                problems.append(
                    "daemon left the device path: failed_batches=%d "
                    "step_downs=%s, %d of %d launches reached the "
                    "dispatcher"
                    % (stats["failed_batches"], stats.get("step_downs"),
                       stats["mesh"]["dispatches"],
                       stats["device_launches"]))
            if device.get("platform") == "tpu" and sz.bucket >= 4096 \
                    and not all(stats.get("kernel_backends", {}).values()):
                problems.append("ed25519 did not take the Pallas kernel: "
                                "%s" % stats.get("kernel_backends"))
        out["ok"] = not problems
        return out
    finally:
        procs.stop_all()
        out["seconds"] = round(time.perf_counter() - t_phase, 2)
        if not out["ok"]:
            for name in ["daemon.err"] + ["%s.out" % n for n in NAMES]:
                log("---- tail of %s ----\n%s"
                    % (name, tail(os.path.join(base_dir, name))))
        if args.keep_logs:
            keep = os.path.join(args.keep_logs, "pool")
            shutil.rmtree(keep, ignore_errors=True)
            os.makedirs(keep, exist_ok=True)
            for name in os.listdir(base_dir):
                src = os.path.join(base_dir, name)
                if os.path.isfile(src):
                    shutil.copy(src, keep)
            for name in NAMES:
                lg = os.path.join(base_dir, name, "logs", name + ".log")
                if os.path.exists(lg):
                    shutil.copy(lg, keep)
        shutil.rmtree(base_dir, ignore_errors=True)


# -------------------------------------------------------------- main

def finish(ok: bool, device, extra=None) -> int:
    """The contract's last line, and the exit code."""
    if device:
        device = {k: device.get(k) for k in ("platform", "kind", "count")}
    if ok:
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    line = {"ok": False, "device": device}
    line.update(extra or {})
    print(json.dumps(line, default=str), flush=True)
    return 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22,
                    help="every input of every phase derives from it")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes (every phase still runs)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the four-chip mesh path and its "
                         "one-device comparison")
    ap.add_argument("--keep-logs", default=None, metavar="DIR",
                    help="copy the pool's daemon/node logs here")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    sz = Sizes(args.tiny)

    if args.child == "kernels":
        return child_kernels(sz, args.seed)
    if args.child == "mesh4":
        return child_mesh4(sz, args.seed)
    if args.child == "device":
        return child_device()

    if not os.path.isdir(os.path.join(ROOT, "plenum_tpu")):
        return finish(False, None, {"error": "plenum_tpu/ is not beside "
                                    "chip_smoke.py"})
    t_run = time.monotonic()
    deadline = t_run + RUN_BUDGET_S
    procs = Procs()
    signal.signal(signal.SIGTERM, lambda s, f: sys.exit(143))
    device = None
    failures = []
    try:
        natives = native_modules()
        emit({"event": "native_modules", **natives})
        missing = [k for k, loaded in natives.items() if not loaded]
        if missing:
            failures.append("native modules fell back to Python: %s"
                            % missing)

        if args.four_chips:
            extra_env = {}
            if chip_env()["JAX_PLATFORMS"] == "cpu":
                # rehearsal: four virtual CPU devices, sharding forced
                extra_env = {
                    "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                                  " --xla_force_host_platform_device_"
                                  "count=4").strip(),
                    "PLENUM_TPU_MESH_CPU_SHARD": "1"}
            rc, res = run_child("mesh4", args, procs, deadline, extra_env)
            device = (res or {}).get("device")
            if rc != 0 or not res or not res["ok"]:
                failures.append("four-chip phase failed (rc %d)" % rc)
            want_count = 4
        else:
            # which device will the children get? a child says so and
            # exits — at full size nothing else starts without a tpu
            rc, res = run_child("device", args, procs,
                                time.monotonic() + PREFLIGHT_BUDGET_S)
            device = (res or {}).get("device")
            if rc != 0 or not device:
                return finish(False, device, {
                    "failures": ["no device: the preflight child failed "
                                 "(rc %d)" % rc]})
            if device["platform"] != "tpu" and not args.tiny:
                return finish(False, device, {
                    "failures": ["platform is %r, not 'tpu'"
                                 % device["platform"]]})
            pool = phase_pool(args, sz, procs, deadline)
            emit({"event": "phase_result", **pool})
            if not pool["ok"]:
                failures.append("pool phase: %s" % pool["problems"])
            pool_device = (pool.get("daemon_ready") or {}).get("device")
            rc, res = run_child("kernels", args, procs, deadline)
            if rc != 0 or not res or not res["ok"]:
                failures.append("kernels phase failed (rc %d): %s" % (
                    rc, (res or {}).get("failed")))
            for who, dev in (("daemon", pool_device),
                             ("kernels", (res or {}).get("device"))):
                if dev != device:
                    failures.append("%s held %s, the preflight saw %s"
                                    % (who, dev, device))
            want_count = 1

        if not jax_backend_untouched():
            failures.append("the parent process initialised a JAX backend")
        if not device or device.get("platform") != "tpu":
            failures.append("platform is %r, not 'tpu'"
                            % (device or {}).get("platform"))
        elif device.get("count") != want_count:
            failures.append("device count is %r, not %d"
                            % (device.get("count"), want_count))
        emit({"event": "summary",
              "seconds": round(time.monotonic() - t_run, 1),
              "failures": failures})
        return finish(not failures, device, {"failures": failures})
    finally:
        procs.stop_all()


if __name__ == "__main__":
    sys.exit(main())
