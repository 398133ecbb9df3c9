"""What the chip bring-up changed, checked on the CPU: who owns the
device (node start path, daemon ready file), where the compile cache
lives, what is counted when a device path is left, and chip_smoke.py's
own failure mode without a chip.
"""
import json
import os
import signal
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ["Alpha", "Beta", "Gamma", "Delta"]


def _clean_env(**extra):
    """The suite's env minus what conftest forces for in-process tests
    (virtual devices, CPU sharding, the BLS tower pinned off)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PLENUM_TPU_MESH_CPU_SHARD",
                        "PLENUM_TPU_BLS_TOWER", "PLENUM_TPU_SANITIZE")}
    env.update(extra)
    return env


# ------------------------------------------------------ compile cache

def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins; otherwise the fixed
    <checkout>/.jax_cache — never a temporary or pid-derived path."""
    import jax
    from plenum_tpu.ops import enable_persistent_compilation_cache
    prior = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_persistent_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_persistent_compilation_cache() \
            == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir \
            == os.path.join(ROOT, ".jax_cache")
    finally:
        monkeypatch.undo()      # the session's own placement again
        assert enable_persistent_compilation_cache() == prior


def test_one_setter_of_the_cache_directory():
    """Every entry point goes through ops.enable_persistent_compilation_
    cache: no other file names the config key."""
    hits = []
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        for f in files:
            if not (f.endswith(".py") or base.endswith("scripts")):
                continue
            path = os.path.join(base, f)
            with open(path, errors="replace") as fh:
                if "jax_compilation_cache_dir\"" in fh.read():
                    hits.append(os.path.relpath(path, ROOT))
    assert hits == [os.path.join("plenum_tpu", "ops", "__init__.py")]


# ------------------------------------------------ one process per chip

@pytest.mark.parametrize("provider,pinned", [("remote", True),
                                             ("adaptive", False)])
def test_device_ownership_follows_verifier_provider(monkeypatch,
                                                    provider, pinned):
    """A node beside a verify daemon (VERIFIER_PROVIDER="remote") pins
    itself to the CPU backend; a node that verifies in-process leaves
    JAX's platform choice alone — it owns its chip."""
    from plenum_tpu.bootstrap import settle_device_ownership
    from plenum_tpu.common.config import Config
    monkeypatch.setenv("JAX_PLATFORMS", "cpu,tpu")    # "as the host set it"
    settle_device_ownership(Config(VERIFIER_PROVIDER=provider))
    assert os.environ["JAX_PLATFORMS"] == ("cpu" if pinned else "cpu,tpu")


def test_node_start_path_leaves_the_chip_to_the_daemon(tdir):
    """scripts/start_plenum_tpu_node's build path, in a fresh process
    whose environment asks for the TPU: with a remote verifier
    configured the node lands on the CPU backend by itself (here the
    TPU cannot be initialised at all, so opening it would raise)."""
    from plenum_tpu.bootstrap import generate_pool
    generate_pool(tdir, NAMES, base_port=19750)
    with open(os.path.join(tdir, "plenum_tpu_config.py"), "w") as f:
        f.write("VERIFIER_PROVIDER = 'remote'\n"
                "VERIFIER_DAEMON_PORT = 1\n")
    code = (
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "from plenum_tpu.bootstrap import build_networked_node\n"
        "node = build_networked_node('Alpha', %r)\n"
        "import jax\n"
        "from plenum_tpu.ops import mesh\n"
        "print(jax.default_backend(), os.environ['JAX_PLATFORMS'],\n"
        "      jax.config.jax_compilation_cache_dir, mesh.probe_platform())\n"
        % (ROOT, tdir))
    out = subprocess.run(
        [sys.executable, "-c", code], env=_clean_env(JAX_PLATFORMS="tpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    backend, pin, cache, probed = out.stdout.split()[-4:]
    assert (backend, pin, probed) == ("cpu", "cpu", "cpu")
    # the node start path placed the compile cache too
    assert cache == os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                   os.path.join(ROOT, ".jax_cache"))


def test_bench_import_does_not_initialise_a_backend():
    """bench.py enables the compile cache at import; its daemon-owning
    sections must finish before the bench process touches a device."""
    code = ("import sys; sys.path.insert(0, %r); import bench\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=_clean_env(),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


# ------------------------------------------------- the daemon's handshake

def test_daemon_ready_file_and_final_stats(tmp_path):
    """Started with a device backend the daemon claims its device
    BEFORE serving and says what it got (ready file: port, backend,
    device facts, compile cache — what bench.py and chip_smoke.py read
    through wait_ready); on SIGTERM it stops cleanly and its last
    stdout line is its counters."""
    from plenum_tpu.crypto.fixtures import make_signed_batch
    from plenum_tpu.crypto.remote_verifier import RemoteVerifier
    from plenum_tpu.server.verify_daemon import wait_ready
    ready = str(tmp_path / "ready.json")
    proc = subprocess.Popen(
        [sys.executable, "-m", "plenum_tpu.server.verify_daemon",
         "--port", "0", "--backend", "tpu_batch", "--bucket", "8",
         "--cpu-floor", "4", "--ready-file", ready],
        cwd=ROOT, env=_clean_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        info = wait_ready(ready, proc, timeout=120)
        assert info["backend"] == "tpu_batch" and info["pid"] == proc.pid
        assert info["device"] == {"platform": "cpu", "kind": "cpu",
                                  "count": 1}
        assert info["compile_cache"] == os.environ.get(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
        rv = RemoteVerifier(("127.0.0.1", info["port"]), timeout=300)
        m, s, v = make_signed_batch(8, seed=1)
        items = list(zip(m, s, v))
        assert rv.verify_batch(items[:2]) == [True, True]     # below floor
        assert rv.verify_batch(items) == [True] * 8           # one bucket
        rv.close()
    finally:
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=60)
    assert proc.returncode == 0, err[-2000:]
    stats = json.loads(out.strip().splitlines()[-1])
    assert stats["host_items"] == 2 and stats["device_items"] == 8
    assert stats["device_launches"] == 1 and stats["launches"] == 2
    assert stats["failed_batches"] == 0 and stats["step_downs"] == {}
    assert stats["mesh"]["dispatches"] == 1
    assert "device: " in err and '"platform": "cpu"' in err   # the log line


def test_wait_ready_fails_when_the_daemon_dies(tmp_path):
    from plenum_tpu.server.verify_daemon import wait_ready
    proc = subprocess.Popen([sys.executable, "-c", "raise SystemExit(3)"])
    with pytest.raises(RuntimeError, match="failed to start"):
        wait_ready(str(tmp_path / "never"), proc, timeout=30)


def test_daemon_floor_takes_openssl_and_is_counted():
    """Below the floor a fused batch takes OpenSSL (never a fresh
    device compile at an odd shape), and the daemon says so."""
    from plenum_tpu.crypto.fixtures import make_signed_batch
    from plenum_tpu.server.verify_daemon import VerifyDaemon
    daemon = VerifyDaemon(backend="adaptive", bucket=4096, cpu_floor=512)
    m, s, v = make_signed_batch(48, seed=2)
    s = list(s)
    s[5] = bytes(64)
    want = [i != 5 for i in range(48)]
    assert daemon._verify_bucketed(list(zip(m, s, v))) == want
    st = daemon.stats()
    assert (st["host_items"], st["device_items"],
            st["device_launches"]) == (48, 0, 0)


# ------------------------------------------------ counted step-downs

def test_breaker_counts_every_host_served_call():
    """`failures` never resets: a single engine failure that the host
    served — no trip, fail_count back to 0 on the next success — stays
    visible to chip_smoke.py and the benchmark."""
    from plenum_tpu.utils.device_breaker import DeviceCircuitBreaker
    br = DeviceCircuitBreaker("engine", "host", max_failures=3,
                              cooldown_s=10.0)

    def boom():
        raise RuntimeError("device fault")

    assert br.run(boom) == (False, None)
    assert br.run(lambda: 7) == (True, 7)
    assert (br.failures, br.fail_count, br.trips) == (1, 0, 0)


def test_engine_warm_up_failure_is_counted(monkeypatch):
    """Attach-time warm-up runs under the serving breaker: a broken
    backend still does not fail bootstrap, but it is no longer silent."""
    from plenum_tpu.state import device_state
    from plenum_tpu.state.pruning_state import PruningState
    from plenum_tpu.storage.kv_memory import KeyValueStorageInMemory

    def broken(self):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(device_state.DeviceStateEngine, "warm", broken)
    state = PruningState(KeyValueStorageInMemory())
    state.attach_device_engine(warm=True)
    assert state._engine_breaker.failures == 1
    state.set(b"k", b"v")
    assert state.headHash            # the host trie serves


def test_sharded_merkle_build_avoids_the_pallas_backend(monkeypatch):
    """A Mosaic kernel cannot be partitioned automatically, so a
    mesh-sharded build must hand the XLA expression to the SPMD
    partitioner even where the single-device build takes Pallas (the
    TPU compiler's refusal is pinned in tests/test_tpu_compile.py)."""
    from plenum_tpu.ops import merkle, mesh, sha256_pallas
    monkeypatch.setattr(merkle, "select_backend", lambda rows: "pallas")
    monkeypatch.setattr(mesh, "_PROVEN",
                        {(sha256_pallas.PALLAS_ENV, ("build", "k"))})
    tree = merkle.DeviceMerkleTree()
    seen = []
    tree._run_build(lambda be: seen.append(be) or (), 4096, ("k",), True)
    tree._run_build(lambda be: seen.append(be) or (), 4096, ("k",), False)
    assert seen == ["plain", "pallas"]


# ----------------------------------------------------- chip_smoke.py

def _last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=_clean_env(), capture_output=True, text=True,
                         timeout=60)
    assert out.returncode != 0
    assert _last_json(out.stdout)["ok"] is False


def test_chip_smoke_without_a_chip_fails_before_any_phase():
    """As the driver runs it in a sandbox: full size, no accelerator —
    the preflight child reports the platform and nothing else starts."""
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=_clean_env(JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=180)
    assert out.returncode != 0
    last = _last_json(out.stdout)
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "phase_result" not in out.stdout


def test_chip_smoke_rehearsal_runs_every_phase_and_still_fails():
    """JAX_PLATFORMS=cpu, --tiny: the pool phase (daemon + four node
    processes + client) and every kernel check run and pass, and the
    run still exits non-zero with "ok": false because the platform is
    not "tpu" — the no-chip failure mode is itself tested."""
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--tiny"], cwd=ROOT,
        env=_clean_env(JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert out.returncode != 0 and last["ok"] is False, out.stderr[-3000:]
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert last["failures"] == ["platform is 'cpu', not 'tpu'"]
    by_event = {}
    for obj in lines:
        by_event.setdefault(obj.get("event"), []).append(obj)
    assert all(by_event["native_modules"][0][m] for m in
               ("bls12_381", "kvlog", "mpt_c", "fastpath", "rlp_c"))
    pool = by_event["phase_result"][0]
    assert pool["ok"] and pool["writes"] == {"valid": 64, "corrupted": 8}
    assert pool["proofs_ok"] == pool["proofs_total"] > 0
    assert set(pool["node_platforms"].values()) <= {None, "cpu"}
    assert pool["daemon_stats"]["device_items"] >= 64
    assert pool["daemon_stats"]["failed_batches"] == 0
    kernels = [o for o in by_event["child_result"]
               if o["phase"] == "kernels"][0]
    assert kernels["ok"] and kernels["device_path"]["step_downs"] == {}
    checks = {o["check"]: o for o in lines if "check" in o}
    assert set(checks) == {"ed25519", "merkle", "state", "bls_aggregate",
                           "bls_pairing"}
    assert all(c["ok"] for c in checks.values())


def test_chip_smoke_four_chip_option_rehearsal():
    """--four-chips on four virtual CPU devices: only the mesh path and
    its one-device comparison run (equal results, shards on four
    distinct devices), the last line reports count 4 — and the run
    fails, because the platform is not "tpu". The driver never gives
    this option; this keeps it running."""
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--tiny", "--four-chips"],
        cwd=ROOT, env=_clean_env(JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert out.returncode != 0 and last["ok"] is False, out.stderr[-3000:]
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    checks = {o["check"]: o for o in lines if "check" in o}
    assert set(checks) == {"mesh_ed25519", "mesh_merkle"}
    assert all(c["ok"] for c in checks.values())
    assert len(set(checks["mesh_ed25519"]["output_shard_devices"])) == 4
    assert len(set(checks["mesh_merkle"]["gather_shard_devices"])) == 4
    result = [o for o in lines if o.get("event") == "child_result"][0]
    assert result["phase"] == "four_chips" and result["ok"]
    assert result["mesh"]["sharded_dispatches"] >= 4
    assert "phase_result" not in out.stdout       # no pool, no kernels
