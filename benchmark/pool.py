"""Launcher of one deployment: the verify daemon that owns the chip, n
node processes through the operator's start script, and their logs and
reports. Copied from chip_smoke.py's pool phase (PR 22), which ran on
the chip; this process never initialises a JAX backend."""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NODE_NAMES = ["Alpha", "Beta", "Gamma", "Delta", "Epsilon", "Zeta", "Eta",
              "Theta", "Iota", "Kappa", "Lambda", "Mu", "Nu", "Xi",
              "Omicron", "Pi", "Rho", "Sigma", "Tau", "Upsilon", "Phi",
              "Chi", "Psi", "Omega", "Aleph"]


T_START = time.perf_counter()


def log(*a) -> None:
    """To standard error, with the seconds since this module was
    imported (a few tenths after the process started): the parts of
    set-up are read off these lines."""
    print("[bench %6.1fs]" % (time.perf_counter() - T_START), *a,
          file=sys.stderr, flush=True)


def tail(path, n=30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


class Procs:
    """Every process the run starts, so that all of them are stopped and
    waited for whatever happens."""

    def __init__(self):
        self.items = []

    def add(self, proc, sig=signal.SIGTERM):
        self.items.append((proc, sig))
        return proc

    def stop(self, procs=None):
        chosen = [(p, s) for p, s in self.items
                  if procs is None or p in procs]
        for proc, sig in chosen:
            if proc.poll() is None:
                proc.send_signal(sig)
        for proc, _sig in chosen:
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        self.items = [(p, s) for p, s in self.items
                      if (p, s) not in chosen]


def native_modules() -> dict:
    """Build and load every native module once here, so that n node
    processes find the built libraries instead of racing to build."""
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


class Daemon:
    """The process that owns the chip. Started through the benchmark's
    thin entry (daemon_entry.py), which calls the program's run_daemon
    unchanged and adds what only that process can read: the device's
    peak memory and, in a traced run, the profiler's bracket."""

    entry = os.path.join(HERE, "daemon_entry.py")

    def __init__(self, procs: Procs, workdir: str, config: dict,
                 tiny: bool, traced: bool):
        self.procs = procs
        self.dir = workdir
        self.config = config
        self.tiny = tiny
        self.traced = traced
        self.ready_file = os.path.join(workdir, "daemon_ready.json")
        self.trace_file = os.path.join(workdir, "daemon_spans.json")
        self.profile_dir = os.path.join(workdir, "profile")
        self.side_file = os.path.join(workdir, "daemon_side.json")
        self.proc = None
        self.info = None
        self.warm = {}

    def _args(self) -> dict:
        """The daemon's arguments as the configuration gives them (none:
        its defaults), with the rehearsal's overrides under --tiny."""
        daemon = dict(self.config["daemon"])
        if self.tiny:
            daemon.update(self.config["tiny"]["daemon"])
        return daemon

    def start(self):
        daemon = self._args()
        cmd = [sys.executable, self.entry, "--port", "0", "--ready-file", self.ready_file,
               "--side-file", self.side_file]
        for key, value in sorted(daemon.items()):
            if value is not None:
                cmd += ["--" + key.replace("_", "-"), str(value)]
        if self.traced:
            cmd += ["--trace-file", self.trace_file,
                    "--profile-dir", self.profile_dir]
        env = dict(os.environ)
        # platform pinned: a chip that cannot be initialised fails the
        # start instead of landing on the CPU backend
        env["JAX_PLATFORMS"] = "cpu" if self.tiny else "tpu"
        env["TPU_LOG_DIR"] = env.get("TPU_LOG_DIR", "disabled")
        with open(os.path.join(self.dir, "daemon.out"), "w") as out, \
                open(os.path.join(self.dir, "daemon.err"), "w") as err:
            self.proc = self.procs.add(subprocess.Popen(
                cmd, cwd=ROOT, env=env, stdout=out, stderr=err))

    def wait_ready(self, timeout: float) -> dict:
        from plenum_tpu.server.verify_daemon import wait_ready
        self.info = wait_ready(self.ready_file, self.proc, timeout=timeout)
        return self.info

    def warm_up(self, seed: int, timeout: float) -> None:
        """Two launches of the one shape the defaults launch: the first
        compiles (or traces, lowers and loads from the cache), the
        second is steady. Counted so the readers can take them off."""
        from plenum_tpu.crypto.fixtures import make_signed_batch
        from plenum_tpu.crypto.remote_verifier import RemoteVerifier
        bucket = self.bucket
        rv = RemoteVerifier(("127.0.0.1", self.info["port"]),
                            timeout=timeout)
        try:
            msgs, sigs, vks = make_signed_batch(bucket, seed=seed % 2**31)
            batch = list(zip(msgs, sigs, vks))
            t0 = time.perf_counter()
            first = all(rv.verify_batch(batch))
            t1 = time.perf_counter()
            second = all(rv.verify_batch(batch))
            t2 = time.perf_counter()
        finally:
            rv.close()
        if not (first and second):
            raise RuntimeError("the daemon rejected valid warm-up "
                               "signatures")
        self.warm = {"first_launch_s": t1 - t0, "steady_launch_s": t2 - t1,
                     "device_items": 2 * bucket, "device_launches": 2}

    @property
    def bucket(self) -> int:
        bucket = self._args().get("bucket")
        if bucket is not None:
            return int(bucket)
        from plenum_tpu.common.config import Config
        return Config.VERIFY_DAEMON_BUCKET

    def signal_profile(self, start: bool) -> None:
        self.proc.send_signal(signal.SIGUSR1 if start else signal.SIGUSR2)

    def stop(self):
        """Clean stop → (final stats line or None, side file or {})."""
        self.procs.stop([self.proc])
        stats = None
        for line in reversed(tail(os.path.join(self.dir, "daemon.out"),
                                  5).splitlines()):
            if line.startswith("{"):
                stats = json.loads(line)
                break
        try:
            with open(self.side_file) as f:
                side = json.load(f)
        except (OSError, ValueError):
            side = {}
        return stats, side


class Pool:
    """n validator processes on a fresh base dir, each through the
    operator's start script. tests/ plants a faulty node by putting its
    own entry for one name into `entries`."""

    entry = os.path.join(ROOT, "scripts", "start_plenum_tpu_node")
    entries = {}

    def __init__(self, procs: Procs, base_dir: str, config: dict,
                 tiny: bool, base_port: int):
        self.procs = procs
        self.base_dir = base_dir
        self.config = config
        self.tiny = tiny
        self.base_port = base_port
        self.names = NODE_NAMES[:config["nodes"]]
        self.f = (len(self.names) - 1) // 3
        self.node_procs = {}
        self._genesis = None

    def generate(self, seed: int) -> dict:
        """generate_pool's pool, and after its own domain genesis lines
        the role-less NYMs of the configuration's `genesis.identities`,
        in the shape of the lines that are there. The nodes read the
        file as they start; the reference reads the same file."""
        from plenum_tpu.bootstrap import generate_pool
        summary = generate_pool(self.base_dir, self.names,
                                base_port=self.base_port,
                                trustee_seed=traffic.trustee_seed(seed))
        count = (self.config.get("genesis") or {}).get("identities", 0)
        if count:
            with open(os.path.join(self.base_dir,
                                   "domain_transactions_genesis"),
                      "a") as f:
                f.writelines(json.dumps(
                    traffic.identity(seed, i).genesis_nym(),
                    sort_keys=True) + "\n" for i in range(count))
        return summary

    def genesis_domain_txns(self):
        """The domain genesis file as generate() left it, read once."""
        if self._genesis is None:
            with open(os.path.join(self.base_dir,
                                   "domain_transactions_genesis")) as f:
                self._genesis = [json.loads(line) for line in f
                                 if line.strip()]
        return self._genesis

    def write_config(self, daemon_port: int) -> None:
        settings = dict(self.config["node_config"])
        if self.tiny:
            settings.update(self.config["tiny"].get("node_config", {}))
        settings["VERIFIER_DAEMON_PORT"] = daemon_port
        with open(os.path.join(self.base_dir, "plenum_tpu_config.py"),
                  "w") as f:
            for key, value in sorted(settings.items()):
                f.write("%s = %r\n" % (key, value))

    def start_nodes(self) -> None:
        """No JAX variable is set for the nodes: VERIFIER_PROVIDER =
        "remote" makes the start path pin the CPU backend by itself."""
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        for name in self.names:
            entry = self.entries.get(name, self.entry)
            with open(os.path.join(self.base_dir, name + ".out"),
                      "w") as out:
                self.node_procs[name] = self.procs.add(subprocess.Popen(
                    [sys.executable, entry, "--name", name,
                     "--base-dir", self.base_dir],
                    cwd=ROOT, env=env, stdout=out,
                    stderr=subprocess.STDOUT), sig=signal.SIGINT)

    def dead_nodes(self):
        return [n for n, p in self.node_procs.items()
                if p.poll() is not None]

    def reports(self) -> dict:
        """Each node's newest validator-info dump, as far as present."""
        out = {}
        for name in self.names:
            path = os.path.join(self.base_dir, name,
                                "%s_info.json" % name.lower())
            try:
                with open(path) as f:
                    info = json.load(f)
                out[name] = dict(info["Node_info"],
                                 Device_mesh=info.get("Device_mesh", {}))
            except (OSError, ValueError, KeyError):
                continue
        return out

    def wait_reports(self, want_domain_size: int, deadline: float) -> dict:
        while True:
            reports = self.reports()
            if len(reports) == len(self.names) and all(
                    r["Ledger_sizes"].get("domain") == want_domain_size
                    for r in reports.values()):
                return reports
            if time.monotonic() > deadline:
                return reports
            time.sleep(0.25)

    def cpu_seconds(self) -> float:
        """User + system CPU seconds of all node processes so far."""
        ticks = os.sysconf("SC_CLK_TCK")
        total = 0.0
        for proc in self.node_procs.values():
            try:
                with open("/proc/%d/stat" % proc.pid) as f:
                    fields = f.read().rsplit(")", 1)[1].split()
                total += (int(fields[11]) + int(fields[12])) / ticks
            except (OSError, IndexError, ValueError):
                pass
        return total

    def stop(self) -> None:
        self.procs.stop(list(self.node_procs.values()))

    def log_tails(self) -> None:
        for name in self.names:
            log("---- tail of %s.out ----\n%s" % (
                name, tail(os.path.join(self.base_dir, name + ".out"))))


def in_thread(fn, *args):
    """Run fn in a thread → (thread, box); box['error'] or box['value']."""
    box = {}

    def run():
        try:
            box["value"] = fn(*args)
        except BaseException as e:  # re-raised by the caller
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box
