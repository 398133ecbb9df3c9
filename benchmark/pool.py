"""Launcher of one deployment: the verify daemon that owns the chip, n
node processes through the operator's start script, and their logs and
reports. Copied from chip_smoke.py's pool phase (PR 22), which ran on
the chip; this process never initialises a JAX backend."""
import ctypes
import errno
import json
import os
import re
import signal
import socket
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


def last_line(path) -> str:
    lines = [ln for ln in tail(path, 5).splitlines() if ln.strip()]
    return lines[-1].strip() if lines else ""


# ------------------------------------------------------------ processes

_PR_SET_PDEATHSIG = 1
try:
    _prctl = ctypes.CDLL(None, use_errno=True).prctl
except (OSError, AttributeError):    # not Linux: children are not tied
    _prctl = None


def die_with_parent():
    """→ a `preexec_fn`: the kernel kills the child when the thread that
    started it ends, so the daemon (which holds the chip) and the nodes
    (which hold ports) go with a run.py that is killed at its limit.
    Only the main thread starts processes: a parent-death signal follows
    the starting THREAD, not the process."""
    parent = os.getpid()

    def tie():
        if _prctl is not None:
            _prctl(_PR_SET_PDEATHSIG, signal.SIGKILL)
            if os.getppid() != parent:     # it died before the call
                os._exit(1)
    return tie


def why_alive(pid: int) -> str:
    """What the kernel says of a process that will not end: its state,
    and where its threads sleep, as far as this host lets one read it
    (/proc/<pid>/task/<tid>/stack or wchan; neither, on the chip host)."""
    out = []
    try:
        with open("/proc/%d/status" % pid) as f:
            out += [ln.strip() for ln in f
                    if ln.split(":")[0] in ("State", "Threads", "SigBlk",
                                            "SigIgn", "SigCgt")]
        asleep = {}
        for tid in os.listdir("/proc/%d/task" % pid):
            for name in ("stack", "wchan"):
                try:
                    with open("/proc/%d/task/%s/%s" % (pid, tid, name)) as f:
                        where = " ".join(f.read().split())[:300]
                except OSError:
                    continue
                if where and where != "0":
                    asleep[where] = asleep.get(where, 0) + 1
                    break
        out += ["%d thread(s) in %s" % (n, where) for where, n in sorted(
            asleep.items(), key=lambda kv: -kv[1])[:8]] \
            or ["no thread's kernel stack or wchan can be read here"]
    except OSError as e:
        out.append("gone: %s" % e)
    return "\n".join(out)


class Procs:
    """Every process the run starts, so that all of them are stopped and
    waited for whatever happens. `killed` names each one that did not end
    on its signal within STOP_WAIT_S, with the seconds it was given."""

    STOP_WAIT_S = 20

    def __init__(self):
        self.items = []       # (proc, signal, name, dumps its stacks)
        self.killed = []

    def add(self, proc, sig=signal.SIGTERM, name=None, dumps_stacks=False):
        """`dumps_stacks`: the process writes every thread's stack to its
        standard error on SIGQUIT (daemon_entry.py arms faulthandler)."""
        self.items.append((proc, sig, name or "pid %d" % proc.pid,
                           dumps_stacks))
        return proc

    def stop(self, procs=None):
        chosen = [item for item in self.items
                  if procs is None or item[0] in procs]
        t0 = time.monotonic()
        for proc, sig, _name, _dumps in chosen:
            if proc.poll() is None:
                proc.send_signal(sig)
        for item in chosen:
            try:
                item[0].wait(timeout=max(
                    0.0, t0 + self.STOP_WAIT_S - time.monotonic()))
            except subprocess.TimeoutExpired:
                self._kill(*item, waited=time.monotonic() - t0)
        self.items = [item for item in self.items if item not in chosen]

    def _kill(self, proc, sig, name, dumps_stacks, waited: float) -> None:
        log("%s (pid %d) has not ended %.1fs after signal %d\n%s" % (
            name, proc.pid, waited, sig, why_alive(proc.pid)))
        if dumps_stacks:
            proc.send_signal(signal.SIGQUIT)
            try:
                proc.wait(timeout=1)      # a second to write them
            except subprocess.TimeoutExpired:
                pass
            waited += 1
        proc.kill()
        proc.wait()
        log("%s killed" % name)
        self.killed.append({"name": name, "waited_s": round(waited, 1)})


# ---------------------------------------------------------------- ports

LEGACY_PORTS = (19000, 31800)     # where the bases lay until PR 36


def ephemeral_range():
    """The range the kernel draws a connection's local port from, or
    None where it cannot be read."""
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            lo, hi = (int(x) for x in f.read().split())
        return lo, hi
    except (OSError, ValueError):
        return None


def port_zone(span: int):
    """→ (lo, hi): where a pool's first port may lie, outside the range
    the kernel draws local ports from, so that no connection of the
    nodes, the daemon or anything else on the host can sit on a port a
    node will bind. Below that range if there is room, else above it,
    else (a host whose range covers everything, or none readable) where
    the bases lay before: the probe and the one fresh start are then
    what is left."""
    eph = ephemeral_range()
    if eph is not None:
        lo, hi = eph
        for zone in ((LEGACY_PORTS[0], lo), (hi + 1, 65536), (10000, lo)):
            if zone[1] - zone[0] >= 40 * span:
                return zone[0], zone[1] - span
    return LEGACY_PORTS[0], LEGACY_PORTS[1] - span


def port_holders(port: int) -> str:
    """Who has `port` as its local port, from /proc/net/tcp (the chip
    host need not have `ss`): state and peer of each socket."""
    states = {"01": "ESTABLISHED", "02": "SYN_SENT", "06": "TIME_WAIT",
              "08": "CLOSE_WAIT", "0A": "LISTEN"}
    found = []
    for path in ("/proc/net/tcp", "/proc/net/tcp6"):
        try:
            with open(path) as f:
                rows = f.read().splitlines()[1:]
        except OSError:
            continue
        for row in rows:
            cols = row.split()
            if len(cols) > 7 and int(cols[1].rsplit(":", 1)[1], 16) == port:
                found.append("%s local %s peer %s %s uid %s inode %s" % (
                    os.path.basename(path), cols[1], cols[2],
                    states.get(cols[3], cols[3]), cols[7], cols[9]))
    return "; ".join(found) or "nothing in /proc/net/tcp"


def busy_ports(base: int, count: int):
    """The ports of base..base+count-1 that cannot be bound now, each
    tried the way a node will bind it (asyncio.start_server: 127.0.0.1,
    SO_REUSEADDR) and closed again."""
    busy = []
    for port in range(base, base + count):
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", port))
        except OSError as e:
            if e.errno != errno.EADDRINUSE:
                raise
            busy.append(port)
        finally:
            s.close()
    return busy


class Ports:
    """Bases for fresh pools: each shown free port by port before it is
    handed out, none handed out twice. `stepped` holds the ports found
    busy, for the result line's notes."""

    def __init__(self, count: int):
        self.count = count
        self.lo, self.hi = port_zone(count)
        slots = max(1, (self.hi - self.lo) // 320)
        self.next = self.lo + (os.getpid() % slots) * 320
        self.stepped = []

    def base(self) -> int:
        for _ in range(200):
            base = self.next
            if base > self.hi:
                base = self.lo
            self.next = base + self.count
            busy = busy_ports(base, self.count)
            if not busy:
                return base
            for port in busy:
                log("port %d is taken: %s" % (port, port_holders(port)))
            self.stepped += busy
        raise RuntimeError("no %d free ports in a row in %d-%d" % (
            self.count, self.lo, self.hi))


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
                cmd, cwd=ROOT, env=env, stdout=out, stderr=err,
                preexec_fn=die_with_parent()),
                name="daemon", dumps_stacks=True)

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

    def log_tail(self, n=30) -> None:
        log("---- tail of daemon.err ----\n%s" % tail(
            os.path.join(self.dir, "daemon.err"), n))

    def signal_profile(self, start: bool) -> None:
        self.proc.send_signal(signal.SIGUSR1 if start else signal.SIGUSR2)

    def stats_now(self, timeout=10) -> dict:
        """The running daemon's stats(), the dict its final line holds
        (it answers a stats frame at once, never behind a batch)."""
        from plenum_tpu.crypto.remote_verifier import RemoteVerifier
        rv = RemoteVerifier(("127.0.0.1", self.info["port"]),
                            timeout=timeout)
        try:
            return rv.daemon_stats()
        finally:
            rv.close()

    def stop(self):
        """Stop → (stats or None, side file or {}). The stats are the
        final line of a daemon that ended by itself. One that had to be
        killed prints none: then they are what it answered when asked
        just before the stop (every node is down by then, so nothing
        is verified in between), and its stacks are logged. The side
        file is there from the moment the profiler's bracket closed."""
        try:
            asked = self.stats_now() if self.proc.poll() is None else None
        except Exception as e:   # a daemon that no longer answers
            log("the daemon gave no stats before its stop: %r" % e)
            asked = None
        killed = len(self.procs.killed)
        self.procs.stop([self.proc])
        stats = None
        for line in reversed(tail(os.path.join(self.dir, "daemon.out"),
                                  5).splitlines()):
            if line.startswith("{"):
                stats = json.loads(line)
                break
        if len(self.procs.killed) > killed:
            self.log_tail(120)
            if stats is None and asked is not None:
                log("no final stats line: the stats are those the daemon "
                    "gave before its stop; its span dump is missing")
                stats = asked
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
                    stderr=subprocess.STDOUT,
                    preexec_fn=die_with_parent()),
                    sig=signal.SIGINT, name=name)

    def dead_nodes(self):
        return [n for n, p in self.node_procs.items()
                if p.poll() is not None]

    def last_word(self, name: str) -> str:
        return last_line(os.path.join(self.base_dir, name + ".out"))

    def failed_bind(self, name: str):
        """The port a dead node could not bind (its last word is the
        OSError of asyncio's create_server; 0 where it names none), or
        None."""
        word = self.last_word(name)
        if "Errno 98" not in word and "ddress already in use" not in word:
            return None
        m = re.search(r"\('127\.0\.0\.1', (\d+)\)", word)
        return int(m.group(1)) if m else 0

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

    def log_tails(self, names=None) -> None:
        for name in names or self.names:
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
