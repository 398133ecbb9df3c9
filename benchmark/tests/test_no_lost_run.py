"""No run ends without a result for a fault of the harness's own, and one
that ends without a line says why as its last word. Every case drives
run.py end to end at --tiny on the CPU (through faulty_run.py, which
swaps one process's entry or plants a fault of the host and changes
nothing else), but for the port arithmetic at the end.

  bind_busy_once    Delta's first start ends on Errno 98: the pool is
                    started once more on fresh ports, the line says so
  bind_busy_always  it ends so twice: no result, within seconds
  exit_1_once       it ends on any other last word: no result, at once
  port_taken        a listener on one of the pool's ports beforehand is
                    stepped over by the probe; no restart
  stop_hangs        a daemon that will not end is killed after its
                    stacks are taken; the traced run still reads the
                    bracket from the side file
  no_xplane_once    a traced run whose first attempt has no trace file
                    makes a second
  kill -9 of run.py leaves no child
  uncaught          an exception nothing expects: traceback, then
                    `no result: uncaught ...` as the last line

    python -m pytest benchmark/tests/test_no_lost_run.py -q
"""
import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import pool as pool_mod  # noqa: E402

FAULTY_RUN = os.path.join(HERE, "tests", "faulty_run.py")


def command(trace=0, cell="pool4-write-burst", seed=11):
    return [sys.executable, FAULTY_RUN, "--workload", cell, "--seed",
            str(seed), "--seconds", "8", "--trace", str(trace), "--tiny"]


def run_tiny(tmp_path, env_extra, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_FAULT_MARK=str(tmp_path / "mark"))
    env.update(env_extra)
    t = time.monotonic()
    proc = subprocess.run(command(trace), cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    proc.seconds = time.monotonic() - t
    return proc


def line_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def last_word(proc):
    return proc.stderr.strip().splitlines()[-1]


def stamp(err, needle):
    """Seconds on the harness's clock of the first log line with needle."""
    for row in err.splitlines():
        if row.startswith("[bench") and needle in row:
            return float(row[6:].split("s]")[0])
    raise AssertionError("no %r in:\n%s" % (needle, err[-3000:]))


def sound_but_for_the_tpu(line):
    got = {k: v[0] for k, v in line["compared"].items()}
    assert got.pop("daemon_faults") == 1
    assert not any(got.values()), got
    assert list(line)[-1] == "compared"


def test_failed_bind_once_is_one_fresh_start(tmp_path):
    proc = run_tiny(tmp_path, {"BENCH_NODE_FAULT": "bind_busy_once"})
    line = line_of(proc)
    sound_but_for_the_tpu(line)
    assert line["notes"]["setup_restarts"] == 1
    assert line["notes"]["bind_failed_port"] > 1024
    assert "Errno 98" in proc.stderr and "one fresh start" in proc.stderr
    # the first attempt's time is inside setup_s: it is what the run cost
    assert line["metrics"]["setup_s"]["value"] \
        > stamp(proc.stderr, "one fresh start")


@pytest.mark.parametrize("fault, word", [
    ("bind_busy_always", "Errno 98"),
    ("exit_1_once", "planted: this node ends before it binds anything")])
def test_other_deaths_in_setup_end_the_run_at_once(tmp_path, fault, word):
    proc = run_tiny(tmp_path, {"BENCH_NODE_FAULT": fault})
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert not proc.stdout.strip()
    last = last_word(proc)
    assert last.startswith("no result: Delta died in set-up:"), last
    assert word in last
    assert "tail of Delta.out" in proc.stderr
    # at the death, not at the set-up budget of 1,100 s
    assert proc.seconds < 120
    restarts = proc.stderr.count("one fresh start")
    assert restarts == (1 if fault == "bind_busy_always" else 0)


def test_a_port_taken_beforehand_is_stepped_over(tmp_path):
    proc = run_tiny(tmp_path, {"BENCH_RUN_FAULT": "port_taken"})
    line = line_of(proc)
    sound_but_for_the_tpu(line)
    assert len(line["notes"]["ports_busy"]) == 1
    assert "setup_restarts" not in line["notes"]
    assert "is taken: tcp local" in proc.stderr
    assert "LISTEN" in proc.stderr


def test_daemon_that_will_not_stop_still_yields_its_bracket(tmp_path):
    # 12 s, not 20, to keep the test short; not less, because on the CPU
    # backend stop_trace itself takes 7 s and more (1 s on the chip)
    proc = run_tiny(tmp_path, {"BENCH_DAEMON_FAULT": "stop_hangs",
                               "BENCH_STOP_WAIT_S": "12"}, trace=1)
    line = line_of(proc)
    (killed,) = line["notes"]["killed"]
    assert killed["name"] == "daemon" and 13.0 <= killed["waited_s"] < 15
    assert "traced_attempts" not in line["notes"]
    # the stacks, through faulthandler, in daemon.err, which the run logs
    assert "most recent call first" in proc.stderr
    assert "has not ended" in proc.stderr and "State:" in proc.stderr
    # the stats are those it gave before the stop: judged as ever
    sound_but_for_the_tpu(line)
    assert "daemon_device_share_pct" in line["metrics"]
    # what needs the daemon's own span dump is missing, and said
    assert "daemon_queue_wait_p50_ms" in line["notes"]["not_read"]
    assert "daemon_queue_wait_p50_ms" not in line["metrics"]
    assert line["device"]["memory_peak_bytes"] is None   # cpu backend


def test_side_file_is_written_when_the_bracket_closes(tmp_path):
    """The entry alone: start, bracket, SIGKILL → the side file holds
    the closed bracket and the profiler's file is there."""
    import trace_reduce
    side = tmp_path / "side.json"
    ready = tmp_path / "ready.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "daemon_entry.py"),
         "--backend", "cpu", "--ready-file", str(ready),
         "--side-file", str(side), "--profile-dir", str(tmp_path / "prof")],
        cwd=ROOT, env=env, stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.monotonic() + 120
        while not ready.exists():
            assert proc.poll() is None and time.monotonic() < deadline
            time.sleep(0.1)
        proc.send_signal(signal.SIGUSR1)
        time.sleep(2.0)
        proc.send_signal(signal.SIGUSR2)
        while not side.exists():
            assert proc.poll() is None and time.monotonic() < deadline
            time.sleep(0.1)
        proc.send_signal(signal.SIGQUIT)     # stacks, and it lives on
        time.sleep(0.5)
        assert proc.poll() is None
    finally:
        proc.kill()
        err = proc.communicate()[1]
    bracket = json.loads(side.read_text())["profile"][-1]
    assert bracket["start"] < bracket["stop"] <= bracket["stopped"]
    assert trace_reduce.newest_xplane(str(tmp_path / "prof"))
    assert "most recent call first" in err


def test_traced_run_without_a_trace_file_makes_a_second(tmp_path):
    proc = run_tiny(tmp_path, {"BENCH_DAEMON_FAULT": "no_xplane_once"},
                    trace=1)
    line = line_of(proc)
    sound_but_for_the_tpu(line)
    assert line["notes"] == {"traced_attempts": 2}
    assert "a second attempt" in proc.stderr
    assert "daemon_queue_wait_p50_ms" in line["metrics"]


def state_and_parent(pid):
    """→ (state letter, parent's pid) of a live process, else None."""
    try:
        with open("/proc/%s/stat" % pid) as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except OSError:
        return None
    return (fields[0], int(fields[1])) if fields[0] != "Z" else None


def children_of(pid):
    return [int(e) for e in os.listdir("/proc") if e.isdigit()
            and (state_and_parent(e) or (None, None))[1] == pid]


def alive(pid):
    return state_and_parent(pid) is not None


def test_kill_9_of_run_py_leaves_no_child(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    err_path = tmp_path / "err"
    with open(err_path, "w") as err:
        proc = subprocess.Popen(command(), cwd=ROOT, env=env, stderr=err,
                                stdout=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 300
        while "client connected" not in err_path.read_text():
            assert proc.poll() is None, err_path.read_text()[-2000:]
            assert time.monotonic() < deadline
            time.sleep(0.2)
        children = children_of(proc.pid)
        assert len(children) == 5, children     # the daemon, four nodes
        proc.kill()
        proc.wait()
        time.sleep(2.0)
        assert [p for p in children if alive(p)] == []
    finally:
        proc.kill()
        for pid in children_of(proc.pid):
            os.kill(pid, signal.SIGKILL)


def test_uncaught_exception_says_why_last(tmp_path):
    proc = run_tiny(tmp_path, {"BENCH_RUN_FAULT": "uncaught"})
    assert proc.returncode == 3
    assert not proc.stdout.strip()
    assert "Traceback (most recent call last)" in proc.stderr
    assert last_word(proc) == \
        "no result: uncaught ValueError: planted: nothing expects this"


@pytest.mark.parametrize("args, code, word", [
    (["--workload", "no-such-cell"], 3, "no result: no cell 'no-such-cell'"),
])
def test_no_result_is_the_last_word(args, code, word):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py")] + args, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == code
    assert not proc.stdout.strip()
    assert last_word(proc).startswith(word)


# ---------------------------------------------------------------- ports

@pytest.mark.parametrize("eph, want", [
    ((32768, 60999), (19000, 32768 - 14)),     # this sandbox: as before
    ((1024, 40000), (40001, 65536 - 14)),      # no room below: above
    ((15000, 65535), (10000, 15000 - 14)),     # under it, over 10000
    ((1024, 65535), (19000, 31800 - 14)),      # no outside: as before
    (None, (19000, 31800 - 14))])              # unreadable: as before
def test_bases_lie_outside_the_ephemeral_range(monkeypatch, eph, want):
    monkeypatch.setattr(pool_mod, "ephemeral_range", lambda: eph)
    assert pool_mod.port_zone(14) == want
    ports = pool_mod.Ports(14)
    assert want[0] <= ports.next <= want[1]


def test_probe_steps_over_a_listener_and_says_who_holds_it(capsys):
    ports = pool_mod.Ports(8)
    holder = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    holder.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    taken = ports.next + 5
    holder.bind(("127.0.0.1", taken))
    holder.listen(1)
    try:
        assert pool_mod.busy_ports(ports.next, 8) == [taken]
        base = ports.base()
        assert base == taken - 5 + 8 and ports.stepped == [taken]
        assert ports.base() == base + 8            # none handed out twice
        assert "LISTEN" in pool_mod.port_holders(taken)
    finally:
        holder.close()
    assert "port %d is taken" % taken in capsys.readouterr().err


def test_a_connection_on_a_port_blocks_a_bind_as_a_listener_does():
    """What took Delta's and Gamma's ports on the chip host need not
    have been a listener: a connection whose LOCAL port the kernel drew
    there refuses the node's bind too, SO_REUSEADDR or not."""
    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.bind(("127.0.0.1", 0))
    server.listen(1)
    client = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    client.connect(server.getsockname())
    try:
        port = client.getsockname()[1]
        assert pool_mod.busy_ports(port, 1) == [port]
        assert "ESTABLISHED" in pool_mod.port_holders(port)
    finally:
        client.close()
        server.close()


def test_failed_bind_is_read_off_the_last_word(tmp_path):
    p = pool_mod.Pool(pool_mod.Procs(), str(tmp_path), {"nodes": 4}, True,
                      23600)
    (tmp_path / "Delta.out").write_text(
        "Traceback (most recent call last):\n  ...\n"
        "OSError: [Errno 98] error while attempting to bind on address "
        "('127.0.0.1', 25406): [errno 98] address already in use\n\n")
    (tmp_path / "Gamma.out").write_text("ValueError: something else\n")
    assert p.failed_bind("Delta") == 25406
    assert p.failed_bind("Gamma") is None
    assert p.last_word("Gamma") == "ValueError: something else"
    assert p.failed_bind("Alpha") is None          # no file at all
