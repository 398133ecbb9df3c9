"""Verify daemon + RemoteVerifier: the multi-process verification
offload seam (one daemon process owns the accelerator; every node ships
its signature batches over a local socket and overlaps the round trip).
Tests run the daemon in-process on the CPU backend — the wire protocol,
coalescing, and pipelining are what's under test, not the kernel.
"""
import asyncio
import json
import os
import signal
import subprocess
import sys

import pytest

from plenum_tpu.common.config import Config
from plenum_tpu.common.constants import NYM, TARGET_NYM, VERKEY
from plenum_tpu.crypto.remote_verifier import RemoteVerifier
from plenum_tpu.crypto.signer import SimpleSigner
from plenum_tpu.network.keys import NodeKeys
from plenum_tpu.network.stack import HA, ClientConnection, RemoteInfo
from plenum_tpu.server.networked_node import NetworkedNode
from plenum_tpu.observability.tracing import Tracer
from plenum_tpu.server.verify_daemon import VerifyDaemon, wait_ready


def make_items(n, tamper=()):
    signer = SimpleSigner(seed=b"\x77" * 32)
    items = []
    for i in range(n):
        msg = b"payload-%d" % i
        sig = signer.sign_bytes(msg)
        if i in tamper:
            sig = bytes(64)
        items.append((msg, sig, signer.verraw))
    return items


def test_remote_verifier_roundtrip():
    async def main():
        daemon = VerifyDaemon(backend="cpu", window=0.001)
        await daemon.start()
        loop = asyncio.get_event_loop()
        rv = await loop.run_in_executor(
            None, lambda: RemoteVerifier(("127.0.0.1", daemon.port)))
        items = make_items(50, tamper={3, 17})
        results = await loop.run_in_executor(None, rv.verify_batch, items)
        assert len(results) == 50
        assert not results[3] and not results[17]
        assert sum(results) == 48
        rv.close()
        await daemon.stop()

    asyncio.run(main())


def test_remote_verifier_pipelined_dispatches_coalesce():
    """Several dispatches before any collect: all are answered, each with
    its own slice (the daemon fuses them into fewer device batches)."""
    async def main():
        daemon = VerifyDaemon(backend="cpu", window=0.005)
        await daemon.start()
        loop = asyncio.get_event_loop()
        rv = await loop.run_in_executor(
            None, lambda: RemoteVerifier(("127.0.0.1", daemon.port)))

        def run():
            pendings = [rv.dispatch(make_items(10, tamper={i}))
                        for i in range(5)]
            return [p.collect() for p in pendings]

        all_results = await loop.run_in_executor(None, run)
        for i, results in enumerate(all_results):
            assert len(results) == 10
            assert not results[i]
            assert sum(results) == 9
        # ready() eventually true without collect
        p = await loop.run_in_executor(
            None, rv.dispatch, make_items(4))
        for _ in range(200):
            if p.ready():
                break
            await asyncio.sleep(0.01)
        assert p.ready()
        assert p.collect() == [True] * 4
        rv.close()
        await daemon.stop()

    asyncio.run(main())


def test_remote_verifier_survives_daemon_death():
    """Daemon dies mid-flight: in-flight batches resolve to all-False
    (clients get nacked and resubmit), dispatch after reconnect works —
    the node's prod loop must never see an unhandled ConnectionError."""
    async def main():
        daemon = VerifyDaemon(backend="cpu", window=0.001)
        await daemon.start()
        port = daemon.port
        loop = asyncio.get_event_loop()
        rv = await loop.run_in_executor(
            None, lambda: RemoteVerifier(("127.0.0.1", port), timeout=2.0))
        p = await loop.run_in_executor(None, rv.dispatch, make_items(5))
        await daemon.stop()
        await asyncio.sleep(0.05)
        # ready() must not raise, and the batch resolves to failure
        for _ in range(100):
            if await loop.run_in_executor(None, p.ready):
                break
            await asyncio.sleep(0.02)
        assert p.ready()
        assert p.collect() == [False] * 5
        # daemon comes back on the same port: next dispatch reconnects
        daemon2 = VerifyDaemon(port=port, backend="cpu", window=0.001)
        await daemon2.start()
        p2 = await loop.run_in_executor(None, rv.dispatch, make_items(3))
        results = await loop.run_in_executor(None, p2.collect)
        assert results == [True] * 3
        rv.close()
        await daemon2.stop()

    asyncio.run(main())


def test_daemon_drops_stalled_client_bounded_memory(monkeypatch):
    """A client that sends requests but never reads its responses must
    not buffer the daemon's memory away: once the per-connection write
    backlog passes the high-water mark the connection is dropped, and
    the observed backlog never exceeds mark + one frame."""
    import socket as socket_mod
    import struct as struct_mod

    import msgpack as msgpack_mod

    from plenum_tpu.server import verify_daemon as vd_mod

    HWM = 32 * 1024
    monkeypatch.setattr(vd_mod, "WRITE_HIGH_WATER", HWM)

    async def main():
        daemon = VerifyDaemon(backend="cpu", window=0.001)
        await daemon.start()
        loop = asyncio.get_event_loop()

        sock = socket_mod.socket()
        # tiny receive window so the daemon's sends back up quickly
        sock.setsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_RCVBUF, 4096)
        await loop.run_in_executor(
            None, sock.connect, ("127.0.0.1", daemon.port))
        for _ in range(50):
            if daemon._writers:
                break
            await asyncio.sleep(0.01)
        assert daemon._writers
        writer = next(iter(daemon._writers))
        dsock = writer.get_extra_info("socket")
        dsock.setsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_SNDBUF, 4096)

        # 40 requests x 5000 garbage items -> ~5 KB response each, never
        # read by the client
        items = [[b"x" * 32, b"y" * 64, b"z" * 32]] * 5000
        max_backlog = 0

        def send_all():
            for i in range(40):
                frame = msgpack_mod.packb([i + 1, items], use_bin_type=True)
                sock.sendall(struct_mod.pack("<I", len(frame)) + frame)

        send_task = loop.run_in_executor(None, send_all)
        frame_bound = 8 * 1024  # one response frame is well under this
        dropped = False
        for _ in range(2000):
            max_backlog = max(max_backlog,
                              writer.transport.get_write_buffer_size())
            if writer not in daemon._writers:
                dropped = True
                break
            await asyncio.sleep(0.005)
        assert dropped, "stalled client was never dropped " \
            f"(max backlog {max_backlog})"
        assert max_backlog <= HWM + frame_bound, max_backlog
        try:
            sock.close()
        except OSError:
            pass
        try:
            await asyncio.wait_for(send_task, 5)
        except Exception:
            pass

        # the daemon still serves a healthy client afterwards
        rv = await loop.run_in_executor(
            None, lambda: RemoteVerifier(("127.0.0.1", daemon.port)))
        results = await loop.run_in_executor(
            None, rv.verify_batch, make_items(5))
        assert results == [True] * 5
        rv.close()
        await daemon.stop()

    asyncio.run(main())


def test_daemon_survives_undecodable_frame():
    """A frame whose payload isn't valid msgpack closes THAT connection
    cleanly (documented close-and-log path) without killing the daemon."""
    import socket as socket_mod
    import struct as struct_mod

    async def main():
        daemon = VerifyDaemon(backend="cpu", window=0.001)
        await daemon.start()
        loop = asyncio.get_event_loop()
        sock = socket_mod.socket()
        await loop.run_in_executor(
            None, sock.connect, ("127.0.0.1", daemon.port))
        junk = b"\xc1\xff\x00garbage-not-msgpack"
        await loop.run_in_executor(
            None, sock.sendall, struct_mod.pack("<I", len(junk)) + junk)
        # daemon closes this connection...
        got = await loop.run_in_executor(None, sock.recv, 1)
        assert got == b""
        sock.close()
        # ...and keeps serving others
        rv = await loop.run_in_executor(
            None, lambda: RemoteVerifier(("127.0.0.1", daemon.port)))
        results = await loop.run_in_executor(
            None, rv.verify_batch, make_items(3, tamper={1}))
        assert results == [True, False, True]
        rv.close()
        await daemon.stop()

    asyncio.run(main())


def test_remote_verifier_tolerates_daemon_starting_late():
    """Node-before-daemon start ordering: construction with nothing
    listening must not raise; the first dispatch after the daemon
    arrives reconnects and succeeds."""
    import socket as socket_mod

    async def main():
        loop = asyncio.get_event_loop()
        # find a free port, then construct against it while closed
        probe = socket_mod.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()
        rv = await loop.run_in_executor(
            None, lambda: RemoteVerifier(("127.0.0.1", port), timeout=2.0))
        assert rv._sock is None  # tolerated, not raised
        # dispatch with daemon still down: resolves all-False, no raise
        p = await loop.run_in_executor(None, rv.dispatch, make_items(2))
        assert await loop.run_in_executor(None, p.collect) == [False, False]
        daemon = VerifyDaemon(port=port, backend="cpu", window=0.001)
        await daemon.start()
        # the re-dial pacer refuses connect attempts for RECONNECT_COOLDOWN
        # after a failure — wait it out before expecting success
        from plenum_tpu.crypto.remote_verifier import RECONNECT_COOLDOWN
        await asyncio.sleep(RECONNECT_COOLDOWN + 0.1)
        results = await loop.run_in_executor(
            None, rv.verify_batch, make_items(4, tamper={2}))
        assert results == [True, True, False, True]
        rv.close()
        await daemon.stop()

    asyncio.run(main())


@pytest.mark.parametrize("traced", [False, True])
def test_networked_pool_orders_via_remote_daemon(traced, tmp_path):
    """Rung-3: a 4-node pool over real sockets with
    VERIFIER_PROVIDER=remote orders client writes through the daemon —
    the full multi-process verification shape, in one process. With a
    trace file on the daemon the same run is a host trace session:
    every node arms the recorder it was built with, and hands out its
    spans when told to stop; without one no node records anything."""
    NAMES = ["Alpha", "Beta", "Gamma", "Delta"]

    async def main():
        daemon = VerifyDaemon(backend="cpu", window=0.001)
        if traced:
            daemon.tracer = Tracer("verify-daemon")
            daemon.trace_file = str(tmp_path / "daemon_spans.json")
        await daemon.start()
        conf = Config(Max3PCBatchSize=10, Max3PCBatchWait=0.2, CHK_FREQ=5,
                      LOG_SIZE=15, HEARTBEAT_FREQ=10,
                      VERIFIER_PROVIDER="remote",
                      VERIFIER_DAEMON_PORT=daemon.port)
        keys = {n: NodeKeys(bytes([i + 50]) * 32)
                for i, n in enumerate(NAMES)}
        nodes, registry = {}, {}
        for name in NAMES:
            node = NetworkedNode(
                name, {n: RemoteInfo(n, HA("127.0.0.1", 1),
                                     keys[n].verkey_raw) for n in NAMES},
                keys[name], HA("127.0.0.1", 0), HA("127.0.0.1", 0),
                config=conf)
            await node.start_async()
            nodes[name] = node
            registry[name] = RemoteInfo(name, node.nodestack.ha,
                                        keys[name].verkey_raw)
        for node in nodes.values():
            for info in registry.values():
                if info.name != node.name:
                    node.nodestack.update_remote(info)

        async def pump(seconds, until=None):
            end = asyncio.get_event_loop().time() + seconds
            while asyncio.get_event_loop().time() < end:
                for n in nodes.values():
                    await n.prod()
                if until is not None and until():
                    return True
                await asyncio.sleep(0.005)
            return until() if until else True

        assert await pump(10, lambda: all(
            len(n.nodestack.connecteds) == 3 for n in nodes.values()))

        # a client broadcasts: every node gets each request from the
        # client too, so every node talks to the daemon (and so reads
        # its control frame)
        clients = {}
        for name in NAMES:
            clients[name] = ClientConnection(
                nodes[name].clientstack.ha,
                expected_verkey=keys[name].verkey_raw)
            await clients[name].connect()
        client = clients["Beta"]
        signer = SimpleSigner(seed=b"\x31" * 32)
        N = 20
        for i in range(1, N + 1):
            req = {"identifier": signer.identifier, "reqId": i,
                   "protocolVersion": 2,
                   "operation": {"type": NYM,
                                 TARGET_NYM: signer.identifier if i == 1
                                 else "dmn%020d" % i,
                                 VERKEY: "~dmn%018d" % i}}
            req["signature"] = signer.sign(dict(req))
            for conn in clients.values():
                conn.send(req)
        # a forged one must be nacked, not ordered
        bad = {"identifier": signer.identifier, "reqId": 999,
               "protocolVersion": 2,
               "operation": {"type": NYM, TARGET_NYM: "dmnFORGED" + "x" * 12,
                             VERKEY: "~x"}}
        bad["signature"] = signer.sign(dict(bad)) [:-3] + "abc"
        client.send(bad)

        assert await pump(40, lambda: all(
            n.node.domain_ledger.size == N for n in nodes.values())), \
            {n.name: n.node.domain_ledger.size for n in nodes.values()}
        assert daemon.served >= N
        nacks = [m for m in client.rx if m.get("op") == "REQNACK"]
        assert await pump(10, lambda: any(
            m.get("reqId") == 999
            for m in client.rx if m.get("op") == "REQNACK")), client.rx

        for conn in clients.values():
            conn.close()
        for n in nodes.values():
            await n.nodestack.stop()
            await n.clientstack.stop()
        assert os.listdir(str(tmp_path)) == []      # no I/O before stop
        written = [n.node.write_trace_dump() for n in nodes.values()]
        await daemon.stop()
        return nodes, written

    nodes, written = asyncio.run(main())
    if not traced:
        assert written == [None] * 4 and os.listdir(str(tmp_path)) == []
        assert all(not n.node.tracer.armed and n.node.tracer.spans() == []
                   for n in nodes.values())
        return
    assert sorted(os.listdir(str(tmp_path))) == sorted(
        ["daemon_spans.json"] + ["node_%s_spans.json" % n for n in NAMES])
    for name, path in zip(NAMES, written):
        with open(path) as f:
            doc = json.load(f)
        meta = doc["metadata"][name]
        assert "MONOTONIC" in meta["clock"]["implementation"]
        assert meta["stats"]["dropped"] == 0 and meta["stats"]["recorded"]
        assert meta["oldest_ts"] <= meta["clock_sync"]["perf_ts"]
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        by_name = {}
        for e in spans:
            by_name.setdefault(e["name"], []).append(e)
        # the tick's envelope and its three socket seams, one each per
        # productive tick, the seams inside the envelope
        ticks = by_name["prod_tick"]
        for seam in ("node_rx", "client_rx", "transport_flush"):
            assert len(by_name[seam]) == len(ticks)
            assert all(e["cat"] == "transport" for e in by_name[seam])
        for tick, rx in zip(ticks, by_name["node_rx"]):
            assert tick["ts"] <= rx["ts"] \
                and rx["ts"] + rx["dur"] <= tick["ts"] + tick["dur"]
        assert any(e["args"]["n"] >= 1 and e["args"]["frm"] in NAMES
                   for e in by_name["propagate_process"])
        # arming at runtime turns on spans, not the journey plane
        assert not [e for e in doc["traceEvents"] if e["name"] in (
            "request_accepted", "propagate_quorum", "wire_send",
            "wire_recv")]
    assert not nodes["Alpha"].node.propagator.trace_context


# ------------------------------------------- control frames (id 0, stats)

@pytest.mark.parametrize("trace_file", [False, True])
def test_trace_session_control_frame(trace_file, tmp_path):
    """A daemon with a trace file tells every connection, once, where
    the host's trace session lives (frame id 0); a RemoteVerifier hands
    it to its callback and still resolves requests in order, one without
    a callback drops it; a daemon without a trace file sends none."""
    async def main():
        daemon = VerifyDaemon(backend="cpu", window=0.001)
        if trace_file:
            daemon.trace_file = str(tmp_path / "sub" / "daemon.json")
        await daemon.start()
        loop = asyncio.get_event_loop()

        def run():
            got = []
            listening = RemoteVerifier(("127.0.0.1", daemon.port))
            listening.on_control = got.append
            deaf = RemoteVerifier(("127.0.0.1", daemon.port))
            out = []
            for rv in (listening, deaf):
                pendings = [rv.dispatch(make_items(6, tamper={i}))
                            for i in range(3)]
                # harvested last to first: frames are matched by id
                out.append([p.collect() for p in reversed(pendings)])
                assert 0 not in rv._results and not rv._outstanding
                rv.close()
            return got, out

        got, out = await loop.run_in_executor(None, run)
        await daemon.stop()
        return got, out

    got, out = asyncio.run(main())
    want = [[j != i for j in range(6)] for i in (2, 1, 0)]
    assert out == [want, want]
    assert got == ([{"trace": {"dir": str(tmp_path / "sub")}}]
                   if trace_file else [])


def test_daemon_stats_without_stopping_it():
    """[id, "stats"] is answered by the connection handler with the
    daemon's counters and never enters the batcher's queue; --stats
    prints them as one line."""
    async def main():
        daemon = VerifyDaemon(backend="cpu", window=0.001)
        await daemon.start()
        loop = asyncio.get_event_loop()

        def run():
            rv = RemoteVerifier(("127.0.0.1", daemon.port))
            try:
                assert all(rv.verify_batch(make_items(7)))
                live = rv.daemon_stats()
                # still usable for verification afterwards
                assert rv.verify_batch(make_items(3, tamper={1})) \
                    == [True, False, True]
            finally:
                rv.close()
            cli = subprocess.run(
                [sys.executable, "-m", "plenum_tpu.server.verify_daemon",
                 "--stats", "--port", str(daemon.port)],
                capture_output=True, text=True, timeout=60,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
            return live, cli

        live, cli = await loop.run_in_executor(None, run)
        launches = daemon.launches
        await daemon.stop()
        return live, cli, launches

    live, cli, launches = asyncio.run(main())
    assert (live["served"], live["host_items"], live["launches"]) \
        == (7, 7, 1)
    assert launches == 2          # the two batches; no stats frame
    assert cli.returncode == 0, cli.stderr
    lines = cli.stdout.strip().splitlines()
    assert len(lines) == 1
    assert json.loads(lines[0])["served"] == 10


def test_daemon_writes_its_trace_only_at_sigterm(tmp_path):
    """The daemon's spans are written in stop(), which SIGTERM reaches:
    thirty batches (the old periodic dump fired every 25) leave no
    file, the signal does, with the final stats line on stdout."""
    ready, trace = tmp_path / "ready.json", tmp_path / "spans.json"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "plenum_tpu.server.verify_daemon",
         "--backend", "cpu", "--port", "0", "--window", "0.001",
         "--ready-file", str(ready), "--trace-file", str(trace)],
        cwd=root, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    try:
        port = wait_ready(str(ready), proc, timeout=120)["port"]
        rv = RemoteVerifier(("127.0.0.1", port))
        got = []
        rv.on_control = got.append
        for _ in range(30):
            assert all(rv.verify_batch(make_items(2)))
        assert rv.daemon_stats()["launches"] == 30
        rv.close()
        assert got == [{"trace": {"dir": str(tmp_path)}}]
        assert not trace.exists()
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0
    assert json.loads(out.strip().splitlines()[-1])["launches"] == 30
    with open(trace) as f:
        doc = json.load(f)
    meta = doc["metadata"]["verify-daemon"]
    assert meta["stats"]["dropped"] == 0
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert names.count("device_verify") == 30
    assert names.count("verify_queue_wait") == 30
    assert not [e for e in doc["traceEvents"]
                if e["name"] == "verify_queue_depth"]
    import inspect
    assert "_dump_trace" not in inspect.getsource(VerifyDaemon._batcher)


def test_device_daemon_states_its_kernel_store(tmp_path):
    """A daemon on a device backend says in its ready file, in its
    [id, "stats"] answer and in its final line whether it loaded or
    built its Pallas kernel (ops/kernel_store.py). On the CPU platform
    nothing reaches the store: every count reads zero."""
    ready = tmp_path / "ready.json"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "plenum_tpu.server.verify_daemon",
         "--backend", "tpu_batch", "--port", "0", "--window", "0.001",
         "--bucket", "8", "--cpu-floor", "64",
         "--ready-file", str(ready)],
        cwd=root, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        info = wait_ready(str(ready), proc, timeout=120)
        rv = RemoteVerifier(("127.0.0.1", info["port"]))
        try:
            assert rv.verify_batch(make_items(3, tamper={1})) \
                == [True, False, True]
            live = rv.daemon_stats()
        finally:
            rv.close()
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0
    untouched = {"loaded": 0, "built": 0, "rebuilt": {}, "load_s": 0.0,
                 "build_s": 0.0}
    assert info["kernel_store"] == untouched and "compile_cache" in info
    assert live["kernel_store"] == untouched
    assert json.loads(out.strip().splitlines()[-1])["kernel_store"] \
        == untouched


def test_daemon_inner_spans_nest_inside_device_verify():
    """A batch over the floor on a device backend: its wait in the
    queue ends where device_verify starts; pack, launch and collect,
    recorded from the worker thread, lie inside device_verify; every
    one carries the batch's items and unique."""
    async def main():
        daemon = VerifyDaemon(backend="tpu_batch", window=0.02, bucket=8,
                              cpu_floor=4)
        daemon.tracer = Tracer("verify-daemon")
        await daemon.start()
        loop = asyncio.get_event_loop()

        def run():
            a = RemoteVerifier(("127.0.0.1", daemon.port), timeout=300)
            b = RemoteVerifier(("127.0.0.1", daemon.port), timeout=300)
            items = make_items(7, tamper={2})
            pa, pb = a.dispatch(items), b.dispatch(items + make_items(1))
            out = pa.collect(), pb.collect()
            small = a.verify_batch(make_items(2))     # under the floor
            a.close()
            b.close()
            return out, small

        (ra, rb), small = await loop.run_in_executor(None, run)
        await daemon.stop()
        return daemon, ra, rb, small

    daemon, ra, rb, small = asyncio.run(main())
    assert ra == [i != 2 for i in range(7)] and rb == ra + [True]
    assert small == [True, True]
    recs = [r for r in daemon.tracer.spans() if r[0] == "X"]
    by_name = {}
    for _k, name, cat, t0, t1, _key, args in recs:
        assert cat == "device"
        by_name.setdefault(name, []).append((t0, t1, args))
    dv = [s for s in by_name["device_verify"] if s[2]["unique"] >= 4]
    # the two requests coalesced (one batch) or not (two): either way
    # each device batch has one chunk of 8 lanes
    assert 1 <= len(dv) <= 2
    for inner in ("verify_pack", "verify_launch", "verify_collect"):
        assert len(by_name[inner]) == len(dv)
    for t0, t1, args in dv:
        assert args["items"] >= args["unique"] >= 7
        inside = [s for name in ("verify_pack", "verify_launch",
                                 "verify_collect")
                  for s in by_name[name] if t0 <= s[0] and s[1] <= t1]
        assert len(inside) == 3
        assert all(s[2] == args for s in inside)
        assert inside[0][1] <= inside[1][0] <= inside[1][1] <= inside[2][0]
        waits = [s for s in by_name["verify_queue_wait"]
                 if s[2] == args]
        assert waits and all(w[0] <= w[1] <= t0 for w in waits)
    # the batch under the floor took OpenSSL: a wait and a round trip,
    # no pack, launch or collect
    assert len(by_name["device_verify"]) == len(dv) + 1
    assert len(by_name["verify_queue_wait"]) == len(dv) + 1
