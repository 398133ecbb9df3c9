#!/usr/bin/env python
"""Headline benchmark — prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

North-star metric (BASELINE.json): ordered write-requests/sec on a
4-node in-process pool (full pipeline: client-batch ed25519
authentication, PROPAGATE quorum, 3PC with real ledgers + MPT roots,
audit txn per batch, Replies) with TPU-batched verification.

vs_baseline divides by the SAME pool running the honest CPU verifier
floor — OpenSSL's Ed25519 via `cryptography`, the equivalent of the
reference's libsodium path (stp_core/crypto/nacl_wrappers.py). It is NOT
the pure-Python strawman: the scalar floor on this host is reported in
the "floors" field for transparency.

Secondary microbench (the round-1 headline) is kept in "secondary":
raw batched ed25519 verify throughput per chip vs the OpenSSL
single-core floor.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# persistent compilation cache: first compile of the big verify buckets
# is 30-110s; every later process loads them in milliseconds (must go
# through jax.config — the env var alone doesn't activate it here)
from plenum_tpu.ops import enable_persistent_compilation_cache
enable_persistent_compilation_cache()

# 4k requests in 1k client chunks: deep enough that the verification
# load (where the device wins) is visible over the Python consensus
# cost, while both pools stay under ~15s per timed run
POOL_REQS = int(os.environ.get("BENCH_POOL_REQS", "4000"))
CLIENT_BATCH = int(os.environ.get("BENCH_CLIENT_BATCH", "2000"))
MICRO_BATCH = int(os.environ.get("BENCH_BATCH", "16384"))
NAMES = ["Alpha", "Beta", "Gamma", "Delta"]
SIM_EPOCH = 1600000000
MP_TRUSTEE_SEED = b"\x42" * 32
# device facts each verify daemon stated in its ready file (one entry
# per daemon-owning run; reported beside the headline)
DAEMON_DEVICES = []


def make_mp_requests(n):
    """Requests for the multi-process pool, authored by its trustee."""
    from plenum_tpu.crypto.signer import DidSigner
    return make_requests(n, DidSigner(seed=MP_TRUSTEE_SEED))


def best_time(fn, runs=3):
    """min wall time of `fn()` over `runs` — the best window of a
    device microbench (the host shares its cores with everything else
    the run started; A0 replaces best-of with medians)."""
    return best_median_time(fn, runs)[0]


def best_median_time(fn, runs=3):
    """→ (best, median) wall seconds over `runs`. Best is the device's
    capability (shared-chip variance suppressed); median is what a
    sustained workload actually sees — both are reported so neither
    number has to stand alone."""
    import statistics
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times), statistics.median(times)


def best_of_runs(runs, min_ordered, side):
    """Best (elapsed, ordered) among runs that ordered at least
    min_ordered requests — a failed/partial run must never become a
    headline number silently."""
    complete = [r for r in runs if r[1] >= min_ordered]
    assert complete, (side, runs)
    return min(complete, key=lambda r: r[0] / r[1])


def make_requests(n, signer):
    """n unique NYM-creation writes by one authenticated author."""
    from plenum_tpu.common.constants import NYM, TARGET_NYM, VERKEY
    from plenum_tpu.common.serializers.base58 import b58encode
    reqs = []
    for i in range(n):
        dest = b58encode(i.to_bytes(16, "big", signed=False).rjust(16, b"\x01"))
        req = {
            "identifier": signer.identifier,
            "reqId": i + 1,
            "protocolVersion": 2,
            "operation": {"type": NYM, TARGET_NYM: dest,
                          VERKEY: "~" + dest},
        }
        req["signature"] = signer.sign(dict(req))
        reqs.append(req)
    return reqs


def make_sim_pool(names, verifier_name, seed=7, batch=None,
                  tracing=False, mesh=True, telemetry=True,
                  extra_conf=None):
    """Build an n-node sim pool with the given verification provider
    (shared scaffolding for the 4-node headline and 25-node backlog
    configs — one drain/hub wiring to maintain). tracing=True turns on
    the flight recorder (observability/) for the overhead config;
    mesh=False pins the device-mesh dispatcher off (Node bootstrap
    applies MESH_* to the process-wide mesh) for the on/off configs;
    telemetry=False pins the always-on telemetry plane off (its
    overhead A/B config — every other config keeps it ON, the
    production shape)."""
    from plenum_tpu.common.config import Config
    from plenum_tpu.crypto.batch_verifier import create_verifier
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork

    timer = MockTimer()
    timer.set_time(SIM_EPOCH)
    net = SimNetwork(timer, DefaultSimRandom(seed), min_latency=0.001,
                     max_latency=0.005)
    overrides = dict(Max3PCBatchSize=batch or CLIENT_BATCH,
                     Max3PCBatchWait=0.05,
                     CHK_FREQ=10, LOG_SIZE=30, HEARTBEAT_FREQ=10 ** 6,
                     TRACING_ENABLED=tracing, MESH_ENABLED=mesh,
                     TELEMETRY_ENABLED=telemetry)
    overrides.update(extra_conf or {})
    conf = Config(**overrides)
    nodes = [Node(name, names, timer, net.create_peer(name), config=conf)
             for name in names]
    if verifier_name == "tpu_hub":
        # co-resident nodes share one coalescing hub: the per-node
        # dispatches of each chunk fuse into ONE latency-bound kernel
        # launch (see CoalescingVerifierHub)
        hub = create_verifier("tpu_hub")
        if tracing:
            # a post-ctor shared hub bypasses Node's tracer attach —
            # record its fused launches into the first node's buffer
            hub.tracer = nodes[0].tracer
        for n in nodes:
            n.authnr._verifier = hub
    else:
        for n in nodes:
            n.authnr._verifier = create_verifier(verifier_name)
    return nodes, timer


def drain_chunk(nodes, timer, chunk, client_id="bench-client",
                target_size=None, max_iters=400, deadline=None):
    """Two-phase intake of one chunk (all nodes dispatch async, then
    harvest — one fused device round trip) + pump until every node's
    domain ledger reaches target_size."""
    if chunk:
        batch = [(r, client_id) for r in chunk]
        pendings = [n.dispatch_client_batch(batch) for n in nodes]
        for n, pending in zip(nodes, pendings):
            n.conclude_client_batch(pending)
    for _ in range(max_iters):
        for nd in nodes:
            nd.service()
        timer.run_for(0.01)
        if target_size is not None and all(
                nd.domain_ledger.size >= target_size for nd in nodes):
            break
        if deadline is not None and time.perf_counter() > deadline:
            break


def pipelined_intake(nodes, timer, chunks, client_id, deadline=None,
                     per_chunk=None):
    """Shared pipelined intake loop (headline + pool25 configs):
    dispatch + flush chunk i's fused verification launch, harvest chunk
    i-1's launch (flushed a full iteration ago, so its device round
    trip hid under the PREVIOUS pump), inject it, then pump its
    consensus rounds under launch i. The lag-1 harvest keeps one launch
    in flight across the whole pump window — with an in-window harvest
    the launch round trip would surface every chunk. `per_chunk` (if given)
    runs between flush and pump — pool25 serves its read traffic there.
    Returns the injected-request count."""
    from collections import deque
    hub = nodes[0].authnr._verifier
    injected = 0
    lag = int(os.environ.get("BENCH_PIPELINE_LAG", "2"))
    in_flight: deque = deque()  # (handles, chunk_len), oldest first
    for chunk in chunks:
        if deadline is not None and time.perf_counter() > deadline:
            break
        # requests are handed to all nodes as the SAME dict objects —
        # exactly what SimNetwork delivery does with every message; no
        # node mutates an intake dict
        batch = [(r, client_id) for r in chunk] if chunk else None
        handles = [n.dispatch_client_batch(batch) for n in nodes] \
            if chunk else None
        if hasattr(hub, "flush"):
            hub.flush()
        if handles:
            in_flight.append((handles, len(chunk)))
        if per_chunk is not None:
            per_chunk()
        if len(in_flight) > lag:
            old_handles, old_len = in_flight.popleft()
            for n, h in zip(nodes, old_handles):
                n.conclude_client_batch(h)
            injected += old_len
        if injected:
            drain_chunk(nodes, timer, None, target_size=injected,
                        deadline=deadline)
    while in_flight:
        old_handles, old_len = in_flight.popleft()
        for n, h in zip(nodes, old_handles):
            n.conclude_client_batch(h)
        injected += old_len
        drain_chunk(nodes, timer, None, target_size=injected,
                    deadline=deadline)
    return injected


def run_multiprocess_pool(reqs, provider, run_label=""):
    """Deployment-shaped north star: 4 node OS processes over the real
    TCP stack (scripts/start_plenum_tpu_node from on-disk keys+genesis),
    client broadcasting to all nodes, REPLYs counted per connection.

    provider="remote": a verify daemon subprocess owns the TPU and fuses
    all nodes' signature batches (server/verify_daemon.py).
    provider="cpu": each node verifies locally via OpenSSL.

    NOTE this host exposes ONE CPU core (os.cpu_count()==1): the 4 node
    processes + client + daemon time-slice a single core, so this
    measures the deployment shape's overheads honestly rather than any
    multi-core speedup. → (elapsed, ordered)
    """
    import shutil
    import signal
    import subprocess
    import tempfile

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from plenum_tpu.bootstrap import generate_pool
    base_dir = tempfile.mkdtemp(prefix="plenum_tpu_bench_")
    procs = []
    daemon_proc = None
    # SIGTERM (driver timeout, operator Ctrl-C via term) must run the
    # finally-cleanup below — otherwise node/daemon children outlive us
    # and poison later runs' ports
    prev_term = signal.signal(signal.SIGTERM,
                              lambda s, f: sys.exit(143))
    try:
        base_port = 19000 + (os.getpid() % 400) * 10
        # the bench client signs as the pool trustee (same seed), so NYM
        # authorization passes under the real genesis authz rules
        generate_pool(base_dir, NAMES, base_port=base_port,
                      trustee_seed=MP_TRUSTEE_SEED)

        daemon_port = base_port + 9
        if provider == "remote":
            ready = os.path.join(base_dir, "daemon_ready")
            daemon_backend = os.environ.get("BENCH_DAEMON_BACKEND",
                                            "adaptive")
            log_dir0 = os.environ.get("BENCH_MP_LOGS")
            dout = open(os.path.join(log_dir0, "daemon.log"), "w") \
                if log_dir0 else subprocess.DEVNULL
            daemon_proc = subprocess.Popen(
                [sys.executable, "-m", "plenum_tpu.server.verify_daemon",
                 "--port", str(daemon_port), "--backend", daemon_backend,
                 "--ready-file", ready],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                stdout=dout, stderr=subprocess.STDOUT)
            if dout is not subprocess.DEVNULL:
                dout.close()  # the child holds its own copy
            # the ready file is one JSON object: port + the device the
            # daemon got (it initializes its backend BEFORE serving, so
            # a chip it cannot open fails the start here)
            from plenum_tpu.server.verify_daemon import wait_ready
            daemon_info = wait_ready(ready, daemon_proc)
            daemon_port = daemon_info["port"]
            DAEMON_DEVICES.append(daemon_info.get("device"))
            print("[bench] verify daemon ready: %s" % json.dumps(
                daemon_info), file=sys.stderr, flush=True)
            # warm the device bucket so XLA compile stays out of the
            # timed window (the daemon compiles ONE fixed batch shape)
            from plenum_tpu.crypto.fixtures import make_signed_batch
            from plenum_tpu.crypto.remote_verifier import RemoteVerifier
            rv = RemoteVerifier(("127.0.0.1", daemon_port), timeout=600)
            # warm the EXACT power-of-two buckets the run dispatches:
            # the pool's chunks are CLIENT_BATCH-sized (deduped across
            # nodes), padding to the next pow2 — warming a different
            # bucket leaves the first timed run paying that bucket's
            # compile/executable-load inside the measurement (the cold
            # 5x first-run syndrome). Several launches per bucket: a
            # fresh process's early device calls pay staged load costs
            # beyond the first compile.
            sizes = {1 << (min(CLIENT_BATCH, POOL_REQS) - 1).bit_length()}
            if POOL_REQS % CLIENT_BATCH:
                sizes.add(1 << ((POOL_REQS % CLIENT_BATCH) - 1)
                          .bit_length())
            sizes.add(4096)
            for size in sorted(sizes):
                wm, ws, wv = make_signed_batch(size, seed=3)
                items = list(zip(wm, ws, wv))
                for _ in range(3):
                    assert all(rv.verify_batch(items))
            rv.close()

        with open(os.path.join(base_dir, "plenum_tpu_config.py"), "w") as f:
            f.write(
                "Max3PCBatchSize = %d\n"
                "Max3PCBatchWait = 0.05\n"
                "CHK_FREQ = 10\n"
                "LOG_SIZE = 30\n"
                "CLIENT_TO_NODE_STACK_QUOTA = 4000\n"
                "NODE_TO_NODE_STACK_QUOTA = 4096\n"
                "NODE_TO_NODE_STACK_SIZE = %d\n"
                "CLIENT_TO_NODE_STACK_SIZE = %d\n"
                "VERIFIER_PROVIDER = %r\n"
                "VERIFIER_DAEMON_PORT = %d\n"
                "METRICS_FLUSH_INTERVAL = 2\n"
                % (CLIENT_BATCH, 16 << 20, 16 << 20, provider,
                   daemon_port))

        # node processes must never touch the (process-exclusive) TPU.
        # With the daemon, the node start path pins itself to the CPU
        # backend from VERIFIER_PROVIDER="remote"
        # (bootstrap.settle_device_ownership) and nothing is set here.
        # The cpu-floor pool has no daemon, and four co-resident nodes
        # cannot each own the chip: that is this launcher's knowledge
        env = dict(os.environ)
        if provider != "remote":
            env["JAX_PLATFORMS"] = "cpu"
        script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "start_plenum_tpu_node")
        log_dir = os.environ.get("BENCH_MP_LOGS")  # debugging aid
        for name in NAMES:
            # provider in the filename so back-to-back remote/cpu runs
            # don't clobber each other's logs
            out = open(os.path.join(
                log_dir, "%s.%s.log" % (name, provider)), "w") \
                if log_dir else subprocess.DEVNULL
            procs.append(subprocess.Popen(
                [sys.executable, script, "--name", name,
                 "--base-dir", base_dir],
                env=env, stdout=out, stderr=subprocess.STDOUT))
            if out is not subprocess.DEVNULL:
                out.close()

        ordered, elapsed = _drive_mp_client(base_dir, reqs, procs)
        return elapsed, ordered
    finally:
        signal.signal(signal.SIGTERM, prev_term)
        for p in procs + ([daemon_proc] if daemon_proc else []):
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs + ([daemon_proc] if daemon_proc else []):
            if p is not None:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
        shutil.rmtree(base_dir, ignore_errors=True)


def _drive_mp_client(base_dir, reqs, procs):
    """Async client: one encrypted connection per node, broadcast every
    request, count REPLYs per connection. Done when EVERY node replied
    to every request (whole pool committed). → (ordered, elapsed)."""
    import asyncio

    from plenum_tpu.bootstrap import (client_ha_from_pool_genesis,
                                      registry_from_pool_genesis)
    from plenum_tpu.network.stack import ClientConnection

    registry = registry_from_pool_genesis(base_dir)
    debug = os.environ.get("BENCH_MP_LOGS") is not None

    def dbg(*a):
        if debug:
            print("[mp-client]", *a, flush=True)

    async def drive():
        conns = {}
        deadline = time.perf_counter() + 120
        for name in NAMES:
            ha = client_ha_from_pool_genesis(base_dir, name)
            while True:
                conn = ClientConnection(
                    ha, expected_verkey=registry[name].verkey)
                try:
                    await conn.connect()
                    conns[name] = conn
                    break
                except OSError:
                    if time.perf_counter() > deadline:
                        raise RuntimeError(
                            "node %s never came up" % name)
                    await asyncio.sleep(0.5)

        dbg("connected to all nodes")
        # wait for the pool to elect a primary: probe with the first
        # request until a REPLY arrives from every node
        probe = reqs[0]
        t_probe = time.perf_counter() + 120
        while time.perf_counter() < t_probe:
            for conn in conns.values():
                conn.send(dict(probe))
            await asyncio.sleep(1.0)
            if all(any(m.get("op") == "REPLY" for m in c.rx)
                   for c in conns.values()):
                break
        else:
            raise RuntimeError("pool never ordered the probe request")
        dbg("probe ordered")

        t0 = time.perf_counter()
        rest = reqs[1:]
        required = frozenset(r["reqId"] for r in rest)
        for conn in conns.values():
            for r in rest:
                conn.send(r)
        dbg("blasted", len(rest), "to each node")
        done_at = None
        hard_deadline = time.perf_counter() + 600
        seen = {n: set() for n in conns}
        last_dbg = time.perf_counter()
        import collections as _coll
        all_ops = {n: _coll.Counter() for n in conns}
        while time.perf_counter() < hard_deadline:
            if debug and time.perf_counter() - last_dbg > 5:
                last_dbg = time.perf_counter()
                dbg("progress", {n: len(s) for n, s in seen.items()},
                    "ops", {n: dict(c) for n, c in all_ops.items()})
            for name, conn in conns.items():
                for m in conn.rx:
                    all_ops[name][m.get("op")] += 1
                    if m.get("op") == "REPLY":
                        # a write REPLY's result is the committed txn:
                        # reqId lives in txn.metadata
                        result = m.get("result", {})
                        rid = result.get(
                            "txn", {}).get("metadata", {}).get("reqId")
                        if rid is None:
                            rid = result.get("reqId")
                        if rid in required:
                            seen[name].add(rid)
                conn.rx.clear()
            if all(len(s) == len(required) for s in seen.values()):
                done_at = time.perf_counter()
                break
            await asyncio.sleep(0.02)
        for conn in conns.values():
            conn.close()
        if done_at is None:
            return (min(len(s) for s in seen.values()),
                    time.perf_counter() - t0)
        return len(required), done_at - t0

    return asyncio.run(drive())


def run_pool(reqs, verifier_name, tracing=False, return_nodes=False,
             telemetry=True, extra_conf=None):
    """→ (elapsed_wall_seconds, ordered_count) for ordering all reqs
    (+ the pool's nodes when return_nodes — the traced run hands its
    ring buffers to the per-stage budget aggregation).

    Chunk intake is PIPELINED: chunk i+1's verification is dispatched
    (async device launch / deferred CPU work) before chunk i's consensus
    rounds are pumped, so the device round trip overlaps the Python
    consensus work instead of serializing with it — the same
    dispatch/conclude split the Node's intake API exposes for the
    production prod loop."""
    nodes, timer = make_sim_pool(NAMES, verifier_name, tracing=tracing,
                                 telemetry=telemetry,
                                 extra_conf=extra_conf)

    target = len(reqs)
    t0 = time.perf_counter()
    chunks = [reqs[i:i + CLIENT_BATCH]
              for i in range(0, target, CLIENT_BATCH)]
    pipelined_intake(nodes, timer, chunks, client_id="bench-client")
    # drain to completion
    deadline = time.perf_counter() + 300
    while time.perf_counter() < deadline:
        for nd in nodes:
            nd.service()
        timer.run_for(0.01)
        if all(nd.domain_ledger.size >= target for nd in nodes):
            break
    elapsed = time.perf_counter() - t0
    ordered = min(nd.domain_ledger.size for nd in nodes)
    if return_nodes:
        return elapsed, ordered, nodes
    return elapsed, ordered


def tracing_overhead():
    """Flight-recorder overhead gate (observability/): the IDENTICAL
    4-node sim pool + ordering workload with tracing enabled vs
    disabled. CPU verifier on both sides so shared-device variance
    cannot mask (or fake) the tracer's cost; interleaved best-of-2 like
    every other pool comparison. The enabled cost must stay in low
    single-digit percent — that is what makes it safe to flip on in
    production when a pool misbehaves."""
    from plenum_tpu.crypto.signer import SimpleSigner

    n = int(os.environ.get("BENCH_TRACE_REQS", str(min(POOL_REQS, 2000))))
    reqs = make_requests(n, SimpleSigner(seed=b"\x52" * 32))
    from plenum_tpu.observability.budget import budget_from_tracers
    from plenum_tpu.observability.export import pool_tracers
    off_runs, on_runs = [], []
    traced_nodes = None
    for i in range(2):
        off_runs.append(run_pool(reqs, "cpu", tracing=False))
        on_elapsed_i, on_ordered_i, traced_nodes = run_pool(
            reqs, "cpu", tracing=True, return_nodes=True)
        on_runs.append((on_elapsed_i, on_ordered_i))
    # per-stage host-ms budget from the LAST traced run's ring buffers
    # — the same spans scripts/trace_budget reads from a dump, so a
    # bench regression and an offline trace point at the same stage
    budget = budget_from_tracers(pool_tracers(traced_nodes)) \
        if traced_nodes is not None else None
    off_elapsed, off_ordered = best_of_runs(off_runs, n, "trace-off")
    on_elapsed, on_ordered = best_of_runs(on_runs, n, "trace-on")
    off_rate = off_ordered / off_elapsed
    on_rate = on_ordered / on_elapsed
    return {
        "reqs": n,
        "traced_req_per_s": round(on_rate, 1),
        "untraced_req_per_s": round(off_rate, 1),
        # positive = tracing costs throughput; can come out slightly
        # negative on a noisy box (within run-to-run jitter)
        "overhead_pct": round(100.0 * (1.0 - on_rate / off_rate), 2),
        # stage-attributable money-path budget (host ms one ordered
        # request costs one node, by stage)
        "host_ms_per_ordered_req": (budget or {}).get(
            "host_ms_per_ordered_req"),
        "budget_ordered_reqs": (budget or {}).get("ordered_reqs"),
    }


def pool_latency_summary(nodes):
    """Merge a pool's per-node telemetry hubs → (ordered_p50_ms,
    ordered_p99_ms, e2e_count) from the intake→reply histograms; Nones
    when telemetry was off or nothing ordered."""
    from plenum_tpu.observability.export import pool_telemetry
    from plenum_tpu.observability.telemetry import TM, merged_snapshot
    hubs = pool_telemetry(nodes)
    if not hubs:
        return None, None, 0
    snap = merged_snapshot(hubs)
    h = (snap.get("histograms") or {}).get(TM.ORDERED_E2E_MS) or {}
    return h.get("p50"), h.get("p99"), h.get("count", 0)


def seam_lane_table(hub):
    """Per-seam lane-occupancy table from a seam hub: {seam: occupancy}
    plus launch counts — the padding-efficiency trajectory the headline
    records each round."""
    if hub is None or not getattr(hub, "enabled", False):
        return {}
    out = {}
    for seam, s in (hub.snapshot().get("seams") or {}).items():
        out[seam] = {
            "occupancy": s.get("lane_occupancy"),
            "launches": s.get("launches"),
            "useful_rows": s.get("useful_rows"),
            "lane_rows": s.get("lane_rows"),
            "compile_events": s.get("compile_events"),
        }
    return out


def telemetry_overhead():
    """Telemetry-plane overhead gate: the IDENTICAL 4-node pool +
    ordering workload with the always-on plane enabled vs disabled —
    the tracing_overhead methodology (CPU verifier on both sides,
    interleaved best-of-2). The plane ships ON by default, so this is
    the number that must stay under 2% (telemetry_overhead_gate) for
    "always-on" to be honest. The ON run also contributes the 4-node
    ordered e2e tail (p50/p99)."""
    from plenum_tpu.crypto.signer import SimpleSigner

    n = int(os.environ.get("BENCH_TELEMETRY_REQS",
                           str(min(POOL_REQS, 2000))))
    reqs = make_requests(n, SimpleSigner(seed=b"\x53" * 32))
    # the device seams record into the PROCESS-wide hub, not the node
    # hubs — an honest off side must silence that too, or the A/B only
    # measures the node-hub half of the plane
    from plenum_tpu.observability.telemetry import (
        NullTelemetryHub, TelemetryHub, set_seam_hub)
    original_seam_hub = None
    off_runs, on_runs = [], []
    on_nodes = None
    for _ in range(2):
        prev = set_seam_hub(NullTelemetryHub(name="device-seams"))
        if original_seam_hub is None:
            original_seam_hub = prev
        off_runs.append(run_pool(reqs, "cpu", telemetry=False))
        set_seam_hub(TelemetryHub(name="device-seams"))
        on_elapsed_i, on_ordered_i, on_nodes = run_pool(
            reqs, "cpu", telemetry=True, return_nodes=True)
        on_runs.append((on_elapsed_i, on_ordered_i))
    set_seam_hub(original_seam_hub)
    off_elapsed, off_ordered = best_of_runs(off_runs, n, "telemetry-off")
    on_elapsed, on_ordered = best_of_runs(on_runs, n, "telemetry-on")
    off_rate = off_ordered / off_elapsed
    on_rate = on_ordered / on_elapsed
    p50, p99, count = pool_latency_summary(on_nodes or [])
    return {
        "reqs": n,
        "telemetry_req_per_s": round(on_rate, 1),
        "no_telemetry_req_per_s": round(off_rate, 1),
        # positive = telemetry costs throughput; slightly negative =
        # run-to-run jitter on a loaded box
        "overhead_pct": round(100.0 * (1.0 - on_rate / off_rate), 2),
        "ordered_p50_ms": p50,
        "ordered_p99_ms": p99,
        "e2e_samples": count,
    }


# the always-on claim's hard ceiling: the telemetry plane must cost
# less than this on the identical-pool A/B or the bench run fails
TELEMETRY_OVERHEAD_MAX_PCT = 2.0


def telemetry_overhead_gate(result, ceiling=None):
    """HARD gate for the telemetry plane's always-on claim: the
    measured on/off overhead must stay under TELEMETRY_OVERHEAD_MAX_PCT.
    Pure function of the telemetry_overhead dict (tier-1 gates the
    gate in tests/test_bench_gate.py, the merkle_regression_gate
    precedent); → list of failures. BENCH_TELEMETRY_GATE=warn
    downgrades main() to warn-only for diagnostic runs on noisy
    hosts — the headline still records the failures."""
    ceiling = TELEMETRY_OVERHEAD_MAX_PCT if ceiling is None else ceiling
    value = result.get("overhead_pct")
    if value is None:
        return ["overhead_pct missing from telemetry_overhead"]
    if value >= ceiling:
        return ["telemetry_overhead_pct %.2f >= allowed %.2f"
                % (value, ceiling)]
    return []


def trace_context_overhead():
    """Journey-plane stamp overhead gate: the IDENTICAL traced 4-node
    pool + ordering workload with wire trace context ON vs OFF — the
    telemetry_overhead methodology (CPU verifier both sides,
    interleaved best-of-2). BOTH sides run with the flight recorder on,
    so the delta isolates exactly what the journey plane adds: stamp
    encode on every envelope flush, stamp decode + wire_send/wire_recv
    instants, and the quorum-close vote attribution. The ON side's ring
    buffers also yield the journey report itself (complete-request
    count + causal check), proving the measured configuration actually
    produces journeys."""
    from plenum_tpu.crypto.signer import SimpleSigner
    from plenum_tpu.observability.export import pool_tracers
    from plenum_tpu.observability.journey import (
        causal_violations, journeys_from_tracers)

    n = int(os.environ.get("BENCH_TRACE_CTX_REQS",
                           str(min(POOL_REQS, 2000))))
    rounds = int(os.environ.get("BENCH_TRACE_CTX_ROUNDS", "3"))
    reqs = make_requests(n, SimpleSigner(seed=b"\x54" * 32))
    off_runs, on_runs = [], []
    on_nodes = None
    # the stamp cost itself is tiny (a few hundred clock samples +
    # instants per thousand ordered requests), so host jitter dominates
    # a 2-round A/B — interleave MORE rounds than the other overhead
    # configs and alternate which side goes first so slow load drift
    # cancels instead of landing on one side
    for i in range(max(2, rounds)):
        def run_off():
            off_runs.append(run_pool(
                reqs, "cpu", tracing=True,
                extra_conf={"TRACE_CONTEXT_ENABLED": False}))

        def run_on():
            nonlocal on_nodes
            on_elapsed_i, on_ordered_i, on_nodes = run_pool(
                reqs, "cpu", tracing=True, return_nodes=True,
                extra_conf={"TRACE_CONTEXT_ENABLED": True})
            on_runs.append((on_elapsed_i, on_ordered_i))

        first, second = (run_off, run_on) if i % 2 == 0 \
            else (run_on, run_off)
        first()
        second()
    off_elapsed, off_ordered = best_of_runs(off_runs, n, "trace-ctx-off")
    on_elapsed, on_ordered = best_of_runs(on_runs, n, "trace-ctx-on")
    off_rate = off_ordered / off_elapsed
    on_rate = on_ordered / on_elapsed
    report = journeys_from_tracers(pool_tracers(on_nodes or []))
    return {
        "reqs": n,
        "stamped_req_per_s": round(on_rate, 1),
        "unstamped_req_per_s": round(off_rate, 1),
        "overhead_pct": round(100.0 * (1.0 - on_rate / off_rate), 2),
        "journey_requests": len(report.get("requests") or {}),
        "journey_complete": report.get("complete_requests", 0),
        "causal_violations": len(causal_violations(report)),
        "critical_path": report.get("breakdown"),
    }


# the journey plane's hard ceiling, same bar as the telemetry plane:
# wire stamps must cost less than this on the identical-pool A/B
TRACE_CONTEXT_OVERHEAD_MAX_PCT = 2.0


def trace_context_overhead_gate(result, ceiling=None):
    """HARD gate for the wire trace-context claim (mirrors
    telemetry_overhead_gate; tier-1 gates the gate in
    tests/test_bench_gate.py): the measured on/off overhead must stay
    under TRACE_CONTEXT_OVERHEAD_MAX_PCT, and the ON side must have
    produced complete, causally ordered journeys — a "free" stamp
    nobody can join is not a feature. → list of failures;
    BENCH_TRACE_CTX_GATE=warn downgrades main() to warn-only."""
    ceiling = TRACE_CONTEXT_OVERHEAD_MAX_PCT if ceiling is None \
        else ceiling
    failures = []
    value = result.get("overhead_pct")
    if value is None:
        failures.append("overhead_pct missing from trace_context_overhead")
    elif value >= ceiling:
        failures.append("trace_context_overhead_pct %.2f >= allowed %.2f"
                        % (value, ceiling))
    if not result.get("journey_complete"):
        failures.append("trace-context ON side produced no complete "
                        "journey records")
    if result.get("causal_violations"):
        failures.append("%d causally inconsistent journey record(s)"
                        % result["causal_violations"])
    return failures


def pool25_journey():
    """25-node traced journey pass: the critical-path breakdown at the
    backlog config's scale — where does an ordered request's wall time
    go across a 25-node pool (wire vs straggler-wait vs local stages)?
    A bounded write-only pass (BENCH_P25J_REQS) with the flight
    recorder + wire trace context on; reported next to pool25_backlog
    (whose throughput numbers stay untraced and comparable across
    rounds)."""
    from plenum_tpu.crypto.signer import SimpleSigner
    from plenum_tpu.observability.export import pool_tracers
    from plenum_tpu.observability.journey import (
        causal_violations, journeys_from_tracers)

    n_nodes = int(os.environ.get("BENCH_P25J_NODES", "25"))
    n = int(os.environ.get("BENCH_P25J_REQS", "1000"))
    batch = int(os.environ.get("BENCH_P25J_BATCH", "250"))
    names = ["N%02d" % i for i in range(n_nodes)]
    nodes, timer = make_sim_pool(
        names, "cpu", seed=26, batch=batch, tracing=True,
        extra_conf={"TRACE_CONTEXT_ENABLED": True})
    reqs = make_requests(n, SimpleSigner(seed=b"\x55" * 32))
    chunks = [reqs[i:i + batch] for i in range(0, n, batch)]
    t0 = time.perf_counter()
    pipelined_intake(nodes, timer, chunks, client_id="p25j-client")
    deadline = time.perf_counter() + 300
    while time.perf_counter() < deadline:
        for nd in nodes:
            nd.service()
        timer.run_for(0.01)
        if all(nd.domain_ledger.size >= n for nd in nodes):
            break
    elapsed = time.perf_counter() - t0
    ordered = min(nd.domain_ledger.size for nd in nodes)
    report = journeys_from_tracers(pool_tracers(nodes))
    return {
        "nodes": n_nodes,
        "reqs": n,
        "ordered": ordered,
        "req_per_s": round(ordered / elapsed, 1) if elapsed else None,
        "journey_requests": len(report.get("requests") or {}),
        "journey_complete": report.get("complete_requests", 0),
        "causal_violations": len(causal_violations(report)),
        # wire vs straggler-wait vs local stages as pct of ordered e2e,
        # averaged over every batch's critical path
        "critical_path": report.get("breakdown"),
    }


def micro_ed25519():
    """Secondary: raw batched verify/s per chip + floors, at the
    headline batch AND across BASELINE's 1 / 1k / 100k sweep."""
    import numpy as np
    from plenum_tpu.crypto.fixtures import make_signed_batch
    from plenum_tpu.ops import ed25519_jax as edj
    from plenum_tpu.crypto.batch_verifier import create_verifier
    from plenum_tpu.crypto import ed25519 as ed

    msgs, sigs, vks = make_signed_batch(MICRO_BATCH, seed=42, unique=256,
                                        msg_prefix=b"bench-req")
    ok = edj.verify_batch(msgs, sigs, vks)  # warmup/compile
    assert bool(np.all(ok))
    # PIPELINED sustained rate is the headline: the deployment shape is
    # a stream of batches (intake pipeline keeps >=1 launch in flight),
    # so each dispatch hides the predecessor's launch round trip. The
    # single-shot number (one launch incl. its full round trip) is kept for
    # transparency — it is what a one-off batch pays.
    rounds = 6

    def make_pipe(pm, ps, pv, n_rounds, depth=2):
        """Depth-bounded pipelined verify driver shared by the
        headline and the sweep — one place owns the pend/drain shape."""
        def run_pipe():
            pend = []
            for _ in range(n_rounds):
                pend.append(edj.verify_batch_async(pm, ps, pv))
                if len(pend) > depth:
                    okd, _valid, _cnt = pend.pop(0)
                    np.asarray(okd)
            for okd, _valid, _cnt in pend:
                np.asarray(okd)
        return run_pipe

    run_pipe = make_pipe(msgs, sigs, vks, rounds)
    run_pipe()
    t_best, t_med = best_median_time(run_pipe, runs=3)
    device_rate = rounds * MICRO_BATCH / t_best
    device_rate_median = rounds * MICRO_BATCH / t_med
    t_ss_b, t_ss_m = best_median_time(
        lambda: edj.verify_batch(msgs, sigs, vks), runs=4)
    single_shot_rate = MICRO_BATCH / t_ss_b
    single_shot_rate_median = MICRO_BATCH / t_ss_m

    cpu = create_verifier("cpu")
    n_cpu = min(2000, MICRO_BATCH)
    items = list(zip(msgs[:n_cpu], sigs[:n_cpu], vks[:n_cpu]))
    t0 = time.perf_counter()
    cpu.verify_batch(items)
    openssl_rate = n_cpu / (time.perf_counter() - t0)

    n_py = 30
    t0 = time.perf_counter()
    for i in range(n_py):
        ed.verify(msgs[i], sigs[i], vks[i])
    python_rate = n_py / (time.perf_counter() - t0)

    # BASELINE's batch sweep: 1 (latency floor — the launch round trip
    # dominates and the CPU floor wins, which is exactly what the
    # adaptive provider encodes), 1k, and 100k (chunked through the
    # already-compiled MICRO_BATCH bucket, launches pipelined through
    # the device queue)
    sweep = {}
    for n in (1, 1000, 100000):
        sm, ss, sv = make_signed_batch(n, seed=7, unique=min(n, 256),
                                       msg_prefix=b"sweep")
        if n <= MICRO_BATCH:
            edj.verify_batch(sm, ss, sv)  # compile this bucket

            def run(sm=sm, ss=ss, sv=sv):
                edj.verify_batch(sm, ss, sv)
        else:
            def run(sm=sm, ss=ss, sv=sv):
                pend = []
                for lo in range(0, len(sm), MICRO_BATCH):
                    chunk = slice(lo, lo + MICRO_BATCH)
                    pend.append(edj.verify_batch_async(
                        sm[chunk], ss[chunk], sv[chunk]))
                for okd, valid, cnt in pend:
                    np.asarray(okd)
            run()  # warm
        t_b, t_m = best_median_time(run, runs=4 if n <= 1000 else 3)
        flo = min(n, 2000)
        t0 = time.perf_counter()
        cpu.verify_batch(list(zip(sm[:flo], ss[:flo], sv[:flo])))
        entry = {
            "device_best_per_s": round(n / t_b, 1),
            "device_median_per_s": round(n / t_m, 1),
            "openssl_per_s": round(flo / (time.perf_counter() - t0), 1),
        }
        if 1 < n <= MICRO_BATCH:
            # PIPELINED: the deployment shape for repeated batches —
            # consensus orders batch after batch, so dispatch i+1 hides
            # dispatch i's launch round trip. Single-shot is
            # the latency floor; this is the sustained rate a pool
            # actually gets from n-sized batches.
            rounds = 6
            run_sweep_pipe = make_pipe(sm, ss, sv, rounds)
            run_sweep_pipe()
            t_b2, t_m2 = best_median_time(run_sweep_pipe, runs=3)
            entry["device_pipelined_per_s"] = round(rounds * n / t_b2, 1)
            entry["device_pipelined_per_s_median"] = round(
                rounds * n / t_m2, 1)
        sweep[str(n)] = entry
    return (device_rate, device_rate_median, single_shot_rate,
            single_shot_rate_median, openssl_rate, python_rate, sweep)


def micro_merkle(n_leaves=None):
    """BASELINE config 4: 1M-leaf merkle build + audit-path batches on
    the device-resident tree (ops/merkle.py: one fused jit for all
    levels; FUSED gather+pack proof batches; lazy host mirror of the
    top levels) vs the hashlib (OpenSSL) scalar floor. Also reported:
    the ragged-size proof path (frontier decomposition), incremental
    device append throughput (the ordered-batch shape), and the
    ProofPipeline chunked double-buffered serving rate."""
    import numpy as _np
    from plenum_tpu.ledger.compact_merkle_tree import CompactMerkleTree
    from plenum_tpu.ledger.hash_store import MemoryHashStore
    from plenum_tpu.ledger.merkle_verifier import MerkleVerifier
    from plenum_tpu.ledger.tree_hasher import TreeHasher
    from plenum_tpu.ops.merkle import DeviceMerkleTree, ProofPipeline

    n_leaves = n_leaves or int(os.environ.get("BENCH_MERKLE_LEAVES",
                                              str(1 << 20)))
    # the dense audit-path config uses a power-of-two tree: round down
    n_leaves = max(4, 1 << (n_leaves.bit_length() - 1))
    leaves = [b"txn-%020d" % i for i in range(n_leaves)]
    dev = DeviceMerkleTree()
    root = dev.build(leaves)  # compile + warm
    t_b, t_m = best_median_time(lambda: dev.build(leaves))
    device_leaves_per_s = n_leaves / t_b
    device_leaves_per_s_median = n_leaves / t_m

    # audit-path batch: device gathers the big bottom levels FUSED with
    # big-endian packing (one dense uint8 download, no host byteswap);
    # the lazily host-mirrored top levels join by vectorized numpy (the
    # mirror keeps per-batch download bytes to the bottom levels
    # only). The PIPELINED number is the serving shape: a
    # node answering a stream of proof batches overlaps each download
    # with the next gather (ProofPipeline, chunked).
    n_proofs = min(10000, n_leaves)
    idx = list(range(0, n_leaves, max(1, n_leaves // n_proofs)))[:n_proofs]
    paths = dev.audit_path_batch(idx[:4])  # compile + fill lazy mirror
    assert dev.verify_path(leaves[idx[0]], idx[0], paths[0], root)
    dev.audit_path_batch_array(idx)        # warm the full batch shape
    t_b, t_m = best_median_time(lambda: dev.audit_path_batch_array(idx))
    proof_rate, proof_rate_median = len(idx) / t_b, len(idx) / t_m

    pipe_depth = int(os.environ.get("BENCH_MERKLE_PIPE_DEPTH", "3"))
    pipe_chunk = int(os.environ.get("BENCH_MERKLE_CHUNK",
                                    str(max(1, len(idx) // 4))))
    chunks = [idx[i:i + pipe_chunk]
              for i in range(0, len(idx), pipe_chunk)]
    pipe = ProofPipeline(dev, depth=pipe_depth, dense=True)
    stream_batches = [c for _ in range(4) for c in chunks]
    for _ in pipe.stream(stream_batches):
        pass  # warm every chunk shape

    def pipelined_round():
        for _ in pipe.stream(stream_batches):
            pass
    t_b, t_m = best_median_time(pipelined_round)
    proof_rate_pipelined = 4 * len(idx) / t_b
    proof_rate_pipelined_median = 4 * len(idx) / t_m

    # hashlib floor: build throughput normalized on a smaller tree,
    # but the PROOF floor walks the full n_leaves-deep tree — same
    # depth, same proof size as the device path
    n_floor = min(100000, n_leaves)
    t0 = time.perf_counter()
    floor_tree = CompactMerkleTree(TreeHasher(), MemoryHashStore())
    for leaf in leaves[:n_floor]:
        floor_tree.append(leaf)
    floor_leaves_per_s = n_floor / (time.perf_counter() - t0)
    for leaf in leaves[n_floor:]:
        floor_tree.append(leaf)

    t0 = time.perf_counter()
    for i in idx:
        floor_tree.inclusion_proof(i, n_leaves)
    proof_floor_per_s = len(idx) / (time.perf_counter() - t0)

    # ---- ragged-size proof batch: RFC 6962 proofs for the size-n_rag
    # prefix tree served by the frontier-decomposition device path
    # (exactly what Ledger.merkleInfoBatch routes through), verified
    # against MerkleVerifier; floor = the host memoized batch walk.
    n_rag = max(3, n_leaves - 123)
    rag_idx = [i for i in idx if i < n_rag]
    rag_pipe = ProofPipeline(dev, depth=pipe_depth)
    rag_paths = rag_pipe.run(rag_idx, n=n_rag, chunk=pipe_chunk)  # warm
    rag_root = floor_tree.merkle_tree_hash(0, n_rag)
    verifier = MerkleVerifier(TreeHasher())
    for j in (0, len(rag_idx) // 2, len(rag_idx) - 1):
        assert verifier.verify_leaf_inclusion(
            leaves[rag_idx[j]], rag_idx[j], rag_paths[j], n_rag, rag_root)

    def ragged_round():
        rag_pipe.run(rag_idx, n=n_rag, chunk=pipe_chunk)
    t_b, t_m = best_median_time(ragged_round)
    ragged_rate, ragged_rate_median = len(rag_idx) / t_b, len(rag_idx) / t_m

    t0 = time.perf_counter()
    floor_tree.inclusion_proofs_batch(rag_idx, n_rag)
    ragged_floor_per_s = len(rag_idx) / (time.perf_counter() - t0)

    # ---- incremental device append: b leaves onto an n_leaves tree in
    # ~2b device hashes (one small dispatch per level) — the ordered-
    # 3PC-batch shape — vs the host level-wise bulk extend and the
    # scalar frontier-merge floor.
    app_b = int(os.environ.get("BENCH_MERKLE_APPEND_B", "8192"))
    rng = _np.random.RandomState(42)
    base = rng.randint(0, 256, size=(n_leaves, 32)).astype(_np.uint8)
    inc = DeviceMerkleTree()
    inc.build_from_leaf_hashes(base)
    app = rng.randint(0, 256, size=(app_b, 32)).astype(_np.uint8)
    inc.append_leaf_hashes(app)
    inc.root_hash  # warm (forces the level dispatch chain + root read)

    def append_round():
        inc.append_leaf_hashes(app)
        return inc.root_hash
    t_b, t_m = best_median_time(append_round)
    append_rate, append_rate_median = app_b / t_b, app_b / t_m

    app_hashes = [app[i].tobytes() for i in range(app_b)]
    shadow = floor_tree.copy_shadow()
    t0 = time.perf_counter()
    for h in app_hashes:
        shadow._append_hash(h, want_path=False)
    append_scalar_per_s = app_b / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    floor_tree.extend_hashes(app_hashes)  # level-wise host bulk extend
    append_bulk_host_per_s = app_b / (time.perf_counter() - t0)

    # ---- dispatches per append, counted from flight-recorder spans:
    # the multi-level fusion gate (ROADMAP item 3 acceptance — one
    # append on a 1M-leaf tree pays 1 + ceil(levels/K) device
    # dispatches instead of 1 + levels; a 1M-leaf incremental build is
    # n_leaves/app_b of these, so the per-append ratio IS the
    # per-build ratio)
    from plenum_tpu.common.config import Config as _Cfg
    from plenum_tpu.observability.tracing import Tracer
    tr = Tracer("bench_merkle")
    inc.attach_tracer(tr)

    def append_dispatch_spans(k):
        prior_k = _Cfg.MERKLE_FUSED_LEVELS
        _Cfg.MERKLE_FUSED_LEVELS = k
        try:
            # reset to the identical tree state for both K values: the
            # level count an append touches depends on the leaf offset,
            # so counting on a mutating tree would skew the ratio
            inc.build_from_leaf_hashes(base)
            tr.clear()
            inc.append_leaf_hashes(app)
            return sum(1 for r in tr.spans()
                       if r[1] == "merkle_append_dispatch")
        finally:
            _Cfg.MERKLE_FUSED_LEVELS = prior_k

    disp_fused = append_dispatch_spans(_Cfg.MERKLE_FUSED_LEVELS)
    disp_unfused = append_dispatch_spans(1)
    inc.attach_tracer(None)

    return {
        "leaves": n_leaves,
        "build_leaves_per_s": round(device_leaves_per_s, 1),
        "build_leaves_per_s_median": round(device_leaves_per_s_median, 1),
        "audit_paths_per_s": round(proof_rate, 1),
        "audit_paths_per_s_median": round(proof_rate_median, 1),
        "audit_paths_pipelined_per_s": round(proof_rate_pipelined, 1),
        "audit_paths_pipelined_per_s_median": round(
            proof_rate_pipelined_median, 1),
        "pipeline": {"depth": pipe_depth, "chunk": pipe_chunk},
        "audit_paths_cpu_floor_per_s": round(proof_floor_per_s, 1),
        "vs_cpu_audit_paths": round(
            proof_rate_pipelined / proof_floor_per_s, 2),
        "vs_cpu_audit_paths_single_shot": round(
            proof_rate / proof_floor_per_s, 2),
        "hashlib_floor_leaves_per_s": round(floor_leaves_per_s, 1),
        "vs_hashlib": round(device_leaves_per_s / floor_leaves_per_s, 2),
        "ragged": {
            "leaves": n_rag,
            "paths_per_s": round(ragged_rate, 1),
            "paths_per_s_median": round(ragged_rate_median, 1),
            "host_memo_floor_per_s": round(ragged_floor_per_s, 1),
            "vs_host_memo": round(ragged_rate / ragged_floor_per_s, 2),
        },
        "incremental_append": {
            "batch": app_b,
            "device_leaves_per_s": round(append_rate, 1),
            "device_leaves_per_s_median": round(append_rate_median, 1),
            "host_bulk_leaves_per_s": round(append_bulk_host_per_s, 1),
            "host_scalar_leaves_per_s": round(append_scalar_per_s, 1),
            "fused_levels": _Cfg.MERKLE_FUSED_LEVELS,
            "dispatches_per_append_fused": disp_fused,
            "dispatches_per_append_unfused": disp_unfused,
            "dispatch_reduction": round(
                disp_unfused / max(1, disp_fused), 2),
        },
    }


def micro_state():
    """BENCH_r06 config: the device MPT state engine
    (state/device_state.py) vs the pure-Python trie floor — batched
    multi-key get, whole-batch apply (level-wise SHA3 dispatches), and
    batched SPV proof generation, the three serving shapes behind
    PruningState. Floors run the identical work through the host
    Trie one key at a time (the pre-engine state of state/)."""
    from plenum_tpu.state.device_state import DeviceStateEngine
    from plenum_tpu.state.trie import BLANK_ROOT, Trie
    from plenum_tpu.storage.kv_memory import KeyValueStorageInMemory

    n_base = int(os.environ.get("BENCH_STATE_BASE", "20000"))
    n_batch = int(os.environ.get("BENCH_STATE_BATCH", "2000"))
    base = [(b"did:bench:%012d" % i,
             b'{"val":{"verkey":"~%020d"},"lsn":%d,"lut":1600000000}'
             % (i, i)) for i in range(n_base)]
    batch = base[:n_batch]
    keys = [k for k, _ in batch]
    fresh = [(b"did:fresh:%012d" % i, v) for i, (_, v) in
             enumerate(batch)]

    kv = KeyValueStorageInMemory()
    eng = DeviceStateEngine(kv)
    root = eng.apply_batch(BLANK_ROOT, base)  # build + warm compile
    eng.get_batch(root, keys)
    eng.proof_batch(root, keys[:64])

    # apply: a 3PC-batch-sized write set onto the standing trie (the
    # root moves, so each timed round applies onto the SAME base root)
    def apply_round():
        return eng.apply_batch(root, fresh)
    apply_round()
    t_b, t_m = best_median_time(apply_round)
    apply_rate, apply_rate_median = n_batch / t_b, n_batch / t_m

    t_b, t_m = best_median_time(lambda: eng.get_batch(root, keys))
    get_rate, get_rate_median = n_batch / t_b, n_batch / t_m

    t_b, t_m = best_median_time(lambda: eng.proof_batch(root, keys))
    proof_rate, proof_rate_median = n_batch / t_b, n_batch / t_m

    # pure-Python floor: identical content through the host trie
    kvf = KeyValueStorageInMemory()
    floor = Trie(kvf)
    t0 = time.perf_counter()
    for k, v in base:
        floor.set(k, v)
    floor_build_per_s = n_base / (time.perf_counter() - t0)
    froot = floor.root_hash
    assert froot == root, "engine root must be byte-equal to the floor"

    shadow = Trie(kvf, froot)
    t0 = time.perf_counter()
    for k, v in fresh:
        shadow.set(k, v)
    floor_apply_per_s = n_batch / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    for k in keys:
        floor.get(k)
    floor_get_per_s = n_batch / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    for k in keys:
        floor.produce_spv_proof(k, froot)
    floor_proof_per_s = n_batch / (time.perf_counter() - t0)

    return {
        "base_keys": n_base,
        "batch": n_batch,
        "apply_keys_per_s": round(apply_rate, 1),
        "apply_keys_per_s_median": round(apply_rate_median, 1),
        "get_keys_per_s": round(get_rate, 1),
        "get_keys_per_s_median": round(get_rate_median, 1),
        "proofs_per_s": round(proof_rate, 1),
        "proofs_per_s_median": round(proof_rate_median, 1),
        "python_floor": {
            "build_keys_per_s": round(floor_build_per_s, 1),
            "apply_keys_per_s": round(floor_apply_per_s, 1),
            "get_keys_per_s": round(floor_get_per_s, 1),
            "proofs_per_s": round(floor_proof_per_s, 1),
        },
        "vs_python_apply": round(apply_rate / floor_apply_per_s, 2),
        "vs_python_get": round(get_rate / floor_get_per_s, 2),
        "vs_python_proofs": round(proof_rate / floor_proof_per_s, 2),
        "note": "floor gets/proofs TRUST the store (zero hashing); the "
                "engine re-verifies every node hash while serving, so "
                "vs_python_get/proofs price added integrity too",
        "engine": eng.stats(),
    }


def micro_executor():
    """BENCH_r07 config: the conflict-lane executor (server/executor.py
    + server/execution_lanes.py) vs the serial apply path — 2k-request
    NYM batches over a 20k-key domain state at conflict ratios
    {0, 0.1, 0.5, 1.0} (fraction of requests writing a shared hot key
    set; the rest create fresh nyms). Two full stacks (storage +
    handler registry + executor) run the IDENTICAL digest streams with
    lanes on vs off, and ledger/state/txn/audit roots are ASSERTED
    byte-equal after every batch — the bench IS the equivalence gate.
    Headline gains: executor_reqs_per_s (lane path at conflict 0.1, the
    acceptance point) and lane_parallel_speedup (lanes/serial)."""
    import random as _random

    from plenum_tpu.common.constants import (
        AUDIT_LEDGER_ID, DOMAIN_LEDGER_ID, NYM, TARGET_NYM, VERKEY)
    from plenum_tpu.common.request import Request
    from plenum_tpu.common.state_codec import (
        encode_state_value, nym_to_state_key)
    from plenum_tpu.server.executor import NodeBatchExecutor
    from plenum_tpu.server.node import NodeBootstrap

    n_base = int(os.environ.get("BENCH_EXEC_BASE", "20000"))
    n_batch = int(os.environ.get("BENCH_EXEC_BATCH", "2000"))
    rounds = int(os.environ.get("BENCH_EXEC_ROUNDS", "3"))
    ratios = (0.0, 0.1, 0.5, 1.0)
    n_hot = 32

    def build_stack(lanes):
        dm = NodeBootstrap.init_storage()
        wm, _rm = NodeBootstrap.init_managers(dm)
        state = dm.get_state(DOMAIN_LEDGER_ID)
        for i in range(n_base):
            state.set(nym_to_state_key("did:bench:%012d" % i),
                      encode_state_value(
                          {"identifier": "genesis", "verkey": "~%d" % i},
                          i + 1, 1600000000))
        state.commit()
        store = {}
        executor = NodeBatchExecutor(wm, store.get, lanes=lanes)
        return dm, executor, store

    def make_batch(rng, conflict):
        hot = ["did:bench:%012d" % i for i in range(n_hot)]
        reqs = []
        for i in range(n_batch):
            if rng.random() < conflict:
                # write a shared hot key: a bare NYM update (no verkey /
                # role change validates for any author) — the write-
                # write conflict shape that must serialize into a lane
                op = {"type": NYM, TARGET_NYM: rng.choice(hot)}
            else:
                dest = "did:fresh:%016x" % rng.getrandbits(63)
                op = {"type": NYM, TARGET_NYM: dest, VERKEY: "~" + dest}
            reqs.append(Request(identifier="author1", reqId=i + 1,
                                operation=op, protocolVersion=2))
        return reqs

    def roots(dm):
        out = []
        ledger = dm.get_ledger(DOMAIN_LEDGER_ID)
        audit = dm.get_ledger(AUDIT_LEDGER_ID)
        out.append(ledger.hashToStr(ledger.uncommitted_root_hash))
        out.append(audit.hashToStr(audit.uncommitted_root_hash))
        out.append(dm.get_state(DOMAIN_LEDGER_ID).headHash.hex())
        return out

    stacks = {mode: build_stack(mode) for mode in (True, False)}
    by_conflict = {}
    pp_time = 1700000000
    # warm both modes through two mixed batches first: the serial path
    # compiles the per-level Keccak/SHA-256 buckets lazily across its
    # first applies, and a cold compile landing inside a timed round
    # would bias the A/B whichever way it fell
    for w in range(2):
        batch = make_batch(_random.Random(777 + w), 0.3)
        pp_time += 1
        for mode in (True, False):
            dm, executor, store = stacks[mode]
            digests = []
            for req in batch:
                store[req.digest] = req
                digests.append(req.digest)
            executor.apply_batch(digests, DOMAIN_LEDGER_ID, pp_time)
    assert roots(stacks[True][0]) == roots(stacks[False][0]), \
        "lane executor diverged from serial apply during warm-up"
    for conflict in ratios:
        best = {True: None, False: None}
        for r in range(rounds):
            # identical digest stream to both modes, fresh per round
            batch = make_batch(
                _random.Random(int(conflict * 10) * 1000 + r), conflict)
            pp_time += 1
            for mode in (True, False):
                dm, executor, store = stacks[mode]
                digests = []
                for req in batch:
                    store[req.digest] = req
                    digests.append(req.digest)
                t0 = time.perf_counter()
                executor.apply_batch(digests, DOMAIN_LEDGER_ID, pp_time)
                dt = time.perf_counter() - t0
                if best[mode] is None or dt < best[mode]:
                    best[mode] = dt
            assert roots(stacks[True][0]) == roots(stacks[False][0]), \
                "lane executor diverged from serial apply at " \
                "conflict=%s round=%d" % (conflict, r)
        lane_rate = n_batch / best[True]
        serial_rate = n_batch / best[False]
        by_conflict["%.1f" % conflict] = {
            "lane_reqs_per_s": round(lane_rate, 1),
            "serial_reqs_per_s": round(serial_rate, 1),
            "speedup": round(lane_rate / serial_rate, 2),
            "lane_ms_per_req": round(1e3 / lane_rate, 4),
            "serial_ms_per_req": round(1e3 / serial_rate, 4),
        }
    # adversarial equivalence phase (untimed): interleaved rejects
    # (role grants by an unauthorized author) riding a conflict batch,
    # then a view-change-shaped revert of every staged batch — the
    # bench gate covers the same shapes the randomized tests pin
    from plenum_tpu.common.constants import ROLE, TRUSTEE
    adv = make_batch(_random.Random(4242), 0.3)
    for i in range(0, len(adv), 7):
        adv[i] = Request(identifier="nobody%d" % i, reqId=50000 + i,
                         operation={"type": NYM,
                                    TARGET_NYM: "evil%d" % i,
                                    ROLE: TRUSTEE},
                         protocolVersion=2)
    pp_time += 1
    for mode in (True, False):
        dm, executor, store = stacks[mode]
        digests = []
        for req in adv:
            store[req.digest] = req
            digests.append(req.digest)
        executor.apply_batch(digests, DOMAIN_LEDGER_ID, pp_time)
    assert roots(stacks[True][0]) == roots(stacks[False][0]), \
        "lane executor diverged on the reject-interleaved batch"
    for mode in (True, False):
        stacks[mode][1].revert_unordered_batches()
    assert roots(stacks[True][0]) == roots(stacks[False][0]), \
        "lane executor diverged across the view-change revert"

    head = by_conflict["0.1"]
    return {
        "batch": n_batch,
        "base_keys": n_base,
        "hot_keys": n_hot,
        "by_conflict": by_conflict,
        "roots_byte_equal": True,  # asserted above: every batch, the
        # reject-interleaved batch, and the view-change revert
        "executor_reqs_per_s": head["lane_reqs_per_s"],
        "lane_parallel_speedup": head["speedup"],
        "execute_ms_per_req_ab": {
            "serial": head["serial_ms_per_req"],
            "lanes": head["lane_ms_per_req"],
        },
    }


def pool25_backlog(provider=None, mesh=True):
    """BASELINE config 5: 25-node simulated pool, mixed read/write
    against a 50k-request backlog. Default provider is the shared TPU
    coalescing hub; provider="cpu" runs the IDENTICAL config on the
    OpenSSL per-node verifier — the CPU-verify comparison VERDICT r4
    asked for. The sim drains the backlog for a bounded wall budget
    (BENCH_P25_WALL seconds) and reports sustained ordered-write +
    served-read throughput."""
    from plenum_tpu.common.config import Config
    from plenum_tpu.common.constants import GET_TXN, NYM, TARGET_NYM, VERKEY
    from plenum_tpu.crypto.signer import SimpleSigner

    n_nodes = int(os.environ.get("BENCH_P25_NODES", "25"))
    backlog = int(os.environ.get("BENCH_P25_BACKLOG", "50000"))
    wall_budget = float(os.environ.get("BENCH_P25_WALL", "240"))
    # config 5 keeps its own batch size: headline tuning must not
    # silently reshape this workload across rounds
    batch = int(os.environ.get("BENCH_P25_BATCH", "500"))
    read_every = 5                       # 20% reads
    names = ["N%02d" % i for i in range(n_nodes)]

    # no client_reply_handler: the headline config skips Reply-payload
    # construction too, keeping the two pools comparable
    provider = provider or "tpu_hub"
    # fresh process seam hub: this config's lane-occupancy table must
    # cover THIS workload's launches, not everything since process start
    from plenum_tpu.observability.telemetry import (
        TelemetryHub, set_seam_hub)
    prev_seam_hub = set_seam_hub(TelemetryHub(name="p25-seams"))
    nodes, timer = make_sim_pool(names, provider, seed=25, batch=batch,
                                 mesh=mesh)
    reads_served = [0]

    signer = SimpleSigner(seed=b"\x26" * 32)
    writes, reads = [], []
    for i in range(backlog):
        if i % read_every == 4:
            reads.append({"identifier": signer.identifier, "reqId": i + 1,
                          "protocolVersion": 2,
                          "operation": {"type": GET_TXN, "ledgerId": 1,
                                        "data": 1 + (i % 50)}})
        else:
            dest = "p25-%08d" % i + "x" * 10
            req = {"identifier": signer.identifier, "reqId": i + 1,
                   "protocolVersion": 2,
                   "operation": {"type": NYM, TARGET_NYM: dest,
                                 VERKEY: "~" + dest[:22]}}
            req["signature"] = signer.sign(dict(req))
            writes.append(req)

    if provider == "tpu_hub":
        # warm the FUSED verification bucket (all nodes' chunks
        # coalesce in the hub) so XLA compile stays out of the window
        from plenum_tpu.crypto.fixtures import make_signed_batch
        from plenum_tpu.ops import ed25519_jax as edj
        wm_, ws_, wv_ = make_signed_batch(n_nodes * batch, seed=2)
        edj.verify_batch(wm_, ws_, wv_)

    t0 = time.perf_counter()
    deadline = t0 + wall_budget
    primary = nodes[0]
    ri_state = [0]
    # (wall_s, min_ordered) samples per chunk: when a run does NOT
    # drain, honest throughput is ordered/wall over the DRAINED PREFIX
    # — the window that ends at the last observed ordering progress —
    # not ordered over the whole wall budget (which silently averages
    # in any stalled tail and understates a saturated-but-slow pool,
    # or overstates one that collapsed early)
    progress = [(0.0, 0)]

    def serve_reads():
        # reads answer from any single node, no consensus round
        rchunk = reads[ri_state[0]:ri_state[0] + batch // read_every]
        ri_state[0] += len(rchunk)
        for r in rchunk:
            primary.process_client_request(dict(r), "p25-read")
            reads_served[0] += 1
        progress.append((time.perf_counter() - t0,
                         min(nd.domain_ledger.size for nd in nodes)))

    wchunks = [writes[i:i + batch] for i in range(0, len(writes), batch)]
    pipelined_intake(nodes, timer, wchunks, client_id="p25",
                     deadline=deadline, per_chunk=serve_reads)
    elapsed = time.perf_counter() - t0
    ordered = min(nd.domain_ledger.size for nd in nodes)
    progress.append((elapsed, ordered))
    drained = ordered >= len(writes)
    # drained prefix: the last sample where ordering still advanced
    prefix_t, prefix_n = elapsed, ordered
    for (t, n_ord) in reversed(progress):
        if n_ord < ordered:
            break
        prefix_t, prefix_n = t, n_ord
    rate_window = prefix_t if not drained and prefix_n else elapsed
    rate_count = prefix_n if not drained else ordered
    # the serving-tier numbers: ordered-request latency tail (merged
    # per-node telemetry histograms, wall-clock ms) + per-seam device
    # lane occupancy for THIS workload (the isolated seam hub)
    p50, p99, e2e_count = pool_latency_summary(nodes)
    lanes = seam_lane_table(set_seam_hub(prev_seam_hub))
    return {
        "nodes": n_nodes,
        "backlog": backlog,
        "wall_s": round(elapsed, 1),
        "ordered_writes": ordered,
        "reads_served": reads_served[0],
        "write_req_per_s": round(rate_count / max(1e-9, rate_window), 1),
        "mixed_req_per_s": round(
            (rate_count + reads_served[0]) / max(1e-9, rate_window), 1),
        "drained": drained,
        # seconds of wall with NO ordering progress at the end of a
        # partial drain (0.0 on a drained run) — the stall a naive
        # ordered/wall average would have hidden
        "stalled_tail_s": round(max(0.0, elapsed - rate_window), 1)
        if not drained else 0.0,
        "ordered_p50_ms": p50,
        "ordered_p99_ms": p99,
        "e2e_samples": e2e_count,
        "lane_occupancy": lanes,
    }


# the hard floor for the device-vs-host merkle ratios: the device path
# must never lose to the host floors it exists to beat (ROADMAP item 3
# acceptance; merkle_regression_gate)
MERKLE_RATIO_FLOOR = 1.0


def merkle_regression_gate(mk, floor=None):
    """HARD headline gate for the merkle hash race: vs_hashlib and
    vs_cpu_audit_paths must hold at or above MERKLE_RATIO_FLOOR.
    Returns the list of failures; main() records them in the headline
    and exits nonzero, so the r03→r05 shape of regression (ratios
    quietly sliding under 1.0 while a warn flag scrolled past) cannot
    ship again. BENCH_MERKLE_GATE=warn downgrades to warn-only for
    diagnostic runs on known-degraded hosts — the headline still
    records the failures. Pure function of the micro_merkle dict, so
    tier-1 gates the gate itself (tests/test_bench_gate.py) without
    running a bench."""
    floor = MERKLE_RATIO_FLOOR if floor is None else floor
    failures = []
    for field in ("vs_hashlib", "vs_cpu_audit_paths"):
        value = mk.get(field)
        if value is None:
            failures.append("%s missing from micro_merkle" % field)
        elif value < floor:
            failures.append("%s %.2f < required %.2f"
                            % (field, value, floor))
    return failures


def merkle_regression_flags(mk):
    """Best-prior tripwire for the merkle ratios (ROADMAP item 3):
    compare this run's device-vs-CPU hash ratios against the BEST
    prior recorded bench round (BENCH_r*.json tails in the repo root)
    and emit warn flags when they drop. This half stays warn-only
    (containers vary round to round); the absolute 1.0 floor is
    merkle_regression_gate and hard-fails the headline."""
    import glob
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    best = {}
    for path in sorted(glob.glob(os.path.join(here, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                tail = json.load(f).get("tail", "")
        except (OSError, ValueError):
            continue
        for field in ("vs_hashlib", "vs_cpu_audit_paths"):
            m = re.search(r'"%s":\s*([0-9.]+)' % field, tail)
            if m:
                value = float(m.group(1))
                if value > best.get(field, (0.0, ""))[0]:
                    best[field] = (value, os.path.basename(path))
    warns = []
    for field in ("vs_hashlib", "vs_cpu_audit_paths"):
        current = mk.get(field)
        prior = best.get(field)
        if current is None or prior is None:
            continue
        if current < prior[0]:
            warns.append("%s %.2f < best prior %.2f (%s)"
                         % (field, current, prior[0], prior[1]))
    return {
        "best_prior": {f: {"value": v, "round": r}
                       for f, (v, r) in sorted(best.items())},
        "warn": warns or None,
    }


PIPELINE_SPEEDUP_FLOOR = 1.5


def _pipeline_parity_roots(pipeline: bool, sanitizer=None):
    """One 4-node fixed-latency pool drained to completion with
    PIPELINE_ENABLED pinned — the tier-1 determinism harness shape
    (tests/test_pipeline.py), re-run inside the bench so the timing
    claim below is only ever made about a pipeline that just proved
    byte-equal roots on THIS box."""
    from plenum_tpu.common.config import Config
    from plenum_tpu.crypto.signer import SimpleSigner
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork

    names = ["Alpha", "Beta", "Gamma", "Delta"]
    timer = MockTimer()
    timer.set_time(SIM_EPOCH)
    # fixed latency: network timing must be mode-independent so any
    # root drift is a real pipeline bug, not a draw-stream artifact
    net = SimNetwork(timer, DefaultSimRandom(77),
                     min_latency=0.003, max_latency=0.003)
    conf = Config(Max3PCBatchSize=5, Max3PCBatchWait=0.2,
                  PIPELINE_ENABLED=pipeline,
                  SANITIZER_ENABLED=sanitizer)
    nodes = [Node(name, names, timer, net.create_peer(name), config=conf)
             for name in names]
    n_reqs = 12
    for req in make_requests(n_reqs, SimpleSigner(seed=b"\x71" * 32)):
        for nd in nodes:
            nd.process_client_request(dict(req), "parity-client")
    for _ in range(400):
        for nd in nodes:
            nd.service()
        timer.run_for(0.01)
        if all(nd.domain_ledger.size >= n_reqs for nd in nodes):
            break
    if not all(nd.domain_ledger.size == n_reqs for nd in nodes):
        return None
    from plenum_tpu.common.constants import NYM
    node = nodes[0]
    state = node.write_manager.request_handlers[NYM].state
    return (node.domain_ledger.root_hash, node.audit_ledger.root_hash,
            bytes(state.committedHeadHash).hex())


def pipeline_ab():
    """Clean-box 25-node pump A/B for the pipeline-parallel node
    runtime (ROADMAP item: break the one-thread ceiling): the IDENTICAL
    deterministic pool + ordering workload with PIPELINE_ENABLED on vs
    off. Parity comes FIRST — a 4-node full-drain A/B must produce
    byte-equal ledger roots before a single timing number is recorded;
    a fast wrong pipeline must never produce a headline. The timing
    side keeps the real OpenSSL verifier (signature work is one of the
    stages the worker thread absorbs) and pins the device seams to
    their host paths (identical on both sides, and on a shared box
    their dispatch-wait jitter is larger than the delta under test)."""
    out = {"nodes": int(os.environ.get("BENCH_PIPE_NODES", "25")),
           "reqs": int(os.environ.get("BENCH_PIPE_REQS", "800")),
           "cores": os.cpu_count() or 1}

    roots_on = _pipeline_parity_roots(pipeline=True)
    roots_off = _pipeline_parity_roots(pipeline=False)
    out["parity_ok"] = (roots_on is not None
                        and roots_on == roots_off)
    out["parity_roots"] = {"on": roots_on, "off": roots_off}
    if not out["parity_ok"]:
        # no timing claim about a divergent pipeline
        return out

    n_nodes = out["nodes"]
    n = out["reqs"]
    wall_budget = float(os.environ.get("BENCH_PIPE_WALL", "150"))
    batch = int(os.environ.get("BENCH_PIPE_BATCH", "200"))
    names = ["P%02d" % i for i in range(n_nodes)]
    from plenum_tpu.crypto.signer import SimpleSigner
    reqs = make_requests(n, SimpleSigner(seed=b"\x72" * 32))
    chunks = [reqs[i:i + batch] for i in range(0, n, batch)]

    def run_one(pipe: bool) -> dict:
        # clean box: device seams pinned to host paths (identical on
        # both sides; their dispatch jitter would swamp the deltas
        # under test) — what remains is the serial host money path the
        # pipeline attacks: parse, verify, count, execute
        nodes, timer = make_sim_pool(
            names, "cpu", seed=13, batch=batch,
            extra_conf=dict(SHA256_BACKEND="scalar",
                            FUSED_BATCH_DISPATCH=False,
                            STATE_DEVICE_ENGINE=False,
                            MESH_ENABLED=False,
                            PIPELINE_ENABLED=pipe))
        t0 = time.perf_counter()
        deadline = t0 + wall_budget
        pipelined_intake(nodes, timer, chunks, client_id="pipe",
                         deadline=deadline)
        while time.perf_counter() < deadline:
            for nd in nodes:
                nd.service()
            timer.run_for(0.01)
            if all(nd.domain_ledger.size >= n for nd in nodes):
                break
        elapsed = time.perf_counter() - t0
        ordered = min(nd.domain_ledger.size for nd in nodes)
        return {
            "req_per_s": round(ordered / max(1e-9, elapsed), 1),
            "ordered": ordered,
            "drained": ordered >= n,
        }

    # INTERLEAVED best-of-N: alternating runs expose both modes to
    # the same box-load profile
    rounds = int(os.environ.get("BENCH_PIPE_ROUNDS", "2"))
    for _ in range(rounds):
        for label, pipe in (("on", True), ("off", False)):
            run = run_one(pipe)
            best = out.get(label)
            if best is None or run["req_per_s"] > best["req_per_s"]:
                out[label] = run
    if out["off"]["req_per_s"]:
        out["pipeline_speedup"] = round(
            out["on"]["req_per_s"] / out["off"]["req_per_s"], 2)
    return out


def pipeline_regression_gate(pab, cores=None, env=None):
    """Hard gate for the pipeline A/B. PARITY IS HARD ALWAYS — even
    under the BENCH_PIPELINE_GATE=warn override, divergent roots fail
    the run: a fast wrong pipeline must never ship. The ≥1.5x speedup
    floor is hard only on hosts with more than 2 cores (below that
    there is no headroom for a worker thread to win — the serial
    fallback IS the right configuration), and it alone is downgraded
    by BENCH_PIPELINE_GATE=warn for known-noisy shared boxes."""
    if not isinstance(pab, dict):
        return ["pipeline_ab produced no result dict"]
    failures = []
    if pab.get("parity_ok") is not True:
        failures.append(
            "pipeline parity_ok %r — pipelined pool roots must be "
            "byte-equal to the serial pool's before any timing claim"
            % (pab.get("parity_ok"),))
    cores = (os.cpu_count() or 1) if cores is None else cores
    env = os.environ if env is None else env
    enforce_speed = cores > 2 and env.get("BENCH_PIPELINE_GATE") != "warn"
    speed = pab.get("pipeline_speedup")
    if speed is None:
        if enforce_speed and pab.get("parity_ok") is True:
            failures.append("pipeline_speedup missing from pipeline_ab")
    elif speed < PIPELINE_SPEEDUP_FLOOR and enforce_speed:
        failures.append(
            "pipeline_speedup %.2f < required %.2fx (%d cores; "
            "BENCH_PIPELINE_GATE=warn downgrades this check only)"
            % (speed, PIPELINE_SPEEDUP_FLOOR, cores))
    return failures


def sanitizer_overhead():
    """Ownership-sanitizer overhead gate: the IDENTICAL 25-node
    pipelined pool + ordering workload with SANITIZER_ENABLED on vs
    off — the telemetry_overhead methodology (interleaved best-of-2)
    on the pipeline_ab clean-box pool. The suite runs with the
    sanitizer armed on every sim-pool fixture, so this is the number
    that must stay under 2% (sanitizer_overhead_gate) for suite-wide
    arming to be honest. Parity comes FIRST: a 4-node pipelined
    full-drain with pins+tokens armed must produce byte-equal ledger,
    audit and state roots against the unsanitized pool before a single
    timing number is recorded — a guard that perturbs consensus must
    never produce a headline."""
    out = {"nodes": int(os.environ.get(
               "BENCH_SAN_NODES", os.environ.get("BENCH_PIPE_NODES",
                                                 "25"))),
           "reqs": int(os.environ.get(
               "BENCH_SAN_REQS", os.environ.get("BENCH_PIPE_REQS",
                                                "800")))}

    roots_on = _pipeline_parity_roots(pipeline=True, sanitizer=True)
    roots_off = _pipeline_parity_roots(pipeline=True, sanitizer=False)
    out["parity_ok"] = (roots_on is not None and roots_on == roots_off)
    out["parity_roots"] = {"on": roots_on, "off": roots_off}
    if not out["parity_ok"]:
        return out

    n_nodes = out["nodes"]
    n = out["reqs"]
    wall_budget = float(os.environ.get("BENCH_SAN_WALL", "150"))
    batch = int(os.environ.get("BENCH_PIPE_BATCH", "200"))
    names = ["S%02d" % i for i in range(n_nodes)]
    from plenum_tpu.crypto.signer import SimpleSigner
    reqs = make_requests(n, SimpleSigner(seed=b"\x73" * 32))
    chunks = [reqs[i:i + batch] for i in range(0, n, batch)]

    def run_one(sanitize: bool) -> dict:
        # same clean box as pipeline_ab — both sides pipelined, so the
        # delta is exactly the pin checks + handoff tokens on the
        # 3PC/queue hot path
        nodes, timer = make_sim_pool(
            names, "cpu", seed=13, batch=batch,
            extra_conf=dict(SHA256_BACKEND="scalar",
                            FUSED_BATCH_DISPATCH=False,
                            STATE_DEVICE_ENGINE=False,
                            MESH_ENABLED=False,
                            PIPELINE_ENABLED=True,
                            SANITIZER_ENABLED=sanitize))
        t0 = time.perf_counter()
        deadline = t0 + wall_budget
        pipelined_intake(nodes, timer, chunks, client_id="san",
                         deadline=deadline)
        while time.perf_counter() < deadline:
            for nd in nodes:
                nd.service()
            timer.run_for(0.01)
            if all(nd.domain_ledger.size >= n for nd in nodes):
                break
        elapsed = time.perf_counter() - t0
        ordered = min(nd.domain_ledger.size for nd in nodes)
        return {
            "req_per_s": round(ordered / max(1e-9, elapsed), 1),
            "ordered": ordered,
            "drained": ordered >= n,
        }

    rounds = int(os.environ.get("BENCH_SAN_ROUNDS", "2"))
    for _ in range(rounds):
        for label, sanitize in (("on", True), ("off", False)):
            run = run_one(sanitize)
            best = out.get(label)
            if best is None or run["req_per_s"] > best["req_per_s"]:
                out[label] = run
    off_rate = out["off"]["req_per_s"]
    if off_rate:
        # positive = the sanitizer costs throughput; slightly negative
        # = run-to-run jitter on a loaded box
        out["overhead_pct"] = round(
            100.0 * (1.0 - out["on"]["req_per_s"] / off_rate), 2)
    return out


# the suite-wide-arming claim's hard ceiling: region pins + handoff
# tokens must cost less than this on the identical-pool A/B
SANITIZER_OVERHEAD_MAX_PCT = 2.0


def sanitizer_overhead_gate(result, ceiling=None, env=None):
    """HARD gate for the ownership sanitizer's always-armed-in-tests
    claim. PARITY IS HARD ALWAYS — even under BENCH_SANITIZER_GATE=warn
    divergent roots fail the run: a guard that changes what the pool
    orders is a bug, not overhead. The <2% overhead ceiling alone is
    downgraded by BENCH_SANITIZER_GATE=warn for known-noisy shared
    boxes. Pure function of the sanitizer_overhead dict (tier-1 gates
    the gate in tests/test_bench_gate.py); → list of failures."""
    if not isinstance(result, dict):
        return ["sanitizer_overhead produced no result dict"]
    failures = []
    if result.get("parity_ok") is not True:
        failures.append(
            "sanitizer parity_ok %r — sanitized pool roots must be "
            "byte-equal to the unsanitized pool's before any timing "
            "claim" % (result.get("parity_ok"),))
    env = os.environ if env is None else env
    enforce = env.get("BENCH_SANITIZER_GATE") != "warn"
    ceiling = SANITIZER_OVERHEAD_MAX_PCT if ceiling is None else ceiling
    value = result.get("overhead_pct")
    if value is None:
        if enforce and result.get("parity_ok") is True:
            failures.append(
                "overhead_pct missing from sanitizer_overhead")
    elif value >= ceiling and enforce:
        failures.append(
            "sanitizer_overhead_pct %.2f >= allowed %.2f "
            "(BENCH_SANITIZER_GATE=warn downgrades this check only)"
            % (value, ceiling))
    return failures


def host_ms_regression_flags(current_total, current_execute=None):
    """Best-prior warn-tripwire for host_ms_per_ordered_req.total AND
    its execute stage (same convention as merkle_regression: warn-only
    — containers vary round to round; the wire A/B and lane A/B ratios
    carry the gated claims). Scans prior BENCH_r*.json headline tails
    for the lowest recorded values and flags when this round costs
    more host-ms per ordered request — total or in the execute stage
    the conflict-lane executor owns."""
    import glob
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    fields = {"total": current_total, "execute": current_execute}
    best = {}
    for path in sorted(glob.glob(os.path.join(here, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                tail = json.load(f).get("tail", "")
        except (OSError, ValueError):
            continue
        for field in fields:
            m = re.search(r'"host_ms_per_ordered_req":\s*\{[^{}]*'
                          r'"%s":\s*([0-9.]+)' % field, tail)
            if m:
                value = float(m.group(1))
                if field not in best or value < best[field][0]:
                    best[field] = (value, os.path.basename(path))
    warns = []
    for field, current in fields.items():
        prior = best.get(field)
        if current is not None and prior is not None \
                and current > prior[0]:
            warns.append("host_ms_per_ordered_req.%s %.3f > best prior "
                         "%.3f (%s)" % (field, current, prior[0],
                                        prior[1]))
    return {
        "best_prior": {f: {"value": v, "round": r}
                       for f, (v, r) in sorted(best.items())} or None,
        "warn": warns or None,
    }


def pool25_both():
    """TPU hub vs CPU verify on the identical 25-node config; the CPU
    side gets the same wall budget, so not-drained shows up as a lower
    sustained rate rather than a disqualified run. On a multi-chip host
    the hub config also runs mesh-off so the mesh's contribution to the
    fused-launch rate is measured, not assumed (one chip: on/off are
    the same passthrough path, so the off run is skipped)."""
    from plenum_tpu.ops import mesh as mesh_mod
    tpu = pool25_backlog("tpu_hub")
    mesh = mesh_mod.get_mesh()
    tpu["mesh_devices"] = mesh.n_devices
    if mesh.n_devices > 1:
        off = pool25_backlog("tpu_hub", mesh=False)
        tpu["mesh_off_write_req_per_s"] = off["write_req_per_s"]
        tpu["mesh_speedup"] = round(
            tpu["write_req_per_s"] / max(1e-9, off["write_req_per_s"]), 2)
    cpu = pool25_backlog("cpu")
    tpu["cpu_write_req_per_s"] = cpu["write_req_per_s"]
    tpu["cpu_mixed_req_per_s"] = cpu["mixed_req_per_s"]
    tpu["cpu_drained"] = cpu["drained"]
    tpu["cpu_stalled_tail_s"] = cpu.get("stalled_tail_s", 0.0)
    tpu["vs_cpu"] = round(
        tpu["write_req_per_s"] / max(1e-9, cpu["write_req_per_s"]), 2)
    # the ratio only compares like with like when BOTH sides finished
    # the identical workload; a partial CPU drain makes vs_cpu a
    # sustained-rate comparison over different prefixes — still
    # reported (both sides now use honest drained-prefix rates), but
    # flagged so the headline can't read it as a completed-run ratio
    tpu["vs_cpu_comparable"] = bool(tpu["drained"] and cpu["drained"])
    return tpu


def gateway_open_loop():
    """Gateway-tier config: OPEN-LOOP Poisson arrivals (the arrival
    process never waits for the pool — sustained offered load, unlike
    the closed-loop backlog drains above) through the client-facing
    gateway into a BLS-enabled 4-node sim pool. Mixed read/write with
    hot-key skew: hot GET_NYMs exercise the signed-read cache (replay
    of proof-carrying answers, invalidated as new signed roots land),
    a retry fraction exercises dedup, a touch-update fraction gives
    the lane pre-planner real write conflicts, and the backlog signal
    feeds admission control live. Tail latency (p50/p99/p999) comes
    from the gateway telemetry hub's log-linear histograms —
    gateway_gate() hard-gates the headline fields."""
    import msgpack
    import random as _random
    from plenum_tpu.bootstrap import node_genesis_txn
    from plenum_tpu.client.client import PoolClient
    from plenum_tpu.client.wallet import Wallet
    from plenum_tpu.common.config import Config
    from plenum_tpu.common.constants import NYM, TARGET_NYM, VERKEY
    from plenum_tpu.common.request import Request
    from plenum_tpu.common.serializers import flat_wire as fw
    from plenum_tpu.crypto.batch_verifier import CoalescingVerifierHub
    from plenum_tpu.crypto.bls import (
        BlsCryptoSignerPlenum, BlsCryptoVerifierPlenum)
    from plenum_tpu.crypto.signer import SimpleSigner
    from plenum_tpu.gateway import Gateway
    from plenum_tpu.observability.telemetry import TM, TelemetryHub
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork

    n_nodes = int(os.environ.get("BENCH_GW_NODES", "4"))
    rate = float(os.environ.get("BENCH_GW_RATE", "600"))     # req/s sim
    secs = float(os.environ.get("BENCH_GW_SECS", "8"))       # sim s
    read_pct = float(os.environ.get("BENCH_GW_READ_PCT", "0.3"))
    dup_pct = 0.02          # client retries the dedup window absorbs
    touch_pct = 0.10        # of writes: updates to a hot dest (lanes)
    hot_n = 16              # hot-key set for reads + touch updates
    wall_budget = float(os.environ.get("BENCH_GW_WALL", "150"))
    tick_dt = 0.05

    names = ["G%02d" % i for i in range(n_nodes)]
    bls_signers = {}
    for i, name in enumerate(names):
        s, _ = BlsCryptoSignerPlenum.generate(bytes([0x30 + i]) * 32)
        bls_signers[name] = s
    timer = MockTimer()
    timer.set_time(SIM_EPOCH)
    net = SimNetwork(timer, DefaultSimRandom(77), min_latency=0.001,
                     max_latency=0.005)
    conf = Config(Max3PCBatchSize=200, Max3PCBatchWait=0.05,
                  CHK_FREQ=10, LOG_SIZE=30, HEARTBEAT_FREQ=10 ** 6,
                  GATEWAY_BACKLOG_HIGH=float(os.environ.get(
                      "BENCH_GW_BACKLOG_HIGH", "150")),
                  GATEWAY_BACKLOG_LOW=float(os.environ.get(
                      "BENCH_GW_BACKLOG_LOW", "75")),
                  GATEWAY_BACKLOG_HARD=float(os.environ.get(
                      "BENCH_GW_BACKLOG_HARD", "1000")))
    genesis = []
    for i, name in enumerate(names):
        genesis.append(node_genesis_txn(
            name, verkey="v%d" % i, node_ip="127.0.0.1", node_port=1,
            client_ip="127.0.0.1", client_port=2,
            steward_nym="S%d" % i, bls_key=bls_signers[name].pk))
    nodes = [Node(name, names, timer, net.create_peer(name),
                  config=conf, bls_signer=bls_signers[name],
                  genesis_txns=genesis)
             for name in names]
    primary = nodes[0]

    # ---- seed the hot-key set so reads and touch updates resolve
    author = SimpleSigner(seed=b"\x71" * 32)
    hot = ["gwhot-%04d" % i + "h" * 12 for i in range(hot_n)]
    seed_reqs = []
    for i, dest in enumerate(hot):
        req = {"identifier": author.identifier, "reqId": i + 1,
               "protocolVersion": 2,
               "operation": {"type": NYM, TARGET_NYM: dest}}
        req["signature"] = author.sign(dict(req))
        seed_reqs.append(req)
    for n in nodes:
        n.process_client_batch([(dict(r), "seed") for r in seed_reqs])
    for _ in range(200):
        for n in nodes:
            n.service()
        timer.run_for(tick_dt)
        if all(n.domain_ledger.size >= hot_n for n in nodes):
            break
    base_size = min(n.domain_ledger.size for n in nodes)

    # ---- open-loop arrival schedule (relative sim seconds)
    rng = _random.Random(4242)
    sched = []                       # (t_rel, request dict)
    req_id = 1000
    write_history = []
    t = 0.0
    while True:
        t += rng.expovariate(rate)
        if t >= secs:
            break
        req_id += 1
        draw = rng.random()
        if write_history and draw < dup_pct:
            sched.append((t, rng.choice(write_history)))   # a retry
            continue
        if draw < dup_pct + read_pct:
            # hot-skewed read: 80% hit the hot set
            if rng.random() < 0.8:
                dest = hot[min(int(rng.expovariate(0.5)), hot_n - 1)]
            elif write_history:
                dest = rng.choice(write_history)[
                    "operation"][TARGET_NYM]
            else:
                dest = hot[0]
            sched.append((t, {"identifier": author.identifier,
                              "reqId": req_id,
                              "operation": {"type": "105",
                                            TARGET_NYM: dest}}))
            continue
        if rng.random() < touch_pct:
            dest = hot[rng.randrange(hot_n)]   # conflicting update
            op = {"type": NYM, TARGET_NYM: dest}
        else:
            dest = "gw-%06d" % req_id + "u" * 10
            op = {"type": NYM, TARGET_NYM: dest, VERKEY: "~" + dest[:22]}
        req = {"identifier": author.identifier, "reqId": req_id,
               "protocolVersion": 2, "operation": op}
        req["signature"] = author.sign(dict(req))
        sched.append((t, req))
        write_history.append(req)

    # ---- gateway wiring: standalone coalescing hub for the
    # pre-screen, proof checking through the REAL PoolClient path
    gw_hub = TelemetryHub(name="gateway")
    verifier_kind = os.environ.get("BENCH_GW_VERIFIER", "tpu_hub")
    gw_verifier = CoalescingVerifierHub(telemetry=gw_hub) \
        if verifier_kind == "tpu_hub" else None
    if gw_verifier is not None:
        from plenum_tpu.crypto.fixtures import make_signed_batch
        from plenum_tpu.ops import ed25519_jax as edj
        for bucket in (32, 64, 128):
            wm, ws, wv = make_signed_batch(bucket, seed=3)
            edj.verify_batch(wm, ws, wv)
    wallet = Wallet()
    wallet.add_identifier(signer=SimpleSigner(seed=b"\x72" * 32))
    proof_client = PoolClient(
        wallet, names, send_fn=lambda n, m: None,
        bls_verifier=BlsCryptoVerifierPlenum(),
        bls_key_provider=lambda n: bls_signers[n].pk)

    def serve_read(msg, _client):
        try:
            return primary.read_manager.get_result(
                Request.from_dict(dict(msg)))
        except Exception:
            return None

    outbound = []
    gw = Gateway(forward_writes=outbound.append, serve_read=serve_read,
                 check_proof=proof_client.check_proof_dict,
                 verifier=gw_verifier, config=conf, telemetry=gw_hub)

    # ---- the open loop
    t0 = time.perf_counter()
    stats = {"arrivals": 0, "reads_arrived": 0, "writes_arrived": 0,
             "admitted_writes": 0, "shed_reads": 0, "shed_writes": 0,
             "cache_hits": 0, "sig_rejects": 0}
    levels_seen = set()
    pool_p99 = None
    now_rel = 0.0
    idx = 0
    tick_i = 0
    completed = True
    while True:
        if time.perf_counter() - t0 > wall_budget:
            completed = False
            break
        ordered = min(n.domain_ledger.size for n in nodes) - base_size
        if idx >= len(sched) and ordered >= stats["admitted_writes"]:
            break
        if idx >= len(sched) and tick_i > len(sched) + 2000:
            completed = False
            break
        now_rel += tick_dt
        tick_i += 1
        due = []
        while idx < len(sched) and sched[idx][0] <= now_rel:
            due.append(sched[idx])
            idx += 1
        envs = []
        for lo in range(0, len(due), 64):
            group = due[lo:lo + 64]
            blobs = [msgpack.packb(m, use_bin_type=True)
                     for _, m in group]
            clients = ["c%d" % (i & 7) for i in range(len(group))]
            envs.append((fw.encode_propagate_envelope(blobs, clients),
                         "lb-%d" % ((lo >> 6) & 3), group[0][0]))
        for _, msg in due:
            stats["arrivals"] += 1
            if msg["operation"]["type"] == "105":
                stats["reads_arrived"] += 1
            else:
                stats["writes_arrived"] += 1
        backlog = stats["admitted_writes"] - ordered
        tick = gw.pump(envs, now=now_rel, backlog=backlog,
                       pool_p99_ms=pool_p99)
        levels_seen.add(tick.level)
        stats["admitted_writes"] += len(tick.admitted_writes)
        stats["shed_reads"] += tick.shed_reads
        stats["shed_writes"] += tick.shed_writes
        stats["cache_hits"] += tick.cache_hits
        stats["sig_rejects"] += tick.sig_rejects
        for env in outbound:
            for n in nodes:
                n.process_gateway_envelope(env, "gw-front")
        del outbound[:]
        for n in nodes:
            n.service()
        timer.run_for(tick_dt)
        if tick_i % 20 == 0:
            _p50, pool_p99, _cnt = pool_latency_summary(nodes)
    elapsed = time.perf_counter() - t0
    ordered = min(n.domain_ledger.size for n in nodes) - base_size

    snap = gw_hub.snapshot()
    e2e = (snap.get("histograms") or {}).get(TM.GATEWAY_E2E_MS) or {}
    dedup_hits = (snap.get("counters") or {}).get(
        TM.GATEWAY_DEDUP_HITS, 0)
    p50_pool, p99_pool, _ = pool_latency_summary(nodes)
    shed = stats["shed_reads"] + stats["shed_writes"]
    return {
        "nodes": n_nodes,
        "offered_rate_per_s": rate,
        "sim_secs": secs,
        "wall_s": round(elapsed, 1),
        "completed": completed,
        "arrivals": stats["arrivals"],
        "reads_arrived": stats["reads_arrived"],
        "writes_arrived": stats["writes_arrived"],
        "admitted_writes": stats["admitted_writes"],
        "ordered_writes": ordered,
        "shed_reads": stats["shed_reads"],
        "shed_writes": stats["shed_writes"],
        "cache_hits": stats["cache_hits"],
        "dedup_hits": dedup_hits,
        "sig_rejects": stats["sig_rejects"],
        "shed_levels_seen": sorted(levels_seen),
        # headline fields (gateway_gate hard-gates their presence)
        "gateway_p50_ms": e2e.get("p50"),
        "gateway_p99_ms": e2e.get("p99"),
        "gateway_p999_ms": e2e.get("p999"),
        "e2e_samples": e2e.get("count", 0),
        "gateway_shed_pct": round(
            100.0 * shed / max(1, stats["arrivals"]), 2),
        "gateway_cache_hit_pct": round(
            100.0 * stats["cache_hits"]
            / max(1, stats["reads_arrived"]), 2),
        "ordered_p50_ms": p50_pool,
        "ordered_p99_ms": p99_pool,
    }


def gate_enforced(env_var):
    """True when the named gate should hard-fail the run — the
    operator downgrades it to warn-only with <env_var>=warn. Pure
    read of the environment so tier-1 can pin the override contract."""
    return os.environ.get(env_var) != "warn"


def gateway_gate(result):
    """HARD headline gate for the gateway tier: the three headline
    fields must be present (p99 additionally backed by p999 and real
    samples), the percentage fields must be sane, and the admission
    ladder's ordering must hold in the observed run — writes shed
    implies reads were already being shed (reads degrade FIRST).
    Returns the list of failures; main() records them in the headline
    and exits nonzero unless BENCH_GATEWAY_GATE=warn. Pure function of
    the gateway_open_loop dict, so tier-1 gates the gate itself
    (tests/test_bench_gate.py) without running a bench."""
    if not isinstance(result, dict):
        return ["gateway_open_loop produced no result dict"]
    failures = []
    for field in ("gateway_p99_ms", "gateway_p999_ms",
                  "gateway_shed_pct", "gateway_cache_hit_pct"):
        if result.get(field) is None:
            failures.append("%s missing from gateway_open_loop" % field)
    samples = result.get("e2e_samples") or 0
    p99 = result.get("gateway_p99_ms")
    if samples and isinstance(p99, (int, float)) and p99 < 0:
        failures.append("gateway_p99_ms %.3f negative with %d samples"
                        % (p99, samples))
    for field in ("gateway_shed_pct", "gateway_cache_hit_pct"):
        value = result.get(field)
        if isinstance(value, (int, float)) \
                and not 0.0 <= value <= 100.0:
            failures.append("%s %.2f outside [0, 100]" % (field, value))
    if (result.get("shed_writes") or 0) > 0 \
            and (result.get("reads_arrived") or 0) > 0 \
            and (result.get("shed_reads") or 0) == 0:
        failures.append(
            "writes were shed while no read was shed — the admission "
            "ladder must degrade reads before writes")
    return failures


def bench_recovery():
    """Recovery SLO config (ROADMAP item 4): a 25-node sim pool
    measures (a) failover latency — primary goes silent under load →
    every honest node completes the view change AND orders again — and
    (b) catchup-completion latency for a lagging node syncing under a
    lying seeder while another peer churns (leaves + rejoins) mid-
    catchup. Latencies are SIM seconds on the MockTimer: deterministic
    and host-load independent, which is what makes them gateable.
    Both are checked against the Config SLOs; the pool runs with the
    flight recorder ON, so a violation auto-dumps a merged timeline
    whose filename embeds the measured latency and the threshold, and
    the leecher backoff + view-change escalation events are counted
    into the report from the same buffers."""
    from plenum_tpu.common.config import Config
    from plenum_tpu.common.constants import NYM, TARGET_NYM, VERKEY
    from plenum_tpu.crypto.signer import SimpleSigner
    from plenum_tpu.runtime.sim_random import DefaultSimRandom
    from plenum_tpu.server.node import Node
    from plenum_tpu.testing.mock_timer import MockTimer
    from plenum_tpu.testing.sim_network import SimNetwork
    from plenum_tpu.testing.adversary import (
        AdversaryController, LivenessViolation, LyingCatchupSeeder,
        Scenario, SilentNode, SLOViolation)

    n_nodes = int(os.environ.get("BENCH_REC_NODES", "25"))
    failover_slo = float(os.environ.get(
        "BENCH_REC_FAILOVER_SLO", str(Config.RECOVERY_FAILOVER_SLO_S)))
    catchup_slo = float(os.environ.get(
        "BENCH_REC_CATCHUP_SLO", str(Config.RECOVERY_CATCHUP_SLO_S)))

    # isolated seam hub: recovery's lane table covers THIS scenario
    from plenum_tpu.observability.telemetry import (
        TelemetryHub, set_seam_hub)
    prev_seam_hub = set_seam_hub(TelemetryHub(name="recovery-seams"))

    timer = MockTimer()
    timer.set_time(SIM_EPOCH)
    net = SimNetwork(timer, DefaultSimRandom(77), min_latency=0.001,
                     max_latency=0.01)
    conf = Config(Max3PCBatchSize=5, Max3PCBatchWait=0.2, CHK_FREQ=5,
                  LOG_SIZE=15, ToleratePrimaryDisconnection=4,
                  NEW_VIEW_TIMEOUT=8, STATE_FRESHNESS_UPDATE_INTERVAL=3,
                  CATCHUP_TXN_TIMEOUT=2, TRACING_ENABLED=True,
                  HEARTBEAT_FREQ=10 ** 6, VERIFIER_PROVIDER="cpu",
                  MESH_ENABLED=False)
    names = ["B%02d" % i for i in range(n_nodes)]
    nodes = [Node(n, names, timer, net.create_peer(n), config=conf)
             for n in names]

    def submit(to_nodes, i, req_id):
        signer = SimpleSigner(seed=bytes([0x41 + i % 60]) * 32)
        req = {"identifier": signer.identifier, "reqId": req_id,
               "protocolVersion": 2,
               "operation": {"type": NYM,
                             TARGET_NYM: signer.identifier,
                             VERKEY: signer.verkey}}
        req["signature"] = signer.sign(dict(req))
        for nd in to_nodes:
            nd.process_client_request(dict(req), "bench-recovery")

    adv = AdversaryController(timer, seed=7)
    adv.set_pool(nodes)
    out = {"nodes": n_nodes, "unit": "sim-seconds",
           "failover_slo_s": failover_slo, "catchup_slo_s": catchup_slo}
    violations = []

    def gated_measure(scn, name, cond, within, slo):
        """Measure + SLO-gate one recovery; a mild SLO miss AND a
        catastrophic liveness failure both land in `violations` (with
        a dumped timeline) instead of killing the bench run — the
        report must come out strictly MORE complete the worse things
        get, never less. → latency or None."""
        try:
            val = scn.measure(cond, within=within, desc=name)
        except LivenessViolation as e:
            path = scn.dump_trace(tag="liveness_%s" % name)
            violations.append("%s%s" % (
                e, " [flight recorder: %s]" % path if path else ""))
            return None
        try:
            scn.check_slo(name, val, slo)
        except SLOViolation as e:
            violations.append(str(e))
        return val

    # ---- failover: the primary goes silent under load
    primary = next(nd for nd in nodes if nd.replica.data.is_primary)
    sc = Scenario(timer, nodes, adversary=adv,
                  honest=[nd.name for nd in nodes if nd is not primary])
    submit(nodes, 0, 1)
    sc.run(3)
    behavior = SilentNode()
    adv.corrupt(primary, behavior)
    honest = sc.honest
    submit(honest, 1, 2)
    base = {nd.name: nd.last_ordered[1] for nd in honest}

    def ordering_resumed():
        return all(nd.view_no >= 1
                   and not nd.replica.data.waiting_for_new_view
                   and nd.last_ordered[1] > base[nd.name]
                   for nd in honest)

    failover_s = gated_measure(sc, "failover", ordering_resumed,
                               4 * failover_slo + 60, failover_slo)
    out["failover_s"] = round(failover_s, 2) \
        if failover_s is not None else None
    # crashed primary restarts: release + catchup back into the pool
    adv.release(primary, behavior)
    primary.start_catchup()
    try:
        sc.run_until(lambda: not primary.leecher.in_progress, 120,
                     "ex-primary rejoins via catchup")
    except LivenessViolation as e:
        violations.append(str(e))

    # ---- catchup under lying seeders + membership churn: one seeder
    # GARBLES chunks (convicted by audit-path verification, then
    # excluded), one STALLS silently (only retry backoff + rotation
    # can route around it), and a third peer churns out/in while the
    # laggard syncs
    laggard = nodes[-1]
    net.disconnect(laggard.name)
    live = [nd for nd in nodes if nd is not laggard]
    sc_live = Scenario(timer, live, adversary=adv,
                       honest=[nd.name for nd in live])
    for i in range(4):
        submit(live, 2 + i, 3 + i)
        sc_live.run(3)
    non_primaries = [nd for nd in live
                     if not nd.replica.data.is_primary]
    liar, staller, churner = non_primaries[:3]
    adv.corrupt(liar, LyingCatchupSeeder())
    adv.corrupt(staller, LyingCatchupSeeder(
        lie_cons_proofs=False, garble_reps=False, stall_every=1))
    net.reconnect(laggard.name)
    laggard.start_catchup()
    # churn racing the catchup: a peer drops and later rejoins
    adv.at(0.2, lambda: net.disconnect(churner.name), "churner leaves")
    adv.at(3.0, lambda: net.reconnect(churner.name), "churner rejoins")
    target = live[0]
    sc2 = Scenario(timer, nodes, adversary=adv,
                   honest=[nd.name for nd in nodes
                           if nd not in (liar, staller, churner)])

    def caught_up():
        return (not laggard.leecher.in_progress
                and laggard.domain_ledger.size
                == target.domain_ledger.size)

    catchup_s = gated_measure(sc2, "catchup", caught_up,
                              4 * catchup_slo + 60, catchup_slo)
    out["catchup_s"] = round(catchup_s, 2) \
        if catchup_s is not None else None
    out["catchup_bad_peers"] = sorted(laggard.leecher.bad_peers)

    # recovery observables straight from the flight-recorder buffers:
    # the backoff/escalation machinery must be VISIBLE, not assumed —
    # one pass per node (spans() copies the whole ring under a lock)
    from collections import Counter
    counts = Counter()
    for nd in nodes:
        counts.update(rec[1] for rec in nd.tracer.spans())
    out["trace_events"] = {name: counts[name] for name in (
        "catchup_start", "catchup_done", "catchup_retry",
        "catchup_bad_peer", "view_change_start", "view_change_done",
        "vc_timeout_escalated")}
    # the counts above come from per-node ring buffers shared with the
    # (much chattier) 3PC/device lanes: if any ring wrapped, early
    # recovery instants were evicted and the counts undercount — flag
    # it rather than report a silently-degraded number
    wrapped = [nd.name for nd in nodes
               if nd.tracer.stats().get("dropped", 0) > 0]
    if wrapped:
        out["trace_events"]["ring_wrapped_nodes"] = len(wrapped)
    # recovery's serving numbers ride along: ordered-latency tail under
    # failover/churn (what clients actually experienced) + the seam
    # lane table for the scenario's device work
    p50, p99, e2e_count = pool_latency_summary(nodes)
    out["ordered_p50_ms"] = p50
    out["ordered_p99_ms"] = p99
    out["e2e_samples"] = e2e_count
    out["lane_occupancy"] = seam_lane_table(set_seam_hub(prev_seam_hub))
    out["slo_ok"] = not violations
    if violations:
        out["violations"] = violations
    return out


def micro_mesh():
    """Device-mesh dispatch layer (ops/mesh.py): the single-device
    overhead gate, plus a per-device-count weak-scaling sweep through
    the REAL dispatcher when this host has more than one chip (the
    8-virtual-device CPU sweep lives in the MULTICHIP harness,
    __graft_entry__.dryrun_multichip).

    The overhead gate compares the production verify path with the mesh
    consulted-and-passing-through against the mesh disabled outright —
    the wiring a single-chip host pays on every dispatch. Must stay
    under 5% (it is one predicate + a counter bump; anything more means
    the seam regressed)."""
    import numpy as np
    from plenum_tpu.crypto.fixtures import make_signed_batch
    from plenum_tpu.ops import ed25519_jax as edj
    from plenum_tpu.ops import mesh as mesh_mod

    m = mesh_mod.get_mesh()
    out = {"devices": m.n_devices,
           "platform": mesh_mod.probe_platform(),
           "shard_min": m.shard_min}
    batch = min(MICRO_BATCH, 8192)
    msgs, sigs, vks = make_signed_batch(batch, seed=11, unique=256,
                                        msg_prefix=b"mesh")
    prior = (m.enabled, m.shard_min, m.max_devices, m.cpu_shard)
    try:
        # passthrough (mesh consulted, gate declines) vs mesh disabled:
        # interleaved best-of so box-load drift hits both sides
        mesh_mod.configure(enabled=True, shard_min=batch + 1)
        edj.verify_batch(msgs, sigs, vks)  # warm/compile
        on_times, off_times = [], []
        for _ in range(3):
            mesh_mod.configure(enabled=True)
            t0 = time.perf_counter()
            edj.verify_batch(msgs, sigs, vks)
            on_times.append(time.perf_counter() - t0)
            mesh_mod.configure(enabled=False)
            t0 = time.perf_counter()
            edj.verify_batch(msgs, sigs, vks)
            off_times.append(time.perf_counter() - t0)
        overhead = 100.0 * (min(on_times) / min(off_times) - 1.0)
        out["single_device_overhead_pct"] = round(overhead, 2)
        out["overhead_gate_pct"] = 5.0
        out["within_gate"] = overhead < 5.0

        if m.n_devices > 1:
            # weak scaling through verify_batch_async (per-device batch
            # constant): efficiency(d) = rate(d) / (d * rate(1)). Its
            # own fixture batch — per_dev * n_devices can exceed the
            # overhead batch, and a short slice would silently shrink
            # the launch while n still claimed the full size
            n_dev_all = m.n_devices
            per_dev = max(512, batch // n_dev_all)
            wm, ws, wv = make_signed_batch(per_dev * n_dev_all, seed=11,
                                           unique=256, msg_prefix=b"mesh")
            sweep = {}
            d = 1
            while d <= n_dev_all:
                # cpu_shard: the sweep exists to measure the SHARDED
                # dispatch path; on a virtual-CPU-device host the
                # production gate would silently turn every point into
                # the same passthrough
                mesh_mod.configure(enabled=True, max_devices=d,
                                   shard_min=1, cpu_shard=True)
                m.reset_devices()
                n = per_dev * d
                sm, ss, sv = wm[:n], ws[:n], wv[:n]
                edj.verify_batch(sm, ss, sv)  # warm/compile

                def run(sm=sm, ss=ss, sv=sv):
                    pend = []
                    for _ in range(4):
                        pend.append(edj.verify_batch_async(sm, ss, sv))
                        if len(pend) > 2:
                            np.asarray(pend.pop(0)[0])
                    for h in pend:
                        np.asarray(h[0])

                t = best_time(run, runs=3)
                sweep[str(d)] = {"batch": n,
                                 "verify_per_s": round(4 * n / t, 1)}
                d *= 2
            r1 = sweep["1"]["verify_per_s"]
            for d_str, entry in sweep.items():
                entry["scaling_efficiency_vs_1"] = round(
                    entry["verify_per_s"] / (int(d_str) * r1), 3)
            out["weak_scaling"] = sweep
    finally:
        mesh_mod.configure(enabled=prior[0], shard_min=prior[1],
                           max_devices=prior[2], cpu_shard=prior[3])
        m.reset_devices()
    return out


def micro_bls():
    """BASELINE config 3: BLS multi-sig aggregate + verify for
    n = 4/25/100 validators (the per-commit state-proof path). Native C
    backend (the framework's ursa equivalent) single-stream, the JAX
    batched-aggregation kernel (ops/bls381_jax.py) for throughput, and
    honest floors: pure Python and a documented optimized-library
    estimate (blst/ursa-class, not installable in this image)."""
    from plenum_tpu.crypto.bls import (
        BlsCryptoSignerPlenum, BlsCryptoVerifierPlenum)
    from plenum_tpu.crypto import bls_ops
    results = {"backend": bls_ops.BACKEND}
    verifier = BlsCryptoVerifierPlenum()
    msg = b"state-root-commitment"
    out = {}
    sigs_by_n = {}
    for n in (4, 25, 100):
        signers = [BlsCryptoSignerPlenum.generate(bytes([i]) * 32)[0]
                   for i in range(n)]
        sigs = [s.sign(msg) for s in signers]
        sigs_by_n[n] = sigs
        pks = [s.pk for s in signers]
        t0 = time.perf_counter()
        reps_a = 10
        for _ in range(reps_a):
            multi = verifier.create_multi_sig(sigs)
        agg_s = (time.perf_counter() - t0) / reps_a
        # the ORDERING-PATH aggregate: process_order only aggregates
        # shares that validate_commit already pairing-checked, so the
        # verifier's share-point cache is hot and aggregation is pure
        # Jacobian point addition (no per-share sqrt)
        for s, pk in zip(sigs, pks):
            verifier.verify_sig(s, msg, pk)
        reps_w = 100
        t0 = time.perf_counter()
        for _ in range(reps_w):
            warm_multi = verifier.create_multi_sig(sigs)
        agg_warm_s = (time.perf_counter() - t0) / reps_w
        assert warm_multi == multi
        # a FRESH verifier's key-dependent setup (n G2 subgroup checks,
        # aggregate key, prepared Miller lines) is paid by warm_keys at
        # catchup/membership-change time (node.py wires it); the cold
        # first verify after that pays only hash-to-curve + 2 pairings
        cold_verifier = BlsCryptoVerifierPlenum()
        t0 = time.perf_counter()
        cold_verifier.warm_keys(pks)
        warm_ms = (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        ok = cold_verifier.verify_multi_sig(multi, msg, pks)
        cold_s = time.perf_counter() - t0
        reps_v = 10
        t0 = time.perf_counter()
        for _ in range(reps_v):
            ok = cold_verifier.verify_multi_sig(multi, msg, pks)
        ver_s = (time.perf_counter() - t0) / reps_v
        assert ok
        out[str(n)] = {"aggregate_per_s": round(1 / agg_warm_s, 1),
                       "aggregate_cold_per_s": round(1 / agg_s, 1),
                       "verify_per_s": round(1 / ver_s, 1),
                       "key_warm_ms": round(warm_ms, 1),
                       "cold_first_verify_ms": round(cold_s * 1e3, 1)}
    results["by_n"] = out
    results["aggregate_desc"] = (
        "aggregate_per_s = the ordering money path (process_order "
        "aggregates shares validate_commit already pairing-checked: "
        "cached points, pure Jacobian addition); aggregate_cold_per_s "
        "= from compressed shares never seen (per-share sqrt)")
    # ---- JAX batched G1 aggregation at n=100 (the TPU half of the
    # SURVEY §2.9 ursa mapping): B independent 100-share aggregations
    # per dispatch, pipelined depth 2 to overlap host packing with
    # device compute. Cross-checked against the C path every run.
    from plenum_tpu.crypto.bls import b58_decode
    from plenum_tpu.ops import bls381_jax as bjk
    raw100 = [b58_decode(s) for s in sigs_by_n[100]]
    want = bls_ops.g1_aggregate_compressed(raw100)
    B_JOBS = 256
    jobs = [raw100] * B_JOBS
    h = bjk.aggregate_dispatch(jobs, 100)          # compile + warm
    pts, okv = bjk.aggregate_collect(h)
    assert pts[0] == want and all(okv)
    times = []
    for _ in range(4):
        t0 = time.perf_counter()
        h1 = bjk.aggregate_dispatch(jobs, 100)
        h2 = bjk.aggregate_dispatch(jobs, 100)
        bjk.aggregate_collect(h1)
        bjk.aggregate_collect(h2)
        times.append((time.perf_counter() - t0) / 2)
    ts = sorted(times)
    best, med = ts[0], ts[len(ts) // 2]
    # C batch floor, single stream (same work, one core)
    t0 = time.perf_counter()
    reps_c = 20
    for _ in range(reps_c):
        bls_ops.g1_aggregate_compressed(raw100)
    c_rate = reps_c / (time.perf_counter() - t0)
    results["aggregate_n100_batched"] = {
        "jobs_per_dispatch": B_JOBS,
        "device_jobs_per_s": round(B_JOBS / best, 1),
        "device_jobs_per_s_median": round(B_JOBS / med, 1),
        "cpu_batch_floor_per_s": round(c_rate, 1),
        "vs_cpu_floor": round(B_JOBS / best / c_rate, 2),
    }
    # ---- device pairing verify (ops/bls381_pairing behind the
    # bls_ops routing): a batch of signature checks becomes ONE
    # bucketed Miller-loop launch with a shared final exponentiation.
    # Verdict parity against the scalar backend is asserted BEFORE any
    # timing — a fast wrong kernel must never post a headline number.
    # On a CPU host this is a validation rate, not a win (the kernel
    # is shaped for the TPU's 8-wide mesh; the native C scalar path
    # above is the CPU money path) — bls_regression_gate checks the
    # number EXISTS and the verdicts matched, not that CPU beats C.
    n_dev = 8
    dev = {"jobs_per_launch": n_dev,
           "desc": "batched device pairing verify (one Miller launch "
                   "+ shared final exp per batch); parity vs the "
                   "scalar backend asserted before timing"}
    if not bls_ops.pairing_device_ready(n_dev):
        dev["skipped"] = ("device pairing unavailable (jax missing, "
                         "feature off, or family stepped down)")
    else:
        dsigners = [BlsCryptoSignerPlenum.generate(
            bytes([0x60 + i]) * 32)[0] for i in range(n_dev)]
        checks = [(s.sign(msg), msg, s.pk) for s in dsigners]
        # adversarial rows keep the parity assertion honest: a wrong
        # message and a signature over a different message must both
        # come back False from the SAME launch that verifies the rest
        checks[-1] = (dsigners[-1].sign(b"tampered"), msg,
                      dsigners[-1].pk)
        checks[-2] = (dsigners[-2].sign(msg), b"other",
                      dsigners[-2].pk)
        want = [verifier.verify_sig(*c) for c in checks]
        got = verifier.verify_sigs_batch(checks)   # compile + warm
        dev["parity_ok"] = got == want
        if dev["parity_ok"]:
            times = []
            for _ in range(2):
                t0 = time.perf_counter()
                assert verifier.verify_sigs_batch(checks) == want
                times.append(time.perf_counter() - t0)
            best_s = min(times)
            dev["bls_verifies_per_s"] = round(n_dev / best_s, 2)
            dev["launch_ms"] = round(best_s * 1e3, 1)
            dev["vs_scalar_native"] = round(
                n_dev / best_s / out["4"]["verify_per_s"], 4)
    results["device_pairing"] = dev
    # ---- floors. Pure-Python pairing measured; optimized-library
    # (ursa/blst-class) verify is a DOCUMENTED estimate: those libraries
    # pair in ~1.3-2 ms => ~500-770 verifies/s on one core. Neither
    # ships in this image (no Rust toolchain), so the bound is cited,
    # not measured — vs_optimized_floor_est uses the 700/s midpoint.
    from plenum_tpu.crypto import bls12_381 as B
    h = B.hash_to_g1(msg)
    sk = 12345
    sig = B.g1_mul(h, sk)
    pk = B.g2_mul(B.G2_GEN, sk)
    t0 = time.perf_counter()
    assert B.multi_pairing(
        [(sig, B.g2_neg(B.G2_GEN)), (h, pk)]) == B.FQ12_ONE
    results["floors"] = {
        "python_verify_per_s": round(1 / (time.perf_counter() - t0), 2),
        "optimized_library_verify_per_s_est": 700,
        "note": "blst/ursa-class libraries verify in ~1.3-2 ms; "
                "documented estimate (not installable here)",
    }
    results["vs_optimized_floor_est"] = round(
        out["100"]["verify_per_s"] / 700, 2)
    return results


# absolute floor for the scalar (native C) multi-sig verify rate at
# n=100 — prior rounds measured 120-360/s, so 25/s means the backend
# silently fell back to pure Python or the money path regressed ~5x
# (bls_regression_gate)
BLS_VERIFY_FLOOR = 25.0


def bls_regression_gate(bls, floor=None):
    """HARD headline gate for the BLS verify path: the device pairing
    batch must have been measured (``bls_verifies_per_s`` present and
    positive) with verdict parity against the scalar backend asserted
    BEFORE timing (``parity_ok``), and the scalar n=100 multi-sig
    verify rate must hold at or above BLS_VERIFY_FLOOR. Returns the
    list of failures; main() records them in the headline and exits
    nonzero unless BENCH_BLS_GATE=warn (diagnostic runs on degraded
    hosts — the headline still records the failures). Pure function of
    the micro_bls dict, so tier-1 gates the gate itself
    (tests/test_bench_gate.py) without running a bench."""
    floor = BLS_VERIFY_FLOOR if floor is None else floor
    if not isinstance(bls, dict):
        return ["micro_bls produced no result dict"]
    failures = []
    dev = bls.get("device_pairing")
    if not isinstance(dev, dict):
        failures.append("device_pairing missing from micro_bls")
    else:
        if dev.get("skipped"):
            failures.append("device pairing was skipped: %s"
                            % (dev["skipped"],))
        elif dev.get("parity_ok") is not True:
            failures.append(
                "device_pairing parity_ok is not True — device "
                "verdicts diverged from the scalar backend")
        rate = dev.get("bls_verifies_per_s")
        if not dev.get("skipped") \
                and (not isinstance(rate, (int, float)) or rate <= 0):
            failures.append(
                "bls_verifies_per_s missing or non-positive")
    scalar = ((bls.get("by_n") or {}).get("100") or {}) \
        .get("verify_per_s")
    if scalar is None:
        failures.append("by_n.100.verify_per_s missing from micro_bls")
    elif scalar < floor:
        failures.append("by_n.100.verify_per_s %.1f < required %.1f"
                        % (scalar, floor))
    return failures


def main():
    from plenum_tpu.crypto.signer import SimpleSigner

    signer = SimpleSigner(seed=b"\x42" * 32)
    reqs = make_requests(POOL_REQS, signer)

    # ---- deployment-shaped north star FIRST: it runs the TPU inside
    # the verify-daemon SUBPROCESS, so it must finish before this
    # process touches the (exclusive) device for the sim pool + micro
    # benches. Both providers measured on the same multi-process shape.
    mp_reqs = make_mp_requests(POOL_REQS)
    # interleaved best-of-2, same as the sim pool: a host shared by
    # four nodes, daemon and client shows run-to-run variance, and the
    # fleet headline
    # must not ride a single draw
    mp_runs_remote, mp_runs_cpu = [], []
    for _ in range(2):
        mp_runs_remote.append(run_multiprocess_pool(mp_reqs, "remote"))
        mp_runs_cpu.append(run_multiprocess_pool(mp_reqs, "cpu"))

    mp_remote_elapsed, mp_remote_ordered = best_of_runs(
        mp_runs_remote, len(mp_reqs) - 1, "mp-remote")
    mp_cpu_elapsed, mp_cpu_ordered = best_of_runs(
        mp_runs_cpu, len(mp_reqs) - 1, "mp-cpu")
    mp_rate = mp_remote_ordered / mp_remote_elapsed
    mp_cpu_rate = mp_cpu_ordered / mp_cpu_elapsed

    # TPU-batched pool (warm once so compile time stays out of the timing;
    # the hub fuses all 4 nodes' chunks, so warm every power-of-two
    # bucket the chunking can produce: full chunks AND the remainder)
    from plenum_tpu.ops import ed25519_jax as edj
    from plenum_tpu.crypto.fixtures import make_signed_batch
    warm_chunks = {min(CLIENT_BATCH, POOL_REQS)}
    if POOL_REQS % CLIENT_BATCH:
        warm_chunks.add(POOL_REQS % CLIENT_BATCH)
    for chunk in warm_chunks:
        wm, ws, wv = make_signed_batch(4 * chunk, seed=1)
        edj.verify_batch(wm, ws, wv)

    # INTERLEAVED best-of-2: back-to-back tpu-then-cpu blocks let
    # box-load drift bias the ratio whichever way the wind blows —
    # alternating runs exposes both pools to the same load profile
    tpu_runs, cpu_runs = [], []
    for _ in range(2):
        tpu_runs.append(run_pool(reqs, "tpu_hub"))
        cpu_runs.append(run_pool(reqs, "cpu"))
    tpu_elapsed, tpu_ordered = best_of_runs(tpu_runs, POOL_REQS, "tpu_hub")
    cpu_elapsed, cpu_ordered = best_of_runs(cpu_runs, POOL_REQS, "cpu")
    tpu_rate = tpu_ordered / tpu_elapsed
    cpu_rate = cpu_ordered / cpu_elapsed

    tracing = tracing_overhead()
    host_ms_regression = host_ms_regression_flags(
        (tracing.get("host_ms_per_ordered_req") or {}).get("total"),
        (tracing.get("host_ms_per_ordered_req") or {}).get("execute"))
    pipe_ab = pipeline_ab()
    pipe_gate_failures = pipeline_regression_gate(pipe_ab)
    san = sanitizer_overhead()
    san_gate_failures = sanitizer_overhead_gate(san)
    telemetry = telemetry_overhead()
    telemetry_gate_failures = telemetry_overhead_gate(telemetry)
    trace_ctx = trace_context_overhead()
    trace_ctx_gate_failures = trace_context_overhead_gate(trace_ctx)
    recovery = bench_recovery()

    (device_rate, device_rate_median, ed_single_shot, ed_single_shot_med,
     openssl_rate, python_rate, ed_sweep) = micro_ed25519()
    mk = micro_merkle()
    mk_regression = merkle_regression_flags(mk)
    mk_gate_failures = merkle_regression_gate(mk)
    mesh_res = micro_mesh()
    bls_results = micro_bls()
    bls_gate_failures = bls_regression_gate(bls_results)
    state_res = micro_state()
    exec_res = micro_executor()
    p25 = pool25_both()
    p25_journey = pool25_journey()
    gw = gateway_open_loop()
    gw_gate_failures = gateway_gate(gw)

    print(json.dumps({
        "metric": "ordered write-reqs/s, 4-node MULTI-PROCESS pool over "
                  "real TCP+AEAD, TPU verify daemon (n=%d; host has %d "
                  "CPU core(s) shared by 4 nodes + daemon + client)"
                  % (POOL_REQS, os.cpu_count() or 1),
        "value": round(mp_rate, 1),
        "unit": "req/s",
        "vs_baseline": round(mp_rate / mp_cpu_rate, 3),
        "verify_daemon_devices": DAEMON_DEVICES,
        "baseline": {
            "desc": "same multi-process pool, per-node OpenSSL Ed25519 "
                    "verify (libsodium-equivalent CPU floor)",
            "value": round(mp_cpu_rate, 1),
        },
        "secondary": {
            "sim_pool": {
                "desc": "in-process 4-node sim pool (round-2 comparable)"
                        ": TPU hub vs OpenSSL",
                "tpu_req_per_s": round(tpu_rate, 1),
                "cpu_req_per_s": round(cpu_rate, 1),
                "vs_cpu": round(tpu_rate / cpu_rate, 3),
            },
            "ed25519_batch_verify_per_chip": round(device_rate, 1),
            "ed25519_batch_verify_per_chip_median": round(
                device_rate_median, 1),
            "ed25519_verify_desc": "per_chip = pipelined sustained "
                "(the deployment shape: a stream of batches hides the "
                "launch round trip); single_shot = one launch incl. its "
                "full round trip",
            "ed25519_single_shot_per_s": round(ed_single_shot, 1),
            "ed25519_single_shot_per_s_median": round(
                ed_single_shot_med, 1),
            "batch": MICRO_BATCH,
            "ed25519_sweep": ed_sweep,
            "floors": {
                "openssl_single_core": round(openssl_rate, 1),
                "pure_python": round(python_rate, 1),
            },
            "vs_openssl_core": round(device_rate / openssl_rate, 2),
            "merkle": mk,
            "merkle_regression": mk_regression,
            "mesh": mesh_res,
            "bls": bls_results,
            "state": state_res,
            "executor": exec_res,
            "pool25_backlog": p25,
            "pool25_journey": p25_journey,
            "gateway": gw,
            "tracing_overhead": tracing,
            "host_ms_regression": host_ms_regression,
            "pipeline_ab": pipe_ab,
            "sanitizer_overhead": san,
            "telemetry_overhead": telemetry,
            "trace_context_overhead": trace_ctx,
            "recovery": recovery,
        },
    }))
    # compact one-line summary LAST: the driver records only a bounded
    # tail of stdout, and the full report above can exceed it — the
    # headline metric must always survive the truncation
    print(json.dumps({
        "headline": {
            "metric": "mp-pool req/s (TPU daemon)",
            "value": round(mp_rate, 1),
            "vs_cpu_floor": round(mp_rate / mp_cpu_rate, 3),
            "cpu_floor": round(mp_cpu_rate, 1),
            "sim_pool_tpu": round(tpu_rate, 1),
            "ed25519_per_chip": round(device_rate, 1),
            "merkle_paths_pipelined": mk["audit_paths_pipelined_per_s"],
            "merkle_vs_hashlib": mk["vs_hashlib"],
            "merkle_vs_cpu_audit_paths": mk["vs_cpu_audit_paths"],
            "merkle_dispatch_reduction": mk["incremental_append"][
                "dispatch_reduction"],
            "merkle_regression": mk_regression["warn"],
            "merkle_gate_ok": not mk_gate_failures,
            "merkle_gate_failures": mk_gate_failures or None,
            "bls_n100_aggregate": (bls_results.get("by_n", {})
                                   .get("100", {})
                                   .get("aggregate_per_s")),
            # device pairing verify (one Miller launch per batch);
            # bls_regression_gate hard-fails when the measurement is
            # missing or device verdicts diverge from the scalar path
            "bls_verifies_per_s": (bls_results.get("device_pairing")
                                   or {}).get("bls_verifies_per_s"),
            "bls_gate_ok": not bls_gate_failures,
            "bls_gate_failures": bls_gate_failures or None,
            "state_proofs_per_s": state_res["proofs_per_s"],
            "state_vs_python_proofs": state_res["vs_python_proofs"],
            "state_vs_python_apply": state_res["vs_python_apply"],
            # conflict-lane executor A/B at conflict 0.1 (the
            # acceptance point): lane path vs serial apply on the
            # identical digest stream, roots asserted byte-equal
            # inside the bench itself
            "executor_reqs_per_s": exec_res["executor_reqs_per_s"],
            "lane_parallel_speedup": exec_res["lane_parallel_speedup"],
            "executor_ms_per_req_serial":
                exec_res["execute_ms_per_req_ab"]["serial"],
            "executor_ms_per_req_lanes":
                exec_res["execute_ms_per_req_ab"]["lanes"],
            "pool25_mixed_req_per_s": p25.get("mixed_req_per_s")
            if isinstance(p25, dict) else None,
            "pool25_write_req_per_s": p25.get("write_req_per_s")
            if isinstance(p25, dict) else None,
            "pool25_drained": p25.get("drained")
            if isinstance(p25, dict) else None,
            "pool25_vs_cpu": p25.get("vs_cpu")
            if isinstance(p25, dict) else None,
            "pool25_vs_cpu_comparable": p25.get("vs_cpu_comparable")
            if isinstance(p25, dict) else None,
            "tracing_overhead_pct": tracing["overhead_pct"],
            "host_ms_per_ordered_req": tracing.get(
                "host_ms_per_ordered_req"),
            # warn-tripwire vs the best prior recorded round (same
            # convention as merkle_regression)
            "host_ms_regression": host_ms_regression["warn"],
            # pipeline-parallel node runtime A/B (25-node clean-box
            # pump): parity asserted byte-equal BEFORE timing, then
            # PIPELINE_ENABLED on over off — the one-thread-ceiling
            # claim (pipeline_regression_gate keeps parity hard even
            # under the warn override)
            "pipeline_speedup": pipe_ab.get("pipeline_speedup"),
            "pipeline_on_req_per_s": (pipe_ab.get("on") or {}).get(
                "req_per_s"),
            "pipeline_off_req_per_s": (pipe_ab.get("off") or {}).get(
                "req_per_s"),
            "pipeline_parity_ok": pipe_ab.get("parity_ok"),
            "pipeline_gate_ok": not pipe_gate_failures,
            "pipeline_gate_failures": pipe_gate_failures or None,
            # ownership sanitizer A/B (same 25-node pipelined pool,
            # pins+tokens on over off): parity hard always, overhead
            # hard-gated <2% so suite-wide arming stays honest
            "sanitizer_overhead_pct": san.get("overhead_pct"),
            "sanitizer_parity_ok": san.get("parity_ok"),
            "sanitizer_gate_ok": not san_gate_failures,
            "sanitizer_gate_failures": san_gate_failures or None,
            # serving-tier tail + device-efficiency trajectory (PR 10):
            # p50/p99 from the 25-node backlog config's merged hubs,
            # compact per-seam occupancy, and the always-on plane's
            # hard-gated A/B cost
            "ordered_p50_ms": p25.get("ordered_p50_ms")
            if isinstance(p25, dict) else None,
            "ordered_p99_ms": p25.get("ordered_p99_ms")
            if isinstance(p25, dict) else None,
            "lane_occupancy": {
                seam: entry.get("occupancy")
                for seam, entry in sorted(
                    (p25.get("lane_occupancy") or {}).items())}
            if isinstance(p25, dict) else None,
            # gateway tier: open-loop Poisson tail + shed/cache rates
            # (gateway_gate hard-fails the run when a field goes
            # missing or the shed ladder inverts)
            "gateway_p99_ms": gw.get("gateway_p99_ms"),
            "gateway_p999_ms": gw.get("gateway_p999_ms"),
            "gateway_shed_pct": gw.get("gateway_shed_pct"),
            "gateway_cache_hit_pct": gw.get("gateway_cache_hit_pct"),
            "gateway_gate_ok": not gw_gate_failures,
            "gateway_gate_failures": gw_gate_failures or None,
            "telemetry_overhead_pct": telemetry["overhead_pct"],
            "telemetry_gate_ok": not telemetry_gate_failures,
            "telemetry_gate_failures": telemetry_gate_failures or None,
            # journey plane: wire-stamp A/B cost (hard-gated <2%) and
            # the 25-node critical-path attribution — wire / straggler
            # / local shares of ordered e2e (pool25_journey config)
            "trace_context_overhead_pct": trace_ctx["overhead_pct"],
            "trace_context_gate_ok": not trace_ctx_gate_failures,
            "trace_context_gate_failures":
                trace_ctx_gate_failures or None,
            "critical_path_wire_pct": (p25_journey.get("critical_path")
                                       or {}).get("wire_pct"),
            "critical_path_straggler_pct": (
                p25_journey.get("critical_path") or {}).get(
                    "straggler_pct"),
            "critical_path_local_pct": (p25_journey.get("critical_path")
                                        or {}).get("local_pct"),
            "critical_path_e2e_ms": (p25_journey.get("critical_path")
                                     or {}).get("e2e_ms_mean"),
            "mesh_devices": mesh_res["devices"],
            "mesh_overhead_pct": mesh_res.get(
                "single_device_overhead_pct"),
            "recovery_failover_s": recovery.get("failover_s"),
            "recovery_failover_slo_s": recovery.get("failover_slo_s"),
            "recovery_catchup_s": recovery.get("catchup_s"),
            "recovery_catchup_slo_s": recovery.get("catchup_slo_s"),
            "recovery_slo_ok": recovery.get("slo_ok"),
        }
    }, separators=(",", ":")))
    # HARD gates — after the headline print so the numbers always
    # survive the driver's stdout truncation, but a failed gate still
    # fails the run (merkle_regression_gate / telemetry_overhead_gate)
    if mk_gate_failures and os.environ.get("BENCH_MERKLE_GATE") != "warn":
        print("MERKLE REGRESSION GATE FAILED: "
              + "; ".join(mk_gate_failures), file=sys.stderr)
        sys.exit(2)
    if telemetry_gate_failures \
            and os.environ.get("BENCH_TELEMETRY_GATE") != "warn":
        print("TELEMETRY OVERHEAD GATE FAILED: "
              + "; ".join(telemetry_gate_failures), file=sys.stderr)
        sys.exit(2)
    if trace_ctx_gate_failures \
            and os.environ.get("BENCH_TRACE_CTX_GATE") != "warn":
        print("TRACE CONTEXT OVERHEAD GATE FAILED: "
              + "; ".join(trace_ctx_gate_failures), file=sys.stderr)
        sys.exit(2)
    if gw_gate_failures and gate_enforced("BENCH_GATEWAY_GATE"):
        print("GATEWAY GATE FAILED: "
              + "; ".join(gw_gate_failures), file=sys.stderr)
        sys.exit(2)
    if bls_gate_failures and gate_enforced("BENCH_BLS_GATE"):
        print("BLS REGRESSION GATE FAILED: "
              + "; ".join(bls_gate_failures), file=sys.stderr)
        sys.exit(2)
    # pipeline_regression_gate applies its own cores/override logic
    # internally — parity failures come back hard regardless of env
    if pipe_gate_failures:
        print("PIPELINE GATE FAILED: "
              + "; ".join(pipe_gate_failures), file=sys.stderr)
        sys.exit(2)
    # sanitizer_overhead_gate likewise folds the warn override in —
    # whatever comes back is hard (parity stays hard under warn)
    if san_gate_failures:
        print("SANITIZER OVERHEAD GATE FAILED: "
              + "; ".join(san_gate_failures), file=sys.stderr)
        sys.exit(2)


if __name__ == "__main__":
    main()
