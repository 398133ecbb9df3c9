"""Verification daemon — one process owns the accelerator, every node
offloads ed25519 batch verification to it over a local socket.

Deployment shape for multi-process pools on one host: the TPU is a
process-exclusive device, so co-located node processes cannot each hold
it. The daemon plays the role the CoalescingVerifierHub plays inside a
single process (crypto/batch_verifier.py): requests from all connected
nodes are coalesced within a small window into ONE fused device launch —
the verify kernel is latency-bound, so k separate launches cost ~k× one
fused launch — and results are scattered back per request.

Pipelining: the device call runs on a single worker thread while the
asyncio loop keeps reading frames, so batch k+1 accumulates during batch
k's device round trip.

Wire protocol (both directions): 4-byte little-endian length prefix +
msgpack payload.
  request : [req_id, [[msg, sig, vk], ...]]
  response: [req_id, results_bytes]   (one 0/1 byte per item)
Control, off the verification path (request ids start at 1):
  [0, {"trace": {"dir": D}}]  daemon → every connection, once, on accept,
      when started with --trace-file: a trace session for the whole
      host. A node arms its flight recorder and writes
      D/node_<Name>_spans.json at its clean stop, beside the daemon's
      own file (server/node.py); other clients drop the frame.
  [req_id, "stats"]  client → daemon: answered at once by the
      connection handler with [req_id, stats() as JSON bytes]; never
      queued behind a batch (``--stats`` prints it).

Out-of-band (no protocol): started with a device backend, the daemon
initializes its device BEFORE serving and states what it got — once in
its log and in the ``--ready-file`` (one JSON object: port, backend,
device {platform, kind, count}, compile_cache, kernel_store) — so the
launcher knows which process owns the chip; a chip that cannot be
initialised fails the start instead of serving from the CPU backend. On
SIGTERM/SIGINT it stops cleanly and prints ONE JSON line of counters to
stdout (logging goes to stderr): device launches and items, host
(OpenSSL floor) items, failed batches, coalesced batch sizes,
kernel-family step-downs, and ``kernel_store``: whether the ed25519
Pallas kernel was loaded from the built-kernel store
(ops/kernel_store.py) or built here, how long that took, and why a
stored file was replaced.

Reference equivalence: the reference verifies inline through libsodium
(plenum/server/client_authn.py:84); this daemon is the tpu-native
replacement for that native-library seam at multi-process scale.
"""
from __future__ import annotations

import asyncio
import json
import logging
import os
import signal
import struct
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Tuple

import msgpack

from plenum_tpu.observability.tracing import CAT_DEVICE, NullTracer

logger = logging.getLogger(__name__)

LEN = struct.Struct("<I")
MAX_FRAME = 64 * 1024 * 1024
# per-connection response backlog past which the peer is declared stalled
# and dropped: the daemon serves every node on the host, so one wedged
# reader must not buffer the others' memory away
WRITE_HIGH_WATER = 8 * 1024 * 1024


class VerifyDaemon:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 backend: str = "adaptive", window: float = None,
                 bucket: int = None, cpu_floor: int = None):
        """bucket: device launches are chunked to EXACTLY this many items
        (padded by repetition) so XLA compiles ONE batch shape — variable
        shapes would hit a fresh ~100 s compile mid-run. cpu_floor:
        fused batches below this take the OpenSSL path (a near-empty
        device launch costs more than scalar verification). Both only
        apply to device backends; backend="cpu" verifies directly.
        None defaults single-source from Config.VERIFY_DAEMON_* (the
        VERIFIER_BATCH_THRESHOLD precedent); explicit args win."""
        from plenum_tpu.common.config import Config
        from plenum_tpu.crypto.batch_verifier import (
            OpenSSLVerifier, create_verifier)
        self.host = host
        self.port = port
        self._backend_name = backend
        self._verifier = create_verifier(backend)
        # the provider whose dispatch packs and launches on the device
        # (the adaptive one routes to it above its threshold): the
        # batcher hands it the tracer and each batch's span args
        self._device_verifier = getattr(
            self._verifier, "device_provider", self._verifier)
        self._floor_verifier = OpenSSLVerifier()
        self._bucket = Config.VERIFY_DAEMON_BUCKET \
            if bucket is None else bucket
        self._cpu_floor = Config.VERIFY_DAEMON_CPU_FLOOR \
            if cpu_floor is None else cpu_floor
        self._window = Config.VERIFY_DAEMON_WINDOW \
            if window is None else window
        self._queue: asyncio.Queue = asyncio.Queue()
        # worker sizing through the single pipeline knob (PT005: one
        # knob, every consumer). The daemon's FALLBACK is 1, not the
        # node pipeline's cores−1 auto: device launches must serialize
        # anyway, and a busy worker is exactly what lets the NEXT
        # batch coalesce deeper — only an explicit PIPELINE_WORKERS
        # raises it (multi-backend / cpu-path deployments).
        from plenum_tpu.runtime.pipeline import resolve_workers
        self._pool = ThreadPoolExecutor(max_workers=resolve_workers(
            getattr(Config, "PIPELINE_WORKERS", None), fallback=1))
        self._server = None
        self._batcher_task = None
        self._writers = set()
        self.served = 0           # items answered (before dedup)
        self.launches = 0         # coalesced batches run
        # where the unique items of those batches were verified — what
        # a launcher reads (stats()) to know the device did the work
        self.device_launches = 0  # fixed-bucket device launches
        self.device_items = 0
        self.host_items = 0       # OpenSSL: cpu backend / below the floor
        self.failed_batches = 0   # backend raised: answered all-False
        self.batch_sizes = {}     # pow2 bucket -> coalesced batches
        # flight recorder: the daemon runs in its own process, so it
        # gets its own tracer (attach a real one + trace_file to dump
        # Perfetto timelines of coalescing vs device round trips at
        # stop). A trace file also opens the host's trace session:
        # every connection is told its directory on accept
        self.tracer = NullTracer("verify-daemon")
        self.trace_file = None

    async def start(self):
        self._server = await asyncio.start_server(
            self._handle_conn, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self._batcher_task = asyncio.get_event_loop().create_task(
            self._batcher())
        logger.info("verify daemon listening on %s:%d", self.host, self.port)

    async def stop(self):
        # cancel the batcher FIRST: left running past shutdown it would
        # keep consuming frames that buffered before the connections die
        # below, answering them all-False through the shut-down pool —
        # and a restarted daemon on the same port never sees them
        if self._batcher_task is not None:
            self._batcher_task.cancel()
            try:
                await self._batcher_task
            except (asyncio.CancelledError, Exception):
                pass
            self._batcher_task = None
        if self._server is not None:
            self._server.close()
            # abort (RST), don't close (FIN-after-flush), live node
            # connections: a graceful close can deliver a final reply
            # ahead of the FIN, so the client keeps dispatching into the
            # dead link instead of re-dialing the restarted daemon.
            # Also required for 3.12's wait_closed(), which waits for
            # EVERY client connection, not just the listener.
            for w in list(self._writers):
                try:
                    w.transport.abort()
                except Exception:
                    pass
            await self._server.wait_closed()
        self._pool.shutdown(wait=False)
        self._dump_trace()

    def _dump_trace(self):
        if self.trace_file is None or not getattr(
                self.tracer, "enabled", False):
            return
        try:
            from plenum_tpu.observability.export import export_chrome_trace
            export_chrome_trace([self.tracer], self.trace_file)
        except Exception:
            logger.warning("trace dump failed", exc_info=True)

    def stats(self) -> dict:
        """Counters a launcher reads after the run (printed as the
        final stdout line on a clean stop): which path verified the
        items, and whether any kernel family left its device path."""
        out = {
            "backend": self._backend_name,
            "bucket": self._bucket,
            "cpu_floor": self._cpu_floor,
            "served": self.served,
            "launches": self.launches,
            "device_launches": self.device_launches,
            "device_items": self.device_items,
            "host_items": self.host_items,
            "failed_batches": self.failed_batches,
            "batch_sizes": {str(k): v for k, v in
                            sorted(self.batch_sizes.items())},
        }
        if self._backend_name != "cpu":
            from plenum_tpu.ops import mesh as mesh_mod
            out["step_downs"] = mesh_mod.step_down_counts()
            out["kernel_backends"] = mesh_mod.kernel_backends()
            out["mesh"] = mesh_mod.mesh_stats()
            # whether this process loaded its Pallas kernel from the
            # built-kernel store or had to trace and compile it
            from plenum_tpu.ops import kernel_store
            out["kernel_store"] = kernel_store.counts()
        return out

    # ------------------------------------------------------------ conns

    def _verify_bucketed(self, items):
        """Fixed-shape device launches: chunk to `bucket` items (pad the
        tail by repetition), dispatch every chunk async FIRST so the
        launches pipeline through the device queue, then collect.

        Multi-chip: the bucket scales by the mesh's device count so one
        fused launch spans every chip (the mesh dispatcher re-buckets
        per device, so the per-device compiled shape is unchanged)."""
        if self._backend_name == "cpu" or len(items) < self._cpu_floor:
            self.host_items += len(items)
            return self._floor_verifier.verify_batch(items)
        if self._bucket <= 0:
            self.device_items += len(items)
            return self._verifier.verify_batch(items)
        b = self._bucket
        from plenum_tpu.ops.mesh import get_mesh
        mesh = get_mesh()
        if mesh.should_shard(b * mesh.n_devices):
            # only when the scaled launch actually clears the shard
            # gate — otherwise it would take the passthrough path at a
            # brand-new (uncompiled) shape for zero mesh benefit
            b *= mesh.n_devices
        chunks = [items[i:i + b] for i in range(0, len(items), b)]
        if len(chunks[-1]) < b:
            pad = chunks[-1][0]
            chunks[-1] = chunks[-1] + [pad] * (b - len(chunks[-1]))
        # daemon-seam lane accounting + round trip: real items vs the
        # fixed-bucket grid launched (the tail chunk's repetition
        # padding is this seam's wasted lanes); this method runs on the
        # worker thread start-to-finish, so the wall time here IS the
        # fused dispatch→collect round trip
        from plenum_tpu.observability import telemetry as tmy
        tm_hub = tmy.get_seam_hub()
        first_call = tm_hub.record_launch(
            tmy.SEAM_DAEMON, len(items), b * len(chunks), shape=b)
        t0 = tm_hub.clock()
        self.device_launches += len(chunks)
        self.device_items += len(items)
        pendings = [self._verifier.dispatch(c) for c in chunks]
        out = []
        for p in pendings:
            out.extend(p.collect())
        tm_hub.record_roundtrip(tmy.SEAM_DAEMON,
                                (tm_hub.clock() - t0) * 1e3,
                                first_call=first_call)
        return out[:len(items)]

    @staticmethod
    def _send(writer: asyncio.StreamWriter, req_id: int, body) -> None:
        frame = msgpack.packb([req_id, body], use_bin_type=True)
        writer.write(LEN.pack(len(frame)) + frame)

    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter):
        self._writers.add(writer)
        try:
            if self.trace_file is not None:
                self._send(writer, 0, {"trace": {"dir": os.path.dirname(
                    os.path.abspath(self.trace_file))}})
            while True:
                hdr = await reader.readexactly(4)
                (n,) = LEN.unpack(hdr)
                if n > MAX_FRAME:
                    logger.warning("oversized frame (%d); closing", n)
                    break
                payload = await reader.readexactly(n)
                try:
                    req_id, items = msgpack.unpackb(payload, raw=False)
                except Exception:
                    # garbage frame: close THIS connection cleanly; an
                    # escaped decode error would kill the reader task
                    # with an unretrieved-exception warning instead
                    logger.warning("undecodable frame; closing",
                                   exc_info=True)
                    break
                if items == "stats":
                    # live counters, answered here: a control request
                    # never waits in the batcher's queue
                    self._send(writer, req_id,
                               json.dumps(self.stats()).encode())
                    continue
                # stamped as read: the batch's verify_queue_wait span
                # starts at its oldest frame's stamp
                await self._queue.put(
                    (writer, req_id, items, self.tracer.now()))
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            self._writers.discard(writer)
            try:
                writer.close()
            except Exception:
                pass

    # ---------------------------------------------------------- batching

    async def _batcher(self):
        loop = asyncio.get_event_loop()
        while True:
            first = await self._queue.get()
            batch = [first]
            # event-driven coalescing: sleep exactly until the next frame
            # or the window deadline — a polling loop would burn the one
            # CPU core the node processes need
            with self.tracer.span("coalesce", CAT_DEVICE) as _csp:
                deadline = loop.time() + self._window
                while True:
                    remaining = deadline - loop.time()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(await asyncio.wait_for(
                            self._queue.get(), remaining))
                    except asyncio.TimeoutError:
                        break
                _csp.add(requests=len(batch))
            all_items: List[Tuple[bytes, bytes, bytes]] = []
            spans = []
            for _, _, items, _ in batch:
                lo = len(all_items)
                try:
                    all_items.extend(
                        (bytes(m), bytes(s), bytes(vk))
                        for m, s, vk in items)
                except Exception:
                    # malformed frame from one client: answer all-False
                    # for ITS span; the batcher must survive (it serves
                    # every node on the host)
                    del all_items[lo:]
                    logger.warning("malformed verify request", exc_info=True)
                spans.append((lo, len(all_items) - lo))
            # dedup byte-identical items across nodes: every node on the
            # host verifies the SAME client requests, so n connected
            # nodes would otherwise cost n× the device work per request
            from plenum_tpu.crypto.batch_verifier import dedup_items
            order, index = dedup_items(all_items)
            # run on the worker thread so the loop keeps reading frames
            # (batch k+1 coalesces during batch k's device round trip)
            t_launch = loop.time()
            logger.debug("batch: %d items (%d unique) from %d requests",
                        len(all_items), len(order), len(batch))
            # what every span of this batch carries; the device
            # provider's pack/launch/collect spans (crypto/
            # batch_verifier.py) are recorded from the worker thread
            # inside device_verify. One batch is in flight at a time
            # (the await below), so the attributes cannot mix batches
            span_args = {"items": len(all_items), "unique": len(order),
                         "requests": len(batch)}
            self._device_verifier.tracer = self.tracer
            self._device_verifier.span_args = span_args
            # the oldest frame's wait: read off the socket → here
            self.tracer.complete("verify_queue_wait", CAT_DEVICE,
                                 batch[0][3], self.tracer.now(),
                                 **span_args)
            try:
                # this span IS the device round trip as the loop sees it
                # (the worker thread serializes launches, so a deep span
                # here means the NEXT batch coalesced under it — exactly
                # the pipelining the timeline should show)
                with self.tracer.span("device_verify", CAT_DEVICE,
                                      **span_args):
                    uniq_results = await loop.run_in_executor(
                        self._pool, self._verify_bucketed, order)
                results = [uniq_results[i] for i in index]
            except Exception:  # plenum-lint: disable=PT006 — the daemon
                # serves every node on the host: ANY backend failure
                # must answer all-False and keep the batcher alive
                logger.warning("verify batch failed", exc_info=True)
                self.failed_batches += 1
                results = [False] * len(all_items)
            logger.debug("batch done in %.2fs", loop.time() - t_launch)
            self.served += len(all_items)
            self.launches += 1
            size_bucket = 1 << max(0, len(order) - 1).bit_length()
            self.batch_sizes[size_bucket] = \
                self.batch_sizes.get(size_bucket, 0) + 1
            for (writer, req_id, _, _), (lo, cnt) in zip(batch, spans):
                body = bytes(bytearray(
                    1 if results[lo + i] else 0 for i in range(cnt)))
                try:
                    if writer.transport.is_closing():
                        continue
                    self._send(writer, req_id, body)
                    # bounded buffering without stalling the batcher on
                    # one slow peer: a connection whose response backlog
                    # passes the high-water mark is aborted (abort, not
                    # close — close would keep the backlog alive trying
                    # to flush it to the stalled reader). Its node fails
                    # in-flight requests to all-False and re-dials — see
                    # RemoteVerifier's failure policy.
                    if writer.transport.get_write_buffer_size() \
                            > WRITE_HIGH_WATER:
                        logger.warning(
                            "dropping stalled verify client "
                            "(write backlog %d bytes)",
                            writer.transport.get_write_buffer_size())
                        self._writers.discard(writer)
                        writer.transport.abort()
                except Exception:
                    pass


def wait_ready(path: str, proc=None, timeout: float = 180.0) -> dict:
    """Launcher side of the start handshake (bench.py, chip_smoke.py):
    wait for the daemon's ready file and return its dict — port,
    backend, and for a device backend the device it claimed. The write
    in run_daemon is atomic, so a present file is complete. Raises if
    the daemon process (a Popen, when given) exits first — a chip that
    cannot be initialised fails the start — or the wait times out."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            with open(path) as f:
                return json.load(f)
        except FileNotFoundError:
            pass
        if (proc is not None and proc.poll() is not None) \
                or time.monotonic() > deadline:
            raise RuntimeError("verify daemon failed to start")
        time.sleep(0.1)


async def run_daemon(host="127.0.0.1", port=0, backend="adaptive",
                     ready_file=None, window: float = None,
                     bucket: int = None, cpu_floor: int = None,
                     trace_file=None):
    ready = {"backend": backend, "pid": os.getpid()}
    if backend != "cpu":
        # claim the device BEFORE serving and say what it is: a chip
        # that cannot be initialised raises here and fails the start —
        # the launcher must never mistake a CPU-backend daemon for the
        # owner of the chip
        from plenum_tpu.ops import mesh as mesh_mod
        ready["device"] = mesh_mod.device_facts()
        import jax
        ready["compile_cache"] = jax.config.jax_compilation_cache_dir
        # all zeros here: the store is read inside the first launch
        # that fills a Pallas block, which no frame has asked for yet
        from plenum_tpu.ops import kernel_store
        ready["kernel_store"] = kernel_store.counts()
        logger.info("verify daemon device: %s", json.dumps(ready["device"]))
    daemon = VerifyDaemon(host, port, backend, window=window,
                          bucket=bucket, cpu_floor=cpu_floor)
    if trace_file:
        from plenum_tpu.observability.tracing import Tracer
        from plenum_tpu.ops import mesh as mesh_mod
        daemon.tracer = Tracer("verify-daemon")
        daemon.trace_file = trace_file
        # mesh_dispatch spans + per-device counters from the daemon's
        # device launches land in the same timeline
        mesh_mod.get_mesh().tracer = daemon.tracer
    await daemon.start()
    ready["port"] = daemon.port
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError, ValueError):
            pass    # not the main thread / no signal support: the
            # caller cancels the task instead
    if ready_file:
        # one-shot startup handshake before any frame is served — not a
        # hot-loop write; rename makes a present file a complete one
        tmp = "%s.%d.tmp" % (ready_file, os.getpid())
        with open(tmp, "w") as f:  # plenum-lint: disable=PT001
            json.dump(ready, f)
        os.replace(tmp, ready_file)
    try:
        await stop.wait()
    finally:
        await daemon.stop()
        # the final stats line: stdout carries nothing else
        print(json.dumps(daemon.stats()), flush=True)


def main():  # pragma: no cover - exercised via subprocess in bench
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--backend", default="adaptive")
    ap.add_argument("--window", type=float, default=None,
                    help="coalescing window s (default: "
                         "Config.VERIFY_DAEMON_WINDOW)")
    ap.add_argument("--bucket", type=int, default=None,
                    help="device launch bucket (default: "
                         "Config.VERIFY_DAEMON_BUCKET)")
    ap.add_argument("--cpu-floor", type=int, default=None,
                    help="OpenSSL floor (default: "
                         "Config.VERIFY_DAEMON_CPU_FLOOR)")
    ap.add_argument("--ready-file", default=None,
                    help="write one JSON object here once listening: "
                         "port, backend, device facts, compile cache")
    ap.add_argument("--trace-file", default=None,
                    help="record coalesce/device spans and dump a "
                         "Chrome trace-event JSON here on clean stop; "
                         "also opens a trace session for every node "
                         "that connects, whose spans land in the same "
                         "directory as node_<Name>_spans.json")
    ap.add_argument("--stats", action="store_true",
                    help="print the counters of the daemon running at "
                         "--host/--port as one JSON line and exit")
    args = ap.parse_args()
    if args.stats:
        from plenum_tpu.crypto.remote_verifier import RemoteVerifier
        rv = RemoteVerifier((args.host, args.port), timeout=5.0)
        try:
            print(json.dumps(rv.daemon_stats()), flush=True)
        finally:
            rv.close()
        return
    logging.basicConfig(level=logging.INFO)
    if args.backend != "cpu":
        # persistent XLA compile cache (the one setter: honours
        # JAX_COMPILATION_CACHE_DIR); saves minutes per bucket shape on
        # every daemon start after the first
        from plenum_tpu.ops import enable_persistent_compilation_cache
        enable_persistent_compilation_cache()
    asyncio.run(run_daemon(args.host, args.port, args.backend,
                           args.ready_file, args.window, args.bucket,
                           args.cpu_floor, trace_file=args.trace_file))


if __name__ == "__main__":  # pragma: no cover
    main()
