#!/usr/bin/env python3
"""The benchmark's entry to the verify daemon: the program's
``verify_daemon.run_daemon`` unchanged, plus what only the process that
owns the chip can read.

  --side-file   the device's peak memory
                (``memory_stats()["peak_bytes_in_use"]`` of the fullest
                chip) and the profiler bracket's times; written when the
                bracket closes and again at exit (the last writer wins,
                the keys are the same), so a daemon that has to be killed
                afterwards has left bracket and trace behind
  --profile-dir SIGUSR1 starts ``jax.profiler`` there, SIGUSR2 stops it:
                the harness brackets a few seconds in the middle of the
                window. The bracket's perf_counter readings and an
                anchor event tie the trace to the daemon's own spans
  SIGQUIT       every thread's stack to standard error (faulthandler):
                the harness sends it to a daemon that has outlived its
                stop, a second before it kills it

Everything else is ``python -m plenum_tpu.server.verify_daemon``.
"""
import argparse
import asyncio
import faulthandler
import json
import logging
import os
import queue
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PROFILER_JOIN_S = 15.0   # inside the 20 s the harness gives a stop


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--backend", default="adaptive")
    ap.add_argument("--window", type=float, default=None)
    ap.add_argument("--bucket", type=int, default=None)
    ap.add_argument("--cpu-floor", type=int, default=None)
    ap.add_argument("--ready-file", default=None)
    ap.add_argument("--trace-file", default=None)
    ap.add_argument("--side-file", required=True)
    ap.add_argument("--profile-dir", default=None)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    faulthandler.register(signal.SIGQUIT, all_threads=True)
    if args.backend != "cpu":
        from plenum_tpu.ops import enable_persistent_compilation_cache
        enable_persistent_compilation_cache()
    from plenum_tpu.server import verify_daemon

    side = {"profile": []}
    side_lock = threading.Lock()

    def write_side():
        with side_lock:
            if args.backend != "cpu":
                import jax
                peaks = []
                for d in jax.local_devices():
                    stats = d.memory_stats() or {}
                    peaks.append(stats.get("peak_bytes_in_use"))
                side["memory_peak_bytes"] = max(
                    (p for p in peaks if p is not None), default=None)
            tmp = args.side_file + ".tmp"
            with open(tmp, "w") as f:
                json.dump(side, f)
            os.replace(tmp, args.side_file)

    # The two profiler calls run on a thread of their own, off the loop
    # (they take a second or so, and the loop must keep reading frames)
    # and off the loop's default executor, which asyncio.run joins on its
    # way out: a call stuck in the profiler must not keep a daemon that
    # has served its last frame from ending.
    calls = queue.Queue()

    def profiler_thread():
        while True:
            call = calls.get()
            if call is None:
                return
            if call == "start":
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(args.profile_dir,
                                         profiler_options=options)
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench_anchor"):
                    time.sleep(0.001)
                side["profile"].append({"start": t})
            else:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                side["profile"][-1].update(
                    stop=t, stopped=time.perf_counter())
                write_side()

    profiler = None
    if args.profile_dir:
        import jax
        profiler = threading.Thread(target=profiler_thread, daemon=True,
                                    name="bench-profiler")
        profiler.start()

    async def run():
        loop = asyncio.get_running_loop()
        if profiler is not None:
            loop.add_signal_handler(signal.SIGUSR1, calls.put, "start")
            loop.add_signal_handler(signal.SIGUSR2, calls.put, "stop")
        await verify_daemon.run_daemon(
            "127.0.0.1", args.port, args.backend, args.ready_file,
            args.window, args.bucket, args.cpu_floor,
            trace_file=args.trace_file)

    try:
        asyncio.run(run())
    finally:
        if profiler is not None:
            calls.put(None)
            profiler.join(PROFILER_JOIN_S)
            if profiler.is_alive():
                side["profiler_stuck"] = True
                print("the profiler's thread has not ended %.0fs after the "
                      "daemon's stop:" % PROFILER_JOIN_S, file=sys.stderr)
                faulthandler.dump_traceback(all_threads=True)
        write_side()


if __name__ == "__main__":
    main()
