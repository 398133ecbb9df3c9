#!/usr/bin/env python3
"""The benchmark's entry to the verify daemon: the program's
``verify_daemon.run_daemon`` unchanged, plus what only the process that
owns the chip can read.

  --side-file   after the clean stop: the device's peak memory
                (``memory_stats()["peak_bytes_in_use"]`` of the fullest
                chip) and the profiler bracket's times
  --profile-dir SIGUSR1 starts ``jax.profiler`` there, SIGUSR2 stops it:
                the harness brackets a few seconds in the middle of the
                window. The bracket's perf_counter readings and an
                anchor event tie the trace to the daemon's own spans

Everything else is ``python -m plenum_tpu.server.verify_daemon``.
"""
import argparse
import asyncio
import json
import logging
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


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
    if args.backend != "cpu":
        from plenum_tpu.ops import enable_persistent_compilation_cache
        enable_persistent_compilation_cache()
    from plenum_tpu.server import verify_daemon

    side = {"profile": []}

    async def run():
        loop = asyncio.get_running_loop()
        if args.profile_dir:
            import jax

            def start():
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(args.profile_dir,
                                         profiler_options=options)
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench_anchor"):
                    time.sleep(0.001)
                side["profile"].append({"start": t})

            def stop():
                t = time.perf_counter()
                jax.profiler.stop_trace()
                side["profile"][-1].update(
                    stop=t, stopped=time.perf_counter())

            # off the loop: starting and stopping take a second or so,
            # and the loop must keep reading frames
            loop.add_signal_handler(
                signal.SIGUSR1, lambda: loop.run_in_executor(None, start))
            loop.add_signal_handler(
                signal.SIGUSR2, lambda: loop.run_in_executor(None, stop))
        await verify_daemon.run_daemon(
            "127.0.0.1", args.port, args.backend, args.ready_file,
            args.window, args.bucket, args.cpu_floor,
            trace_file=args.trace_file)

    try:
        asyncio.run(run())
    finally:
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


if __name__ == "__main__":
    main()
