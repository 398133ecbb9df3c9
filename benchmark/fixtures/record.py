#!/usr/bin/env python3
"""Builder: record the small trace selftest.py reduces. Two launches of
the daemon's one shape (4,096 signatures through the program's batch
verifier) under the profiler, with the same options and anchor as
daemon_entry.py. Run on the chip; writes <out>/ed25519_two_launches.xplane.pb
(9 MB, most of it the kernel's own metadata; the repo keeps it as
`xz -9`, 1.4 MB, which trace_reduce.load reads)."""
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out_dir):
    from plenum_tpu.ops import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    import jax
    from plenum_tpu.crypto.batch_verifier import create_verifier
    from plenum_tpu.crypto.fixtures import make_signed_batch
    msgs, sigs, vks = make_signed_batch(4096, seed=3)
    batch = list(zip(msgs, sigs, vks))
    verifier = create_verifier("tpu_batch")
    assert all(verifier.verify_batch(batch))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    tmp = os.path.join(out_dir, "_profile")
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench_anchor"):
        time.sleep(0.001)
    for _ in range(2):
        assert all(verifier.verify_batch(batch))
        time.sleep(0.05)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                      recursive=True)
    shutil.copy(found[-1],
                os.path.join(out_dir, "ed25519_two_launches.xplane.pb"))
    shutil.rmtree(tmp)
    print(jax.devices(), os.path.getsize(
        os.path.join(out_dir, "ed25519_two_launches.xplane.pb")))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
