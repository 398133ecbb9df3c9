#!/usr/bin/env python3
"""The yardstick's own arithmetic, checked by hand-made cases. Run by
hand (it is not part of tier-1):

    JAX_PLATFORMS=cpu python benchmark/selftest.py
"""
import glob
import hashlib
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import generators  # noqa: E402
import kernel_work  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402
from client import Op  # noqa: E402
from reference import merkle, mpt  # noqa: E402
from reference.codec import b58decode, b58encode  # noqa: E402


def check_schedules():
    burst = {"generator": "burst", "params": {
        "burst": 4, "period_s": 2.0, "quiet_tail_s": 3.0}}
    for seed in (1, 2 ** 31 + 5):
        plan = generators.plan(burst, seed, 10.0)
        # bursts at 0, 2, 4, 6 (none from 7 s on), the same for any seed
        assert plan["due"] == [t for t in (0.0, 2.0, 4.0, 6.0)
                               for _ in range(4)], plan
    closed = {"generator": "closed", "params": {
        "outstanding": 4096, "max_rate": 2500}}
    plan = generators.plan(closed, 1, 30.0)
    assert plan == {"kind": "closed", "outstanding": 4096,
                    "max_ops": 4096 + 75000}


def check_rates_and_percentiles():
    # a reply log with a stall: 100 writes due at t = 0..9.9 s, each
    # answered 50 ms later, but nothing answers between t = 4 and t = 7:
    # writes due there are answered at 7.0 s
    ops = []
    for i in range(100):
        op = Op({"reqId": i}, b"", True)
        op.due = 100.0 + i * 0.1
        done = op.due + 0.05
        if 104.0 <= done < 107.0:
            done = 107.0
        op.done = done
        ops.append(op)
    ops[-1].done = None   # and one that is never answered
    lat = stats.latencies_ms(ops)
    assert lat[-1] == math.inf
    # 30 writes stalled (due 3.95..6.9 s): the longest waits 3,000 ms
    assert abs(max(v for v in lat if v != math.inf) - 3000.0) < 60.0
    assert abs(stats.percentile(lat, 50) - 50.0) < 1e-6
    # nearest rank: p95 of 100 is the 95th smallest, inside the stall
    assert 2000.0 < stats.percentile(lat, 95) < 3000.0
    assert stats.percentile(lat, 100) == math.inf
    # the whole window counts, stall included: 99 answered in 10 s
    rate = stats.whole_window_rate([op.done for op in ops], 100.0, 110.0)
    assert abs(rate - 9.9) < 1e-9, rate
    # an answer after the close is not inside the window
    assert stats.whole_window_rate([1.0, 2.0, 11.0], 0.0, 10.0) == 0.2
    assert stats.percentile([5.0], 95) == 5.0


def check_kernel_work():
    # hand count: multiplications 19 + 1008 + 448 + 512 + 112 + 13,
    # squarings 252 + 1008 + 254; 400 and 210 limb products each
    fm = kernel_work.field_mults()
    assert fm == {"mult": 2112, "square": 1514}, fm
    assert kernel_work.ed25519_verify_madds() == 2112 * 400 + 1514 * 210
    assert kernel_work.ed25519_verify_madds(10) == 2112 * 100 + 1514 * 55


def check_reference():
    # RFC 6962 section 2.1 by its own recursion, against the stack form
    def mth(leaves):
        if len(leaves) == 1:
            return merkle.leaf_hash(leaves[0])
        k = 1 << ((len(leaves) - 1).bit_length() - 1)
        return hashlib.sha256(
            b"\x01" + mth(leaves[:k]) + mth(leaves[k:])).digest()
    for n in (1, 2, 3, 5, 8, 13, 64, 100):
        leaves = [b"leaf-%d" % i for i in range(n)]
        assert merkle.root_of_hashes(
            [merkle.leaf_hash(x) for x in leaves]) == mth(leaves), n
    assert b58decode(b58encode(b"\0\0abc")) == b"\0\0abc"
    # RLP: the yellow paper's examples
    assert mpt.rlp(b"dog") == b"\x83dog"
    assert mpt.rlp([b"cat", b"dog"]) == b"\xc8\x83cat\x83dog"
    assert mpt.rlp(b"") == b"\x80" and mpt.rlp([]) == b"\xc0"
    assert mpt.rlp(b"\x0f") == b"\x0f"
    assert mpt.rlp(b"a" * 56)[:2] == b"\xb8\x38"
    # one leaf: root = H(rlp([hex-prefix(key nibbles, leaf), value]))
    want = hashlib.sha3_256(mpt.rlp([b"\x20" + b"k", b"v" * 40])).digest()
    assert mpt.root({b"k": b"v" * 40}) == want
    # order of insertion cannot matter: the root is of the set
    items = {b"key-%d" % i: b"value-%d" % i * 3 for i in range(300)}
    assert mpt.root(items) == mpt.root(dict(reversed(list(items.items()))))
    assert mpt.root({}) == hashlib.sha3_256(b"\x80").digest()


def check_trace_reduction():
    assert trace_reduce.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) \
        == [[1, 4], [5, 8]]
    spans = [{"name": "coalesce", "ph": "X", "ts": 0, "dur": 900},
             {"name": "device_verify", "ph": "X", "ts": 2000, "dur": 1000,
              "args": {"unique": 4000}},
             {"name": "device_verify", "ph": "X", "ts": 4000, "dur": 1000,
              "args": {"unique": 3}}]
    assert trace_reduce.label_gap(0, 1000, spans, 512) == "coalesce"
    assert trace_reduce.label_gap(2100, 2900, spans, 512) \
        == "device_verify host side"
    assert trace_reduce.label_gap(4000, 5000, spans, 512) \
        == "host OpenSSL batch"
    assert trace_reduce.label_gap(6000, 9000, spans, 512) \
        == "waiting for frames"
    found = glob.glob(os.path.join(HERE, "fixtures", "*.xplane.pb.xz"))
    assert found, "no recorded trace in fixtures/"
    red = trace_reduce.reduce(found[0])
    # recorded on a TPU v5 lite: two 4,096-signature launches under the
    # profiler (fixtures/record.py); expected.json holds what one look
    # at that trace showed
    import json
    with open(os.path.join(HERE, "fixtures", "expected.json")) as f:
        want = json.load(f)
    assert red["devices"] == want["devices"]
    assert abs(red["busy_s"] - want["busy_s"]) < 1e-6, red["busy_s"]
    import re
    pattern = re.compile(want["kernel_pattern"])
    kernels = [d for n, d in red["op_events"] if pattern.search(n)]
    assert len(kernels) == want["kernel_events"], len(kernels)
    assert abs(sum(kernels) - want["kernel_s"]) < 1e-6
    assert red["anchor_ns"] is not None
    assert red["busy_s"] < red["window_s"]


def main():
    for fn in (check_schedules, check_rates_and_percentiles,
               check_kernel_work, check_reference, check_trace_reduction):
        fn()
        print("ok  ", fn.__name__)


if __name__ == "__main__":
    main()
