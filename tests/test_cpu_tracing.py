"""CPU time beside wall time in the flight recorder: counters of several
values, the `cpu` counter's cadence, `cpu_us` on a served node's
`prod_tick`, and the `wire_rx` span of the frame receive path on real
localhost links (node links both ways, a client link), armed and not."""
import asyncio
import json

import pytest

from plenum_tpu.common.config import Config
from plenum_tpu.network.keys import NodeKeys
from plenum_tpu.network.stack import (
    HA, ClientConnection, ClientStack, NodeStack, RemoteInfo)
from plenum_tpu.observability.export import summarize, trace_events
from plenum_tpu.observability.tracing import (
    CAT_WIRE, CPU_SAMPLE_S, NullTracer, Tracer)


def _manual_clock():
    t = [0.0]
    return t, (lambda: t[0])


def _stepping_clock(step):
    """A fake CPU clock that moves on by `step` at every read."""
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]
    return clock


# ------------------------------------------------------------ counters


def test_counter_with_several_values_is_one_record():
    t, clock = _manual_clock()
    tracer = Tracer("n1", capacity=8, clock=clock)
    t[0] = 1.0
    tracer.counter("cpu", thread_s=1.5, process_s=2.25)
    tracer.counter("depth", 5)
    cpu, depth = tracer.spans()
    assert cpu == ("C", "cpu", "", 1.0, None, None,
                   {"thread_s": 1.5, "process_s": 2.25})
    assert depth[6] == {"depth": 5}
    # one Perfetto counter event carrying both series
    [ev] = [e for e in trace_events([tracer]) if e["name"] == "cpu"]
    assert (ev["ph"], ev["ts"], ev["args"]) == (
        "C", 1000000, {"thread_s": 1.5, "process_s": 2.25})
    counters = summarize({"traceEvents": trace_events([tracer])})[
        "counters"]
    assert counters["cpu.thread_s"]["last"] == 1.5
    assert counters["cpu.process_s"]["last"] == 2.25
    assert counters["depth"]["last"] == 5
    tracer.disarm()
    tracer.counter("cpu", thread_s=9.0, process_s=9.0)
    assert len(tracer.spans()) == 2
    assert NullTracer().counter("cpu", thread_s=1.0) is None


def test_cpu_samples_at_most_one_per_interval():
    t, clock = _manual_clock()
    tracer = Tracer("n1", capacity=64, clock=clock,
                    thread_clock=_stepping_clock(0.01),
                    process_clock=_stepping_clock(0.03))
    for at in (0.0, 0.05, 0.0999, 0.1, 0.15, 0.25, 0.3, 0.4, 1.0):
        t[0] = at
        tracer.sample_cpu()
    samples = [r for r in tracer.spans() if r[1] == "cpu"]
    assert [r[3] for r in samples] == [0.0, 0.1, 0.25, 0.4, 1.0]
    assert all(r[0] == "C" for r in samples)
    # each sample reads both clocks once, in order
    assert [r[6]["thread_s"] for r in samples] == pytest.approx(
        [0.01, 0.02, 0.03, 0.04, 0.05])
    assert [r[6]["process_s"] for r in samples] == pytest.approx(
        [0.03, 0.06, 0.09, 0.12, 0.15])
    assert CPU_SAMPLE_S == 0.1
    tracer.disarm()
    t[0] = 5.0
    tracer.sample_cpu()
    assert len([r for r in tracer.spans() if r[1] == "cpu"]) == 5
    assert NullTracer().sample_cpu() is None
    assert NullTracer().cpu_now() == 0.0


def test_cpu_now_reads_the_thread_clock():
    tracer = Tracer("n1", capacity=4, thread_clock=lambda: 7.5)
    assert tracer.cpu_now() == 7.5
    real = Tracer("n1", capacity=4)
    a = real.cpu_now()
    sum(range(20000))
    assert real.cpu_now() >= a


# ------------------------------------------------------- the prod tick


def test_prod_tick_carries_cpu_us_under_a_fake_thread_clock(tmp_path):
    from plenum_tpu.server.networked_node import NetworkedNode
    names = ["Alpha", "Beta", "Gamma", "Delta"]
    keys = {n: NodeKeys(bytes([i + 50]) * 32) for i, n in enumerate(names)}
    node = NetworkedNode(
        "Alpha", {n: RemoteInfo(n, HA("127.0.0.1", 1), keys[n].verkey_raw)
                  for n in names},
        keys["Alpha"], HA("127.0.0.1", 0), HA("127.0.0.1", 0),
        config=Config(HEARTBEAT_FREQ=60))
    t, clock = _manual_clock()
    tracer = Tracer("Alpha", capacity=64, clock=clock,
                    thread_clock=_stepping_clock(0.002))
    node.node.tracer = tracer
    loop = asyncio.new_event_loop()
    try:
        # one inbound message makes the tick productive
        node.nodestack.rx.append(({"op": "NOT_A_MESSAGE"}, "Beta"))
        loop.run_until_complete(node.prod())
        recs = tracer.spans()
        [tick] = [r for r in recs if r[1] == "prod_tick"]
        # the thread clock is read once at the tick's start and once at
        # its end: one step of the fake clock
        assert tick[6]["cpu_us"] == pytest.approx(2000.0)
        assert tick[6]["produced"] >= 1
        # the other tick spans carry no CPU time
        assert all("cpu_us" not in (r[6] or {}) for r in recs
                   if r[1] in ("node_rx", "client_rx", "transport_flush"))
        [sample] = [r for r in recs if r[1] == "cpu"]
        assert set(sample[6]) == {"thread_s", "process_s"}
        # the read loops record into the node's tracer
        assert node.nodestack.tracer is tracer
        assert node.clientstack.tracer is tracer
        # an idle turn inside the interval: no tick, no sample
        t[0] = CPU_SAMPLE_S / 2
        loop.run_until_complete(node.prod())
        assert len(tracer.spans()) == len(recs)
        # the dump closes the record with a last sample, inside the
        # interval or not
        node.node.trace_session_dir = str(tmp_path)
        with open(node.node.write_trace_dump()) as f:
            doc = json.load(f)
        assert [e["ts"] for e in doc["traceEvents"]
                if e.get("name") == "cpu"] == [0, CPU_SAMPLE_S / 2 * 1e6]
        recs = tracer.spans()
        tracer.disarm()
        node.nodestack.rx.append(({"op": "NOT_A_MESSAGE"}, "Beta"))
        t[0] = 1.0
        loop.run_until_complete(node.prod())
        assert len(tracer.spans()) == len(recs)
    finally:
        loop.close()


# --------------------------------------------------- the receive path


async def _node_pair(config):
    keys = {n: NodeKeys(bytes([i + 60]) * 32)
            for i, n in enumerate(("Alpha", "Beta"))}
    stacks, registry = {}, {}
    for name in keys:
        stacks[name] = NodeStack(name, HA("127.0.0.1", 0), keys[name], {},
                                 config)
        await stacks[name].start()
        registry[name] = RemoteInfo(name, stacks[name].ha,
                                    keys[name].verkey_raw)
    for name, stack in stacks.items():
        for info in registry.values():
            if info.name != name:
                stack.add_remote(info)
    return stacks


async def _pump(stacks, until, seconds=5.0):
    end = asyncio.get_event_loop().time() + seconds
    while asyncio.get_event_loop().time() < end:
        for s in stacks.values():
            s.service_lifecycle()
            s.flush_outboxes()
        if until():
            return True
        await asyncio.sleep(0.01)
    return until()


def _wire_rx(tracer):
    return [r for r in tracer.spans() if r[1] == "wire_rx"]


def test_node_links_record_one_wire_rx_per_frame_both_ways():
    async def main():
        stacks = await _node_pair(Config(ENABLE_HEARTBEATS=False))
        a, b = stacks["Alpha"], stacks["Beta"]
        assert isinstance(b.tracer, NullTracer)
        assert await _pump(stacks, lambda: a.connecteds == {"Beta"}
                           and b.connecteds == {"Alpha"})
        b.tracer = Tracer("Beta")
        a.tracer = Tracer("Alpha")
        for i in range(50):          # one tick's outbox: a signed BATCH
            a.send({"op": "TEST", "i": i}, "Beta")
        assert await _pump(stacks, lambda: len(b.rx) == 50)
        sent_at = b.tracer.now()
        a.send({"op": "TEST", "i": 50}, "Beta")   # alone: a raw frame
        assert await _pump(stacks, lambda: len(b.rx) == 51)
        batch, single = _wire_rx(b.tracer)
        for rec in (batch, single):
            assert rec[0] == "X" and rec[2] == CAT_WIRE == "wire"
            assert set(rec[6]) == {"messages", "sigs", "bytes", "cpu_us"}
            assert 0 <= rec[6]["cpu_us"] <= (rec[4] - rec[3]) * 1e6 + 50
        assert (batch[6]["messages"], batch[6]["sigs"]) == (50, 1)
        assert (single[6]["messages"], single[6]["sigs"]) == (1, 0)
        assert batch[6]["bytes"] > single[6]["bytes"] > 0
        # the span starts with the bytes in hand: the read loop's wait
        # for the second frame (which began before it was sent) is in
        # no span
        assert single[3] >= sent_at
        # a probe on Beta's dialed link: Alpha takes it in on its
        # accepted link, Beta the answer on its dialed one
        b.remotes["Alpha"].conn.send_frame(b"pi")
        assert await _pump(stacks, lambda: len(_wire_rx(b.tracer)) == 3)
        [ping] = _wire_rx(a.tracer)
        pong = _wire_rx(b.tracer)[2]
        for rec in (ping, pong):
            assert (rec[6]["messages"], rec[6]["sigs"]) == (0, 0)
        assert pong[3] >= ping[4]
        # disarmed: frames still delivered, no span
        b.tracer.disarm()
        a.send({"op": "TEST", "i": 51}, "Beta")
        assert await _pump(stacks, lambda: len(b.rx) == 52)
        assert len(_wire_rx(b.tracer)) == 3
        for s in stacks.values():
            await s.stop()
    asyncio.new_event_loop().run_until_complete(main())


def test_client_link_records_one_wire_rx_per_frame():
    async def main():
        keys = NodeKeys(b"\x71" * 32)
        stack = ClientStack("Alpha.client", HA("127.0.0.1", 0), keys,
                            Config())
        await stack.start()
        stack.tracer = Tracer("Alpha")
        client = ClientConnection(stack.ha, expected_verkey=keys.verkey_raw)
        await client.connect()

        async def until(cond):
            for _ in range(500):
                if cond():
                    return True
                await asyncio.sleep(0.01)
            return cond()

        for i in range(3):
            client.send({"op": "REQ", "i": i})
        assert await until(lambda: len(stack.rx) == 3)
        client.conn.send_frame(b"\xc1")        # not msgpack: dropped
        assert await until(lambda: len(_wire_rx(stack.tracer)) == 4)
        spans = _wire_rx(stack.tracer)
        assert [r[6]["messages"] for r in spans] == [1, 1, 1, 0]
        assert all(r[2] == "wire" and r[6]["sigs"] == 0
                   and r[6]["bytes"] > 0 and r[6]["cpu_us"] >= 0
                   for r in spans)
        stack.tracer.disarm()
        client.send({"op": "REQ", "i": 3})
        assert await until(lambda: len(stack.rx) == 4)
        assert len(_wire_rx(stack.tracer)) == 4
        client.close()
        await stack.stop()
    asyncio.new_event_loop().run_until_complete(main())
