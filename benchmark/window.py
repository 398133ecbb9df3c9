"""The measured window: release operations as the plan says, take in
answers, stop releasing when the window closes, then wait for every
answer that is due."""
import asyncio
import time


async def probe(client, op, deadline: float) -> None:
    """The pool needs a primary before it orders: resend one valid write
    until every node has answered it. Part of set-up."""
    while True:
        client.send(op)
        for _ in range(20):
            await asyncio.sleep(0.05)
            client.drain()
            if client.settled(op):
                return
        if time.monotonic() > deadline:
            raise RuntimeError("the pool never ordered the probe write")


async def run_window(client, ops, plan: dict, seconds: float,
                     on_mark=None, marks=()):
    """→ dict: t0, t1 (perf_counter), released ops in order. `marks` are
    (seconds from start, label) at which on_mark(label) is called."""
    marks = sorted(marks)
    mi = 0
    released = []
    nxt = 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    closed = plan["kind"] == "closed"
    inflight = 0
    dry = False
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        while mi < len(marks) and now - t0 >= marks[mi][0]:
            on_mark(marks[mi][1])
            mi += 1
        if closed:
            room = plan["outstanding"] - inflight
            if room > 0 and nxt + room > len(ops):
                dry = True
                room = len(ops) - nxt
            for _ in range(max(0, room)):
                op = ops[nxt]
                nxt += 1
                op.due = now
                client.send(op)
                released.append(op)
                inflight += 1
        else:
            due = plan["due"]
            while nxt < len(due) and t0 + due[nxt] <= now:
                op = ops[nxt]
                op.due = t0 + due[nxt]
                nxt += 1
                client.send(op)
                released.append(op)
        await asyncio.sleep(0.002)
        inflight -= len(client.drain())
    t1 = time.perf_counter()
    while mi < len(marks):
        on_mark(marks[mi][1])
        mi += 1
    return {"t0": t0, "t1": t1, "released": released, "ran_dry": dry}


async def drain(client, released, deadline: float) -> float:
    """Wait for every node's last word on every released operation, a
    minute past the close if need be → seconds waited."""
    t = time.perf_counter()
    pending = [op for op in released if not client.settled(op)]
    while pending and time.monotonic() < deadline:
        await asyncio.sleep(0.02)
        client.drain()
        pending = [op for op in pending if not client.settled(op)]
    return time.perf_counter() - t
