"""The load generator's side of the pool: one encrypted connection to
every node's client listener (the program's ClientConnection, the entry
an Indy client uses), every request broadcast request by request as
PoolClient does, and a log of what came back and when."""
import asyncio
import json
import time


class Op:
    """One operation: what was sent, when it was due, what came back."""
    __slots__ = ("request", "wire", "valid", "due", "sent", "done",
                 "answers", "refused")

    def __init__(self, request, wire, valid):
        self.request = request
        self.wire = wire
        self.valid = valid
        self.due = None       # perf_counter when it should have gone out
        self.sent = None      # perf_counter when the last copy went out
        self.done = None      # perf_counter when f+1 answers matched
        self.answers = {}     # canonical REPLY body -> [node, ...]
        self.refused = {}     # node -> (REQNACK | REJECT, reason)


def reply_body(result: dict) -> str:
    """A REPLY without its proof: a node that ordered a request before
    its own client copy arrived answers from the ledger later and proves
    the same txn against a later tree (settled in PR 22, cause 3), so
    rootHash and auditPath legitimately differ between nodes."""
    return json.dumps({k: v for k, v in result.items()
                       if k not in ("rootHash", "auditPath")},
                      sort_keys=True, default=str)


class Client:
    def __init__(self, names, f: int):
        self.names = list(names)
        self.f = f
        self.conns = {}
        self.ops = {}           # reqId -> Op
        self.stray = 0          # messages that match no operation sent

    async def connect(self, base_dir: str, deadline: float) -> None:
        from plenum_tpu.bootstrap import (
            client_ha_from_txns, pool_genesis_txns, registry_from_txns)
        from plenum_tpu.network.stack import ClientConnection
        pool_txns = pool_genesis_txns(base_dir)
        registry = registry_from_txns(pool_txns)
        for name in self.names:
            ha = client_ha_from_txns(pool_txns, name)
            while True:
                conn = ClientConnection(
                    ha, expected_verkey=registry[name].verkey)
                try:
                    await conn.connect()
                    self.conns[name] = conn
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise RuntimeError("node %s never came up" % name)
                    await asyncio.sleep(0.25)

    @staticmethod
    def wire(request: dict) -> bytes:
        from plenum_tpu.network.stack import serializer
        return serializer.serialize(request)

    def register(self, op: Op) -> None:
        self.ops[op.request["reqId"]] = op

    def send(self, op: Op) -> None:
        for conn in self.conns.values():
            conn.conn.send_frame(op.wire)
        op.sent = time.perf_counter()

    def dead_links(self):
        return [n for n, c in self.conns.items()
                if c.conn is None or not c.conn.alive]

    def drain(self):
        """Take in what the reader tasks queued → ops newly confirmed
        (valid: f+1 matching REPLYs) or newly refused by f+1 nodes."""
        now = time.perf_counter()
        resolved = []
        need = self.f + 1
        for name, conn in self.conns.items():
            rx = conn.rx
            while rx:
                m = rx.popleft()
                kind = m.get("op")
                if kind == "REPLY":
                    result = m.get("result") or {}
                    rid = result.get("txn", {}).get(
                        "metadata", {}).get("reqId")
                    op = self.ops.get(rid)
                    if op is None:
                        self.stray += 1
                        continue
                    nodes = op.answers.setdefault(reply_body(result), [])
                    nodes.append(name)
                    if op.done is None and len(nodes) >= need:
                        op.done = now
                        resolved.append(op)
                elif kind in ("REQNACK", "REJECT"):
                    op = self.ops.get(m.get("reqId"))
                    if op is None:
                        self.stray += 1
                        continue
                    op.refused[name] = (kind, m.get("reason"))
                    if not op.valid and op.done is None \
                            and len(op.refused) >= need:
                        op.done = now
                        resolved.append(op)
                elif kind != "REQACK":
                    self.stray += 1
        return resolved

    def settled(self, op: Op) -> bool:
        """Every node has said its last word on this operation."""
        if op.valid:
            return sum(len(v) for v in op.answers.values()) \
                >= len(self.names)
        return len(op.refused) >= len(self.names)

    def close(self) -> None:
        for conn in self.conns.values():
            conn.close()
