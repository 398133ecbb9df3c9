"""Controls: the plain reference put in the pool's place with ONE
guarantee of the configuration broken. Each must come out as not
correct under check.compare, at the cell's own size.

  no_signature_check  a pool that orders a write whatever its signature
                      says (the guarantee: a write with a bad signature
                      is refused by every node and ordered by none)
  one_reply_enough    a pool in which a write is answered by one node
                      only (the guarantee: f+1 matching REPLYs)
  node_loses_tail     one node's ledger and state stop short of the last
                      committed write (the guarantee: every node ends
                      with the same ledger and state)
"""
import copy

from check import Observed
from client import Op, reply_body
from reference import pool as ref

CONTROLS = ("no_signature_check", "one_reply_enough", "node_loses_tail")


def reference_pool(names, f, ops, genesis_domain_txns, daemon_ready,
                   daemon_stats, tiny, break_guarantee=None,
                   txn_time=1700000000) -> Observed:
    """Answers and reports as a pool made of the reference would give
    them to the same operations, in the order they were released."""
    replay = ref.Replay(genesis_domain_txns)
    out_ops = []
    last = None
    for op in ops:
        new = Op(op.request, op.wire, op.valid)
        new.due, new.sent = op.due, op.sent
        verkey = replay.verkey_of(op.request["identifier"])
        ok = verkey is not None and ref.signature_valid(op.request, verkey)
        if ok or break_guarantee == "no_signature_check":
            last = ref.expected_txn(op.request, replay.size + 1, txn_time)
            replay.append(last)
            answering = names[:1] if break_guarantee == "one_reply_enough" \
                else names
            new.answers[reply_body(last)] = list(answering)
            new.done = op.done if len(answering) > f else None
        else:
            new.refused = {n: ("REQNACK", "bad signature") for n in names}
            new.done = op.done
        out_ops.append(new)
    whole = (replay.size, replay.ledger_root(), replay.state_root())
    short = None
    if break_guarantee == "node_loses_tail" and last is not None:
        # the same ledger and state without the last committed write
        replay.leaf_hashes.pop()
        del replay.state[last["txn"]["data"]["dest"].encode()]
        short = (replay.size, replay.ledger_root(), replay.state_root())
    reports = {}
    for i, name in enumerate(names):
        size, lroot, sroot = short if (
            short and i == len(names) - 1) else whole
        reports[name] = {
            "Ledger_sizes": {"domain": size},
            "Committed_ledger_root_hashes": {"domain": lroot},
            "Committed_state_root_hashes": {"domain": sroot},
            "Device_mesh": {}}
    return Observed(names, f, out_ops, reports, copy.deepcopy(daemon_ready),
                    copy.deepcopy(daemon_stats), tiny=tiny)
