"""What decides `correct`: every answer due in the window against the
plain reference (reference/pool.py), every node's ledger and state
against the reference's replay, and the daemon's own account of where
it verified. Every number compared has the limit 0: the comparisons are
exact."""
import json

from reference import pool as ref

LIMITS = {
    "unconfirmed_writes": 0,     # valid, due, no f+1 matching REPLYs
    "unanswered_by_a_node": 0,   # valid, some node never sent a REPLY
    "wrong_replies": 0,          # REPLY body != the reference's txn
    "reference_disagrees": 0,    # generator's valid flag vs reference
    "corrupted_ordered": 0,      # corrupted write with a REPLY
    "corrupted_not_refused": 0,  # corrupted write some node let stand
    "seqno_faults": 0,           # seqNos not one run without gaps
    "nodes_off_ledger": 0,       # node's ledger size/root != replay
    "nodes_off_state": 0,        # node's state root != replay
    "nodes_on_accelerator": 0,   # node process that opened a device
    "daemon_faults": 0,          # off the tpu, step-downs, failed batches
    "generator_ran_dry": 0,
}


class Observed:
    """What a pool gave back: the operations with their answers, the
    nodes' reports, the daemon's ready file and final counters. The
    program fills it from a run; a control fills it from the reference
    with one guarantee broken."""

    def __init__(self, names, f, ops, reports, daemon_ready, daemon_stats,
                 ran_dry=False, tiny=False, expect_kernel=True):
        self.names = list(names)
        self.f = f
        self.ops = ops
        self.reports = reports
        self.daemon_ready = daemon_ready or {}
        self.daemon_stats = daemon_stats
        self.ran_dry = ran_dry
        self.tiny = tiny
        self.expect_kernel = expect_kernel


def compare(obs: Observed, genesis_domain_txns) -> dict:
    """→ {name: value} for every name in LIMITS, plus notes."""
    n = len(obs.names)
    out = dict.fromkeys(LIMITS, 0)
    notes = {}
    replay = ref.Replay(genesis_domain_txns)
    genesis_size = replay.size

    ordered = []   # (seqNo, txn) as the pool's answers give them
    for op in obs.ops:
        verkey = replay.verkey_of(op.request["identifier"])
        valid = verkey is not None and ref.signature_valid(
            op.request, verkey)
        if valid != op.valid:
            out["reference_disagrees"] += 1
        if not valid:
            if op.answers:
                out["corrupted_ordered"] += 1
            if len(op.refused) < n:
                out["corrupted_not_refused"] += 1
            continue
        if op.done is None:
            out["unconfirmed_writes"] += 1
        if sum(len(v) for v in op.answers.values()) < n:
            out["unanswered_by_a_node"] += 1
        best = None
        for body, nodes in op.answers.items():
            got = json.loads(body)
            md = got.get("txnMetadata") or {}
            # the REPLY repeats the seqNo beside the txn (merkle info)
            if got.pop("seqNo", md.get("seqNo")) != md.get("seqNo"):
                got["seqNo"] = "differs from txnMetadata"
            want = ref.expected_txn(op.request, md.get("seqNo"),
                                    md.get("txnTime"))
            if got != want:
                out["wrong_replies"] += len(nodes)
                notes.setdefault("first_wrong_reply",
                                 {"got": got, "want": want, "nodes": nodes})
            if best is None or len(nodes) > len(best[1]):
                best = (want, nodes)
        if best is not None and len(best[1]) > obs.f:
            ordered.append((best[0]["txnMetadata"]["seqNo"], best[0]))

    ordered.sort(key=lambda x: x[0] if isinstance(x[0], int) else -1)
    for i, (seq_no, txn) in enumerate(ordered):
        if seq_no != genesis_size + 1 + i:
            out["seqno_faults"] += 1
        replay.append(txn)
    want = {"size": replay.size, "ledger_root": replay.ledger_root(),
            "state_root": replay.state_root()}
    notes["reference"] = want
    for name in obs.names:
        r = obs.reports.get(name)
        if r is None:
            out["nodes_off_ledger"] += 1
            out["nodes_off_state"] += 1
            continue
        if r["Ledger_sizes"].get("domain") != want["size"] \
                or r["Committed_ledger_root_hashes"].get("domain") \
                != want["ledger_root"]:
            out["nodes_off_ledger"] += 1
            notes.setdefault("first_off_ledger", {
                "node": name, "size": r["Ledger_sizes"].get("domain"),
                "root": r["Committed_ledger_root_hashes"].get("domain")})
        if r["Committed_state_root_hashes"].get("domain") \
                != want["state_root"]:
            out["nodes_off_state"] += 1
        if r.get("Device_mesh", {}).get("platform") not in (None, "cpu"):
            out["nodes_on_accelerator"] += 1
    out["daemon_faults"] = len(daemon_problems(obs, notes))
    out["generator_ran_dry"] = int(obs.ran_dry)
    return {"values": out, "notes": notes}


def daemon_problems(obs: Observed, notes: dict):
    problems = []
    device = obs.daemon_ready.get("device") or {}
    if device.get("platform") != "tpu":
        problems.append("the daemon holds %s, not a tpu" % device)
    stats = obs.daemon_stats
    if stats is None:
        problems.append("the daemon printed no final stats line")
    else:
        if stats["device_launches"] < 1:
            problems.append("no device launch")
        if stats["failed_batches"]:
            problems.append("failed_batches=%d" % stats["failed_batches"])
        if stats.get("step_downs"):
            problems.append("step_downs=%s" % stats["step_downs"])
        if stats["mesh"]["dispatches"] < stats["device_launches"]:
            problems.append("%d of %d launches reached the dispatcher" % (
                stats["mesh"]["dispatches"], stats["device_launches"]))
        if obs.expect_kernel and not obs.tiny \
                and not all(stats.get("kernel_backends", {}).values()):
            problems.append("ed25519 did not take the Pallas kernel: %s"
                            % stats.get("kernel_backends"))
    if problems:
        notes["daemon_problems"] = problems
    return problems


def verdict(values: dict) -> bool:
    return all(values[k] <= LIMITS[k] for k in LIMITS)


def table(values: dict) -> dict:
    """{name: [value, limit]} as the result line carries it."""
    return {k: [values[k], LIMITS[k]] for k in LIMITS}
