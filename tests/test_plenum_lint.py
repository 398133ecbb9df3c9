"""plenum-lint rule fixtures — every rule must fire on its historical
bug shape and stay quiet on the fixed shape.

Each PTxxx case pins (bad → fires, good → clean) against snippets
modeled on the actual incidents: PT003's bad fixture IS the pre-PR-1
propagator pattern, PT002's the eager-device-probe/asarray-in-dispatch
shapes PR 4 removed, PT006's the broad excepts PR 2 narrowed. Plus
pragma suppression, baseline round-trip/count/stale semantics, the
--json schema, and CLI plumbing (--changed empty diff, --select /
--disable / --severity, unknown-code rejection).
"""
import ast
import json
import os
import subprocess
import sys
import textwrap

import pytest

from plenum_tpu.analysis import repo_root, run_analysis
from plenum_tpu.analysis.baseline import Baseline
from plenum_tpu.analysis.core import Analyzer, ModuleContext
from plenum_tpu.analysis.cli import main as cli_main
from plenum_tpu.analysis.rules import RULE_CLASSES, build_rules
from plenum_tpu.analysis.rules.pt005_config_drift import (
    ConfigLiteralDriftRule, load_config_values)

REPO = repo_root()


def check_snippet(rule, source, rel_path):
    """Run one rule over an in-memory module."""
    source = textwrap.dedent(source)
    ctx = ModuleContext(rel_path, source, ast.parse(source))
    assert rule.applies(rel_path), (rule.code, rel_path)
    findings = [f for f in rule.check(ctx)
                if not ctx.suppressed(f.rule, f.line)]
    return findings


def rule_by_code(code, **kwargs):
    for cls in RULE_CLASSES:
        if cls.code == code:
            return cls(**kwargs) if kwargs else cls()
    raise AssertionError(code)


# --------------------------------------------------------------- PT001

PT001_BAD = """
    import time

    class Service:
        def process_propagate(self, msg, frm):
            time.sleep(0.1)

        async def serve_forever(self):
            data = open("/tmp/x").read()
            return self.pending.result(), data
"""

PT001_GOOD = """
    import asyncio

    class Service:
        def process_propagate(self, msg, frm):
            self.queue.append(msg)

        async def serve_forever(self):
            await asyncio.sleep(0.1)
            out = await self.loop.run_in_executor(None, self.work)
            return out
"""


def test_pt001_fires_on_blocking_calls_in_handlers():
    findings = check_snippet(rule_by_code("PT001"), PT001_BAD,
                             "plenum_tpu/server/svc.py")
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 3
    assert "time.sleep" in msgs
    assert "Future.result()" in msgs
    assert "open()" in msgs


def test_pt001_clean_on_async_idioms():
    assert check_snippet(rule_by_code("PT001"), PT001_GOOD,
                         "plenum_tpu/consensus/svc.py") == []


def test_pt001_scoped_to_server_and_consensus():
    rule = rule_by_code("PT001")
    assert not rule.applies("plenum_tpu/ops/merkle.py")
    assert not rule.applies("plenum_tpu/client/client.py")


# --------------------------------------------------------------- PT002

PT002_BAD = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def _probe():
        return jax.devices()[0].platform   # the pre-PR-4 eager probe

    def dispatch_batch(rows):
        out = _kernel(jnp.asarray(rows))
        out.block_until_ready()
        return np.asarray(out)
"""

PT002_GOOD = """
    import jax.numpy as jnp
    import numpy as np

    def dispatch_batch(rows):
        idx = np.asarray(list(rows))       # host data: no taint
        return _kernel(jnp.asarray(idx))   # un-awaited device handle

    def collect_batch(handle):
        return np.asarray(handle)          # collect half MAY sync
"""


def test_pt002_fires_on_eager_probe_and_dispatch_syncs():
    findings = check_snippet(rule_by_code("PT002"), PT002_BAD,
                             "plenum_tpu/ops/newkernel.py")
    rules_hit = [f.message.split(" ")[0] for f in findings]
    assert len(findings) == 3, findings
    assert any("jax.devices" in f.message for f in findings)
    assert any("block_until_ready" in f.message for f in findings)
    assert any("np.asarray() on a device array" in f.message
               for f in findings)
    del rules_hit


def test_pt002_clean_on_async_dispatch_and_collect():
    assert check_snippet(rule_by_code("PT002"), PT002_GOOD,
                         "plenum_tpu/ops/newkernel.py") == []


def test_pt002_mesh_module_is_exempt():
    assert not rule_by_code("PT002").applies("plenum_tpu/ops/mesh.py")


def test_pt002_nested_def_does_not_leak_taint():
    """A nested worker's device locals are a different scope: they must
    not taint the outer dispatch half's same-named host variables."""
    src = """
        import jax.numpy as jnp
        import numpy as np

        def dispatch_batch(rows):
            def worker(x):
                out = jnp.add(x, x)
                return out
            out = [1, 2, 3]               # host list, same name
            return int(out[0]), np.asarray(out), worker
    """
    assert check_snippet(rule_by_code("PT002"), src,
                         "plenum_tpu/ops/newkernel.py") == []


def test_pt002_taint_chains_resolve_regardless_of_order():
    src = """
        import jax.numpy as jnp
        import numpy as np

        def dispatch_batch(rows):
            c = b                          # chain head textually first
            b = a
            a = jnp.asarray(rows)
            return np.asarray(c)           # still a device sync
    """
    findings = check_snippet(rule_by_code("PT002"), src,
                             "plenum_tpu/ops/newkernel.py")
    assert len(findings) == 1
    assert "np.asarray() on a device array" in findings[0].message


# --------------------------------------------------------------- PT003

# the literal pre-PR-1 propagator shape: first-sighting payloads enter
# the vote-collecting state without authentication
PT003_BAD = """
    class Propagator:
        def _process_one(self, payload, sender_client, frm):
            state = self.requests.lookup_state(payload)
            if state is None:
                state = self.requests.add(Request.from_dict(payload))
            state.propagates.add(frm)
            if self.quorums.propagate.is_reached(len(state.propagates)):
                self._finalise(state)
"""

PT003_GOOD = """
    class Propagator:
        def _process_one(self, payload, sender_client, frm):
            state = self.requests.lookup_state(payload)
            if state is None:
                request = Request.from_dict(payload)
                if self._authenticator is not None \\
                        and not self._authenticator(request):
                    return
                state = self.requests.add(request)
            state.propagates.add(frm)
            if self.quorums.propagate.is_reached(len(state.propagates)):
                self._finalise(state)

        def propagate(self, request, client_name):
            # client-intake path: no frm param, authenticated at intake
            state = self.requests.add(request)
            state.propagates.add(self.name)
"""


def test_pt003_fires_on_pre_pr1_propagator_pattern():
    findings = check_snippet(rule_by_code("PT003"), PT003_BAD,
                             "plenum_tpu/server/propagator.py")
    assert len(findings) == 1
    assert "without an authenticator check" in findings[0].message
    assert findings[0].symbol == "Propagator._process_one"


def test_pt003_clean_on_authenticated_handler():
    assert check_snippet(rule_by_code("PT003"), PT003_GOOD,
                         "plenum_tpu/server/propagator.py") == []


def test_pt003_live_gate_on_real_propagator():
    """Stripping the authenticator gate from the REAL propagator must
    produce a non-baselined PT003 — the regression the rule exists
    for."""
    path = os.path.join(REPO, "plenum_tpu", "server", "propagator.py")
    with open(path) as f:
        src = f.read()
    assert "_authenticator" in src
    hole = src.replace("self._authenticator", "self._ignored")
    ctx = ModuleContext("plenum_tpu/server/propagator.py", hole,
                        ast.parse(hole))
    findings = rule_by_code("PT003").check(ctx)
    assert any(f.symbol == "Propagator._process_one" for f in findings)
    # and the current source stays clean
    ctx2 = ModuleContext("plenum_tpu/server/propagator.py", src,
                         ast.parse(src))
    assert rule_by_code("PT003").check(ctx2) == []


# --------------------------------------------------------------- PT004

PT004_BAD = """
    import threading

    class Daemon:
        def start(self):
            self._t = threading.Thread(target=self._work)
            self._t.start()

        def _work(self):
            self.count += 1

        def report(self):
            self.count = 0
"""

PT004_GOOD = """
    import threading

    class Daemon:
        def start(self):
            self._t = threading.Thread(target=self._work)
            self._t.start()

        def _work(self):
            with self._lock:
                self.count += 1
            self._buf[0] = "x"      # fixed-slot write: not a rebind

        def report(self):
            with self._lock:
                self.count = 0
            self._buf[1] = "y"
"""


def test_pt004_fires_on_unlocked_cross_thread_writes():
    findings = check_snippet(rule_by_code("PT004"), PT004_BAD,
                             "plenum_tpu/server/daemon.py")
    assert len(findings) == 1
    assert "self.count" in findings[0].message


def test_pt004_clean_on_locked_and_fixed_slot_writes():
    assert check_snippet(rule_by_code("PT004"), PT004_GOOD,
                         "plenum_tpu/server/daemon.py") == []


# PT004 pipeline boundaries (PR 19): queue-crossing values must be
# immutable, and consensus state is prod-thread-owned — a worker-side
# write flags with no loop-side co-writer at all.

PT004_PIPELINE_BAD = """
    import threading

    class Stage:
        def start(self):
            self._t = threading.Thread(target=self._work)
            self._t.start()

        def feed(self, env, frm):
            self._queue.put({"env": env, "frm": frm})

        def _work(self):
            self.prepares = {}
"""

PT004_PIPELINE_GOOD = """
    import threading

    class Stage:
        def start(self):
            self._t = threading.Thread(target=self._work)
            self._t.start()

        def feed(self, job):
            self._queue.put(job)        # frozen record crosses whole

        def _work(self):
            parsed = {}                 # worker-local is fine
            self._buf[0] = parsed       # fixed-slot handoff
"""


def test_pt004_flags_mutable_container_crossing_queue():
    findings = check_snippet(rule_by_code("PT004"), PT004_PIPELINE_BAD,
                             "plenum_tpu/runtime/stage.py")
    assert any("mutable dict crosses a thread queue" in f.message
               for f in findings)


def test_pt004_flags_worker_side_consensus_state_write():
    findings = check_snippet(rule_by_code("PT004"), PT004_PIPELINE_BAD,
                             "plenum_tpu/runtime/stage.py")
    assert any("self.prepares" in f.message
               and "owned by the prod thread" in f.message
               for f in findings)


def test_pt004_clean_on_frozen_records_and_local_state():
    assert check_snippet(rule_by_code("PT004"), PT004_PIPELINE_GOOD,
                         "plenum_tpu/runtime/stage.py") == []


# --------------------------------------------------------------- PT005

PT005_BAD = """
    def make_daemon(bucket: int = 4096, floor=512):
        pass

    def route(n):
        if n >= 2048:
            return "device"
        return "host"
"""

PT005_GOOD = """
    def make_daemon(bucket: int = None, floor=None):
        from plenum_tpu.common.config import Config
        bucket = Config.VERIFY_DAEMON_BUCKET if bucket is None else bucket

    def widths(sig, vk):
        # equality width checks and shape math are structure, not knobs
        ok = len(sig) != 64 and len(vk) == 32
        buf = 64 * 1024 * 1024
        return ok, buf, sig[32:]
"""


def _pt005_rule():
    values = load_config_values(
        os.path.join(REPO, "plenum_tpu", "common", "config.py"))
    return ConfigLiteralDriftRule(config_values=values)


def test_pt005_fires_on_threshold_shaped_duplicates():
    findings = check_snippet(_pt005_rule(), PT005_BAD,
                             "plenum_tpu/server/newdaemon.py")
    hit = {f.message.split()[1] for f in findings}
    assert hit == {"4096", "512", "2048"}
    assert any("MERKLE_DEVICE_PROOF_CHUNK" in f.message
               or "VERIFY_DAEMON_BUCKET" in f.message for f in findings)


def test_pt005_clean_on_config_refs_and_structure_math():
    assert check_snippet(_pt005_rule(), PT005_GOOD,
                         "plenum_tpu/server/newdaemon.py") == []


def test_pt005_config_values_constant_folding():
    values = load_config_values(
        os.path.join(REPO, "plenum_tpu", "common", "config.py"))
    assert "VERIFY_DAEMON_BUCKET" in values[4096]
    assert "TRACING_BUFFER_SPANS" in values[1 << 16]   # 1 << 16 folded
    assert "MSG_LEN_LIMIT" in values[128 * 1024]       # 128 * 1024


# --------------------------------------------------------------- PT006

PT006_BAD = """
    from plenum_tpu.ops import ed25519_jax

    def verify(items):
        try:
            return ed25519_jax.verify_batch(items)
        except Exception:
            return None
"""

PT006_GOOD = """
    from plenum_tpu.ops import ed25519_jax

    def verify(items):
        try:
            return ed25519_jax.verify_batch(items)
        except (AttributeError, NotImplementedError):   # PR 2 precedent
            return None

    def relog(items):
        try:
            return ed25519_jax.verify_batch(items)
        except Exception:
            log("failed")
            raise                       # re-raise: swallows nothing
"""


def test_pt006_fires_on_broad_except_over_device_call():
    findings = check_snippet(rule_by_code("PT006"), PT006_BAD,
                             "plenum_tpu/server/v.py")
    assert len(findings) == 1
    assert "ed25519_jax.verify_batch" in findings[0].message


def test_pt006_clean_on_narrow_or_reraising_handlers():
    assert check_snippet(rule_by_code("PT006"), PT006_GOOD,
                         "plenum_tpu/server/v.py") == []


def test_pt006_any_call_counts_inside_ops_and_crypto():
    src = """
        def load():
            try:
                return _local_builder()
            except Exception:
                return None
    """
    assert check_snippet(rule_by_code("PT006"), src,
                         "plenum_tpu/crypto/newlib.py")
    assert not check_snippet(rule_by_code("PT006"), src,
                             "plenum_tpu/storage/helper2.py")


# --------------------------------------------------------------- PT007

# the PR-7 incident shape: the leecher's fixed-period retry timer
PT007_BAD = """
    from plenum_tpu.runtime.timer import RepeatingTimer

    class Leecher:
        def start(self):
            self._retry_timer = RepeatingTimer(self._timer, 6,
                                               self._retry)

        def _arm_resend(self):
            self._t = RepeatingTimer(self._timer, interval=2.5,
                                     callback=self._resend)
"""

PT007_GOOD = """
    from plenum_tpu.runtime.timer import RepeatingTimer

    class Leecher:
        def start(self):
            # config-sourced period is fine even on a retry target...
            self._retry_timer = RepeatingTimer(
                self._timer, self._config.CATCHUP_TXN_TIMEOUT,
                self._retry)

        def _schedule_retry(self):
            # ...and one-shot self-rescheduling with backoff is the
            # preferred shape (no RepeatingTimer at all)
            self._timer.schedule(self._retry_delay(), self._fire)

        def start_metrics(self):
            # periodic NON-retry work may keep a literal cadence
            self._flush_timer = RepeatingTimer(self._timer, 10,
                                               self._flush)
"""


def test_pt007_fires_on_literal_period_retry_timers():
    findings = check_snippet(rule_by_code("PT007"), PT007_BAD,
                             "plenum_tpu/server/catchup2.py")
    assert len(findings) == 2
    assert all("backoff" in f.message for f in findings)


def test_pt007_clean_on_config_period_backoff_and_non_retry():
    assert check_snippet(rule_by_code("PT007"), PT007_GOOD,
                         "plenum_tpu/server/catchup2.py") == []


def test_pt007_out_of_scope_paths():
    rule = rule_by_code("PT007")
    assert not rule.applies("plenum_tpu/testing/adversary/controller.py")
    assert rule.applies("plenum_tpu/client/client.py")


# --------------------------------------------------------------- PT008

# the PR-8 incident shape: _has_prepared re-counting the sender dict on
# every inbound PREPARE (O(n) per message, O(n^2) per batch per node)
PT008_BAD = """
    class OrderingService:
        def _has_prepared(self, key):
            count = len([s for s in self.prepares[key]
                         if s != self._data.primary_name])
            return self._data.quorums.prepare.is_reached(count)

        def process_commit(self, commit, frm):
            for sender in self.commits[(commit.viewNo,
                                        commit.ppSeqNo)].items():
                self._check(sender)
"""

PT008_GOOD = """
    class OrderingService:
        def _has_prepared(self, key):
            # incremental counter maintained at vote insert: one dict
            # read per quorum check
            return self._data.quorums.prepare.is_reached(
                self._prepare_vote_count.get(key, 0))

        def process_preprepare_batch(self, pps, frm):
            # ONE loop per inbound wire batch is the columnar design,
            # not the quadratic shape — batch handlers are exempt
            for pp in pps:
                for digest in pp.reqIdr:
                    self._note_digest(digest, frm)

        def _gc_below(self, seq):
            # non-handler housekeeping may walk the stores
            for key in [k for k in self.commits if k[1] <= seq]:
                del self.commits[key]
"""


def test_pt008_fires_on_per_item_loops_in_hot_handlers():
    findings = check_snippet(rule_by_code("PT008"), PT008_BAD,
                             "plenum_tpu/consensus/ordering2.py")
    assert len(findings) == 2
    assert all("columnar" in f.message for f in findings)


def test_pt008_clean_on_counters_batch_handlers_and_housekeeping():
    assert check_snippet(rule_by_code("PT008"), PT008_GOOD,
                         "plenum_tpu/consensus/ordering2.py") == []


def test_pt008_out_of_scope_paths():
    rule = rule_by_code("PT008")
    assert rule.applies("plenum_tpu/consensus/ordering_service.py")
    assert not rule.applies("plenum_tpu/server/propagator.py")
    assert not rule.applies("plenum_tpu/testing/sim_network.py")


# --------------------------------------------------------------- PT009

# the cardinality-bomb shape the TM registry exists to prevent: a
# per-peer/per-ledger metric NAME mints a new time series per value
PT009_BAD = """
    class Service:
        def serve(self, peer, ledger_id, hub):
            self.telemetry.observe("latency_%s" % peer, 1.5)
            self.telemetry.count(f"retries_{ledger_id}")
            hub.record_launch("seam_{}".format(ledger_id), 8, 16)
            with self.telemetry.timer("stage_" + peer):
                pass
"""

PT009_GOOD = """
    from plenum_tpu.observability.telemetry import TM, SEAM_MESH

    class Service:
        def serve(self, peer, ledger_id, hub, items):
            # registry constants: the closed name set
            self.telemetry.observe(TM.ORDERED_E2E_MS, 1.5)
            self.telemetry.count(TM.VIEW_CHANGES)
            hub.record_launch(SEAM_MESH, len(items), 16)
            # a plain literal is bounded cardinality (the dead-name
            # test owns orphan literals)
            self.telemetry.gauge("backlog_depth", len(items))
            # literal-only concatenation is a constant too
            self.telemetry.observe("stage_" "3pc_ms", 2.0)
            # unrelated builtins named count must not match
            n = "abc".count("a") + [1, 2].count(1)
            return n
"""


def test_pt009_fires_on_dynamic_metric_names():
    findings = check_snippet(rule_by_code("PT009"), PT009_BAD,
                             "plenum_tpu/server/some_service.py")
    assert len(findings) == 4
    assert all("time series" in f.message for f in findings)


def test_pt009_clean_on_registry_constants_and_literals():
    assert check_snippet(rule_by_code("PT009"), PT009_GOOD,
                         "plenum_tpu/server/some_service.py") == []


def test_pt009_whole_tree_is_clean():
    # every live record site uses registry constants — the rule gates
    # the tree it was written for
    new, baselined, _ = run_analysis([os.path.join(REPO, "plenum_tpu")],
                                     select=["PT009"])
    assert new == [] and baselined == []


# --------------------------------------------------------------- PT010

# the per-message wire shape the flat codec killed: one serializer /
# factory invocation per inner envelope entry in a hot wire handler
PT010_BAD = """
    class Stack:
        def _process_batch(self, msg, frm):
            for entry in msg.messages:
                m = node_message_factory.get_instance(**entry)
                self.rx.append(m)

        def flush_outboxes(self, out):
            frames = [self.serializer.serialize(m) for m in out]
            return frames

        def _unpack_wire(self, msg, frm):
            for raw in msg.get("messages", []):
                self.rx.append(serializer.deserialize(raw))
"""

PT010_GOOD = """
    class Stack:
        def _process_batch(self, msg, frm):
            # ONE parse for the whole envelope, columns to the intake
            env = flat_wire.parse_envelope(msg.payload)
            for sec in env.sections:
                self.route_columns(sec, frm)

        def flush_outboxes(self, out):
            # one pack per envelope, hoisted out of the per-item path
            payload = flat_wire.encode_three_pc([], out, [])
            self.send_frame(payload)

        def _collect(self, msg):
            # per-item loops without serializer calls are fine
            for entry in msg.messages:
                self.rx.append(entry)

        def summarize(self, report):
            # a serializer call over a non-wire collection is fine
            return [self.serializer.serialize(r)
                    for r in report.sections]
"""


def test_pt010_fires_on_per_item_serializer_calls():
    findings = check_snippet(rule_by_code("PT010"), PT010_BAD,
                             "plenum_tpu/network/some_stack.py")
    assert len(findings) == 3
    assert all("per-item" in f.message for f in findings)
    assert {f.message.split("'")[1] for f in findings} \
        == {"get_instance", "serialize", "deserialize"}


def test_pt010_clean_on_whole_envelope_codec():
    assert check_snippet(rule_by_code("PT010"), PT010_GOOD,
                         "plenum_tpu/network/some_stack.py") == []


def test_pt010_nested_loops_report_one_finding_per_call():
    src = """
        class Stack:
            def flush_all(self, out):
                for chunk in out:
                    for m in chunk:
                        self.serializer.serialize(m)
    """
    findings = check_snippet(rule_by_code("PT010"), src,
                             "plenum_tpu/network/some_stack.py")
    assert len(findings) == 1


def test_pt010_out_of_scope_layers_unchecked():
    # the codec itself (common/serializers/) legitimately loops over
    # per-item blobs — the rule scopes to the wire handler layers
    rule = rule_by_code("PT010")
    assert not rule.applies("plenum_tpu/common/serializers/flat_wire.py")
    assert rule.applies("plenum_tpu/network/stack.py")
    assert rule.applies("plenum_tpu/server/node.py")


def test_pt010_tree_has_only_justified_baseline_entries():
    # the untrusted client-batch unwrap is baselined with its
    # justification; nothing NEW may appear
    new, baselined, _ = run_analysis(
        [os.path.join(REPO, "plenum_tpu")], select=["PT010"],
        baseline_path=os.path.join(REPO, "lint_baseline.json"))
    assert new == []
    assert len(baselined) == 1


# --------------------------------------------------------------- PT011

# declaration drift the conflict-lane executor must never suffer: a
# write handler whose validation/apply reaches state keys its
# touched_keys declaration cannot produce (or that never declares)
PT011_BAD = """
    class DriftingHandler(WriteRequestHandler):
        def touched_keys(self, request):
            key = thing_to_state_key(request.operation["dest"])
            return TouchedKeys(reads=((1, key),), writes=((1, key),))

        def dynamic_validation(self, request, req_pp_time=None):
            # reachable: same recipe as the declaration
            key = thing_to_state_key(request.operation["dest"])
            self.state.get(key, isCommitted=False)
            # NOT reachable: a second key family the declaration
            # never mentions
            self.state.get(owner_index_key(request.identifier))

        def update_state(self, txn, prev_result, request,
                         is_committed=False):
            self.state.set(b"some:literal:key", b"v")
            # shadowing touched_keys' own local name must not grant
            # reachability to an undeclared recipe
            key = owner_index_key(request.identifier)
            self.state.get(key)


    class UndeclaredHandler(WriteRequestHandler):
        def dynamic_validation(self, request, req_pp_time=None):
            self.state.get(thing_to_state_key(request.operation["d"]))

        def update_state(self, txn, prev_result, request,
                         is_committed=False):
            domain_state = self.database_manager.get_state(1)
            domain_state.set(thing_to_state_key("x"), b"v")
"""

PT011_GOOD = """
    class DeclaredHandler(WriteRequestHandler):
        def touched_keys(self, request):
            key = thing_to_state_key(request.operation["dest"])
            return TouchedKeys(
                reads=((1, key), (1, REGISTRY_PATH)),
                writes=((1, key), (1, REGISTRY_PATH)))

        def dynamic_validation(self, request, req_pp_time=None):
            key = thing_to_state_key(request.operation["dest"])
            self.state.get(key, isCommitted=False)
            self.state.get(REGISTRY_PATH, isCommitted=False)

        def update_state(self, txn, prev_result, request,
                         is_committed=False):
            self.state.set(
                thing_to_state_key(get_payload_data(txn)["dest"]), b"v")
            self.state.set(REGISTRY_PATH, b"r")


    class NotAHandler:
        # state-shaped calls outside WriteRequestHandler classes are
        # out of scope
        def update_state(self, txn):
            self.state.set(b"whatever", b"v")


    class ReadSide(ReadRequestHandler):
        def get_result(self, request):
            return self.state.get(b"anything")
"""


def test_pt011_fires_on_undeclared_and_unreachable_keys():
    findings = check_snippet(rule_by_code("PT011"), PT011_BAD,
                             "plenum_tpu/server/handlers_x.py")
    # DriftingHandler: owner_index_key get + literal set + the
    # local-name-shadowing get; UndeclaredHandler: both accesses
    # (incl. the get_state local)
    assert len(findings) == 5
    msgs = [f.message for f in findings]
    assert sum("not reachable" in m for m in msgs) == 3
    assert sum("no touched_keys declaration" in m for m in msgs) == 2
    assert {f.symbol.split(".")[0] for f in findings} \
        == {"DriftingHandler", "UndeclaredHandler"}


def test_pt011_clean_on_declared_recipes():
    assert check_snippet(rule_by_code("PT011"), PT011_GOOD,
                         "plenum_tpu/server/handlers_x.py") == []


def test_pt011_tree_has_only_justified_baseline_entries():
    # NODE (whole-state scans) and the TAA digest-chain handlers are
    # inherently dynamic: serial-lane opt-outs carried as justified
    # baseline entries; nothing NEW may appear
    new, baselined, _ = run_analysis(
        [os.path.join(REPO, "plenum_tpu")], select=["PT011"],
        baseline_path=os.path.join(REPO, "lint_baseline.json"))
    assert new == []
    assert len(baselined) == 8


# -------------------------------------------------------------- pragmas

def test_inline_pragma_suppresses_one_line():
    src = """
        import time

        def process_x(self, frm):
            time.sleep(1)  # plenum-lint: disable=PT001
            time.sleep(2)
    """
    findings = check_snippet(rule_by_code("PT001"), src,
                             "plenum_tpu/server/s.py")
    assert [f.line for f in findings] == [6]


def test_file_level_pragma_and_disable_all():
    src = """\
        # plenum-lint: disable=PT001
        import time

        def process_x(self, frm):
            time.sleep(1)
    """
    assert check_snippet(rule_by_code("PT001"), src,
                         "plenum_tpu/server/s.py") == []
    src_all = src.replace("disable=PT001", "disable=all")
    assert check_snippet(rule_by_code("PT001"), src_all,
                         "plenum_tpu/server/s.py") == []


# ------------------------------------------------------------- baseline

def _fake_findings():
    from plenum_tpu.analysis.core import Finding
    f = Finding("PT006", "error", "plenum_tpu/x.py", 10, 4, "msg", "A.b")
    g = Finding("PT006", "error", "plenum_tpu/x.py", 30, 4, "msg", "A.b")
    h = Finding("PT001", "error", "plenum_tpu/y.py", 5, 0, "other", "C.d")
    return [f, g, h]


def test_baseline_round_trip_and_count_semantics(tmp_path):
    findings = _fake_findings()
    base = Baseline.from_findings(findings, justification="because")
    path = str(tmp_path / "baseline.json")
    base.save(path)
    loaded = Baseline.load(path)
    # duplicate (rule,path,symbol,message) collapses to count=2
    assert len(loaded.entries) == 2
    assert any(e.get("count") == 2 for e in loaded.entries)
    assert all(e["justification"] == "because" for e in loaded.entries)
    new, old = loaded.match(findings)
    assert new == [] and len(old) == 3
    # a third identical finding exceeds the count budget → new
    extra = findings + [findings[0]]
    new, old = loaded.match(extra)
    assert len(new) == 1 and len(old) == 3


def test_baseline_stale_and_line_drift(tmp_path):
    findings = _fake_findings()
    base = Baseline.from_findings(findings)
    drifted = [f.__class__(f.rule, f.severity, f.path, f.line + 100,
                           f.col, f.message, f.symbol) for f in findings]
    new, old = base.match(drifted[:2])          # y.py finding fixed
    assert new == [] and len(old) == 2          # lines don't matter
    assert ("PT001", "plenum_tpu/y.py", "C.d", "other") in base.stale()


def test_baseline_missing_file_is_empty(tmp_path):
    base = Baseline.load(str(tmp_path / "nope.json"))
    assert base.entries == []
    new, old = base.match(_fake_findings())
    assert len(new) == 3 and old == []


def test_baseline_version_mismatch_rejected(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"version": 99, "entries": []}))
    with pytest.raises(ValueError):
        Baseline.load(str(path))


# ------------------------------------------------------------------ CLI

def run_cli(args, capsys):
    code = cli_main(args)
    out = capsys.readouterr().out
    return code, out


def test_cli_json_schema_stability(capsys):
    code, out = run_cli(
        ["--json", os.path.join(REPO, "plenum_tpu", "ops", "mesh.py")],
        capsys)
    data = json.loads(out)
    assert code == 0
    assert sorted(data) == ["findings", "summary", "tool", "version"]
    assert data["version"] == 1 and data["tool"] == "plenum-lint"
    assert sorted(data["summary"]) == [
        "baselined", "errors", "files", "findings", "new", "warnings"]


def test_cli_json_finding_keys(tmp_path, capsys):
    bad = tmp_path / "plenum_tpu" / "server"
    bad.mkdir(parents=True)
    (bad / "s.py").write_text(textwrap.dedent(PT001_BAD))
    code, out = run_cli(["--json", "--no-baseline",
                         "--root", str(tmp_path), str(bad / "s.py")],
                        capsys)
    data = json.loads(out)
    assert code == 1
    assert data["summary"]["errors"] == 3
    for f in data["findings"]:
        assert sorted(f) == ["baselined", "col", "line", "message",
                             "path", "rule", "severity", "symbol"]


def test_cli_unknown_rule_code_rejected(capsys):
    code, _ = run_cli(["--disable", "PT999"], capsys)
    assert code == 2


def test_cli_severity_override_downgrades_exit(tmp_path, capsys):
    bad = tmp_path / "plenum_tpu" / "server"
    bad.mkdir(parents=True)
    (bad / "s.py").write_text(textwrap.dedent(PT001_BAD))
    code, _ = run_cli(["--no-baseline", "--root", str(tmp_path),
                       "--severity", "PT001=warning", str(bad / "s.py")],
                      capsys)
    assert code == 0


def test_cli_select_runs_single_rule(tmp_path, capsys):
    bad = tmp_path / "plenum_tpu" / "server"
    bad.mkdir(parents=True)
    (bad / "s.py").write_text(textwrap.dedent(PT001_BAD))
    code, out = run_cli(["--json", "--no-baseline", "--select", "PT003",
                         "--root", str(tmp_path), str(bad / "s.py")],
                        capsys)
    assert code == 0 and json.loads(out)["summary"]["findings"] == 0


def test_cli_changed_empty_diff_is_clean(tmp_path, capsys):
    """--changed against a scope with no changed files: clean message,
    exit 0 (the metrics_stats empty-store convention)."""
    code, out = run_cli(["--changed", str(tmp_path)], capsys)
    assert code == 0
    assert "no changed Python files" in out


def test_cli_changed_fails_closed_without_git(tmp_path, capsys):
    """--root outside any git repo: the pre-commit gate must error
    (exit 2), never read a broken git as an empty diff."""
    code = cli_main(["--changed", "--root", str(tmp_path)])
    capsys.readouterr()
    assert code == 2


def test_cli_changed_scope_respects_path_boundaries(tmp_path, capsys):
    """--changed with a scope of .../server must not pull in the
    sibling .../server_extra.py via bare prefix matching."""
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    subprocess.run(["git", "-C", str(tmp_path), "-c", "user.name=t",
                    "-c", "user.email=t@t", "commit", "-q",
                    "--allow-empty", "-m", "init"], check=True)
    pkg = tmp_path / "plenum_tpu"
    (pkg / "server").mkdir(parents=True)
    (pkg / "server" / "s.py").write_text(textwrap.dedent(PT001_BAD))
    (pkg / "server_extra.py").write_text(textwrap.dedent(PT001_BAD))
    code, out = run_cli(["--changed", "--json", "--no-baseline",
                         "--root", str(tmp_path),
                         str(pkg / "server")], capsys)
    data = json.loads(out)
    paths = {f["path"] for f in data["findings"]}
    assert data["summary"]["files"] == 1
    assert paths == {"plenum_tpu/server/s.py"}


def test_cli_nonexistent_path_errors(capsys):
    code, _ = run_cli([os.path.join(REPO, "plenum_tpu_TYPO")], capsys)
    assert code == 2


def test_cli_scoped_write_baseline_keeps_out_of_scope_entries(
        tmp_path, capsys):
    pkg = tmp_path / "plenum_tpu" / "server"
    pkg.mkdir(parents=True)
    (pkg / "a.py").write_text(textwrap.dedent(PT001_BAD))
    (pkg / "b.py").write_text(textwrap.dedent(PT001_BAD))
    bpath = tmp_path / "baseline.json"
    code, _ = run_cli(["--root", str(tmp_path), "--baseline", str(bpath),
                       "--write-baseline", str(pkg)], capsys)
    assert code == 0
    full = Baseline.load(str(bpath))
    # re-writing scoped to ONE file must keep the other file's entries
    code, _ = run_cli(["--root", str(tmp_path), "--baseline", str(bpath),
                       "--write-baseline", str(pkg / "a.py")], capsys)
    assert code == 0
    merged = Baseline.load(str(bpath))
    assert {e["path"] for e in merged.entries} \
        == {e["path"] for e in full.entries}
    code, _ = run_cli(["--root", str(tmp_path), "--baseline", str(bpath),
                       str(pkg)], capsys)
    assert code == 0


def test_cli_write_baseline_round_trip(tmp_path, capsys):
    bad = tmp_path / "plenum_tpu" / "server"
    bad.mkdir(parents=True)
    (bad / "s.py").write_text(textwrap.dedent(PT001_BAD))
    bpath = tmp_path / "baseline.json"
    code, _ = run_cli(["--root", str(tmp_path), "--baseline", str(bpath),
                       "--write-baseline", str(bad / "s.py")], capsys)
    assert code == 0 and bpath.exists()
    code, _ = run_cli(["--root", str(tmp_path), "--baseline", str(bpath),
                       str(bad / "s.py")], capsys)
    assert code == 0      # everything grandfathered


def test_script_entry_point_runs():
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "plenum_lint"),
         "--list-rules"], capture_output=True, text=True, timeout=60)
    assert res.returncode == 0
    for cls in RULE_CLASSES:
        assert cls.code in res.stdout


# ------------------------------------------------------------ integration

def test_parse_error_becomes_pt000(tmp_path):
    f = tmp_path / "broken.py"
    f.write_text("def oops(:\n")
    analyzer = Analyzer(build_rules(root=str(tmp_path)), str(tmp_path))
    findings = analyzer.run_files([str(f)])
    assert [x.rule for x in findings] == ["PT000"]


def test_run_analysis_matches_shipped_baseline():
    new, baselined, _ = run_analysis(
        [os.path.join(REPO, "plenum_tpu")], root=REPO,
        baseline_path=os.path.join(REPO, "lint_baseline.json"))
    assert new == [], "\n".join(f.render() for f in new)
    assert len(baselined) > 0


# ------------------------------------------------- PT012/13/14 (engine)

def check_program(code, files, tmp_path):
    """Run ONE whole-program rule over a fixture tree: files maps
    repo-relative paths to sources (written under tmp_path, which
    acts as the repo root — paths under plenum_tpu/... so root/rule
    scoping matches production)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    rule = rule_by_code(code)
    analyzer = Analyzer([rule], str(tmp_path), use_engine_cache=False)
    return analyzer.run_files(analyzer.collect_files([str(tmp_path)]))


# PT012 — the literal pre-fix PR-7 jitter shape: retry delay derived
# from hash() of a tuple CONTAINING THE NODE NAME (a str: salted by
# PYTHONHASHSEED, so every replica computes a different delay stream
# and seeded sims don't replay), reachable from a consensus root.
PT012_BAD_JITTER = """
    class LedgerLeecher:
        def _schedule_retry(self, retry):
            salt = str(self._name)
            unit = hash((salt, self.lid, retry))
            return (unit % 1000) / 1000.0
"""

# ...and the shipped fix (catchup.py today): crc32 of the name as an
# int salt, hash() only over ints (stable in CPython) — stays silent.
PT012_GOOD_JITTER = """
    import zlib

    class LedgerLeecher:
        def __init__(self, name):
            self._jitter_salt = zlib.crc32(name.encode())

        def _schedule_retry(self, retry):
            unit = hash((self._jitter_salt, self.lid, retry))
            return (unit % 1000) / 1000.0
"""

PT012_ROOT_CALLER = """
    from plenum_tpu.server.catchup import LedgerLeecher

    class ViewChangeService:
        def _request_catchup(self, retry):
            leecher = LedgerLeecher()
            return leecher._schedule_retry(retry)
"""


def test_pt012_fires_on_prefix_pr7_jitter_shape(tmp_path):
    findings = check_program("PT012", {
        "plenum_tpu/server/catchup.py": PT012_BAD_JITTER,
        "plenum_tpu/consensus/view_change_service.py":
            PT012_ROOT_CALLER,
    }, tmp_path)
    assert len(findings) == 1
    f = findings[0]
    assert f.path == "plenum_tpu/server/catchup.py"
    assert f.symbol == "LedgerLeecher._schedule_retry"
    assert "hash()" in f.message and "PYTHONHASHSEED" in f.message


def test_pt012_silent_on_shipped_crc32_fix(tmp_path):
    findings = check_program("PT012", {
        "plenum_tpu/server/catchup.py": PT012_GOOD_JITTER,
        "plenum_tpu/consensus/view_change_service.py":
            PT012_ROOT_CALLER,
    }, tmp_path)
    assert findings == []


def test_pt012_unreachable_source_stays_silent(tmp_path):
    """Reach-specificity: the same salted hash with NO path from any
    consensus root must not fire."""
    findings = check_program("PT012", {
        "plenum_tpu/server/catchup.py": PT012_BAD_JITTER,
    }, tmp_path)
    assert findings == []


def test_pt012_set_iteration_in_root_fires_and_sorted_passes(tmp_path):
    bad = """
        class ViewChangeService:
            def _finish_view_change(self, nv):
                referenced = {tuple(x) for x in nv.viewChanges}
                return [frm for frm, digest in referenced]
    """
    good = """
        class ViewChangeService:
            def _finish_view_change(self, nv):
                referenced = sorted({tuple(x) for x in nv.viewChanges})
                return [frm for frm, digest in referenced]
    """
    path = "plenum_tpu/consensus/view_change_service.py"
    fired = check_program("PT012", {path: bad}, tmp_path)
    assert len(fired) == 1 and "set" in fired[0].message
    assert check_program("PT012", {path: good}, tmp_path) == []


def test_pt012_unseeded_random_and_time_value(tmp_path):
    src = """
        import random
        import time

        def plan_lanes(touches):
            lane = random.choice(touches)
            return lane

        def _stamp():
            return time.time()

        def plan_more(touches):
            return _stamp()

        def _timer_delta_ok(t0):
            elapsed = time.time() - t0
            return len([elapsed])
    """
    findings = check_program("PT012", {
        "plenum_tpu/server/execution_lanes.py": src}, tmp_path)
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2
    assert any("random.choice" in m for m in msgs)
    assert any("time.time() escapes" in m for m in msgs)


def test_pt012_pragma_suppresses_program_finding(tmp_path):
    src = """
        import random

        def plan_lanes(touches):
            return random.choice(touches)  # plenum-lint: disable=PT012
    """
    assert check_program("PT012", {
        "plenum_tpu/server/execution_lanes.py": src}, tmp_path) == []


# PT013 — dispatch halves must reach their collect, including handles
# handed across functions (the PR 8 fused-window / PR 13 merged-resolve
# shape).
PT013_BAD = """
    from plenum_tpu.ops.trie_jax import dispatch_node_hash_batch

    def stage_level(blobs):
        handle = dispatch_node_hash_batch(blobs)
        return len(blobs)

    def fire_and_forget(blobs):
        dispatch_node_hash_batch(blobs)
"""

PT013_BAD_CROSS = """
    def stage_level(blobs):
        return dispatch_node_hash_batch(blobs)

    def apply_batch(blobs):
        stage_level(blobs)
        return True
"""

PT013_GOOD = """
    from plenum_tpu.ops.trie_jax import (
        collect_node_hash_batch, dispatch_node_hash_batch)

    def stage_level(blobs):
        handle = dispatch_node_hash_batch(blobs)
        return collect_node_hash_batch(handle)

    def stage_pipelined(self, blobs):
        self._inflight = dispatch_node_hash_batch(blobs)

    def stage_handoff(blobs):
        return dispatch_node_hash_batch(blobs)

    def apply_batch(blobs):
        h = stage_handoff(blobs)
        return collect_node_hash_batch(h)
"""


def test_pt013_fires_on_dropped_and_discarded_handles(tmp_path):
    findings = check_program("PT013", {
        "plenum_tpu/state/device_state.py": PT013_BAD}, tmp_path)
    assert len(findings) == 2
    assert {f.symbol for f in findings} == {"stage_level",
                                           "fire_and_forget"}
    assert all("node_hash_batch" in f.message for f in findings)


def test_pt013_fires_interprocedurally_on_dropped_handoff(tmp_path):
    """stage_level returns the open generation; apply_batch discards
    it — the finding lands at the frame that dropped it."""
    findings = check_program("PT013", {
        "plenum_tpu/state/device_state.py": PT013_BAD_CROSS},
        tmp_path)
    assert len(findings) == 1
    assert findings[0].symbol == "apply_batch"


def test_pt013_silent_on_collected_stored_and_handed_off(tmp_path):
    assert check_program("PT013", {
        "plenum_tpu/state/device_state.py": PT013_GOOD},
        tmp_path) == []


# PT014 — the literal pre-fix per-level Keccak shape (PR 6 review):
# batch rows = raw len(blobs), block axis = raw max(need) — one XLA
# compile per distinct level size.
PT014_BAD_KECCAK = """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    @functools.partial(jax.jit, static_argnames=("nblocks",))
    def _keccak_kernel(words, nblocks):
        return words

    def dispatch_level_hash(blobs):
        need = [len(b) // 136 + 1 for b in blobs]
        nblocks = max(need)
        arr = np.zeros((len(blobs), nblocks, 17), dtype=np.uint32)
        return _keccak_kernel(jnp.asarray(arr), nblocks)
"""

PT014_GOOD_KECCAK = """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from plenum_tpu.ops import pow2_at_least

    @functools.partial(jax.jit, static_argnames=("nblocks",))
    def _keccak_kernel(words, nblocks):
        return words

    def dispatch_level_hash(blobs):
        need = [len(b) // 136 + 1 for b in blobs]
        nblocks = pow2_at_least(max(need))
        bp = pow2_at_least(len(blobs))
        arr = np.zeros((bp, nblocks, 17), dtype=np.uint32)
        return _keccak_kernel(jnp.asarray(arr), nblocks)
"""

# the r05 / bls381 shape: bucketed on one branch, raw on the other
PT014_BAD_CONDITIONAL = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from plenum_tpu.ops import pow2_at_least

    @jax.jit
    def _kernel(rows):
        return rows

    def dispatch_jobs(jobs, sharded):
        bp = pow2_at_least(len(jobs)) if sharded else len(jobs)
        arr = np.zeros((bp, 48), dtype=np.uint8)
        return _kernel(jnp.asarray(arr))
"""


def test_pt014_fires_on_prefix_keccak_shape(tmp_path):
    findings = check_program("PT014", {
        "plenum_tpu/ops/sha3.py": PT014_BAD_KECCAK}, tmp_path)
    assert len(findings) == 1
    f = findings[0]
    assert f.symbol == "dispatch_level_hash"
    assert "_keccak_kernel" in f.message
    assert "compile" in f.message


def test_pt014_silent_on_bucketed_shapes(tmp_path):
    assert check_program("PT014", {
        "plenum_tpu/ops/sha3.py": PT014_GOOD_KECCAK}, tmp_path) == []


def test_pt014_fires_on_conditional_bucketing(tmp_path):
    """The exact r05/bls381 bug: padded_size(B) on the sharded branch,
    raw B on the other — flagged even though a bucket helper appears
    in the function."""
    findings = check_program("PT014", {
        "plenum_tpu/ops/bls.py": PT014_BAD_CONDITIONAL}, tmp_path)
    assert len(findings) == 1
    assert "CONDITIONALLY" in findings[0].message


def test_pt014_param_passthrough_lifts_to_caller(tmp_path):
    """A seam forwarding caller-shaped operands verbatim is not the
    owner of the bucket obligation — its un-bucketed CALLER is."""
    src = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def _kernel(rows):
            return rows

        def compress(rows, nvalid):
            return _kernel(rows)

        def caller_raw(msgs):
            arr = np.zeros((len(msgs), 64), dtype=np.uint8)
            return compress(jnp.asarray(arr), len(msgs))
    """
    findings = check_program("PT014", {
        "plenum_tpu/ops/shim.py": src}, tmp_path)
    assert len(findings) == 1
    assert findings[0].symbol == "caller_raw"
    assert "compress" in findings[0].message


def test_pt013_covers_bls_pairing_and_msm_seam_names(tmp_path):
    """ISSUE 17: the device pairing/MSM seams (ops/bls381_pairing) use
    the X_dispatch/X_collect name shape — a pairing handle dropped on
    the floor or fired-and-forgotten must flag, while the collect,
    store-on-self and cross-function handoff shapes stay clean."""
    bad = """
        from plenum_tpu.ops.bls381_pairing import (
            msm_dispatch, pairing_dispatch)

        def check_batch(jobs):
            handles = pairing_dispatch(jobs, 2)
            return len(jobs)

        def msm_fire(points, scalars):
            msm_dispatch(points, scalars)
    """
    findings = check_program("PT013", {
        "plenum_tpu/crypto/bls_router.py": bad}, tmp_path)
    assert len(findings) == 2
    assert {f.symbol for f in findings} == {"check_batch", "msm_fire"}

    good = """
        from plenum_tpu.ops.bls381_pairing import (
            msm_collect, msm_dispatch, pairing_collect,
            pairing_dispatch)

        def check_batch(jobs):
            return pairing_collect(pairing_dispatch(jobs, 2))

        def msm_start(self, points, scalars):
            self._inflight = msm_dispatch(points, scalars)

        def msm_handoff(points, scalars):
            return msm_dispatch(points, scalars)

        def msm_run(points, scalars):
            return msm_collect(msm_handoff(points, scalars))
    """
    assert check_program("PT013", {
        "plenum_tpu/crypto/bls_router.py": good}, tmp_path) == []


def test_pt014_covers_bls_pairing_bucket_obligation(tmp_path):
    """ISSUE 17: a pairing dispatch shaping its job axis from raw
    len(jobs) — one Miller-loop compile per distinct batch size — must
    flag; the pow2 bucket the real seam uses stays clean."""
    bad = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def _pairing_kernel(rows):
            return rows

        def pairing_dispatch(jobs, n_pairs):
            arr = np.zeros((len(jobs), n_pairs, 48), dtype=np.uint8)
            return _pairing_kernel(jnp.asarray(arr))
    """
    findings = check_program("PT014", {
        "plenum_tpu/ops/bls381_pairing.py": bad}, tmp_path)
    assert len(findings) == 1
    assert findings[0].symbol == "pairing_dispatch"
    assert "_pairing_kernel" in findings[0].message

    good = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        from plenum_tpu.ops import pow2_at_least

        @jax.jit
        def _pairing_kernel(rows):
            return rows

        def pairing_dispatch(jobs, n_pairs):
            bp = pow2_at_least(len(jobs))
            pp = pow2_at_least(n_pairs)
            arr = np.zeros((bp, pp, 48), dtype=np.uint8)
            return _pairing_kernel(jnp.asarray(arr))
    """
    assert check_program("PT014", {
        "plenum_tpu/ops/bls381_pairing.py": good}, tmp_path) == []


def test_pt012_to_pt014_report_through_baseline(tmp_path):
    """Program-rule findings ride the ordinary baseline machinery."""
    for rel, src in {
            "plenum_tpu/ops/sha3.py": PT014_BAD_KECCAK}.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    rule = rule_by_code("PT014")
    analyzer = Analyzer([rule], str(tmp_path), use_engine_cache=False)
    findings = analyzer.run_files(
        analyzer.collect_files([str(tmp_path)]))
    base = Baseline.from_findings(findings, justification="pinned")
    new, old = base.match(findings)
    assert new == [] and len(old) == 1


# PT015 — the trace-stamp advisory boundary. A stamp is peer-
# controlled wire bytes: parsing it anywhere a consensus root can
# reach hands a byzantine peer a steering wheel into ordering.
PT015_ROOT_PARSES = """
    from plenum_tpu.network.flat_wire import decode_trace_stamp

    class OrderingService:
        def _order(self, batch, raw):
            stamp = decode_trace_stamp(raw)
            if stamp is not None:
                batch = sorted(batch, key=lambda d: stamp[1])
            return batch
"""

PT015_PARSE_DEF = """
    def decode_trace_stamp(raw):
        return None
"""

# the shipped shape: parsing confined to an observability seam no
# consensus root reaches — stamps feed the tracer and nothing else
PT015_SEAM_PARSES = """
    from plenum_tpu.network.flat_wire import decode_trace_stamp

    def record_wire_recv(tracer, raw):
        stamp = decode_trace_stamp(raw)
        if stamp is not None:
            tracer.instant("wire_recv", args={"origin": stamp[0]})
"""


def test_pt015_fires_on_parse_inside_consensus_closure(tmp_path):
    findings = check_program("PT015", {
        "plenum_tpu/consensus/ordering_service.py": PT015_ROOT_PARSES,
        "plenum_tpu/network/flat_wire.py": PT015_PARSE_DEF,
    }, tmp_path)
    assert len(findings) == 1
    f = findings[0]
    assert f.path == "plenum_tpu/consensus/ordering_service.py"
    assert f.symbol == "OrderingService._order"
    assert "advisory" in f.message and "decode_trace_stamp" in f.message


def test_pt015_fires_on_helper_reached_from_root(tmp_path):
    """The parse doesn't have to sit IN the root — any function the
    consensus closure reaches is inside the boundary."""
    helper = """
        from plenum_tpu.network.flat_wire import decode_trace_stamp

        class BatchTagger:
            def tag(self, raw):
                return decode_trace_stamp(raw)
    """
    root = """
        from plenum_tpu.server.batch_tagger import BatchTagger

        class OrderingService:
            def _order(self, batch, raw):
                tag = BatchTagger().tag(raw)
                return (batch, tag)
    """
    findings = check_program("PT015", {
        "plenum_tpu/consensus/ordering_service.py": root,
        "plenum_tpu/server/batch_tagger.py": helper,
        "plenum_tpu/network/flat_wire.py": PT015_PARSE_DEF,
    }, tmp_path)
    assert len(findings) == 1
    assert findings[0].symbol == "BatchTagger.tag"
    assert findings[0].path == "plenum_tpu/server/batch_tagger.py"


def test_pt015_silent_on_observability_seam(tmp_path):
    findings = check_program("PT015", {
        "plenum_tpu/observability/wire_recv.py": PT015_SEAM_PARSES,
        "plenum_tpu/network/flat_wire.py": PT015_PARSE_DEF,
    }, tmp_path)
    assert findings == []


def test_pt015_fires_when_parse_surface_calls_consensus(tmp_path):
    """Direction 2: the decode helper itself triggering consensus work
    is the same taint flowing the other way."""
    decode_calls_root = """
        from plenum_tpu.consensus.ordering_service import OrderingService

        def decode_trace_stamp(raw):
            OrderingService()._order(raw)
            return None
    """
    root = """
        class OrderingService:
            def _order(self, batch):
                return batch
    """
    findings = check_program("PT015", {
        "plenum_tpu/network/flat_wire.py": decode_calls_root,
        "plenum_tpu/consensus/ordering_service.py": root,
    }, tmp_path)
    assert len(findings) == 1
    f = findings[0]
    assert f.symbol == "decode_trace_stamp"
    assert "_order" in f.message and "advisory" in f.message


# ------------------------------------- PT016 (thread-region ownership)

# The pipeline ownership contract, statically: server/node.py hands a
# closure across a queue into a runtime worker loop, and the worker's
# call closure — crossing back into consensus code in ANOTHER module —
# rebinds consensus-named state. PT004 (one-class heuristic) cannot
# see this; the engine's region propagation can.
PT016_PIPELINE_MOD = """
    import threading

    class NodePipeline:
        def start(self):
            self._t = threading.Thread(target=self._worker_loop)
            self._t.start()

        def _worker_loop(self):
            job = self._in.get()
            self._ordering.count_vote(job)
"""

PT016_ORDERING_BAD = """
    class Ordering:
        def count_vote(self, vote):
            self.prepare_count = vote.n
"""

# the sanctioned shape: the worker only parses and hands an IMMUTABLE
# result back over the queue — no consensus write, nothing mutable in
# flight
PT016_PIPELINE_GOOD = """
    import threading

    class NodePipeline:
        def start(self):
            self._t = threading.Thread(target=self._worker_loop)
            self._t.start()

        def _worker_loop(self):
            raw = self._in.get()
            parsed = bytes(raw)
            self._out.put(parsed)
"""


def test_pt016_fires_on_cross_module_worker_consensus_write(tmp_path):
    findings = check_program("PT016", {
        "plenum_tpu/runtime/pipeline.py": PT016_PIPELINE_MOD,
        "plenum_tpu/consensus/ordering.py": PT016_ORDERING_BAD,
    }, tmp_path)
    assert len(findings) == 1
    f = findings[0]
    assert f.path == "plenum_tpu/consensus/ordering.py"
    assert f.symbol == "Ordering.count_vote"
    assert "self.prepare_count (consensus state)" in f.message
    assert "owned by the prod thread" in f.message


def test_pt016_clean_on_immutable_queue_handoff(tmp_path):
    assert check_program("PT016", {
        "plenum_tpu/runtime/pipeline.py": PT016_PIPELINE_GOOD,
    }, tmp_path) == []


def test_pt016_dual_region_write_needs_lock(tmp_path):
    dual = """
        import threading

        class Stage:
            def start(self):
                self._t = threading.Thread(target=self._work)
                self._t.start()

            def _work(self):
                self.cursor = 1

            def advance(self):
                self.cursor = 2
    """
    findings = check_program("PT016", {
        "plenum_tpu/runtime/stage.py": dual}, tmp_path)
    assert len(findings) == 1
    assert "self.cursor is written from both" in findings[0].message
    locked = """
        import threading

        class Stage:
            def start(self):
                self._t = threading.Thread(target=self._work)
                self._t.start()

            def _work(self):
                with self._lock:
                    self.cursor = 1

            def advance(self):
                with self._lock:
                    self.cursor = 2
    """
    assert check_program("PT016", {
        "plenum_tpu/runtime/stage.py": locked}, tmp_path) == []


def test_pt016_init_writes_never_flag(tmp_path):
    """Construction happens before any thread exists — __init__ writes
    are region-free by definition."""
    src = """
        import threading

        class Stage:
            def __init__(self):
                self.prepares = {}
                self._t = threading.Thread(target=self._work)

            def _work(self):
                return self.prepares
    """
    assert check_program("PT016", {
        "plenum_tpu/runtime/stage.py": src}, tmp_path) == []


# ------------------------------------------ PT017 (handoff discipline)


def test_pt017_fires_on_fresh_mutable_queue_payload(tmp_path):
    src = """
        class Stage:
            def feed(self, env, frm):
                self._queue.put({"env": env, "frm": frm})
    """
    findings = check_program("PT017", {
        "plenum_tpu/runtime/stage.py": src}, tmp_path)
    assert len(findings) == 1
    assert "freshly built mutable dict crosses a thread queue" \
        in findings[0].message


def test_pt017_fires_on_mutate_after_put(tmp_path):
    src = """
        class Stage:
            def submit(self, items):
                batch = list(items)
                self._queue.put(batch)
                batch.append(None)
    """
    findings = check_program("PT017", {
        "plenum_tpu/runtime/stage.py": src}, tmp_path)
    assert len(findings) == 1
    assert "mutated after put()" in findings[0].message
    assert "batch" in findings[0].message


def test_pt017_kv_store_put_is_not_a_handoff(tmp_path):
    """A KV-store put persists a snapshot — mutating the value after
    is not sharing it with another thread."""
    src = """
        class Store:
            def save(self, key, items):
                batch = list(items)
                self._store.put(key, batch)
                batch.append(None)
    """
    assert check_program("PT017", {
        "plenum_tpu/storage/kv.py": src}, tmp_path) == []


def test_pt017_fires_on_consensus_capture_into_closure(tmp_path):
    src = """
        import threading

        class Node:
            def start(self):
                t = threading.Thread(
                    target=lambda: self._drain(self.prepares))
                t.start()

            def _drain(self, votes):
                return votes
    """
    findings = check_program("PT017", {
        "plenum_tpu/server/node.py": src}, tmp_path)
    assert len(findings) == 1
    assert "consensus-owned state (self.prepares) is captured" \
        in findings[0].message


def test_pt017_method_spawn_target_is_not_a_capture(tmp_path):
    """Reading a method off self to CALL it is how every spawn works —
    only consensus state read as data counts."""
    src = """
        import threading

        class Node:
            def start(self):
                t = threading.Thread(target=self._worker_loop)
                t.start()

            def _worker_loop(self):
                return None
    """
    assert check_program("PT017", {
        "plenum_tpu/server/node.py": src}, tmp_path) == []


# ------------------------- PT004 subsumption + engine-fallback contract


def test_pt004_held_out_when_engine_active(tmp_path):
    """With PT016 in the run and the engine healthy, the per-module
    heuristic stays silent — its findings arrive under PT016/PT017
    (byte-identical messages, migratable keys)."""
    p = tmp_path / "plenum_tpu" / "runtime" / "stage.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent(PT004_PIPELINE_BAD))
    rules = [rule_by_code("PT004"), rule_by_code("PT016"),
             rule_by_code("PT017")]
    analyzer = Analyzer(rules, str(tmp_path), use_engine_cache=False)
    findings = analyzer.run_files(
        analyzer.collect_files([str(tmp_path)]))
    assert analyzer.engine_error is None
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert "PT004" not in by_rule
    # the same two defects, now whole-program findings
    assert any("self.prepares (consensus state)" in f.message
               for f in by_rule.get("PT016", []))
    assert any("mutable dict crosses a thread queue" in f.message
               for f in by_rule.get("PT017", []))


def test_pt004_fallback_when_engine_unavailable(tmp_path, monkeypatch):
    """Engine build failure must degrade to the heuristic, not to
    silence: PT004 re-enters the per-module pass and engine_error is
    surfaced."""
    from plenum_tpu.analysis.engine import Engine

    def boom(cls, *a, **kw):
        raise RuntimeError("engine exploded")

    monkeypatch.setattr(Engine, "build", classmethod(boom))
    p = tmp_path / "plenum_tpu" / "runtime" / "stage.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent(PT004_PIPELINE_BAD))
    rules = [rule_by_code("PT004"), rule_by_code("PT016"),
             rule_by_code("PT017")]
    analyzer = Analyzer(rules, str(tmp_path), use_engine_cache=False)
    findings = analyzer.run_files(
        analyzer.collect_files([str(tmp_path)]))
    assert analyzer.engine_error is not None
    assert "engine exploded" in analyzer.engine_error
    by_rule = {f.rule for f in findings}
    assert "PT004" in by_rule
    assert "PT016" not in by_rule and "PT017" not in by_rule


def test_pt004_runs_normally_without_superseding_rule(tmp_path):
    """PT004 alone (no PT016 registered in the run) keeps its original
    behavior — the subsumption is a property of the RUN, not the rule."""
    findings = check_snippet(rule_by_code("PT004"), PT004_PIPELINE_BAD,
                             "plenum_tpu/runtime/stage.py")
    assert any("self.prepares" in f.message for f in findings)


# -------------------------------- baseline migration (PT004 → PT016/17)


def test_baseline_migrates_pt004_keys_on_load(tmp_path):
    """Grandfathered PT004 entries re-key to the subsuming rule by
    message shape — justifications survive the rule split with zero
    hand-edits."""
    from plenum_tpu.analysis.baseline import migrate_entries

    entries = [
        {"rule": "PT004", "path": "plenum_tpu/runtime/stage.py",
         "symbol": "Stage._work",
         "message": "self.prepares (consensus state) is written from "
                    "the worker-thread path (_work) — consensus state "
                    "is owned by the prod thread; workers may only "
                    "parse and hand immutable results back over the "
                    "queue",
         "justification": "pinned"},
        {"rule": "PT004", "path": "plenum_tpu/runtime/stage.py",
         "symbol": "Stage.feed",
         "message": "a freshly built mutable dict crosses a thread "
                    "queue via put() — queue payloads must be "
                    "immutable (bytes, numpy views, frozen records): "
                    "the consumer would share state the producer can "
                    "still mutate",
         "justification": "pinned"},
        {"rule": "PT006", "path": "plenum_tpu/x.py", "symbol": "f",
         "message": "broad except", "justification": "pinned"},
    ]
    migrated, n = migrate_entries(entries)
    assert n == 2
    assert [e["rule"] for e in migrated] == ["PT016", "PT017", "PT006"]
    # justifications ride along untouched
    assert all(e["justification"] == "pinned" for e in migrated)
    # and Baseline.load applies the same migration
    path = tmp_path / "lint_baseline.json"
    path.write_text(json.dumps({"version": 1, "entries": entries}))
    loaded = Baseline.load(str(path))
    assert [e["rule"] for e in loaded.entries] == \
        ["PT016", "PT017", "PT006"]


def test_baseline_unmigratable_pt004_surfaces_as_stale(tmp_path):
    """A PT004 entry whose message matches no migration fragment stays
    PT004 — and with the engine active PT004 never fires, so match()
    leaves it unconsumed and stale() reports it. Zero silent drops."""
    from plenum_tpu.analysis.baseline import migrate_entries

    entries = [{"rule": "PT004", "path": "plenum_tpu/runtime/x.py",
                "symbol": "X.f",
                "message": "self.count is written from both the "
                           "daemon thread (_loop) and loop code "
                           "(service) without a lock — use a lock or "
                           "the Tracer fixed-slot pattern",
                "justification": "pinned"}]
    migrated, n = migrate_entries(list(entries))
    assert n == 0 and migrated[0]["rule"] == "PT004"
    b = Baseline(migrated)
    new, old = b.match([])
    assert new == [] and old == []
    assert b.stale() == [("PT004", "plenum_tpu/runtime/x.py", "X.f",
                          entries[0]["message"])]


def test_pt016_message_is_byte_identical_to_pt004(tmp_path):
    """The migration contract: for the same defect the engine rule
    emits PT004's exact message, so re-keying the rule id alone is a
    complete migration."""
    p = tmp_path / "plenum_tpu" / "runtime" / "stage.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent(PT004_PIPELINE_BAD))
    heuristic = check_snippet(rule_by_code("PT004"), PT004_PIPELINE_BAD,
                              "plenum_tpu/runtime/stage.py")
    engine_findings = check_program("PT016", {
        "plenum_tpu/runtime/stage.py": PT004_PIPELINE_BAD}, tmp_path)
    engine_findings += check_program("PT017", {
        "plenum_tpu/runtime/stage.py": PT004_PIPELINE_BAD}, tmp_path)
    assert {f.message for f in heuristic} == \
        {f.message for f in engine_findings}


# ----------------------------------------------- SARIF: the new rules


def test_sarif_descriptors_cover_region_rules():
    from plenum_tpu.analysis.sarif import DOCS_URI, _rule_descriptor
    for code in ("PT016", "PT017"):
        desc = _rule_descriptor(rule_by_code(code))
        assert desc["id"] == code
        assert desc["helpUri"] == DOCS_URI
        assert desc["name"]
