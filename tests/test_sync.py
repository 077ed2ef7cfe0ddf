"""The host concurrency plane's static suite (`fsx sync`,
docs/CONCURRENCY.md): the thread-contract checker over the real repo
AND over planted violations of every contract class, the bounded
interleaving model checker (positives + planted negatives + the arena
bound tightness proof), the shared tuning table, and the unified
crash-propagation path for every worker type."""

import ast
import threading

import pytest

from flowsentryx_tpu.sync import contracts, tuning
from flowsentryx_tpu.sync.channel import SinkChannel, WorkerCrash
from flowsentryx_tpu.sync.contracts import (
    ClassPlan,
    CursorPlan,
    FieldContract,
    check_class,
    check_ctl,
    check_cursors,
    run_contracts,
)


# ---------------------------------------------------------------------------
# thread-contract checker: the real repo
# ---------------------------------------------------------------------------

class TestContractsOnRepo:
    def test_repo_passes_clean(self):
        rep = run_contracts()
        assert rep.ok, "\n".join(str(f) for f in rep.findings)
        assert rep.stats["classes"] >= 3
        assert rep.stats["registered_fields"] >= 40
        assert rep.stats["cursor_classes"] == 4
        assert rep.stats["ctl_sites"] > 0

    def test_quick_mode_runs_same_checks(self):
        rep = run_contracts(quick=True)
        assert rep.ok and rep.stats["quick"] is True

    def test_every_ctl_field_has_one_writer_side(self):
        # the SealedBatchQueue ctl block's documented one-writer rule
        # is fully covered by the declaration table
        from flowsentryx_tpu.core import schema

        declared = set(contracts.CTL_WRITERS)
        assert declared == {"hbeat", "first_ts", "t0", "stop",
                            "wstate", "emit_drop", "spin_us", "idle_us",
                            # cluster status block (PR 10): engine line
                            "c_hbeat", "c_state", "c_batches", "c_records",
                            # supervisor line (c_t0_wall: ISSUE 15,
                            # the monotonic epoch's wall twin)
                            "c_stop", "c_gen", "c_t0", "c_t0_wall",
                            # rebalance plane (ISSUE 16): the engine
                            # ack line vs the supervisor fence line
                            "c_pid", "c_handoff", "c_layout_ack",
                            "c_layout_gen", "c_fence"}
        for name in declared:
            if name.startswith("c_"):
                # cluster status-block fields live in the STATUS_*
                # layout (cluster/mailbox.py StatusBlock)
                assert hasattr(schema, f"STATUS_{name[2:].upper()}_OFFSET")
            else:
                assert hasattr(schema, f"SHM_{name.upper()}_OFFSET")


# ---------------------------------------------------------------------------
# thread-contract checker: planted violations, one per contract class
# ---------------------------------------------------------------------------

def _plan(fields, **kw):
    return ClassPlan(module="planted.py", cls="C", fields=fields, **kw)


def _check(src, plan):
    return check_class(ast.parse(src), "planted.py", plan)


class TestPlantedViolations:
    def test_dispatch_field_touched_from_worker(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def run(self):\n"
            "        t = threading.Thread(target=self._worker)\n"
            "        t.start()\n"
            "        self._staged += 1\n"
            "    def _worker(self):\n"
            "        self._staged = 0\n")
        out = _check(src, _plan(
            {"_staged": FieldContract("dispatch", "dispatch-owned")},
            worker_targets=("_worker",)))
        assert len(out) == 1
        f = out[0]
        assert f.contract == "discipline" and f.line == 8
        assert "C._worker" in f.where and "_staged" in f.reason
        assert "planted.py" in str(f) and ":8:" in str(f)

    def test_worker_context_propagates_through_calls(self):
        # the violation hides one call deep: the checker must flood the
        # worker context through the intra-class call graph
        src = (
            "import threading\n"
            "class C:\n"
            "    def run(self):\n"
            "        threading.Thread(target=self._worker).start()\n"
            "    def _worker(self):\n"
            "        self._helper()\n"
            "    def _helper(self):\n"
            "        self._staged += 1\n")
        out = _check(src, _plan(
            {"_staged": FieldContract("dispatch", "dispatch-owned")},
            worker_targets=("_worker",)))
        assert [f.line for f in out] == [8]

    def test_cv_field_accessed_unlocked(self):
        src = (
            "class C:\n"
            "    def good(self):\n"
            "        with self.cv:\n"
            "            self._q.append(1)\n"
            "    def bad(self):\n"
            "        self._q.append(1)\n")
        out = _check(src, _plan(
            {"_q": FieldContract("cv", "queue")}, lock_attr="cv"))
        assert len(out) == 1
        assert out[0].line == 6 and "outside" in out[0].reason

    def test_cv_write_allows_unlocked_read(self):
        src = (
            "class C:\n"
            "    def read(self):\n"
            "        return self._pending\n"
            "    def bad_write(self):\n"
            "        self._pending += 1\n")
        out = _check(src, _plan(
            {"_pending": FieldContract("cv-write", "count")},
            lock_attr="cv"))
        assert len(out) == 1
        assert out[0].line == 5 and "WRITTEN" in out[0].reason

    def test_atomic_ref_rejects_read_modify_write(self):
        src = (
            "class C:\n"
            "    def swap(self, p):\n"
            "        self.params = p\n"        # plain rebind: legal
            "    def bad(self):\n"
            "        self.params['w'] = 0\n")  # item store: racy
        out = _check(src, _plan(
            {"params": FieldContract("atomic-ref", "hot swap")}))
        assert len(out) == 1
        assert out[0].line == 5
        assert "read-modify-write" in out[0].reason

    def test_quiescent_write_outside_quiescent_set(self):
        src = (
            "class C:\n"
            "    def __init__(self):\n"
            "        self._active = False\n"
            "    def serve(self):\n"
            "        self._active = True\n")
        out = _check(src, _plan(
            {"_active": FieldContract("quiescent-write", "mode flag")},
            quiescent=("__init__",)))
        assert len(out) == 1 and out[0].line == 5

    def test_section_field_touched_outside_section(self):
        src = (
            "class C:\n"
            "    def _launch(self):\n"
            "        self.table = 1\n"
            "    def elsewhere(self):\n"
            "        self.table = 2\n")
        out = _check(src, _plan(
            {"table": FieldContract("section:launch", "device carry")},
            sections={"launch": ("_launch",)}))
        assert len(out) == 1
        assert out[0].line == 5 and "'launch' section" in out[0].reason

    def test_unregistered_shared_state_detected(self):
        # mutated under BOTH contexts with no registry entry: the
        # registry-rot guard the tentpole requires
        src = (
            "import threading\n"
            "class C:\n"
            "    def run(self):\n"
            "        threading.Thread(target=self._worker).start()\n"
            "        self._count += 1\n"
            "    def _worker(self):\n"
            "        self._count += 1\n")
        out = _check(src, _plan({}, worker_targets=("_worker",)))
        assert len(out) == 1
        f = out[0]
        assert f.contract == "unregistered"
        assert "_count" in f.reason and "no sync-registry entry" in f.reason
        assert f.line == 7  # points at the worker-reachable half

    def test_single_context_mutation_not_flagged(self):
        src = (
            "class C:\n"
            "    def a(self):\n"
            "        self._count = 1\n"
            "    def b(self):\n"
            "        self._count += 1\n")
        assert _check(src, _plan({})) == []

    def test_undeclared_thread_target(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def run(self):\n"
            "        threading.Thread(target=self._rogue).start()\n"
            "    def _rogue(self):\n"
            "        pass\n")
        out = _check(src, _plan({}))
        assert len(out) == 1
        assert out[0].contract == "registry"
        assert "_rogue" in out[0].reason

    def test_stale_registry_entries_are_findings(self):
        src = "class C:\n    def a(self):\n        self._x = 1\n"
        out = _check(src, _plan(
            {"_x": FieldContract("dispatch", "x"),
             "_ghost": FieldContract("dispatch", "gone")},
            worker_targets=("_no_such_worker",),
            quiescent=("_no_such_quiescent",),
            sections={"s": ("_no_such_member",)}))
        reasons = "\n".join(f.reason for f in out)
        assert "declared thread target does not exist" in reasons
        assert "never accessed" in reasons
        assert "missing method" in reasons
        assert "quiescent list names a missing method" in reasons

    def test_missing_class_is_a_finding(self):
        out = _check("class Other:\n    pass\n", _plan({}))
        assert out and out[0].contract == "registry"

    def test_extra_grant_silences(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def run(self):\n"
            "        threading.Thread(target=self._worker).start()\n"
            "    def _worker(self):\n"
            "        return self._staged\n")
        plan = _plan(
            {"_staged": FieldContract("dispatch", "x",
                                      extra=("_worker",))},
            worker_targets=("_worker",))
        assert [f for f in _check(src, plan)
                if f.contract == "discipline"] == []


class TestCursorAndCtlViolations:
    def test_tail_store_on_producer_side(self):
        # queue-cursor misuse: the producer releasing slots would let
        # it overwrite unread records
        src = (
            "class Q:\n"
            "    def produce(self, n):\n"
            "        self._head[0] = n\n"
            "        self._tail[0] = n\n"
            "    def consume(self, n):\n"
            "        self._tail[0] = n\n")
        out = check_cursors(ast.parse(src), "planted.py", CursorPlan(
            module="planted.py", cls="Q",
            producer=("produce",), consumer=("consume",)))
        assert len(out) == 1
        f = out[0]
        assert f.contract == "cursor" and f.line == 4
        assert "tail cursor stored outside the consumer side" in f.reason

    def test_head_store_on_consumer_side(self):
        src = (
            "class Q:\n"
            "    def consume(self, n):\n"
            "        self._head[0] = n\n")
        out = check_cursors(ast.parse(src), "planted.py", CursorPlan(
            module="planted.py", cls="Q",
            producer=("produce",), consumer=("consume",)))
        assert len(out) == 1 and "head cursor" in out[0].reason

    def test_repo_shm_obeys_cursor_plans(self):
        from pathlib import Path

        root = Path(contracts.__file__).resolve().parents[2]
        for plan in contracts.CURSORS:
            tree = ast.parse((root / plan.module).read_text())
            assert check_cursors(
                tree, plan.module, plan) == []

    def test_undeclared_ctl_field(self):
        src = "def f(q):\n    q.ctl_set('rogue_field', 1)\n"
        out = check_ctl(ast.parse(src), "planted.py", "worker")
        assert len(out) == 1 and "UNDECLARED" in out[0].reason

    def test_ctl_write_from_wrong_side(self):
        src = "def f(q):\n    q.ctl_set('hbeat', 1)\n"  # worker-owned
        out = check_ctl(ast.parse(src), "planted.py", "engine")
        assert len(out) == 1
        assert "hbeat" in out[0].reason and "worker-written" in out[0].reason

    def test_ctl_write_with_no_declared_side(self):
        src = "def f(q):\n    q.ctl_set('stop', 1)\n"
        out = check_ctl(ast.parse(src), "planted.py", None)
        assert len(out) == 1 and "no declared writer side" in out[0].reason

    # -- cluster plane (PR 10): planted negatives -----------------------

    def test_cluster_supervisor_field_written_from_engine(self):
        # an engine writing the supervisor-owned restart generation
        # would forge its own restart epoch — two writers on the
        # plain-store lifecycle line
        src = "def f(sb):\n    sb.ctl_set('c_gen', 2)\n"
        out = check_ctl(ast.parse(src), "planted.py", "cluster-engine")
        assert len(out) == 1
        assert "c_gen" in out[0].reason
        assert "supervisor-written" in out[0].reason

    def test_cluster_mailbox_tail_store_on_publish_side(self):
        # gossip-mailbox misuse: the publisher releasing slots would
        # let it overwrite verdict wires the peer has not merged yet
        src = (
            "class M:\n"
            "    def publish(self, n):\n"
            "        self._head[0] = n\n"
            "        self._tail[0] = n\n"
            "    def pop_wires(self, n):\n"
            "        self._tail[0] = n\n")
        out = check_cursors(ast.parse(src), "planted.py", CursorPlan(
            module="planted.py", cls="M",
            producer=("publish",), consumer=("pop_wires",)))
        assert len(out) == 1
        assert "tail cursor stored outside the consumer side" \
            in out[0].reason


# ---------------------------------------------------------------------------
# the tuning table
# ---------------------------------------------------------------------------

class TestNetRegistry:
    """ISSUE 15 satellite: the transport's contracts — owner sections
    for the NetMailbox (publish=queue_tx only, merge=everything
    network-facing), the cross-section handoff deque, the epoch-rebase
    fields, and the c_t0_wall writer side — with one planted negative
    per new discipline."""

    def test_netmailbox_plan_pins_expected_disciplines(self):
        plan = contracts.NETMAILBOX_PLAN
        assert plan.sections["publish"] == ("queue_tx",)
        assert "pump" in plan.sections["merge"]
        assert "_accept" in plan.sections["merge"]
        f = plan.fields
        assert f["txq_dropped"].discipline == "section:publish"
        assert f["_outq"].discipline == "documented"
        for merge_field in ("_sock", "_tx_seq", "_own_map", "net_map",
                            "_rx_state", "_ready", "epoch_skew_max",
                            "epoch_skew_dropped", "rx_gap", "rx_dup",
                            "reorder_evict"):
            assert f[merge_field].discipline == "section:merge", \
                merge_field
        assert f["peers"].discipline == "quiescent-write"
        # the engine plane registers its net leg
        assert contracts.GOSSIP_PLAN.fields["net"].discipline \
            == "documented"

    def test_planted_publish_counter_written_from_merge_side(self):
        # txq_dropped belongs to the publish section alone: a pump-side
        # bump would be a second writer racing the sink section
        src = (
            "class C:\n"
            "    def queue_tx(self):\n"
            "        self._txq += 1\n"
            "    def pump(self):\n"
            "        self._txq += 1\n")
        out = _check(src, _plan(
            {"_txq": FieldContract("section:publish", "drops")},
            sections={"publish": ("queue_tx",), "merge": ("pump",)}))
        assert [f.line for f in out] == [5]
        assert "publish" in out[0].reason

    def test_planted_canonical_map_written_from_publish_side(self):
        # net_map (the canonical rebased map) is merge-owned: folding
        # it at queue_tx time would race the rx fold
        src = (
            "class C:\n"
            "    def queue_tx(self):\n"
            "        self.net_map[1] = 2\n"
            "    def pump(self):\n"
            "        self.net_map[1] = 2\n")
        out = _check(src, _plan(
            {"net_map": FieldContract("section:merge",
                                      "canonical map")},
            sections={"publish": ("queue_tx",), "merge": ("pump",)}))
        assert [f.line for f in out] == [3]
        assert "merge" in out[0].reason

    def test_planted_peer_table_written_while_serving(self):
        # peers is quiescent-write: a merge-side mutation would race
        # the publish side's... nothing mechanical guards it but the
        # quiescent rule — which is exactly what must flag it
        src = (
            "class C:\n"
            "    def __init__(self):\n"
            "        self.peers = {}\n"
            "    def add_peer(self, k, a):\n"
            "        self.peers[k] = a\n"
            "    def pump(self):\n"
            "        self.peers[1] = ('x', 2)\n")
        out = _check(src, _plan(
            {"peers": FieldContract("quiescent-write", "peer table")},
            quiescent=("__init__", "add_peer")))
        assert [f.line for f in out] == [7]

    def test_repo_netmailbox_obeys_its_plan(self):
        rep = run_contracts()
        assert not [f for f in rep.findings
                    if "transport" in f.path]

    def test_ctl_t0_wall_is_supervisor_written(self):
        assert contracts.CTL_WRITERS["c_t0_wall"] == "supervisor"
        # a cluster-engine-side write of the wall epoch would be a
        # second writer on a supervisor-owned TSO field
        src = "st.ctl_set('c_t0_wall', 5)\n"
        out = check_ctl(ast.parse(src), "planted.py",
                        "cluster-engine")
        assert len(out) == 1 and "supervisor" in out[0].reason


class TestRebalanceRegistry:
    """ISSUE 16 satellite: the elastic plane's contracts — the
    EngineRebalancer's dispatch-owned handoff state, the ElasticPolicy
    decision state, the HandoffMailbox SPSC cursors, and the five new
    ctl lines split engine-ack vs supervisor-fence — with one planted
    negative per new discipline."""

    def test_rebalance_plans_pin_expected_disciplines(self):
        rb = contracts.REBALANCE_PLAN
        assert rb.cls == "EngineRebalancer"
        for f in ("_acked_gen", "_fence_seen", "_staged", "_receiver",
                  "_mbx"):
            assert rb.fields[f].discipline == "dispatch", f
        el = contracts.ELASTIC_PLAN
        assert el.cls == "ElasticPolicy"
        for f in ("_streak", "_cooldown_until", "suppressed",
                  "decisions"):
            assert el.fields[f].discipline == "dispatch", f
        # the engine plane registers its rebalance counter line
        assert contracts.ENGINE_PLAN.fields["_rebalance"].discipline \
            == "dispatch"

    def test_planted_rebalancer_state_written_from_worker(self):
        # a worker thread staging handoff rows would race the serving
        # loop's reconcile/step — _staged is dispatch-owned
        src = (
            "class C:\n"
            "    def step(self):\n"
            "        self._staged = None\n"
            "    def run(self):\n"
            "        self._staged = 1\n")
        out = _check(src, _plan(
            {"_staged": FieldContract("dispatch", "staged rows")},
            worker_targets=("run",)))
        assert [f.line for f in out] == [5]

    def test_planted_fence_stamped_from_engine_side(self):
        # only the supervisor stamps the fence: an engine stamping its
        # own fence could unfence itself mid-commit and serve a
        # half-flipped route
        assert contracts.CTL_WRITERS["c_fence"] == "supervisor"
        src = "def f(st):\n    st.ctl_set('c_fence', 0)\n"
        out = check_ctl(ast.parse(src), "planted.py", "cluster-engine")
        assert len(out) == 1 and "supervisor" in out[0].reason

    def test_planted_layout_ack_forged_by_supervisor(self):
        # the ack line is the ENGINE's proof it observed the flip; the
        # supervisor acking for a rank would lift the fence without
        # convergence
        assert contracts.CTL_WRITERS["c_layout_ack"] == "cluster-engine"
        src = "def f(st):\n    st.ctl_set('c_layout_ack', 2)\n"
        out = check_ctl(ast.parse(src), "planted.py", "supervisor")
        assert len(out) == 1 and "cluster-engine" in out[0].reason

    def test_planted_handoff_mailbox_consumer_stores_head(self):
        # the SPSC rule on the handoff stream: the recipient storing
        # the head cursor would republish slots under the donor
        src = (
            "class M:\n"
            "    def _publish(self, n):\n"
            "        self._head[0] = n\n"
            "    def pop_slots(self, n):\n"
            "        self._head[0] = n\n"
            "        self._tail[0] = n\n")
        out = check_cursors(ast.parse(src), "planted.py", CursorPlan(
            module="planted.py", cls="M",
            producer=("_publish",), consumer=("pop_slots",)))
        assert len(out) == 1
        assert "head cursor stored outside the producer side" \
            in out[0].reason

    def test_repo_rebalance_obeys_its_plan(self):
        rep = run_contracts()
        assert not [f for f in rep.findings
                    if "rebalance" in f.path or "elastic" in f.path]

    def test_rebalance_module_is_engine_side(self):
        assert contracts.CTL_MODULE_SIDE[
            "flowsentryx_tpu/cluster/rebalance.py"] == "cluster-engine"


class TestTuningTable:
    def test_engine_and_ingest_reference_the_table(self):
        from flowsentryx_tpu.ingest import worker

        assert worker.IDLE_SLEEP_S == tuning.IDLE_SLEEP_S
        assert worker.EMIT_STOP_TIMEOUT_S == tuning.EMIT_STOP_TIMEOUT_S
        # the engine sources import the module (not copied literals)
        import flowsentryx_tpu.engine.engine as eng_mod

        assert eng_mod.tuning is tuning

    def test_values_are_the_measured_ones(self):
        assert tuning.GIL_YIELD_S == 20e-6
        assert tuning.IDLE_SLEEP_S == 200e-6
        assert tuning.SPIN_US_DEFAULT == 150
        assert tuning.EMIT_STOP_TIMEOUT_S == 2.0

    def test_jax_free(self):
        import sys
        import subprocess

        r = subprocess.run(
            [sys.executable, "-c",
             "import sys; import flowsentryx_tpu.sync.contracts; "
             "import flowsentryx_tpu.sync.interleave; "
             "import flowsentryx_tpu.sync.tuning; "
             "sys.exit(1 if 'jax' in sys.modules else 0)"],
            capture_output=True)
        assert r.returncode == 0, r.stderr.decode()


# ---------------------------------------------------------------------------
# the model checker
# ---------------------------------------------------------------------------

class TestExploreFramework:
    def test_finds_a_classic_lost_update(self):
        from flowsentryx_tpu.sync.interleave import (
            ModelViolation, explore)

        def mk():
            box = [0]

            def racer(name):
                yield f"{name}:read"
                v = box[0]
                yield f"{name}:write"
                box[0] = v + 1

            def finale():
                if box[0] != 2:
                    raise ModelViolation(f"lost update: {box[0]}")

            return ([("a", racer("a")), ("b", racer("b"))], finale)

        res = explore("lost_update", mk, expect_violation=True)
        assert res.ok and res.counterexample is not None
        assert "lost update" in res.counterexample.detail

    def test_expect_marker_pins_the_bug_class(self):
        # a negative demo must not stay green on an UNRELATED
        # violation (e.g. a workload deadlock): only a counterexample
        # carrying the expected marker counts
        from flowsentryx_tpu.sync.interleave import (
            ModelViolation, explore)

        def mk():
            def t():
                yield "boom"
                raise ModelViolation("some other defect")

            return ([("t", t())], None)

        hit = explore("neg", mk, expect_violation=True,
                      expect_marker="some other defect")
        assert hit.ok
        miss = explore("neg", mk, expect_violation=True,
                       expect_marker="the intended bug")
        assert not miss.ok
        # the non-matching counterexample is still surfaced for debug
        assert "some other defect" in miss.counterexample.detail

    def test_deadlock_is_reported(self):
        from flowsentryx_tpu.sync.interleave import explore

        def mk():
            def stuck():
                yield (lambda: False, "never")

            return ([("t", stuck())], None)

        res = explore("deadlock", mk)
        assert not res.ok
        assert "deadlock" in res.counterexample.detail

    def test_exhaustive_count_is_exact(self):
        from flowsentryx_tpu.sync.interleave import explore

        def mk():
            def t(name, n):
                for i in range(n):
                    yield f"{name}{i}"

            return ([("a", t("a", 2)), ("b", t("b", 2))], None)

        res = explore("count", mk)
        # interleavings of 2+2 independent steps: C(4,2) = 6
        assert res.ok and res.interleavings == 6


class TestProtocolModels:
    def test_channel_crash_atomicity_holds(self):
        from flowsentryx_tpu.sync import interleave as il

        res = il.explore("atomic", il._mk_channel_crash(False))
        assert res.ok and res.interleavings > 0 and not res.capped

    def test_split_complete_counterexample_found(self):
        from flowsentryx_tpu.sync import interleave as il

        res = il.explore("split", il._mk_channel_crash(True),
                         expect_violation=True)
        assert res.ok
        assert "crash-atomicity violated" in res.counterexample.detail
        # the schedule names the planted split step
        assert any("decrement-only" in s
                   for s in res.counterexample.schedule)

    def test_stop_drains_under_all_schedules(self):
        from flowsentryx_tpu.sync import interleave as il

        res = il.explore("drain", lambda: il._mk_channel_stop_drain())
        assert res.ok and res.interleavings > 100 and not res.capped

    def test_queue_wraparound_views_stable(self, tmp_path):
        from flowsentryx_tpu.sync import interleave as il

        res = il.explore(
            "wrap", il._mk_queue(tmp_path / "q.shm", False))
        assert res.ok and res.interleavings > 0 and not res.capped

    def test_premature_release_counterexample(self, tmp_path):
        from flowsentryx_tpu.sync import interleave as il

        res = il.explore(
            "misuse", il._mk_queue(tmp_path / "q.shm", True),
            expect_violation=True)
        assert res.ok
        assert "overwritten before release" in res.counterexample.detail


class TestArenaBoundTight:
    """The headline proof: safe_slots passes ALL interleavings,
    one slot fewer yields a concrete staged-copy-overwrite schedule."""

    def test_shipped_bound_passes_all_interleavings(self):
        from flowsentryx_tpu.engine.arena import DispatchArena
        from flowsentryx_tpu.sync import interleave as il

        depth = il._ARENA_DEPTH
        safe = DispatchArena.safe_slots(depth)
        assert safe == depth + 2
        # the claims wrap the arena at the bound: the proof covers reuse
        assert il._ARENA_SINGLES > safe
        res = il.explore("safe", il._mk_arena(
            safe, depth, il._ARENA_SINGLES))
        assert res.ok and res.interleavings > 0 and not res.capped

    def test_one_below_yields_staged_copy_overwrite(self):
        from flowsentryx_tpu.sync import interleave as il

        depth = il._ARENA_DEPTH
        res = il.explore("tight", il._mk_arena(
            depth + 1, depth, il._ARENA_SINGLES),
            expect_violation=True)
        assert res.ok
        cx = res.counterexample
        assert "staged-copy overwrite" in cx.detail
        # the schedule is a concrete replayable thread:step list
        assert any(s.startswith("dispatch:claim") for s in cx.schedule)
        assert cx.schedule[-1].startswith("worker:launch")

    def test_full_report_shape(self):
        from flowsentryx_tpu.sync.interleave import run_interleave

        rep = run_interleave()
        assert rep.ok
        assert rep.bound["safe_slots"] == (
            rep.bound["readback_depth"] + 2)
        assert rep.bound["counterexample_found"] is True
        assert rep.bound["interleavings_at_safe"] > 0
        j = rep.to_json()
        assert {"ok", "interleavings", "steps", "bound",
                "checks"} <= set(j)
        neg = [c for c in j["checks"] if c["expect_violation"]]
        assert neg and all(c["counterexample"] for c in neg)


# ---------------------------------------------------------------------------
# SinkChannel unit behavior (the engine-facing surface)
# ---------------------------------------------------------------------------

class TestSinkChannel:
    def test_pending_counts_chunks_not_entries(self):
        ch = SinkChannel()
        ch.submit("mega", n_chunks=4)
        ch.submit_many(["a", "b"], lambda _: 2)
        assert ch.pending == 8
        assert ch.try_pop() == ["mega"]
        ch.complete(4)
        assert ch.pending == 4

    def test_coalesce_folds_consecutive_ready(self):
        ch = SinkChannel()
        ch.submit_many([1, 2, 9, 3], lambda _: 1)
        # first item pops unconditionally, the fold takes consecutive
        # predicate-passing followers (the sink's ready-group shape)
        assert ch.try_pop(coalesce=lambda x: x < 5) == [1, 2]
        assert ch.try_pop(coalesce=lambda x: x < 5) == [9, 3]
        assert ch.try_pop() is None

    def test_check_raises_named_worker_crash(self):
        ch = SinkChannel("ingest worker 3")
        ch.complete(0, exc=ValueError("boom"))
        with pytest.raises(WorkerCrash,
                           match="ingest worker 3 crashed"):
            ch.check()
        assert isinstance(ch.crashed(), ValueError)

    def test_wait_below_released_by_crash(self):
        ch = SinkChannel()
        ch.submit("x", 3)

        def killer():
            ch.record_exc(RuntimeError("dead"))

        t = threading.Thread(target=killer)
        t.start()
        ch.wait_below(0, quantum=0.01)  # must not hang
        t.join()
        with pytest.raises(WorkerCrash):
            ch.check()

    def test_blocking_pop_drains_then_none_after_stop(self):
        ch = SinkChannel()
        ch.submit("tail", 1)
        ch.request_stop()
        assert ch.pop(quantum=0.01) == ["tail"]
        assert ch.pop(quantum=0.01) is None
        assert ch.drained()


# ---------------------------------------------------------------------------
# unified crash propagation: one loud shape per worker type
# ---------------------------------------------------------------------------

class TestCrashPropagationPerWorker:
    """docs/CONCURRENCY.md §crash: sink thread and strict-mode ingest
    death both surface as the same loud WorkerCrash on the dispatch
    side (the sink-thread case is pinned in
    test_engine.py::test_sink_crash_fails_engine_loudly)."""

    def test_strict_ingest_crash_is_loud_after_drain(self, tmp_path):
        import time

        from flowsentryx_tpu.core import schema
        from flowsentryx_tpu.core.config import BatchConfig
        from flowsentryx_tpu.engine.shm import ShmRing
        from flowsentryx_tpu.ingest import ShardedIngest
        from tests.test_ingest import make_records

        base = str(tmp_path / "fring")
        n = 2
        rings = [ShmRing.create(
            schema.shard_ring_path(base, k, n), 1 << 14,
            schema.FLOW_RECORD_DTYPE) for k in range(n)]
        rec = make_records(256 * 2, n_ips=64)
        parts = [rec[schema.shard_of(rec["saddr"], n) == k]
                 for k in range(n)]
        for ring, part in zip(rings, parts):
            assert ring.produce(part) == len(part)
        ing = ShardedIngest(base, n, queue_slots=16, precompact=False,
                            t0_grace_s=0.2, strict=True)
        ing.start(BatchConfig(max_batch=64, deadline_us=10_000),
                  schema.WIRE_RAW48, None)
        try:
            ing.wait_ready()
            deadline = time.monotonic() + 20
            while ing.t0_ns is None:
                ing.poll_batches(0)
                assert time.monotonic() < deadline
                time.sleep(0.01)
            ing._procs[0].terminate()
            ing._procs[0].join(timeout=10)
            # strict mode: keep consuming — the corpse's queue must
            # drain first (no sealed batch lost), THEN the death
            # surfaces as the unified loud WorkerCrash
            with pytest.raises(WorkerCrash,
                               match="ingest worker 0 crashed"):
                deadline = time.monotonic() + 30
                while True:
                    ing.poll_batches(8)
                    assert time.monotonic() < deadline, \
                        "strict crash never surfaced"
                    time.sleep(0.005)
        finally:
            ing.close()
        stats = ing.ingest_stats()
        assert stats["strict"] is True and stats["crashed"] is True

    def test_default_posture_stays_fail_open(self):
        # the strict flag defaults off: constructing without it keeps
        # the per-shard fail-open behavior test_ingest pins
        from flowsentryx_tpu.ingest import ShardedIngest
        import inspect

        sig = inspect.signature(ShardedIngest.__init__)
        assert sig.parameters["strict"].default is False


# ---------------------------------------------------------------------------
# SLO / latency-plane registry (PR 11): the new shared fields are
# registered with the correct disciplines, and each discipline's
# planted violation is caught — the PR 9 convention for every new
# piece of cross-thread engine state.
# ---------------------------------------------------------------------------

class TestSloRegistry:
    def test_new_fields_registered_with_expected_disciplines(self):
        f = contracts.ENGINE_PLAN.fields
        assert f["_rung_ewma_s"].discipline == "section:launch"
        # the dispatch-thread policy readers are explicit grants, part
        # of the documented discipline (advisory float reads)
        for reader in ("_slo_cap", "_slo_pressed",
                       "_deadline_flush_due"):
            assert reader in f["_rung_ewma_s"].extra
        assert f["_lat"].discipline == "section:sink"
        assert f["slo_us"].discipline == "quiescent-write"
        assert f["_slo_budget_s"].discipline == "quiescent-write"
        # the EWMA writer is part of the launch section
        assert "_note_step_s" in contracts.ENGINE_PLAN.sections["launch"]

    def test_planted_ewma_write_outside_launch_section(self):
        # an EWMA store from a worker-reachable method that is NOT in
        # the launch section (and not a granted reader) must be a
        # discipline finding — this is what makes the registry entry
        # enforceable rather than documentation
        src = (
            "import threading\n"
            "class C:\n"
            "    def run(self):\n"
            "        threading.Thread(target=self._sink_worker).start()\n"
            "    def _launch(self):\n"
            "        self._ewma[1] = 0.5\n"
            "    def _sink_worker(self):\n"
            "        self._ewma[1] = 0.9\n")
        out = check_class(ast.parse(src), "planted.py", ClassPlan(
            module="planted.py", cls="C",
            worker_targets=("_sink_worker",),
            sections={"launch": ("_launch",)},
            fields={"_ewma": FieldContract("section:launch",
                                           "per-rung EWMA")}))
        assert len(out) == 1
        assert out[0].line == 8 and "_ewma" in out[0].reason

    def test_planted_latency_recorder_touched_off_sink_section(self):
        src = (
            "class C:\n"
            "    def _sink(self):\n"
            "        self._lat.record(1)\n"
            "    def poll(self):\n"
            "        self._lat.record(2)\n")
        out = check_class(ast.parse(src), "planted.py", ClassPlan(
            module="planted.py", cls="C",
            sections={"sink": ("_sink",)},
            fields={"_lat": FieldContract("section:sink",
                                          "latency plane")}))
        assert len(out) == 1
        assert out[0].line == 5 and "'sink' section" in out[0].reason

    def test_planted_slo_flag_written_while_serving(self):
        src = (
            "class C:\n"
            "    def __init__(self):\n"
            "        self.slo_us = 0\n"
            "    def serve(self):\n"
            "        self.slo_us = 100\n")
        out = check_class(ast.parse(src), "planted.py", ClassPlan(
            module="planted.py", cls="C", quiescent=("__init__",),
            fields={"slo_us": FieldContract("quiescent-write",
                                            "budget flag")}))
        assert len(out) == 1 and out[0].line == 5

    def test_unregistered_ewma_like_state_is_flagged(self):
        # deleting the registry entry must not be silent: a dict
        # mutated from both the dispatch path and a worker without an
        # entry trips the unregistered-shared-state detector
        src = (
            "import threading\n"
            "class C:\n"
            "    def run(self):\n"
            "        threading.Thread(target=self._worker).start()\n"
            "        self._ewma[1] = 0.1\n"
            "    def _worker(self):\n"
            "        self._ewma[2] = 0.2\n")
        out = check_class(ast.parse(src), "planted.py", ClassPlan(
            module="planted.py", cls="C",
            worker_targets=("_worker",), fields={}))
        assert any(f.contract == "unregistered"
                   and "_ewma" in f.reason for f in out)


# ---------------------------------------------------------------------------
# Predictive-governor registry (ISSUE 18): every new piece of shared
# state — the governor itself, the pre-warm buffer, and the shed-deferral counters on both gossip
# planes — registered with the correct discipline, and each new
# discipline surface's planted violation caught.
# ---------------------------------------------------------------------------

class TestPredictRegistry:
    def test_new_fields_registered_with_expected_disciplines(self):
        f = contracts.ENGINE_PLAN.fields
        assert f["_gov"].discipline == "dispatch"
        assert f["_warm_buf"].discipline == "dispatch"
        # the prewarm site reads the EWMA table from the serving loop:
        # an explicit documented grant, like the PR 11 policy readers
        assert "_run_inline" in f["_rung_ewma_s"].extra
        assert "_note_step_s" in contracts.ENGINE_PLAN.sections["launch"]
        g = contracts.GOSSIP_PLAN.fields
        assert g["_ticks_deferred"].discipline == "section:merge"
        assert g["_defer_streak"].discipline == "section:merge"
        n = contracts.NETMAILBOX_PLAN.fields
        assert n["resync_deferred"].discipline == "section:merge"
        assert n["_resync_defer_streak"].discipline == "section:merge"

    def test_governor_plan_covers_every_mutable_attr(self):
        # registry-rot guard in the forward direction: every attribute
        # DispatchGovernor.__init__/reset_counters assigns is a
        # registered field — a new counter added without a contract
        # entry fails here by name
        import flowsentryx_tpu.engine.predict as predict_mod

        gov = predict_mod.DispatchGovernor()
        public = {k for k in vars(gov)
                  if k not in ("rung_sizes", "batch_records",
                               "conf_min")}  # quiescent config
        assert public == set(contracts.PREDICT_PLAN.fields)

    def test_planted_governor_touched_from_worker(self):
        # the dispatch discipline on the governor: a worker thread
        # driving any hook (here: the forecast swap) must be a finding
        src = (
            "import threading\n"
            "class C:\n"
            "    def run(self):\n"
            "        threading.Thread(target=self._worker).start()\n"
            "        self.forecast = None\n"
            "    def _worker(self):\n"
            "        self.forecast = 1\n")
        out = check_class(ast.parse(src), "planted.py", ClassPlan(
            module="planted.py", cls="C",
            worker_targets=("_worker",),
            fields={"forecast": FieldContract("dispatch",
                                              "live forecast")}))
        assert len(out) == 1
        assert out[0].line == 7 and "forecast" in out[0].reason

    def test_planted_deferral_counter_outside_merge_section(self):
        # the shed-deferral counters ride the merge section: a bump
        # from the publish side (sink section territory) is a finding
        src = (
            "class C:\n"
            "    def tick(self):\n"
            "        self._ticks_deferred += 1\n"
            "    def publish(self):\n"
            "        self._ticks_deferred += 1\n")
        out = check_class(ast.parse(src), "planted.py", ClassPlan(
            module="planted.py", cls="C",
            sections={"merge": ("tick",)},
            fields={"_ticks_deferred": FieldContract(
                "section:merge", "shed deferral accounting")}))
        assert len(out) == 1
        assert out[0].line == 5 and "'merge' section" in out[0].reason

    def test_planted_rung_ewma_written_outside_launch(self):
        # the per-rung EWMA is launch-section state (seeded by the warm
        # pass, refined by the launch tail): a sink-side write is a
        # finding
        src = (
            "import threading\n"
            "class C:\n"
            "    def run(self):\n"
            "        threading.Thread(target=self._sink_worker).start()\n"
            "    def _note_step_s(self):\n"
            "        self._rung_ewma_s[8] = 0.1\n"
            "    def _sink_worker(self):\n"
            "        self._rung_ewma_s[8] = 0.2\n")
        out = check_class(ast.parse(src), "planted.py", ClassPlan(
            module="planted.py", cls="C",
            worker_targets=("_sink_worker",),
            sections={"launch": ("_note_step_s",)},
            fields={"_rung_ewma_s": FieldContract(
                "section:launch", "per-rung step-time EWMA")}))
        assert len(out) == 1
        assert out[0].line == 8 and "_rung_ewma_s" in out[0].reason
