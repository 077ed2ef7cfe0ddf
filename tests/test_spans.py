"""The one span type (engine/metrics.py), the closed seal→verdict stage
chain, the ``spans`` block that windows by subtraction, the host spans in
the profiler's trace, the named stages of the fused step, and the
verdict-ring drop counter (ISSUE 29)."""

import dataclasses
import json
import platform
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import (
    BatchConfig, FsxConfig, LimiterConfig, TableConfig,
)
from flowsentryx_tpu.engine import ArraySource, CollectSink, Engine, NullSink
from flowsentryx_tpu.engine import health
from flowsentryx_tpu.engine.metrics import (
    LatencyHist, LatencyRecorder, PipelineMetrics, Span, span_store,
)
from flowsentryx_tpu.engine.traffic import Scenario, TrafficGen, TrafficSpec
from flowsentryx_tpu.ops import fused


def small_cfg(batch=256, cap=1 << 12) -> FsxConfig:
    return FsxConfig(
        table=TableConfig(capacity=cap),
        batch=BatchConfig(max_batch=batch, verdict_k=64),
        limiter=LimiterConfig(pps_threshold=200.0, bps_threshold=1e9),
    )


def flood(n):
    return TrafficGen(TrafficSpec(
        scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7, n_attack_ips=8,
        n_benign_ips=24, attack_fraction=0.8, seed=13)).next_records(n)


def record_chain(eng):
    """Every ``LatencyRecorder.record`` call of a run, as its kwargs."""
    calls = []
    real = eng._lat.record

    def spy(**kw):
        calls.append(kw)
        real(**kw)

    eng._lat.record = spy
    return calls


def run_inline(tmp_path, **kw):
    eng = Engine(small_cfg(), ArraySource(flood(256 * 12)), CollectSink(),
                 mega_n="auto", **kw)
    eng.warm()
    calls = record_chain(eng)
    return eng.run(), calls


def run_sealed(tmp_path, **kw):
    """A real one-worker ShardedIngest fleet over a Python-made ring."""
    if platform.system() != "Linux":
        pytest.skip("shm ingest requires Linux")
    from flowsentryx_tpu.engine.shm import ShmRing
    from flowsentryx_tpu.ingest import ShardedIngest

    recs = flood(256 * 12)
    base = str(tmp_path / "fring")
    ring = ShmRing.create(schema.shard_ring_path(base, 0, 1), 1 << 12,
                          schema.FLOW_RECORD_DTYPE)
    assert ring.produce(recs) == len(recs)
    src = ShardedIngest(base, 1, queue_slots=16, precompact=False,
                        t0_grace_s=0.2)
    eng = Engine(small_cfg(cap=1 << 14), src, CollectSink(),
                 readback_depth=4, mega_n="auto", **kw)
    eng.warm()
    calls = record_chain(eng)
    try:
        deadline = time.monotonic() + 30
        while src.t0_ns is None:  # epoch handshake, then drain-stop
            src.poll_batches(0)
            assert time.monotonic() < deadline
            time.sleep(0.01)
        src.request_stop()
        rep = eng.run()
    finally:
        src.close()
    return rep, calls


class TestStageChain:
    @pytest.mark.parametrize("path,sink_thread", [
        ("inline", False), ("inline", True),
        ("sealed", False), ("sealed", True)])
    def test_chain_sums_to_seal_to_verdict_for_every_entry(
            self, tmp_path, path, sink_thread):
        rep, calls = (run_inline if path == "inline" else run_sealed)(
            tmp_path, sink_thread=sink_thread)
        assert rep.records == 256 * 12 and calls
        assert any(c["n"] > 256 for c in calls)  # a mega group was charged
        for c in calls:
            chain = sum(c[f"{s}_s"] for s in LatencyRecorder.CHAIN)
            assert chain == pytest.approx(c["total_s"], abs=1e-9)
            assert all(c[f"{s}_s"] >= 0 for s in LatencyRecorder.CHAIN)
            if path == "inline":
                assert c["queue_s"] == 0.0  # no sealed queue on this path
        lat = rep.latency
        assert lat["negatives"] == 0
        assert set(lat["stages"]) == set(LatencyRecorder.STAGES)
        sp = rep.spans
        total = sp["latency.seal_to_verdict"]
        assert total["n"] == rep.records
        chain_us = sum(sp[f"latency.{s}"]["sum_us"]
                       for s in LatencyRecorder.CHAIN)
        assert chain_us == pytest.approx(total["sum_us"], rel=1e-9)
        if path == "sealed":
            assert sp["latency.queue"]["sum_us"] > 0
            assert sp["fsx.ingest.w0.queue"]["n"] == rep.batches
            assert rep.ingest["workers"]["0"]["queue_ms"]["n"] == rep.batches

    def test_recorder_defaults_keep_old_callers(self):
        r = LatencyRecorder()
        r.record(1e-3, 5e-4, 1e-5, 4e-4, 1e-4, n=3)
        assert r.stages["hold"].n == 3 and r.stages["hold"].sum_us == 0.0
        r.record(1e-3, 0, 0, 0, 0, n=2, hold_s=-1e-6)
        assert r.negatives == 1


class TestSpanStore:
    def test_window_by_subtraction_equals_a_fresh_histogram(self):
        from benchmark import span_window

        rng = np.random.default_rng(7)
        before = rng.lognormal(-7, 2, 500)   # seconds: µs to seconds
        during = rng.lognormal(-5, 1.5, 800)
        weights = rng.integers(1, 2049, 800)
        span = Span("fsx.test")
        for s in before:
            span.add(float(s))
        snap0 = span_store({span.name: span.hist})
        fresh = LatencyHist()
        for s, n in zip(during, weights):
            span.add(float(s), int(n))
            fresh.add(float(s), int(n))
        snap1 = span_store({span.name: span.hist})
        # through JSON, as a reader of two reports has them
        snap0, snap1 = json.loads(json.dumps(snap0)), json.loads(
            json.dumps(snap1))
        w = span_window.subtract(snap0["fsx.test"], snap1["fsx.test"])
        assert w["n"] == fresh.n == int(weights.sum())
        assert w["sum_us"] == pytest.approx(fresh.sum_us, rel=1e-9)
        assert w["buckets"] == {
            int(i): int(c) for i, c in enumerate(fresh.counts) if c}
        # the benchmark's walk (written from the scheme's definition)
        # and the program's agree below the all-time max's clamp
        for q in (1, 50, 90, 99):
            assert span_window.percentile_us(w, q) == pytest.approx(
                fresh.percentile_us(q), abs=0.05)
        assert span_window.subtract(None, snap1["fsx.test"])["n"] \
            == span.hist.n
        assert span_window.subtract(snap0["fsx.test"], None) is None

    @pytest.mark.parametrize("us", [0.2, 1, 15, 16, 17, 1000, 36_864,
                                    36_865, 1e6, 5e7, 1e9])
    def test_bucket_edges_match_the_documented_scheme(self, us):
        """``benchmark/span_window.py`` states the scheme in words and
        does not import the program: hold the two together."""
        from benchmark import span_window
        from flowsentryx_tpu.engine.metrics import _lat_bucket, _lat_edge_us

        idx = _lat_bucket(us)
        u = max(int(np.ceil(us)), 1)
        e = int(np.floor(np.log2(u)))
        want = min(16 * e + (16 * (u - 2 ** e)) // 2 ** e,
                   span_window.BUCKETS - 1)
        assert idx == want
        assert span_window.upper_edge_us(idx) == _lat_edge_us(idx)
        if idx < span_window.BUCKETS - 1:
            assert u <= span_window.upper_edge_us(idx) <= u * (1 + 1 / 16) + 1

    def test_span_times_a_block_and_keeps_the_report_keys(self):
        span = Span("fsx.test")
        assert span.percentiles_ms() == {}
        with span:
            time.sleep(0.002)
        with span(seq=3) as s:
            time.sleep(0.001)
        assert s.seconds == s.t1 - s.t0 >= 0.001
        span.add(0.5, n=2)
        p = span.percentiles_ms()
        assert set(p) == {"p50", "p99", "max", "mean", "n"}
        assert p["n"] == 4 and p["max"] == pytest.approx(500.0)
        assert 2.0 <= p["p50"] <= 500.0
        assert span.hist.sum_us >= 1_003_000

    def test_report_views_share_the_store(self):
        """``stages_ms`` and the ``spans`` block are two faces of the
        same histograms; every span name is in the block."""
        eng = Engine(small_cfg(), ArraySource(flood(256 * 6)), NullSink(),
                     mega_n="auto", sink_thread=False)
        eng.warm()
        rep = eng.run()
        sp = rep.spans
        assert {s.name for s in PipelineMetrics().spans()} <= set(sp)
        assert {f"latency.{s}" for s in LatencyRecorder.STAGES} <= set(sp)
        for face, name in (("fill", "fsx.dispatch.poll"),
                           ("dispatch", "fsx.dispatch.launch"),
                           ("readback", "fsx.sink.fetch"),
                           ("e2e", "fsx.e2e")):
            assert rep.stages_ms[face]["n"] == sp[name]["n"] > 0
        for entry in sp.values():
            assert set(entry) == {"n", "sum_us", "max_us", "hist"}
            assert entry["hist"]["scheme"] == "log2x16us"
            assert sum(entry["hist"]["buckets"].values()) == entry["n"]
        # cumulative across run() calls: a second report only grows
        eng.reset_stream(ArraySource(flood(256 * 2)))
        assert eng.run().spans["fsx.dispatch.launch"]["n"] > 0
        # one upload and one launch a dispatch (warm()'s too: the store
        # counts from boot), one fetch a sink group
        assert sp["fsx.dispatch.upload"]["n"] \
            == sp["fsx.dispatch.launch"]["n"] > rep.dispatch["dispatches"]
        assert sp["fsx.sink.decode"]["n"] == sp["fsx.sink.fetch"]["n"] \
            == sp["fsx.sink.apply"]["n"]

    def test_status_merges_reports_and_two_reads_make_a_window(
            self, tmp_path):
        """``fsx status --engine-report`` adds the ``spans`` blocks of
        several engines; two reads subtracted are the window between."""
        from benchmark import span_window
        from flowsentryx_tpu.cli import _iter_engine_reports, _merged_spans

        a, b = Span("fsx.sink.apply"), Span("fsx.sink.apply")
        paths = [tmp_path / "r0.json", tmp_path / "r1.json"]

        def read():
            # rank 0 as `fsx serve` prints it, rank 1 in the cluster
            # runner's {"report": ...} wrapper
            paths[0].write_text(json.dumps(
                {"spans": span_store({a.name: a.hist})}))
            paths[1].write_text(json.dumps(
                {"report": {"spans": span_store({b.name: b.hist})}}))
            return _merged_spans(list(_iter_engine_reports(
                [str(tmp_path / "r*.json")])))

        a.add(0.001, 5)
        b.add(0.004, 7)
        first = read()
        assert first["fsx.sink.apply"]["n"] == 12
        a.add(0.016, 3)
        b.add(0.016, 1)
        w = span_window.subtract(first["fsx.sink.apply"],
                                 read()["fsx.sink.apply"])
        assert w["n"] == 4 and w["sum_us"] == pytest.approx(64000.0)
        # 16 ms lies in the octave's last sixteenth: its edge is 2^14 us
        assert span_window.percentile_us(w, 50) == 16384.0
        assert _merged_spans([("x", None, "unreadable")]) is None


class TestHostSpansInTheTrace:
    def test_one_groups_spans_share_seq_across_threads(self, tmp_path):
        eng = Engine(small_cfg(), ArraySource(flood(256 * 8)), NullSink(),
                     mega_n="auto", sink_thread=True)
        eng.warm()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            rep = eng.run()
        finally:
            jax.profiler.stop_trace()
        files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
        assert len(files) == 1
        seqs: dict[str, set] = {}
        lines: dict[str, set] = {}
        for plane in ProfileData.from_file(str(files[0])).planes:
            if plane.name != "/host:CPU":
                continue
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("fsx."):
                        seqs.setdefault(ev.name, set()).update(
                            v for k, v in ev.stats if k == "seq")
                        lines.setdefault(ev.name, set()).add(i)
        launched = seqs["fsx.dispatch.launch"]
        assert launched == set(range(min(launched), max(launched) + 1))
        assert len(launched) == rep.dispatch["dispatches"]
        assert seqs["fsx.dispatch.upload"] == launched
        # a sink group carries its first entry's ordinal
        assert seqs["fsx.sink.fetch"] and seqs["fsx.sink.fetch"] <= launched
        assert seqs["fsx.sink.fetch"] == seqs["fsx.sink.apply"] \
            == seqs["fsx.sink.decode"]
        # two threads: the sink's spans are on another line
        assert lines["fsx.sink.fetch"].isdisjoint(
            lines["fsx.dispatch.launch"])
        assert {"fsx.dispatch.poll", "fsx.sink.wait", "fsx.report"} \
            <= set(seqs)


class TestStepScopes:
    @staticmethod
    def _program(program, aging=False):
        """(engine, one of its step programs, an argument for it)."""
        cfg = small_cfg()
        if aging:
            cfg = dataclasses.replace(cfg, table=dataclasses.replace(
                cfg.table, evict_ttl_s=12.0, evict_every=64))
        eng = Engine(cfg, ArraySource(flood(8)), NullSink(),
                     mega_n="auto", sink_thread=False)
        raw = np.zeros((257, schema.COMPACT_RECORD_WORDS), np.uint32)
        if program == "compact_step":
            return eng, eng.step, raw
        g = max(eng.megasteps)
        return eng, eng.megasteps[g], np.stack([raw] * g)

    @pytest.mark.parametrize("program", ["compact_step", "mega_rung"])
    def test_every_stage_is_named_in_the_lowered_step(self, program):
        eng, fn, arg = self._program(program)
        text = fn.lower(eng.table, eng.stats, eng.params, arg).as_text(
            debug_info=True)
        for stage in fused.STEP_SCOPES:
            assert f"fsx.{stage}" in text, stage
        assert "fsx.evict" not in text  # aging is off in this config

    @pytest.mark.parametrize("program,outside", [
        ("compact_step", ["jit"]), ("mega_rung", ["jit", "scan"])])
    def test_every_operation_lies_under_a_stage(self, program, outside):
        """``step.stage_unscoped_ms.tput`` is what the step spends under
        no ``fsx.<stage>``: an operation a change adds outside the
        scopes lands there and the stage table stops adding up to
        anything one can name (ISSUE 36).  Outside them lie the
        program's own ``jit`` and the megastep's ``scan``, as before."""
        from flowsentryx_tpu.audit.graph import iter_staged_eqns

        eng, fn, arg = self._program(program)
        staged = list(iter_staged_eqns(jax.make_jaxpr(fn)(
            eng.table, eng.stats, eng.params, arg)))
        assert [eqn.primitive.name for stage, eqn in staged
                if stage is None] == outside
        assert {stage for stage, _ in staged} - {None} \
            == set(fused.STEP_SCOPES)

    @pytest.mark.parametrize("program,outside", [
        ("compact_step", ["jit"]), ("mega_rung", ["jit", "scan"])])
    def test_aging_adds_one_stage_and_nothing_unscoped(self, program,
                                                       outside):
        """With ``evict_ttl_s`` on the sweep is an eighth stage,
        ``fsx.evict`` (the benchmark's ``step.stage_evict_ms.tput``
        reads it), and still no operation lies outside the scopes."""
        from flowsentryx_tpu.audit.graph import iter_staged_eqns

        eng, fn, arg = self._program(program, aging=True)
        args = (eng.table, eng.stats, eng.params, arg)
        assert "fsx.evict" in fn.lower(*args).as_text(debug_info=True)
        staged = list(iter_staged_eqns(jax.make_jaxpr(fn)(*args)))
        assert [eqn.primitive.name for stage, eqn in staged
                if stage is None] == outside
        assert {stage for stage, _ in staged} - {None} \
            == set(fused.STEP_SCOPES) | {"evict"}

    def test_the_table_counters_keep_their_names(self):
        """``fsx serve``'s report and each benchmark run's ``stats``
        line carry the three table counters under these names; the
        benchmark's readers (``probe.stale_read_share.tput``,
        ``table.untracked_share.tput``, the churn driver's
        ``evicted_gap``) read them by name."""
        assert schema.GlobalStats._fields[-3:] == (
            "evicted", "stale_reads", "untracked")
        eng, _, _ = self._program("compact_step", aging=True)
        stats = eng.run().stats
        assert {"evicted", "stale_reads", "untracked"} <= set(stats)


@pytest.mark.parametrize("script", [
    "check_trace_reduce.py", "check_trace_scopes.py",
    "check_trace_scopes_evict.py"])
def test_the_benchmarks_trace_checks_pass(script):
    """The arithmetic behind the device-trace metrics, on traces whose
    answers are known: run by hand until ISSUE 39, which added the
    third (the aging sweep as an eighth stage, ``fsx.evict``)."""
    root = Path(__file__).resolve().parents[1]
    p = subprocess.run([sys.executable, f"benchmark/{script}"], cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout[-800:] + p.stderr[-800:]
    assert p.stdout.strip().splitlines()[-1] == "ok"


class TestVerdictRingDropped:
    def test_dropped_blocks_reach_report_and_health(self):
        class FullRingSink(CollectSink):
            """A sink that, like ShmVerdictSink on a full ring, drops
            what does not fit and counts it."""

            dropped = 0

            def apply(self, update):
                self.dropped += len(update.key)

        sink = FullRingSink()
        rep = Engine(small_cfg(), ArraySource(flood(256 * 12)), sink,
                     sink_thread=False).run()
        assert sink.dropped > 0
        assert rep.readback["verdict_ring_dropped"] == sink.dropped
        assert rep.health["state"] == health.DEGRADED
        assert f"verdict_ring_dropped:{sink.dropped}" in rep.health["reasons"]

    def test_a_sink_that_cannot_drop_stays_healthy(self):
        rep = Engine(small_cfg(), ArraySource(flood(256 * 4)), CollectSink(),
                     sink_thread=False).run()
        assert rep.readback["verdict_ring_dropped"] is None
        assert rep.health["state"] == health.HEALTHY
        assert health.engine_health(
            readback={"verdict_ring_dropped": 0})["state"] == health.HEALTHY
