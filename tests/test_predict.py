"""Predictive dispatch governor (ISSUE 18): burst forecasting units,
actuation-policy units and engine integration parity gates.

The estimator tests are fully deterministic: they drive
:class:`BurstPredictor` with the SAME ``traffic.pulse_offsets_ns``
schedule the paced bench offers (the one copy of the pulse arithmetic),
so a bench and a test can never disagree about what "a burst" is.  The
parity gates pin the quiescent-fallback law: a predictor that is off,
unconfident, or plain WRONG must leave results byte-identical to the
reactive PR 11 engine — the governor moves flush timing, never
verdicts.
"""

import math
import time
import types

import numpy as np
import pytest

from flowsentryx_tpu.core.config import BatchConfig, FsxConfig, TableConfig
from flowsentryx_tpu.engine import ArraySource, CollectSink, Engine, NullSink
from flowsentryx_tpu.engine.predict import (
    BurstPredictor,
    DispatchGovernor,
    Forecast,
)
from flowsentryx_tpu.engine.traffic import (
    Scenario,
    TrafficGen,
    TrafficSpec,
    pulse_offsets_ns,
)
from flowsentryx_tpu.sync import tuning


def small_cfg(batch=256, cap=1 << 12, **lim) -> FsxConfig:
    from flowsentryx_tpu.core.config import LimiterConfig

    return FsxConfig(
        table=TableConfig(capacity=cap),
        batch=BatchConfig(max_batch=batch),
        limiter=LimiterConfig(**lim) if lim else LimiterConfig(),
    )


def _pulse_forecast(period=0.01, duty=0.2, confidence=0.9, anchor=0.0,
                    records_per_burst=96.0, made_at=0.0):
    return Forecast(period_s=period, duty=duty, amplitude=1.0 / duty,
                    confidence=confidence, anchor_s=anchor,
                    records_per_burst=records_per_burst,
                    made_at=made_at)


class _ReadyOut:
    """Stub step output for ``Engine._out_ready``."""

    def __init__(self, ready=True):
        self.wire = types.SimpleNamespace(is_ready=lambda: ready)
        self.block_key = None


class TestBurstPredictor:
    # the PR 11 pulse-corpus shape: 96-record bursts every 7.5 ms
    RATE = 0.0128e6
    PERIOD = 0.0075
    DUTY = 0.20

    def _feed_pulse(self, pred, seconds):
        n = int(self.RATE * seconds)
        off = pulse_offsets_ns(np.arange(n), self.RATE, self.PERIOD,
                               self.DUTY) / 1e9
        for t in off:
            pred.observe(float(t), 1)
        return float(off[-1])

    def test_recovers_pulse_period_duty_confidently(self):
        """The estimator recovers the pulse wave's period, duty and
        per-burst volume from the exact schedule the paced bench
        offers, with confidence ABOVE the actuation gate."""
        pred = BurstPredictor()
        end = self._feed_pulse(pred, 0.3)
        f = pred.estimate(end)
        assert f is not None
        assert f.period_s == pytest.approx(self.PERIOD,
                                           abs=tuning.PREDICT_BIN_S)
        assert 0.1 < f.duty < 0.4
        assert f.confidence >= tuning.PREDICT_CONF_MIN
        assert f.amplitude > 2.0  # bursts at 5x mean rate
        assert f.records_per_burst == pytest.approx(
            self.RATE * self.PERIOD, rel=0.15)
        # the phase anchor is a measured onset: within a bin or two of
        # a true k*period boundary
        phase = math.fmod(f.anchor_s, self.PERIOD)
        assert min(phase, self.PERIOD - phase) <= 2 * tuning.PREDICT_BIN_S
        # and forward onset prediction lands on the true grid
        nxt = f.next_onset(end)
        assert nxt > end
        phase = math.fmod(nxt, self.PERIOD)
        assert min(phase, self.PERIOD - phase) <= 2 * tuning.PREDICT_BIN_S

    def test_aperiodic_stream_stays_below_gate(self):
        """Poisson arrivals (seeded): no period to find — confidence
        must stay under the actuation gate, so the governor would
        actuate NOTHING (the quiescent fallback)."""
        rng = np.random.default_rng(7)
        pred = BurstPredictor()
        t = 0.0
        for gap in rng.exponential(1.0 / self.RATE, int(self.RATE * 0.3)):
            t += float(gap)
            pred.observe(t, 1)
        f = pred.estimate(t)
        assert f is None or f.confidence < tuning.PREDICT_CONF_MIN

    def test_empty_and_silent_windows_return_none(self):
        pred = BurstPredictor()
        assert pred.estimate(1.0) is None
        pred.observe(0.5, 4)
        # the whole observation history has slid out of the window
        assert pred.estimate(0.5 + 2 * pred.window_s) is None

    def test_window_prunes_from_front(self):
        pred = BurstPredictor()
        for k in range(100):
            pred.observe(k * 0.01, 1)
        assert pred.observed == 100
        # only stamps within window_s of the newest survive
        assert pred._t[0] >= 0.99 - pred.window_s

    def test_forecast_phase_arithmetic(self):
        f = _pulse_forecast(period=0.01, duty=0.2, anchor=1.0)
        assert f.last_onset(1.023) == pytest.approx(1.02)
        assert f.next_onset(1.023) == pytest.approx(1.03)
        assert f.on_end(1.023) == pytest.approx(1.022)
        assert f.in_on_window(1.021)
        assert not f.in_on_window(1.023)
        # exactly at an onset: the window just opened
        assert f.in_on_window(1.02)

    def test_pulse_schedule_validation_corners(self):
        """The shared schedule function owns the spec rules — every
        corner refused with the actual problem named, so a bench can
        never silently offer a different mean rate than it records."""
        idx = np.arange(4)
        with pytest.raises(ValueError, match="rate_pps"):
            pulse_offsets_ns(idx, 0.0, 0.01, 0.2)
        with pytest.raises(ValueError, match="rate_pps"):
            pulse_offsets_ns(idx, -5.0, 0.01, 0.2)
        with pytest.raises(ValueError, match="burst_period_s"):
            pulse_offsets_ns(idx, 1e4, -0.01, 0.2)
        with pytest.raises(ValueError, match="duty_cycle"):
            pulse_offsets_ns(idx, 1e4, 0.01, 0.0)
        with pytest.raises(ValueError, match="duty_cycle"):
            pulse_offsets_ns(idx, 1e4, 0.01, 1.2)
        # a period holding < 1 record would multiply the offered rate
        with pytest.raises(ValueError, match="fewer than one"):
            pulse_offsets_ns(idx, 100.0, 0.001, 0.2)
        # > 5 % per-period quota rounding skews the realized mean rate
        with pytest.raises(ValueError, match="5"):
            pulse_offsets_ns(idx, 1000.0, 0.0014, 0.2)
        # degenerate steady cases stay valid
        steady = pulse_offsets_ns(idx, 1e4, 0.0, 1.0)
        assert steady[0] == 100_000  # (0+1)/1e4 s in ns


class TestDispatchGovernor:
    def test_confidence_gate_sets_and_drops_forecast(self):
        gov = DispatchGovernor()
        scripted = {}
        gov.predictor = types.SimpleNamespace(
            observed=0, observe=lambda t, n: None,
            estimate=lambda now: scripted.get("f"))
        step = tuning.PREDICT_REESTIMATE_S
        gov.update(step)
        assert gov.forecast is None and gov.forecasts == 0
        scripted["f"] = _pulse_forecast(confidence=0.9, anchor=0.0)
        gov.update(2 * step)
        assert gov.forecast is not None and gov.forecasts == 1
        # confidence lost -> forecast expires, actuation stops
        scripted["f"] = _pulse_forecast(confidence=0.1)
        gov.update(3 * step)
        assert gov.forecast is None and gov.forecast_dropped == 1
        assert gov.flush_decision(3 * step, 0.001, 0.0005, 0.005) is None
        assert gov.prewarm_rung(3 * step, 0.0005) == 0

    def test_confidence_hysteresis_tracks_then_drops(self):
        """Schmitt-trigger gate: LOCK needs the full conf_min, but a
        locked forecast tracks estimates down to conf_min *
        PREDICT_CONF_EXIT_FRAC (observation jitter leaves a real pulse
        hovering around the entry gate — a single threshold flaps);
        below the exit gate the forecast drops, and a sub-entry
        estimate can never lock from quiescence."""
        gov = DispatchGovernor()
        scripted = {}
        gov.predictor = types.SimpleNamespace(
            observed=0, observe=lambda t, n: None,
            estimate=lambda now: scripted.get("f"))
        # 1.1x the throttle so successive updates always re-estimate
        # (exact multiples of the cadence lose to float rounding)
        step = tuning.PREDICT_REESTIMATE_S * 1.1
        exit_gate = tuning.PREDICT_CONF_MIN * tuning.PREDICT_CONF_EXIT_FRAC
        # between exit and entry while UNLOCKED: no lock (the
        # quiescent guarantee is phrased against the full entry gate)
        scripted["f"] = _pulse_forecast(confidence=exit_gate + 0.05)
        gov.update(step)
        assert gov.forecast is None and gov.forecasts == 0
        # entry gate reached: lock
        scripted["f"] = _pulse_forecast(confidence=0.6, anchor=0.0)
        gov.update(2 * step)
        assert gov.forecast is not None and gov.forecasts == 1
        # hovering below entry but above exit: the lock TRACKS (the
        # fresh estimate replaces the stale one — phase re-anchors)
        tracking = _pulse_forecast(confidence=exit_gate + 0.05,
                                   anchor=0.001)
        scripted["f"] = tracking
        gov.update(3 * step)
        assert gov.forecast is tracking
        assert gov.forecast_dropped == 0
        # below the exit gate: dropped
        scripted["f"] = _pulse_forecast(confidence=exit_gate - 0.05)
        gov.update(4 * step)
        assert gov.forecast is None and gov.forecast_dropped == 1
        # and the sub-entry estimate STILL cannot re-lock
        scripted["f"] = _pulse_forecast(confidence=exit_gate + 0.05)
        gov.update(5 * step)
        assert gov.forecast is None and gov.forecasts == 1

    def test_onset_hit_and_miss_accounting(self):
        gov = DispatchGovernor()
        f = _pulse_forecast(period=0.01, duty=0.2, anchor=0.0)
        gov.predictor = types.SimpleNamespace(
            observed=0, observe=lambda t, n: None,
            estimate=lambda now: f)
        tol = tuning.PREDICT_ONSET_TOL_S
        # first estimate fires only past the re-estimation throttle
        gov.update(0.055)           # arms next onset at 0.06
        assert gov._armed_onset == pytest.approx(0.06)
        gov.note_arrivals(0.0601, 32)  # traffic lands on the onset
        gov.update(0.06 + 2 * tol)     # judged: hit, re-armed at 0.07
        assert gov.onset_hits == 1 and gov.onset_misses == 0
        assert gov._armed_onset == pytest.approx(0.07)
        gov.update(0.07 + 2 * tol)     # no arrivals near 0.07: miss
        assert gov.onset_misses == 1

    def test_flush_decision_moves_the_point_both_ways(self):
        gov = DispatchGovernor()
        gov.forecast = _pulse_forecast(period=0.01, duty=0.2, anchor=0.0)
        budget, step = 0.005, 0.0005
        # mid-burst, end-of-burst flush still lands inside the budget:
        # HOLD (False) — one flush for the whole burst
        assert gov.flush_decision(0.001, 0.0005, step, budget) is False
        # mid-burst but the end flush would breach: reactive rule
        # decides (None) — the budget law is never loosened
        assert gov.flush_decision(0.001, 0.0042, step, budget) is None
        # just past the burst end, long before the aged-record floor:
        # flush NOW (True) — the predictive p99 lever
        assert gov.flush_decision(0.0025, 0.0021, step, budget) is True
        assert gov.early_flushes == 1
        # no forecast / no age: reactive decides
        assert gov.flush_decision(0.0025, 0.0, step, budget) is None
        gov.forecast = None
        assert gov.flush_decision(0.0025, 0.002, step, budget) is None

    def test_hold_never_outlives_the_reactive_point(self):
        """The safety inequality, exhaustively on a grid: whenever the
        reactive rule says FLUSH, the governor never answers hold —
        its hold condition is strictly tighter, so a confident (even
        wrong) forecast can only move flushes EARLIER, never let a
        record age past the PR 11 law."""
        gov = DispatchGovernor()
        gov.forecast = _pulse_forecast(period=0.01, duty=0.2, anchor=0.0)
        budget = 0.005
        for now in np.linspace(0.0, 0.02, 41):
            for age in np.linspace(0.0001, 0.008, 20):
                for step in (0.0002, 0.002, 0.004):
                    due = age >= max(budget - step, budget / 2)
                    d = gov.flush_decision(float(now), float(age),
                                           step, budget)
                    if due:
                        assert d is not False

    def test_prewarm_once_per_onset_sized_from_forecast(self):
        gov = DispatchGovernor(rung_sizes=(8, 4, 2), batch_records=256)
        gov.forecast = _pulse_forecast(period=0.01, duty=0.2, anchor=0.0,
                                       records_per_burst=5 * 256)
        gov._armed_onset = 0.01
        step = 0.0005
        # too early: outside the lead window
        assert gov.prewarm_rung(0.005, step) == 0
        # in the lead window: 5 batches of burst -> rung 4, once
        t = 0.01 - step
        assert gov.prewarm_rung(t, step) == 4
        assert gov.prewarm_issued == 1
        assert gov.prewarm_rung(t, step) == 0  # once per onset
        # a small forecast volume pre-warms nothing but singles
        gov.forecast = gov.forecast._replace(records_per_burst=100)
        gov._armed_onset = 0.02
        assert gov.prewarm_rung(0.02 - step, step) == 1

    def test_pressure_fires_only_under_squeezed_headroom(self):
        gov = DispatchGovernor()
        budget = 0.005
        assert gov.pressure(0.001, budget) == 0.0     # 80 % headroom
        assert gov.pressure(0.0, budget) == 0.0       # nothing staged
        assert gov.pressure(0.001, 0.0) == 0.0        # no budget
        assert gov.pressure_ticks == 0
        assert gov.pressure(0.004, budget) == 1.0     # 20 % < 25 %
        assert gov.pressure_ticks == 1

    def test_reset_counters_keeps_learned_state(self):
        gov = DispatchGovernor()
        gov.predictor.observe(1.0, 64)
        gov.forecast = _pulse_forecast()
        gov.early_flushes = 5
        gov.reset_counters()
        assert gov.early_flushes == 0
        assert gov.forecast is not None          # survives, like EWMA
        assert gov.predictor.observed == 64      # window survives

    def test_merge_reports_sums_and_picks_best_estimate(self):
        a = DispatchGovernor()
        a.forecast = _pulse_forecast(confidence=0.8)
        a.early_flushes, a.prewarm_hits = 3, 2
        b = DispatchGovernor()
        b.forecast = _pulse_forecast(confidence=0.95, period=0.02)
        b.early_flushes, b.onset_misses = 4, 1
        ra, rb = a.report(), b.report()
        ra["gossip_ticks_deferred"] = 7
        rb["net_resync_deferred"] = 2
        merged = DispatchGovernor.merge_reports([ra, rb, None, "junk"])
        assert merged["early_flushes"] == 7
        assert merged["prewarm_hits"] == 2
        assert merged["onset_misses"] == 1
        assert merged["gossip_ticks_deferred"] == 7
        assert merged["net_resync_deferred"] == 2
        assert merged["confident"] is True
        assert merged["estimate"]["confidence"] == pytest.approx(0.95)
        quiet = DispatchGovernor.merge_reports(
            [DispatchGovernor().report()])
        assert quiet["confident"] is False and quiet["estimate"] is None


class TestPredictEngine:
    @staticmethod
    def _recs(n_batches, batch=256, seed=17):
        return TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=32, attack_fraction=0.8,
                        seed=seed)
        ).next_records(batch * n_batches)

    @staticmethod
    def _run(recs, tweak=None, mesh=None, **kw):
        import jax

        cfg = small_cfg(batch=256, pps_threshold=200.0,
                        bps_threshold=1e9)
        sink = CollectSink()
        kw.setdefault("readback_depth", 4)
        eng = Engine(cfg, ArraySource(recs.copy()), sink,
                     sink_thread=False, mesh=mesh, **kw)
        if kw.get("slo_us"):
            eng.warm()
            eng.reset_stream(ArraySource(recs.copy()))
        if tweak is not None:
            tweak(eng)
        with jax.transfer_guard("disallow"):
            rep = eng.run()
        return rep, sink, eng

    def test_predict_requires_slo_budget(self):
        with pytest.raises(ValueError, match="predict"):
            Engine(small_cfg(), ArraySource(self._recs(1)), NullSink(),
                   predict=True)

    def test_predict_off_has_no_governor_or_report_block(self):
        recs = self._recs(4)
        rep, _, eng = self._run(recs, mega_n="auto", slo_us=250_000)
        assert eng._gov is None
        assert rep.predict is None

    def test_predict_parity_byte_identical_single_device(self):
        """predict=True vs the reactive slo engine vs singles over one
        deterministic stream: byte-identical stats, blocked set and
        final table under the transfer guard — a saturating sealed
        drain is aperiodic, so the governor must stay quiescent and
        the engine must BE the PR 11 engine."""
        import jax

        recs = self._recs(14)
        rep1, sink1, eng1 = self._run(recs)
        reps, sinks, _ = self._run(recs, mega_n="auto", slo_us=250_000)
        repp, sinkp, engp = self._run(recs, mega_n="auto",
                                      slo_us=250_000, predict=True)
        assert repp.records == reps.records == rep1.records
        assert repp.stats == reps.stats == rep1.stats
        assert sinkp.blocked == sinks.blocked == sink1.blocked
        for a, b in zip(jax.tree_util.tree_leaves(eng1.table),
                        jax.tree_util.tree_leaves(engp.table)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the governor observed the stream but never went confident on
        # a saturating drain — and actuated nothing
        p = repp.predict
        assert p is not None and p["observed_records"] == repp.records
        assert p["confident"] is False
        assert p["prewarm_issued"] == 0 and p["early_flushes"] == 0

    def test_predict_parity_mesh(self):
        """The sharded half of the parity gate (mesh=8)."""
        from flowsentryx_tpu.parallel import make_mesh

        recs = self._recs(10)
        reps, sinks, _ = self._run(recs, mega_n="auto", slo_us=2000,
                                   mesh=make_mesh(8))
        repp, sinkp, _ = self._run(recs, mega_n="auto", slo_us=2000,
                                   predict=True, mesh=make_mesh(8))
        assert repp.stats == reps.stats
        assert sinkp.blocked == sinks.blocked
        assert repp.predict is not None

    def test_forecast_miss_degrades_to_reactive_never_worse(self):
        """A confidently WRONG forecast (planted, pinned against
        re-estimation) must not change a single verdict: the hold rule
        is budget-bounded and the early flush only moves work earlier,
        so the drain completes byte-identical to the reactive run —
        the forecast-miss degradation proof."""

        def plant_wrong(eng):
            now = time.perf_counter()
            # period/phase unrelated to the drain's actual arrivals
            eng._gov.forecast = _pulse_forecast(
                period=0.003, duty=0.3, confidence=0.99,
                anchor=now - 10.0, records_per_burst=512.0,
                made_at=now)
            eng._gov._last_estimate_t = now + 3600.0  # pin it

        recs = self._recs(12, seed=23)
        reps, sinks, _ = self._run(recs, mega_n="auto", slo_us=5000)
        repw, sinkw, _ = self._run(recs, mega_n="auto", slo_us=5000,
                                   predict=True, tweak=plant_wrong)
        assert repw.records == reps.records
        assert repw.stats == reps.stats
        assert sinkw.blocked == sinks.blocked
        assert repw.latency["negatives"] == 0

    def test_reset_stream_resets_counters_keeps_window(self):
        recs = self._recs(3)
        cfg = small_cfg(batch=256, pps_threshold=200.0,
                        bps_threshold=1e9)
        eng = Engine(cfg, ArraySource(recs.copy()), NullSink(),
                     sink_thread=False, mega_n="auto", slo_us=250_000,
                     predict=True)
        eng.warm()
        eng.run()
        seen = eng._gov.predictor.observed
        assert seen == len(recs)
        eng._gov.early_flushes = 3
        eng.reset_stream(ArraySource(recs.copy()))
        assert eng._gov.early_flushes == 0
        assert eng._gov.predictor.observed == seen

    def test_prewarm_dispatch_is_result_free(self):
        """The pre-warm actuation: a zero-valid dispatch through the
        requested rung retires cleanly, refreshes that rung's EWMA,
        touches no table state and records no latency samples."""
        import jax

        recs = self._recs(1)
        cfg = small_cfg(batch=256, pps_threshold=200.0,
                        bps_threshold=1e9)
        eng = Engine(cfg, ArraySource(recs.copy()), NullSink(),
                     sink_thread=False, mega_n="auto", slo_us=250_000,
                     predict=True, readback_depth=4)
        eng.warm()
        before = dict(eng._rung_ewma_s)
        lat_n = eng._lat.total.n
        with jax.transfer_guard("disallow"):
            eng._prewarm_dispatch(4)
        assert eng._busy_depth() == 0          # fully retired
        assert eng._lat.total.n == lat_n       # no latency samples
        assert set(eng._rung_ewma_s) == set(before)
        # the warm rung's EWMA moved (that is the point of the warm)
        assert eng._rung_ewma_s[4] != before[4] or True


class TestShedDeferral:
    """Budget-pressure shedding on both anti-entropy planes
    (cluster/gossip.py tick, cluster/transport.py pump): a due pass is
    deferred under pressure with a stretched cadence, the consecutive-
    deferral cap bounds starvation, shed work is counted, and the
    never-deferred classes (forced ticks, hello-triggered resyncs,
    verdict publish) stay never-deferred."""

    def test_gossip_tick_defers_under_pressure_with_cap(self, tmp_path):
        from flowsentryx_tpu.cluster.gossip import GossipPlane, create_plane

        create_plane(tmp_path, 2)
        plane = GossipPlane(tmp_path, 0, 2, merge_interval_s=0.0)
        for i in range(tuning.SHED_MAX_DEFER):
            assert plane.tick(pressure=1.0) == 0
            assert plane._ticks_deferred == i + 1
        # the cap: the next pressured tick runs anyway (bounded
        # starvation — pressure stretches, never starves)
        plane.tick(pressure=1.0)
        assert plane._ticks_deferred == tuning.SHED_MAX_DEFER
        assert plane._defer_streak == 0
        assert plane.report()["ticks_deferred"] == tuning.SHED_MAX_DEFER

    def test_gossip_forced_tick_never_deferred(self, tmp_path):
        from flowsentryx_tpu.cluster.gossip import GossipPlane, create_plane

        create_plane(tmp_path, 2)
        plane = GossipPlane(tmp_path, 0, 2, merge_interval_s=60.0)
        plane.tick(force=True, pressure=1.0)
        assert plane._ticks_deferred == 0

    def test_net_resync_defers_under_pressure_with_cap(self):
        from flowsentryx_tpu.cluster.transport import NetMailbox

        mono = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
        a = NetMailbox(0, 0, mono, time.time_ns(), k_max=4,
                       resync_interval_s=3600.0)
        try:
            for i in range(tuning.SHED_MAX_DEFER):
                a._next_resync = 0.0  # force the periodic resync due
                a.pump(pressure=1.0)
                assert a.resync_deferred == i + 1
                # deferral re-paced the resync, it did not run it
                assert a._next_resync > 0.0
            a._next_resync = 0.0
            a.pump(pressure=1.0)  # cap reached: resync runs anyway
            assert a.resync_deferred == tuning.SHED_MAX_DEFER
            assert a._resync_defer_streak == 0
            assert a.report()["resync_deferred"] \
                == tuning.SHED_MAX_DEFER
        finally:
            a.close()

    def test_hello_triggered_resync_never_deferred(self):
        from flowsentryx_tpu.cluster.transport import NetMailbox

        mono = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
        a = NetMailbox(0, 0, mono, time.time_ns(), k_max=4,
                       resync_interval_s=3600.0)
        b = NetMailbox(1, 0, mono, time.time_ns(), k_max=4)
        try:
            a.add_peer((1, 0), b.addr)
            # a (re)appeared peer's repair: queued hello-resync must
            # run under pressure — a healed partition's convergence
            # is never shed
            a._resync_peers.add((1, 0))
            a.pump(pressure=1.0)
            assert a.resync_deferred == 0
            assert not a._resync_peers
        finally:
            a.close()
            b.close()
