"""Engine-layer tests: traffic generators, batcher, sources, serving loop.

Runs on the virtual CPU mesh (conftest).  The serving loop here is the
"simulated kernel" integration of SURVEY.md §7.3: synthetic scenario →
ring records → micro-batches → fused step → verdict writeback, no root
or NIC required.
"""

import numpy as np
import pytest

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import (
    BatchConfig, FsxConfig, LimiterKind, TableConfig,
)
from flowsentryx_tpu.engine import (
    ArraySource,
    CollectSink,
    Engine,
    MicroBatcher,
    NullSink,
    TrafficSource,
)
from flowsentryx_tpu.engine.traffic import Scenario, TrafficGen, TrafficSpec
from flowsentryx_tpu.engine.writeback import extract_updates
from flowsentryx_tpu.ops.agg import INVALID_KEY


def small_cfg(batch=256, cap=1 << 12, verdict_k=64, **lim) -> FsxConfig:
    from flowsentryx_tpu.core.config import LimiterConfig

    return FsxConfig(
        table=TableConfig(capacity=cap),
        batch=BatchConfig(max_batch=batch, verdict_k=verdict_k),
        limiter=LimiterConfig(**lim) if lim else LimiterConfig(),
    )


class TestTraffic:
    def test_scenarios_produce_valid_records(self):
        for sc in Scenario:
            gen = TrafficGen(TrafficSpec(scenario=sc, seed=1))
            buf = gen.next_records(512)
            assert buf.dtype == schema.FLOW_RECORD_DTYPE
            assert len(buf) == 512
            assert (buf["saddr"] > 0).all()
            # synthetic clock advances at the configured rate
            assert buf["ts_ns"][-1] > buf["ts_ns"][0]

    def test_single_source_flood_is_single_source(self):
        gen = TrafficGen(
            TrafficSpec(scenario=Scenario.ICMP_FLOOD_SINGLE, attack_fraction=1.0)
        )
        buf = gen.next_records(256)
        assert len(np.unique(buf["saddr"])) == 1
        assert (buf["ip_proto"] == 1).all()  # ICMP

    def test_labels_split_pools(self):
        gen = TrafficGen(TrafficSpec(scenario=Scenario.SYN_BENIGN_MIX, seed=3))
        buf = gen.next_records(2048)
        labels = gen.labels_for(buf)
        assert 0.3 < labels.mean() < 0.7  # ~50/50 mix
        # attack features look flood-like: tiny IAT means
        iat = buf["feat"][:, schema.Feature.FWD_IAT_MEAN]
        assert iat[labels].mean() < 100
        assert iat[~labels].mean() > 1000

    def test_rate_controls_clock(self):
        slow = TrafficGen(TrafficSpec(rate_pps=1e3, seed=0))
        fast = TrafficGen(TrafficSpec(rate_pps=1e6, seed=0))
        n = 1000
        dt_slow = np.diff(slow.next_records(n)["ts_ns"].astype(np.int64)).mean()
        dt_fast = np.diff(fast.next_records(n)["ts_ns"].astype(np.int64)).mean()
        assert dt_slow == pytest.approx(1e6, rel=0.01)  # 1 kpps -> 1 ms
        assert dt_fast == pytest.approx(1e3, rel=0.01)  # 1 Mpps -> 1 us


class TestBatcher:
    def test_size_trigger(self):
        mb = MicroBatcher(BatchConfig(max_batch=128, deadline_us=10**6))
        gen = TrafficGen(TrafficSpec())
        out = mb.add(gen.next_records(300))
        assert len(out) == 2  # 300 records -> two full 128-batches, 44 pending
        assert mb.fill == 44
        for raw in out:
            assert raw.shape == (129, schema.RECORD_WORDS)
            assert raw[128, 0] == 128  # n_valid

    def test_deadline_trigger_and_padding(self):
        mb = MicroBatcher(BatchConfig(max_batch=128, deadline_us=1))
        gen = TrafficGen(TrafficSpec())
        assert mb.add(gen.next_records(10)) == []
        import time

        time.sleep(0.001)
        assert mb.flush_due()
        raw = mb.take()
        assert raw[128, 0] == 10
        assert mb.fill == 0 and mb.take() is None

    def test_wire_equals_encode_raw(self):
        """Batcher output must be byte-identical to schema.encode_raw."""
        mb = MicroBatcher(BatchConfig(max_batch=64, deadline_us=10**6), t0_ns=7)
        gen = TrafficGen(TrafficSpec(seed=9))
        buf = gen.next_records(64)
        [raw] = mb.add(buf)
        np.testing.assert_array_equal(raw, schema.encode_raw(buf, 64, t0_ns=7))

    def test_compact_wire_equals_encode_compact(self):
        """compact16 batcher output == schema.encode_compact (same
        quantizer, same metadata row)."""
        from flowsentryx_tpu.models import logreg

        params = logreg.golden_params()
        quant = schema.model_quant_args(params)
        t0 = 1_000_000
        mb = MicroBatcher(BatchConfig(max_batch=64, deadline_us=10**4),
                          t0_ns=t0, wire=schema.WIRE_COMPACT16, quant=quant)
        gen = TrafficGen(TrafficSpec(seed=9))
        buf = gen.next_records(64)
        [comp] = mb.add(buf)
        assert comp.shape == (65, schema.COMPACT_RECORD_WORDS)
        np.testing.assert_array_equal(
            comp, schema.encode_compact(buf, 64, t0_ns=t0, **quant)
        )

    def test_compact_wire_rejects_long_deadline(self):
        with pytest.raises(ValueError, match="65 ms"):
            MicroBatcher(BatchConfig(max_batch=64, deadline_us=100_000),
                         wire=schema.WIRE_COMPACT16)

    def test_compact_wire_seals_at_ts_span_boundary(self):
        """A compact batch may not span >65 ms of RECORD time (u16 us
        delta field); slow streams must seal early, not saturate."""
        mb = MicroBatcher(BatchConfig(max_batch=64, deadline_us=10**4),
                          wire=schema.WIRE_COMPACT16,
                          quant=dict(feat_mode="minifloat"))
        gen = TrafficGen(TrafficSpec(seed=4, rate_pps=1e4))  # 100 us gaps
        buf = gen.next_records(64)  # spans ~6.4 ms: fits one batch
        assert len(mb.add(buf)) == 1
        slow = gen.next_records(64)
        slow["ts_ns"] = slow["ts_ns"][0] + np.arange(64, dtype=np.uint64) * 2_000_000
        sealed = mb.add(slow)  # 2 ms spacing -> 126 ms span: must split
        total = sum(int(s[-1, 0]) for s in sealed) + mb.fill
        assert total == 64
        assert len(sealed) >= 1
        for s in sealed:
            n = int(s[-1, 0])
            dts = (s[:n, 3] >> 16).astype(np.int64)
            assert dts.max() < 65_000  # no saturated deltas
        # drain the remainder and check it too
        rest = mb.take()
        if rest is not None:
            n = int(rest[-1, 0])
            assert ((rest[:n, 3] >> 16).astype(np.int64) < 65_000).all()

    def test_precompact_passthrough(self):
        """Kernel-quantized compact records flow through the batcher
        untouched except the ts rebase: features/flags/len identical,
        dt fields batch-relative and monotone."""
        import time as _time

        mb = MicroBatcher(BatchConfig(max_batch=32, deadline_us=10**4, verdict_k=32),
                          wire=schema.WIRE_COMPACT16,
                          quant=dict(feat_mode="minifloat"))
        now = _time.clock_gettime_ns(_time.CLOCK_MONOTONIC)
        rec = np.zeros(32, schema.COMPACT_RECORD_DTYPE)
        rec["w0"] = np.arange(32)
        rec["w1"] = 0x04030201
        rec["w2"] = 0x08070605
        # kernel stamps: spaced 100 us, ending "now"
        ts_us = (now // 1000 - (31 - np.arange(32)) * 100).astype(np.uint64)
        rec["w3"] = (np.uint32(100 // 8) | np.uint32(schema.FLAG_UDP) << 11
                     | (ts_us & np.uint64(0xFFFF)).astype(np.uint32) << 16)
        [wire] = mb.add_precompact(rec)
        assert int(wire[-1, 0]) == 32
        np.testing.assert_array_equal(wire[:32, 0], rec["w0"])
        np.testing.assert_array_equal(wire[:32, 1], rec["w1"])
        np.testing.assert_array_equal(wire[:32, 2], rec["w2"])
        assert ((wire[:32, 3] & 0x7FF) == 100 // 8).all()
        dts = (wire[:32, 3] >> 16).astype(np.int64)
        assert dts[0] == 0 and (np.diff(dts) >= 0).all()
        assert abs(dts[-1] - 3100) <= 2  # 31 x 100 us spacing preserved

    def test_engine_serves_precompact_source(self):
        """End-to-end: a source delivering KERNEL-quantized 16 B records
        (a compact-emit data plane) drives the engine to the same
        decisions — flood sources blocked, benign untouched."""
        import time as _time

        from flowsentryx_tpu.core.config import LimiterConfig

        class PrecompactSource:
            precompact = True

            def __init__(self, spec, total):
                self.gen = TrafficGen(spec)
                self.left = total

            def poll(self, n):
                n = min(n, self.left)
                if n <= 0:
                    return np.zeros(0, schema.COMPACT_RECORD_DTYPE)
                self.left -= n
                buf = self.gen.next_records(n)
                out = np.zeros(n, schema.COMPACT_RECORD_DTYPE)
                q = schema.quantize_feat_minifloat(buf["feat"])
                out["w0"] = buf["saddr"]
                out["w1"] = (q[:, 0] | q[:, 1] << 8 | q[:, 2] << 16
                             | q[:, 3] << 24)
                out["w2"] = (q[:, 4] | q[:, 5] << 8 | q[:, 6] << 16
                             | q[:, 7] << 24)
                len8 = np.minimum(
                    (buf["pkt_len"].astype(np.uint32) + 4) >> 3, 2047)
                # kernel stamps: wrapped us of a just-now stream
                now = _time.clock_gettime_ns(_time.CLOCK_MONOTONIC)
                span = buf["ts_ns"] - buf["ts_ns"][0]
                ts16 = (((np.uint64(now) + span) // 1000)
                        & np.uint64(0xFFFF)).astype(np.uint32)
                out["w3"] = (len8
                             | (buf["flags"].astype(np.uint32) & 0x1F) << 11
                             | ts16 << 16)
                return out

            def exhausted(self):
                return self.left <= 0

        cfg = FsxConfig(
            limiter=LimiterConfig(pps_threshold=200.0, bps_threshold=1e9),
            table=TableConfig(capacity=1 << 12),
            batch=BatchConfig(max_batch=512),
        )
        spec = TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                           n_attack_ips=16, attack_fraction=0.8, seed=21)
        src = PrecompactSource(spec, total=512 * 16)
        sink = CollectSink()
        eng = Engine(cfg, src, sink, readback_depth=4)
        assert eng.precompact and eng.wire == schema.WIRE_COMPACT16
        rep = eng.run()
        assert rep.records == 512 * 16
        attack = set(int(k) for k in TrafficGen(spec).attack_ips)
        blocked = set(sink.blocked)
        assert blocked and blocked <= attack  # attackers only
        assert rep.stats["dropped"] > 0

    def test_buffer_reuse_masks_stale_tail(self):
        """A short batch reusing a buffer that previously held a full one
        must mask the stale tail via n_valid."""
        mb = MicroBatcher(BatchConfig(max_batch=32, deadline_us=10**6, verdict_k=32))
        gen = TrafficGen(TrafficSpec(seed=4))
        # cycle through all buffers once with full batches
        for _ in range(mb.n_buffers):
            mb.add(gen.next_records(32))
        mb.add(gen.next_records(5))
        raw = mb.take()
        assert raw[32, 0] == 5
        import jax

        batch = jax.jit(schema.decode_raw)(raw)
        assert int(np.asarray(batch.valid).sum()) == 5


class TestSources:
    def test_array_source_replays_once(self):
        gen = TrafficGen(TrafficSpec(seed=5))
        rec = gen.next_records(100)
        src = ArraySource(rec)
        got = [src.poll(33) for _ in range(5)]
        assert [len(g) for g in got] == [33, 33, 33, 1, 0]
        assert src.exhausted()

    def test_traffic_source_bounded(self):
        src = TrafficSource(TrafficSpec(seed=6), total=50)
        assert len(src.poll(40)) == 40
        assert not src.exhausted()
        assert len(src.poll(40)) == 10
        assert src.exhausted()
        assert len(src.poll(40)) == 0


class TestWriteback:
    def test_extract_updates_filters_padding(self):
        bk = np.array([5, INVALID_KEY, 9, INVALID_KEY], np.uint32)
        bu = np.array([1.5, 0.0, 2.5, 0.0], np.float32)
        upd = extract_updates(bk, bu)
        assert upd.key.tolist() == [5, 9]
        assert upd.until_s.tolist() == [1.5, 2.5]

    def test_collect_sink_last_wins_semantics(self):
        """The vectorized dict update must keep the per-key-loop
        semantics: LAST expiry wins for a key repeated within one
        update, and later updates overwrite earlier ones."""
        from flowsentryx_tpu.engine.writeback import BlacklistUpdate

        sink = CollectSink()
        sink.apply(BlacklistUpdate(
            key=np.array([7, 9, 7], np.uint32),
            until_s=np.array([1.0, 2.0, 3.0], np.float32)))
        assert sink.blocked[7] == 3.0 and sink.blocked[9] == 2.0
        sink.apply(BlacklistUpdate(
            key=np.array([9], np.uint32),
            until_s=np.array([5.0], np.float32)))
        assert sink.blocked[9] == 5.0
        assert sink.updates == 2


class TestVerdictWire:
    """The compact device→host verdict wire (ops/fused.pack_verdict_wire
    ↔ engine/writeback.decode_verdict_wire)."""

    def test_pack_decode_roundtrip(self):
        import jax
        import jax.numpy as jnp

        from flowsentryx_tpu.engine.writeback import decode_verdict_wire
        from flowsentryx_tpu.ops import fused

        bk = np.full(32, INVALID_KEY, np.uint32)
        bu = np.zeros(32, np.float32)
        bk[[3, 7, 20]] = [111, 222, 333]
        bu[[3, 7, 20]] = [1.5, 2.5, 3.5]
        wire = np.asarray(jax.jit(
            lambda k, u: fused.pack_verdict_wire(
                k, u, jnp.float32(9.25), np.uint32(4), 8)
        )(bk, bu))
        assert wire.shape == (fused.verdict_wire_words(8),)
        vw = decode_verdict_wire(wire)
        assert vw.key.tolist() == [111, 222, 333]
        assert vw.until_s.tolist() == [1.5, 2.5, 3.5]
        assert vw.count == 3 and not vw.overflow
        assert vw.route_drop == 4 and vw.now == 9.25

    def test_overflow_flag_and_true_count(self):
        import jax
        import jax.numpy as jnp

        from flowsentryx_tpu.engine.writeback import decode_verdict_wire
        from flowsentryx_tpu.ops import fused

        bk = np.arange(1, 13, dtype=np.uint32)  # 12 blocked flows
        bu = np.arange(12, dtype=np.float32)
        vw = decode_verdict_wire(np.asarray(jax.jit(
            lambda k, u: fused.pack_verdict_wire(
                k, u, jnp.float32(0.0), np.uint32(0), 8)
        )(bk, bu)))
        assert vw.overflow and vw.count == 12
        # the K slots still carry the FIRST 8 in order (order-preserving
        # compaction), but the overflow flag tells the host they are
        # incomplete — it must fall back to the full fetch
        assert vw.key.tolist() == list(range(1, 9))

    def test_merge_preserves_chunk_order_last_wins(self):
        """Merged mega wires keep chunk order so a key re-blocked in a
        later chunk resolves to the LATER expiry downstream."""
        import jax
        import jax.numpy as jnp

        from flowsentryx_tpu.engine.writeback import decode_verdict_wire
        from flowsentryx_tpu.ops import fused

        def mk(keys, untils, now):
            bk = np.full(16, INVALID_KEY, np.uint32)
            bu = np.zeros(16, np.float32)
            bk[:len(keys)] = keys
            bu[:len(keys)] = untils
            return fused.pack_verdict_wire(
                jnp.asarray(bk), jnp.asarray(bu), jnp.float32(now),
                np.uint32(1), 8)

        merged = np.asarray(jax.jit(lambda: fused.merge_verdict_wires(
            jnp.stack([mk([5, 6], [1.0, 2.0], 0.5),
                       mk([5], [9.0], 0.8)])))())
        vw = decode_verdict_wire(merged)
        assert vw.key.tolist() == [5, 6, 5]  # chunk order preserved
        assert vw.until_s.tolist() == [1.0, 2.0, 9.0]
        assert vw.count == 3 and not vw.overflow
        assert vw.route_drop == 2
        assert vw.now == pytest.approx(0.8)
        upd = extract_updates(vw.key, vw.until_s)
        sink = CollectSink()
        sink.apply(upd)
        assert sink.blocked[5] == 9.0  # last wins


class TestEngineLoop:
    def test_flood_scenario_blocks_attackers(self):
        """Config 2: multi-source UDP flood at 10 Mpps synthetic — the
        limiter + classifier must blacklist attack sources and pass the
        benign minority through."""
        cfg = small_cfg(batch=512, pps_threshold=200.0, bps_threshold=1e9)
        sink = CollectSink()
        src = TrafficSource(
            TrafficSpec(
                scenario=Scenario.UDP_FLOOD_MULTI,
                rate_pps=1e7,
                n_attack_ips=32,
                attack_fraction=0.8,
                seed=7,
            ),
            total=512 * 40,
        )
        eng = Engine(cfg, src, sink, readback_depth=4)
        rep = eng.run()
        assert rep.batches == 40
        assert rep.records == 512 * 40
        assert rep.stats["dropped"] > 0
        assert rep.blocked_sources > 0
        # every stage reported timings (pop/stage are the sealed-loop
        # sub-stages: present in the report, empty on the inline path)
        assert set(rep.stages_ms) == {"fill", "pop", "stage", "dispatch",
                                      "readback"}
        # the latency plane charged every record of the 40 batches
        assert rep.spans["latency.seal_to_verdict"]["n"] == 512 * 40

    def test_benign_traffic_mostly_passes(self):
        cfg = small_cfg(batch=256, pps_threshold=1e9, bps_threshold=1e12)
        sink = CollectSink()
        src = TrafficSource(
            TrafficSpec(scenario=Scenario.BENIGN, rate_pps=1e4, seed=8),
            total=256 * 10,
        )
        eng = Engine(cfg, src, sink)
        rep = eng.run()
        # benign interactive flows: no rate drops; ML may flag a few
        assert rep.stats["dropped_rate"] == 0
        assert rep.stats["allowed"] > rep.records * 0.9

    def test_mega_dispatch_matches_single(self):
        """Engine(mega_n=4): backlog-grouped lax.scan dispatch must
        reproduce the single-dispatch engine's verdicts, stats, and
        final table EXACTLY (the megastep is trajectory-identical by
        construction; this pins the ENGINE's grouping/flattening
        plumbing), while actually grouping (fewer dispatch timings
        than batches)."""
        import jax

        # ONE pregenerated stream: TrafficGen's rng consumption depends
        # on the poll chunk size, and the mega engine polls group-sized
        # chunks — polling the generator live would feed the two
        # engines different records, not different processing.
        recs = TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=32, attack_fraction=0.8, seed=11)
        ).next_records(256 * 32)

        def run(mega_n):
            cfg = small_cfg(batch=256, pps_threshold=200.0,
                            bps_threshold=1e9)
            sink = CollectSink()
            eng = Engine(cfg, ArraySource(recs.copy()), sink,
                         readback_depth=4, mega_n=mega_n)
            rep = eng.run()
            return rep, sink, eng

        rep1, sink1, eng1 = run(0)
        rep4, sink4, eng4 = run(4)
        assert rep4.records == rep1.records
        assert rep4.stats == rep1.stats
        assert sink4.blocked == sink1.blocked
        # grouping actually happened: 32 batches in ≤ 8 + stragglers
        assert (rep4.stages_ms["dispatch"]["n"]
                < rep1.stages_ms["dispatch"]["n"])
        for a, b in zip(jax.tree_util.tree_leaves(eng1.table),
                        jax.tree_util.tree_leaves(eng4.table)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the dispatch block accounts for every batch: groups staged
        # through the arena (1 host copy each), singles direct
        d = rep4.dispatch
        assert d["mode"] == "fixed" and d["group_sizes"] == [4]
        assert sum(int(g) * n for g, n in d["group_hist"].items()) == 32
        assert d["staged_batches"] == 4 * d["group_hist"]["4"]

    def test_adaptive_mega_matches_single_and_fixed(self):
        """Engine(mega_n="auto"): the power-of-two coalescing ladder is
        a pure dispatch-granularity change — byte-identical stats,
        blacklist (keys AND untils) and final table vs singles-only and
        fixed --mega on the same stream, while actually coalescing
        through MORE than one rung, with the whole loop clean under
        ``jax.transfer_guard("disallow")`` (the arena device_put is an
        explicit transfer)."""
        import jax

        recs = TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=32, attack_fraction=0.8, seed=11)
        ).next_records(256 * 28)  # 28 = 3 full 8-groups + 4: two rungs

        def run(mega_n):
            cfg = small_cfg(batch=256, pps_threshold=200.0,
                            bps_threshold=1e9)
            sink = CollectSink()
            eng = Engine(cfg, ArraySource(recs.copy()), sink,
                         readback_depth=4, mega_n=mega_n,
                         sink_thread=False)
            with jax.transfer_guard("disallow"):
                rep = eng.run()
            return rep, sink, eng

        rep1, sink1, eng1 = run(0)
        rep4, sink4, _ = run(4)
        repa, sinka, enga = run("auto")
        assert repa.records == rep4.records == rep1.records
        assert repa.stats == rep4.stats == rep1.stats
        assert sinka.blocked == sink4.blocked == sink1.blocked
        for a, b in zip(jax.tree_util.tree_leaves(eng1.table),
                        jax.tree_util.tree_leaves(enga.table)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        d = repa.dispatch
        assert d["mode"] == "adaptive"
        assert d["group_sizes"] == [8, 4, 2]
        hist = {int(g): n for g, n in d["group_hist"].items()}
        assert sum(g * n for g, n in hist.items()) == repa.batches == 28
        assert len([g for g in hist if g > 1]) >= 2  # ≥ two rungs fired
        assert d["host_copies_per_batch"] <= 1.0
        assert (repa.stages_ms["dispatch"]["n"]
                < rep1.stages_ms["dispatch"]["n"])

    def test_mega_auto_requires_pow2_cap(self):
        cfg = small_cfg(batch=128)
        with pytest.raises(ValueError, match="cap"):
            Engine(cfg, TrafficSource(TrafficSpec(), total=128),
                   NullSink(), mega_n=1, mega_auto=True)
        with pytest.raises(ValueError, match="auto"):
            Engine(cfg, TrafficSource(TrafficSpec(), total=128),
                   NullSink(), mega_n="four")

    def test_mega_requires_compact_wire(self):
        cfg = small_cfg(batch=256)
        with pytest.raises(ValueError, match="compact16"):
            Engine(cfg, TrafficSource(TrafficSpec(), total=256),
                   NullSink(), wire=schema.WIRE_RAW48, mega_n=4)

    def test_meshed_engine_matches_single_device(self):
        """Engine(mesh=8 devices) serves through the IP-hash-sharded
        step (VERDICT r2 item 4) and reproduces the single-device run
        bit-for-bit: same stats, same blocked set, same batch count."""
        from flowsentryx_tpu.parallel import make_mesh

        def run(mesh):
            cfg = small_cfg(batch=512, cap=1 << 12, pps_threshold=200.0,
                            bps_threshold=1e9)
            src = TrafficSource(
                TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                            n_attack_ips=32, attack_fraction=0.8, seed=7),
                total=512 * 24,
            )
            sink = CollectSink()
            eng = Engine(cfg, src, sink, readback_depth=4, mesh=mesh)
            rep = eng.run()
            return rep, eng

        rep_s, _ = run(None)
        rep_m, eng_m = run(make_mesh(8))
        assert eng_m.mesh is not None  # really served sharded
        # the mesh path keeps the compact16 wire (sharded compact step)
        assert eng_m.wire == schema.WIRE_COMPACT16
        # every counter alike but the sharded step's own row count
        rows = rep_m.stats.pop("owner_rows")
        assert rep_s.stats.pop("owner_rows") == 0 < rows
        assert rep_m.stats == rep_s.stats
        assert rep_m.blocked_sources == rep_s.blocked_sources
        assert rep_m.batches == rep_s.batches == 24

    def test_meshed_mega_engine_matches_meshed_single(self):
        """Engine(mesh=8, mega_n=4): the sharded mega-step (lax.scan of
        shard-mapped steps) must reproduce the per-batch meshed engine
        exactly — same stats, blocked set, and batch count — while
        grouping dispatches."""
        from flowsentryx_tpu.parallel import make_mesh

        recs = TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=32, attack_fraction=0.8, seed=13)
        ).next_records(512 * 16)

        def run(mega_n):
            cfg = small_cfg(batch=512, cap=1 << 12, pps_threshold=200.0,
                            bps_threshold=1e9)
            sink = CollectSink()
            eng = Engine(cfg, ArraySource(recs.copy()), sink,
                         readback_depth=8, mesh=make_mesh(8),
                         mega_n=mega_n)
            rep = eng.run()
            return rep, sink

        rep1, sink1 = run(0)
        rep4, sink4 = run(4)
        assert rep4.stats == rep1.stats
        assert sink4.blocked == sink1.blocked
        assert rep4.batches == rep1.batches == 16
        assert (rep4.stages_ms["dispatch"]["n"]
                < rep1.stages_ms["dispatch"]["n"])

    def test_meshed_adaptive_mega_matches_meshed_single(self):
        """Engine(mesh=8, mega_n="auto"): every rung of the sharded
        ladder (lax.scan of shard-mapped steps per power-of-two size)
        must reproduce the per-batch meshed engine exactly, under the
        transfer guard — the sharded half of the adaptive-coalescing
        parity gate."""
        import jax

        from flowsentryx_tpu.parallel import make_mesh

        recs = TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=32, attack_fraction=0.8, seed=13)
        ).next_records(512 * 12)  # 8 + 4: two rungs

        def run(mega_n):
            cfg = small_cfg(batch=512, cap=1 << 12, pps_threshold=200.0,
                            bps_threshold=1e9)
            sink = CollectSink()
            eng = Engine(cfg, ArraySource(recs.copy()), sink,
                         readback_depth=8, mesh=make_mesh(8),
                         mega_n=mega_n, sink_thread=False)
            with jax.transfer_guard("disallow"):
                rep = eng.run()
            return rep, sink

        rep1, sink1 = run(0)
        repa, sinka = run("auto")
        assert repa.stats == rep1.stats
        assert sinka.blocked == sink1.blocked
        assert repa.batches == rep1.batches == 12
        hist = {int(g): n for g, n in
                repa.dispatch["group_hist"].items()}
        assert sum(g * n for g, n in hist.items()) == 12
        assert any(g > 1 for g in hist)
        assert (repa.stages_ms["dispatch"]["n"]
                < rep1.stages_ms["dispatch"]["n"])

    def test_meshed_engine_single_device_mesh_falls_back(self):
        from flowsentryx_tpu.parallel import make_mesh

        cfg = small_cfg(batch=128)
        eng = Engine(cfg, TrafficSource(TrafficSpec(seed=9), total=128),
                     NullSink(), mesh=make_mesh(1))
        assert eng.mesh is None  # 1-device mesh -> plain fused step

    @pytest.mark.parametrize("n_mesh,count", [(0, 1), (8, 8)])
    def test_report_names_the_devices_the_table_is_on(self, n_mesh, count):
        """EngineReport.device is read off the table's own shards: one
        device single-device, all eight under mesh=8 — and it survives
        the jax-free merge `fsx status --engine-report` and the
        supervisor's aggregate() both apply."""
        from flowsentryx_tpu.engine.health import fleet_devices
        from flowsentryx_tpu.parallel import make_mesh

        cfg = small_cfg(batch=128, cap=1 << 12)
        eng = Engine(cfg, TrafficSource(TrafficSpec(seed=9), total=256),
                     NullSink(), mesh=make_mesh(n_mesh) if n_mesh else None)
        rep = eng.run()
        assert rep.device == {"platform": "cpu", "kind": "cpu",
                              "count": count}
        shards = {s.device for s in eng.table.key.addressable_shards}
        assert len(shards) == count
        merged = fleet_devices({0: rep.device, 1: rep.device, 2: None})
        assert merged["platforms"] == ["cpu"]
        assert merged["count"] == 2 * count
        assert sorted(merged["per_engine"]) == ["0", "1"]
        assert fleet_devices({0: None}) is None

    def test_max_batches_bound(self):
        cfg = small_cfg(batch=128)
        src = TrafficSource(TrafficSpec(seed=9))  # unbounded
        rep = Engine(cfg, src, NullSink()).run(max_batches=5)
        assert rep.batches == 5

    def test_trailing_partial_batch_flushes(self):
        cfg = small_cfg(batch=256)
        src = TrafficSource(TrafficSpec(seed=10), total=300)
        rep = Engine(cfg, src, NullSink()).run()
        assert rep.records == 300
        assert rep.batches == 2  # 256 + padded 44

    @staticmethod
    def _run_sharded(recs, n_workers, base, queue_slots=16, warm=False,
                     readback_depth=4, cfg=None, rebind=False, **eng_kw):
        """Serve ``recs`` through a real ShardedIngest fleet over
        Python-created ring shards; returns (report, sink).  ``warm``
        pays the XLA compiles BEFORE the workers start filling their
        bounded queues — multi-second cold compiles otherwise stall the
        drain long enough for emit timeouts to drop batches (the fsx
        serve --mega boot order).  ``rebind`` builds the engine on an
        empty record source and hands it the fleet through
        ``reset_stream``, as a benchmark that keeps one warmed engine
        across streams does."""
        import time as _time

        from flowsentryx_tpu.engine.shm import ShmRing
        from flowsentryx_tpu.ingest import ShardedIngest

        shard = schema.shard_of(recs["saddr"], n_workers)
        for k in range(n_workers):
            ring = ShmRing.create(
                schema.shard_ring_path(base, k, n_workers),
                1 << 12, schema.FLOW_RECORD_DTYPE)
            part = recs[shard == k]
            assert ring.produce(part) == len(part)
        src = ShardedIngest(base, n_workers, queue_slots=queue_slots,
                            precompact=False, t0_grace_s=0.2)
        sink = CollectSink()
        if cfg is None:
            cfg = small_cfg(batch=256, cap=1 << 14,
                            pps_threshold=200.0, bps_threshold=1e9)
        first = (ArraySource(np.zeros(0, schema.FLOW_RECORD_DTYPE))
                 if rebind else src)
        eng = Engine(cfg, first, sink, readback_depth=readback_depth,
                     **eng_kw)
        if warm:
            eng.warm()
        if rebind:
            eng.reset_stream(src, sink)
        try:
            deadline = _time.monotonic() + 30
            while src.t0_ns is None:  # epoch handshake, then drain-stop
                src.poll_batches(0)
                assert _time.monotonic() < deadline
                _time.sleep(0.01)
            src.request_stop()
            rep = eng.run()
        finally:
            src.close()
        return rep, sink

    @staticmethod
    def _flood_records(n):
        return TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=8, n_benign_ips=24,
                        attack_fraction=0.8, seed=13)
        ).next_records(n)

    #: The two points of the dispatch lattice a sealed source is served
    #: at: singles on the lossless raw48 wire, and the adaptive mega
    #: ladder on compact16 (what the benchmark's ring cells run).
    SEALED_POINTS = pytest.mark.parametrize(
        "wire,mega_n",
        [(schema.WIRE_RAW48, 0), (schema.WIRE_COMPACT16, "auto")],
        ids=["raw48-singles", "compact16-auto"])

    @SEALED_POINTS
    @pytest.mark.parametrize("kind", list(LimiterKind),
                             ids=lambda k: k.value)
    def test_sharded_ingest_one_worker_bit_identical(
            self, tmp_path, wire, mega_n, kind):
        """N=1 sharded vs the inline N=0 path on the SAME stream: one
        worker preserves the exact record order AND batch composition,
        so everything — verdict counts, blocked set, until-times, batch
        count — must be bit-identical through the queue transport (the
        N=0-equivalence acceptance gate of the ingest subsystem), at
        both points of the lattice and under every limiter: grouping
        is dispatch granularity only."""
        import platform

        if platform.system() != "Linux":
            pytest.skip("shm ingest requires Linux")
        recs = self._flood_records(256 * 12)
        cfg = small_cfg(batch=256, cap=1 << 14, kind=kind,
                        pps_threshold=200.0, bps_threshold=1e9,
                        bucket_rate_pps=200.0, bucket_burst=64.0)
        sink0 = CollectSink()
        rep0 = Engine(cfg, ArraySource(recs.copy()), sink0,
                      readback_depth=4, wire=wire).run()
        rep1, sink1 = self._run_sharded(
            recs, 1, str(tmp_path / "fring"), cfg=cfg, wire=wire,
            mega_n=mega_n, warm=bool(mega_n))
        assert rep1.records == rep0.records == len(recs)
        assert rep1.batches == rep0.batches
        assert sink1.blocked == sink0.blocked  # keys AND until, exact
        assert len(sink0.blocked) >= 8         # the flood was condemned
        assert rep1.stats == rep0.stats
        assert rep1.ingest["n_workers"] == 1
        assert rep1.ingest["workers"]["0"]["seq_gaps"] == 0
        d = rep1.dispatch
        assert d["mode"] == ("adaptive" if mega_n else "single")
        assert d["host_copies_per_batch"] == 1.0

    def test_rebind_to_sealed_source_stages_through_the_arena(
            self, tmp_path):
        """An engine built on a record source without grouping never
        allocated an arena; rebound to a sealed fleet
        (``reset_stream``) it serves through the one sealed loop —
        every batch staged once — and stays bit-identical to the
        inline path."""
        import platform

        if platform.system() != "Linux":
            pytest.skip("shm ingest requires Linux")
        recs = self._flood_records(256 * 8)
        sink0 = CollectSink()
        rep0 = Engine(small_cfg(batch=256, cap=1 << 14,
                                pps_threshold=200.0, bps_threshold=1e9),
                      ArraySource(recs.copy()), sink0,
                      readback_depth=4, wire=schema.WIRE_RAW48).run()
        rep1, sink1 = self._run_sharded(
            recs, 1, str(tmp_path / "fring"), wire=schema.WIRE_RAW48,
            rebind=True)
        assert rep1.records == rep0.records == len(recs)
        assert sink1.blocked == sink0.blocked
        assert rep1.stats == rep0.stats
        assert rep1.dispatch["host_copies_per_batch"] == 1.0
        assert rep1.dispatch["arena"]["slots"] == 4 + 2

    @SEALED_POINTS
    def test_sealed_slot_reuse_under_live_overwrite_bit_identical(
            self, tmp_path, wire, mega_n):
        """Mutate-after-release at serving scale: a 2-slot queue with
        16 batches forces every shm slot to be RE-USED by the live
        worker many times while earlier batches are still dispatched-
        but-unsunk — the engine's zero-copy loop released each slot the
        moment it staged the view into the arena, so the worker's
        overwrites race real in-flight dispatches.  The run must stay
        bit-identical to the inline path (no torn batch can reach the
        device), every batch must have gone through the arena exactly
        once, and the sealed sub-stage timers must have fired."""
        import platform

        if platform.system() != "Linux":
            pytest.skip("shm ingest requires Linux")
        recs = self._flood_records(256 * 16)
        sink0 = CollectSink()
        rep0 = Engine(small_cfg(batch=256, cap=1 << 14,
                                pps_threshold=200.0, bps_threshold=1e9),
                      ArraySource(recs.copy()), sink0,
                      readback_depth=4, wire=wire,
                      sink_thread=False).run()
        rep1, sink1 = self._run_sharded(
            recs, 1, str(tmp_path / "fring"), queue_slots=2,
            wire=wire, mega_n=mega_n, warm=bool(mega_n),
            sink_thread=False)
        assert rep1.records == rep0.records == len(recs)
        assert rep1.batches == rep0.batches
        assert sink1.blocked == sink0.blocked
        assert rep1.stats == rep0.stats
        d = rep1.dispatch
        assert d["host_copies_per_batch"] == 1.0
        assert d["staged_batches"] == rep1.batches
        assert rep1.stages_ms["pop"].get("n", 0) > 0
        assert rep1.stages_ms["stage"].get("n", 0) > 0

    @SEALED_POINTS
    def test_sharded_ingest_two_workers_equivalent(self, tmp_path, wire,
                                                   mega_n):
        """N=2 regroups records into per-shard batches, and the table
        updates are batch-granular — so records at a flow's decision
        boundary may legally move between verdict classes, and
        until-times (stamped off the sealing batch's device clock) may
        shift by one batch span.  What MUST hold: the same sources end
        up blocked, per-flow order is preserved (seq_gaps 0), every
        record is classified exactly once, and the class drift stays
        within a few batch boundaries' worth."""
        import platform

        if platform.system() != "Linux":
            pytest.skip("shm ingest requires Linux")
        recs = self._flood_records(256 * 8)
        sink0 = CollectSink()
        rep0 = Engine(small_cfg(batch=256, cap=1 << 14,
                                pps_threshold=200.0, bps_threshold=1e9),
                      ArraySource(recs.copy()), sink0,
                      readback_depth=4, wire=wire).run()
        rep2, sink2 = self._run_sharded(
            recs, 2, str(tmp_path / "fring"), wire=wire, mega_n=mega_n,
            warm=bool(mega_n))
        assert rep2.records == rep0.records == len(recs)
        assert sink2.blocked.keys() == sink0.blocked.keys()
        for ip, until in sink0.blocked.items():
            assert abs(sink2.blocked[ip] - until) < 1e-3
        classes = ("allowed", "dropped_blacklist", "dropped_rate",
                   "dropped_ml")
        assert (sum(rep2.stats[k] for k in classes)
                == sum(rep0.stats[k] for k in classes) == len(recs))
        for k in classes:
            assert abs(rep2.stats[k] - rep0.stats[k]) <= 0.05 * len(recs), k
        ing = rep2.ingest
        assert ing is not None and ing["n_workers"] == 2
        assert ing["dead_workers"] == []
        assert all(w["seq_gaps"] == 0 for w in ing["workers"].values())


class TestGroupGranularity:
    """What holds at GROUP granularity besides byte-identity (which
    ``test_adaptive_mega_matches_single_and_fixed`` pins): the
    simulated kernel tier's accounting and the arena's slot rule."""

    def test_sim_kernel_tier_accounting_at_group_granularity(self):
        """Escalated records arriving in group-sized bursts: the tier's
        per-band accounting and the engine's verdicts must match the
        ungrouped run exactly, and the PR 6 rule — coalescing shortness
        judged on the PRE-filter poll count — must hold at group
        granularity (a flood the tier mostly drops still fills the top
        rung, it does not flush batch-by-batch)."""

        class DropMostTier:
            """Deterministic stand-in for distill.SimKernelTier: drops
            ~3/4 of records in-kernel, escalates the rest."""

            def __init__(self):
                self.seen = 0
                self.kept = 0

            def filter(self, records):
                self.seen += len(records)
                out = records[records["saddr"] % 4 == 0]
                self.kept += len(out)
                return out

            def report(self):
                return {"kernel_drops": self.seen - self.kept,
                        "escalated": self.kept}

        recs = TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=32, attack_fraction=0.8, seed=23)
        ).next_records(256 * 40)

        def run(**kw):
            cfg = small_cfg(batch=256, pps_threshold=200.0,
                            bps_threshold=1e9)
            tier = DropMostTier()
            sink = CollectSink()
            eng = Engine(cfg, ArraySource(recs.copy()), sink,
                         sink_thread=False, kernel_tier=tier, **kw)
            return eng.run(), sink, tier

        rep0, sink0, tier0 = run(readback_depth=4)
        rep1, sink1, tier1 = run(mega_n="auto")
        # the tier saw every record, in both modes, exactly once —
        # group-sized polls must not double-filter or skip records
        assert tier1.seen == tier0.seen == len(recs)
        assert tier1.kept == tier0.kept
        assert rep1.escalation["kernel_drops"] == \
            rep0.escalation["kernel_drops"]
        assert (rep1.escalation["escalated"]
                == rep0.escalation["escalated"] == tier1.kept)
        # every escalated record was classified exactly once; batch
        # COMPOSITION legitimately differs (a filtering tier makes
        # seal boundaries deadline-dependent — the documented
        # regrouping drift of the 2-worker ingest test), so the gate
        # is the blocked-source set + drift-bounded classes, not
        # byte-identity
        classes = ("allowed", "dropped_blacklist", "dropped_rate",
                   "dropped_ml")
        assert (sum(rep1.stats[k] for k in classes)
                == sum(rep0.stats[k] for k in classes) == tier1.kept)
        assert sink1.blocked.keys() == sink0.blocked.keys()
        # the dropped-in-kernel flood still counted as deep backlog:
        # the top rung fired instead of short-poll flushing every batch
        assert rep1.dispatch["group_hist"].get("8", 0) >= 1

    def test_safe_slots_bound(self):
        """The arena reuse-safety bound, depth + 2; engines allocate
        it."""
        from flowsentryx_tpu.engine.arena import DispatchArena

        assert DispatchArena.safe_slots(8) == 10
        assert DispatchArena.safe_slots(0) == 3  # depth floors at 1
        eng = Engine(small_cfg(batch=256),
                     TrafficSource(TrafficSpec(), total=256), NullSink(),
                     mega_n=4, readback_depth=12)
        assert eng._arena.slots == 14


class TestCompactReadback:
    """The compact verdict wire through the ENGINE: the compacted
    readback must produce byte-identical BlacklistUpdates and verdict
    counts vs the full-fetch path on single-device, sharded, and
    megastep configurations — including the K_MAX-overflow fallback
    (verdict_k far below the per-batch block count)."""

    @staticmethod
    def _recs(n, seed=17):
        return TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=32, attack_fraction=0.8, seed=seed)
        ).next_records(n)

    @staticmethod
    def _run(recs, verdict_k, sink_thread=True, **eng_kw):
        cfg = small_cfg(batch=512, cap=1 << 12, verdict_k=verdict_k,
                        pps_threshold=200.0, bps_threshold=1e9)
        sink = CollectSink()
        eng = Engine(cfg, ArraySource(recs.copy()), sink,
                     readback_depth=4, sink_thread=sink_thread, **eng_kw)
        rep = eng.run()
        return rep, sink

    def test_single_device_parity_and_overflow_fallback(self):
        recs = self._recs(512 * 24)
        rep_full, sink_full = self._run(recs, verdict_k=0)
        rep_c, sink_c = self._run(recs, verdict_k=64)
        rep_o, sink_o = self._run(recs, verdict_k=2)  # forces overflow
        assert len(sink_full.blocked) > 2  # overflow case is exercised
        # byte-identical updates: same keys AND same until expiries
        assert sink_c.blocked == sink_full.blocked
        assert sink_o.blocked == sink_full.blocked
        assert rep_c.stats == rep_full.stats == rep_o.stats
        assert rep_full.readback["mode"] == "full"
        assert rep_c.readback["mode"] == "compact"
        assert rep_c.readback["fallback_sinks"] == 0
        assert rep_c.readback["compact_sinks"] > 0
        assert rep_o.readback["fallback_sinks"] > 0  # overflow fell back
        # the point of the wire: steady-state D2H per batch shrinks
        assert (rep_c.readback["bytes_per_batch"]
                < rep_full.readback["bytes_per_batch"] / 4)

    def test_single_thread_sink_parity(self):
        """sink_thread=False (the single-loop engine) must decide
        identically — threading changes scheduling, never verdicts."""
        recs = self._recs(512 * 8)
        rep_t, sink_t = self._run(recs, verdict_k=64, sink_thread=True)
        rep_s, sink_s = self._run(recs, verdict_k=64, sink_thread=False)
        assert sink_t.blocked == sink_s.blocked
        assert rep_t.stats == rep_s.stats
        assert rep_s.readback["sink_occupancy"] is None

    def test_sharded_parity_with_overflow(self):
        from flowsentryx_tpu.parallel import make_mesh

        recs = self._recs(512 * 24)
        rep_full, sink_full = self._run(recs, verdict_k=0,
                                        mesh=make_mesh(8))
        rep_c, sink_c = self._run(recs, verdict_k=2, mesh=make_mesh(8))
        assert len(sink_full.blocked) > 2
        assert sink_c.blocked == sink_full.blocked
        assert rep_c.stats == rep_full.stats
        assert rep_c.readback["fallback_sinks"] > 0

    def test_megastep_parity_with_overflow(self):
        recs = self._recs(512 * 16)
        rep_full, sink_full = self._run(recs, verdict_k=0, mega_n=4)
        rep_c, sink_c = self._run(recs, verdict_k=2, mega_n=4)
        assert len(sink_full.blocked) > 2
        assert sink_c.blocked == sink_full.blocked
        assert rep_c.stats == rep_full.stats
        assert rep_c.readback["fallback_sinks"] > 0

    @pytest.mark.parametrize("eng_kw", [
        {}, {"mega_n": 4}, {"mega_n": 4, "sink_thread": False}],
        ids=["singles", "mega4", "mega4_single_loop"])
    def test_fallback_and_compact_count_every_sunk_batch_once(self, eng_kw):
        """``fallback_sinks`` and ``compact_sinks`` are both in batches:
        an overflowed mega entry counts each batch it carries, so the
        two add up to the batches sunk (ISSUE 32: the fallback share
        of sunk batches is read from them)."""
        recs = self._recs(512 * 16)
        rep, _ = self._run(recs, verdict_k=2, **eng_kw)
        rb = rep.readback
        assert rb["fallback_sinks"] > 0 and rb["compact_sinks"] > 0
        assert rb["fallback_sinks"] + rb["compact_sinks"] == rep.batches == 16
        if "mega_n" in eng_kw:
            # whole entries of 4 fall back, never a part of one
            assert rep.dispatch["group_hist"] == {"4": 4}
            assert rb["fallback_sinks"] % 4 == 0


class TestSinkThread:
    """The two-thread engine's failure/shutdown contract."""

    def test_sink_crash_fails_engine_loudly(self):
        class BoomSink:
            def apply(self, update):
                if len(update.key):
                    raise ValueError("boom: verdict ring gone")

        cfg = small_cfg(batch=256, pps_threshold=200.0, bps_threshold=1e9)
        src = TrafficSource(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=8, attack_fraction=0.8, seed=7),
            total=256 * 30,
        )
        eng = Engine(cfg, src, BoomSink(), readback_depth=4,
                     sink_thread=True)
        with pytest.raises(RuntimeError, match="sink thread crashed"):
            eng.run()
        # joined, not wedged: the engine did not leave a live thread
        assert not eng._sink_active

    def test_drain_on_shutdown_with_inflight_batches(self):
        """A deep pipe at source exhaustion: the shutdown drain must
        sink EVERY dispatched batch, in record-FIFO order, before the
        report is built."""
        cfg = small_cfg(batch=128)
        src = TrafficSource(TrafficSpec(seed=5), total=128 * 10)
        eng = Engine(cfg, src, CollectSink(), readback_depth=8,
                     sink_thread=True)
        seen, times = [], []
        eng.on_reap = lambda n, t: (seen.append(n), times.append(t))
        rep = eng.run()
        assert rep.records == 128 * 10
        assert sum(seen) == 128 * 10
        assert times == sorted(times)  # FIFO sink order preserved
        rb = rep.readback
        assert rb["compact_sinks"] + rb["fallback_sinks"] >= 1
        assert rep.spans["latency.seal_to_verdict"]["n"] == sum(seen)

    def test_threaded_sink_stress(self):
        """Fast tier-1 stress: a closed-loop flood burst through the
        two-thread engine — every record classified exactly once,
        attackers blocked, and the readback accounting consistent."""
        cfg = small_cfg(batch=256, pps_threshold=500.0, bps_threshold=1e9)
        spec = TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                           n_attack_ips=16, attack_fraction=0.7, seed=23)
        sink = CollectSink()
        eng = Engine(cfg, TrafficSource(spec, total=256 * 40), sink,
                     readback_depth=4, sink_thread=True)
        rep = eng.run()
        assert rep.records == 256 * 40
        classes = ("allowed", "dropped_blacklist", "dropped_rate",
                   "dropped_ml")
        assert sum(rep.stats[k] for k in classes) == rep.records
        assert sink.blocked  # verdicts actually landed
        rb = rep.readback
        assert rb["sink_thread"] is True
        assert 0.0 <= rb["sink_occupancy"] <= 1.0
        assert rb["mode"] == "compact" and rb["k_max"] == 64
        assert rb["d2h_bytes"] > 0
        # compact steady state: bytes/batch bounded by wire size + the
        # occasional overflow fallback
        assert rb["compact_sinks"] > 0


class TestServeCheckpointEvery:
    def test_periodic_checkpoint_and_restore(self, tmp_path, capsys):
        """fsx serve --checkpoint-every snapshots mid-serve (crash loses
        at most one interval) and the final report spans the total
        wall; the snapshot restores into a fresh serve run."""
        import json as js

        from flowsentryx_tpu import cli
        from flowsentryx_tpu.engine import checkpoint as ckpt

        path = tmp_path / "state.npz"
        assert cli.main(["serve", "--scenario", "syn_benign_mix",
                         "--rate", "1e6", "--packets", "20480",
                         "--checkpoint", str(path),
                         "--checkpoint-every", "0.2"]) == 0
        rep = js.loads(capsys.readouterr().out)
        assert rep["records"] == 20480
        assert path.exists()
        table, stats, t0_ns, salt, missing = ckpt.load_state(path)
        assert not missing
        # --checkpoint-every without --checkpoint refuses
        assert cli.main(["serve", "--scenario", "syn_benign_mix",
                         "--packets", "512",
                         "--checkpoint-every", "1"]) == 1
        capsys.readouterr()
        # the snapshot restores (salt adoption = the serve --restore path)
        assert cli.main(["serve", "--scenario", "syn_benign_mix",
                         "--rate", "1e6", "--packets", "2048",
                         "--restore", str(path)]) == 0


class TestServeMegaAuto:
    """``fsx serve --mega auto`` — the adaptive-coalescing operator
    surface."""

    @staticmethod
    def _small_cfg_file(tmp_path, model="logreg_int8"):
        import dataclasses

        cfg = FsxConfig()
        cfg = dataclasses.replace(
            cfg,
            batch=dataclasses.replace(cfg.batch, max_batch=256),
            table=dataclasses.replace(cfg.table, capacity=1 << 12),
            model=dataclasses.replace(cfg.model, name=model),
        )
        p = tmp_path / "cfg.json"
        p.write_text(cfg.to_json())
        return str(p)

    def test_serve_mega_auto_adaptive_dispatch(self, tmp_path, capsys):
        import json as js

        from flowsentryx_tpu import cli

        assert cli.main(["serve", "--scenario", "syn_benign_mix",
                         "--config", self._small_cfg_file(tmp_path),
                         "--rate", "1e6", "--packets", "4096",
                         "--mega", "auto", "--no-sink-thread"]) == 0
        rep = js.loads(capsys.readouterr().out)
        assert rep["records"] == 4096
        d = rep["dispatch"]
        assert d["mode"] == "adaptive"
        assert d["group_sizes"] == [8, 4, 2]
        # warm() compile-triggered every rung without polluting the hist
        assert sum(int(g) * n for g, n in d["group_hist"].items()) \
            == rep["batches"]

    def test_serve_mega_auto_refused_without_compact16(self, tmp_path,
                                                      capsys):
        """'auto' needs the compact16 wire exactly like a fixed
        ``--mega N``: an observer-less model (mlp serves raw48) must be
        refused BEFORE the engine boots, not with a post-compile
        traceback."""
        from flowsentryx_tpu import cli

        assert cli.main(["serve", "--scenario", "syn_benign_mix",
                         "--config",
                         self._small_cfg_file(tmp_path, model="mlp"),
                         "--packets", "512", "--mega", "auto"]) == 1
        assert "compact16" in capsys.readouterr().err

    def test_serve_mega_rejects_non_int_non_auto(self, capsys):
        from flowsentryx_tpu import cli

        with pytest.raises(SystemExit):
            cli.main(["serve", "--scenario", "syn_benign_mix",
                      "--packets", "256", "--mega", "four"])
        assert "auto" in capsys.readouterr().err

class TestPallasModelFamily:
    def test_engine_with_pallas_scorer(self):
        """The registered Pallas scorer drives the full serving loop
        (interpret mode here; Mosaic on real TPU) and produces the same
        verdicts as the XLA scorer."""
        import dataclasses

        cfg = small_cfg(batch=256, pps_threshold=1e9, bps_threshold=1e12)
        cfg_p = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, name="logreg_int8_pallas")
        )
        spec = TrafficSpec(scenario=Scenario.SYN_BENIGN_MIX, seed=12)
        rep_a = Engine(cfg, TrafficSource(spec, total=1024), CollectSink()).run()
        rep_b = Engine(cfg_p, TrafficSource(spec, total=1024), CollectSink()).run()
        assert rep_a.stats == rep_b.stats
        assert rep_a.table == rep_b.table


class TestPacedLatency:
    """Per-record arrival→verdict-sunk latency measurement: the
    open-loop PacedSource + Engine.on_reap pair the latency bench is
    built on (VERDICT r3 weak #2/#6: batch-level e2e conflates queueing
    with readback-group policy)."""

    def _pool(self, n=2048, seed=3):
        return TrafficGen(TrafficSpec(seed=seed)).next_records(n)

    def test_paced_source_open_loop_schedule(self):
        from flowsentryx_tpu.engine import PacedSource

        src = PacedSource(self._pool(), rate_pps=1e6, total=5000)
        got = 0
        import time

        t0 = time.perf_counter()
        while not src.exhausted():
            got += len(src.poll(512))
        wall = time.perf_counter() - t0
        assert got == 5000
        # Scheduled times advance at exactly the offered spacing
        # (diff of RELATIVE times: absolute perf_counter values on a
        # long-uptime host have ulp > 1e-12).
        st = src.pop_scheduled(5000) - src.t_start
        assert np.allclose(np.diff(st), 1e-6, atol=1e-9)
        # Open loop: 5000 records at 1 Mpps are scheduled across 5 ms;
        # the wall clock must cover at least the schedule span.
        assert wall >= 4e-3

    def test_paced_source_stamps_scheduled_ts(self):
        from flowsentryx_tpu.engine import PacedSource

        src = PacedSource(self._pool(), rate_pps=1e5, total=100)
        recs = []
        while not src.exhausted():
            r = src.poll(64)
            if len(r):
                recs.append(r)
        ts = np.concatenate([r["ts_ns"] for r in recs]).astype(np.int64)
        assert np.array_equal(np.diff(ts), np.full(99, 10_000))  # 10 µs

    def test_per_record_reap_latencies(self):
        """Every offered record gets exactly one latency sample; FIFO
        pairing of scheduled times with reap callbacks is exact."""
        from flowsentryx_tpu.engine import PacedSource

        cfg = small_cfg(batch=128)
        total = 128 * 6
        src = PacedSource(self._pool(), rate_pps=5e5, total=total)
        eng = Engine(cfg, src, CollectSink(), readback_depth=0)
        lats: list[float] = []

        def on_reap(n, t_done):
            lats.extend(t_done - src.pop_scheduled(n))

        eng.on_reap = on_reap
        rep = eng.run()
        assert rep.records == total
        assert len(lats) == total
        assert src.popped == total  # every record accounted for
        lats_a = np.array(lats)
        assert (lats_a > 0).all()
        # CPU backend, tiny batches: sanity bound, not a perf claim.
        assert np.percentile(lats_a, 50) < 5.0

    def test_reap_hook_counts_match_depth(self):
        """readback_depth=1 defers exactly one batch; the hook still
        sees every record exactly once by end of run."""
        from flowsentryx_tpu.engine import PacedSource

        cfg = small_cfg(batch=64)
        total = 64 * 5
        src = PacedSource(self._pool(), rate_pps=2e5, total=total)
        eng = Engine(cfg, src, CollectSink(), readback_depth=1)
        seen = []
        eng.on_reap = lambda n, t: seen.append(n)
        eng.run()
        assert sum(seen) == total

    def test_verdicts_sink_when_ready_not_at_depth(self):
        """A deep readback pipe must not defer verdicts: with
        readback_depth=8 and batches arriving ~30 ms apart, each
        batch's verdicts must sink as soon as the device finishes —
        NOT after 8 more batches are dispatched (the r4 open-loop
        defect: depth x batch-fill time of pure queueing)."""
        from flowsentryx_tpu.engine import PacedSource

        cfg = small_cfg(batch=64)
        # warm run compiles the step OUTSIDE the paced clock
        warm = PacedSource(self._pool(), rate_pps=1e6, total=64)
        eng = Engine(cfg, warm, CollectSink(), readback_depth=8)
        eng.run()
        # 64-record batches at 2000 pps: one batch every 32 ms
        src = PacedSource(self._pool(), rate_pps=2000, total=64 * 3)
        eng.reset_stream(src)
        lats = []
        eng.on_reap = lambda n, t: lats.extend(t - src.pop_scheduled(n))
        eng.run()
        assert len(lats) == 64 * 3
        # the FIRST batch's records must have sunk long before the run
        # ended (~96 ms in): generous 20 ms bound vs the 64+ ms a
        # depth-deferred reap would show
        first_batch = np.asarray(lats[:64]) * 1e3
        assert float(np.median(first_batch)) < 20.0, first_batch[:4]

    def test_deadline_flush_waits_for_idle_pipe(self):
        """The deadline trigger must not flush near-empty buffers into
        a busy pipe (each flush costs a full padded step — the r4
        tiny-batch overload spiral).  With in-flight work present the
        flush defers; it still fires once the pipe drains, so latency
        stays bounded."""
        from flowsentryx_tpu.engine import PacedSource

        cfg = small_cfg(batch=256)  # deadline_us default 200
        src = PacedSource(self._pool(), rate_pps=3e4, total=3000)
        eng = Engine(cfg, src, CollectSink(), readback_depth=2)
        rep = eng.run()
        assert rep.records == 3000
        # 3000 records / 256 = 12 size-triggered seals; deadline splits
        # may add a few, but the r4 behavior (a flush every 200 us ->
        # ~100 near-empty batches for this stream) must be gone
        assert rep.batches <= 30, rep.batches

    def test_reset_stream_reuses_compiled_step(self):
        """One engine, two paced runs: state persists, stream plumbing
        resets, per-record accounting stays exact across rebinds."""
        from flowsentryx_tpu.engine import PacedSource

        cfg = small_cfg(batch=64)
        src1 = PacedSource(self._pool(), rate_pps=2e5, total=64 * 3)
        eng = Engine(cfg, src1, CollectSink(), readback_depth=0)
        step_obj = eng.step
        rep1 = eng.run()
        t0_anchor = eng.batcher.t0_ns
        src2 = PacedSource(self._pool(seed=9), rate_pps=2e5, total=64 * 4)
        lats = []
        eng.reset_stream(src2, readback_depth=1)
        eng.on_reap = lambda n, t: lats.extend(t - src2.pop_scheduled(n))
        rep2 = eng.run()
        assert eng.step is step_obj  # no recompile
        assert rep2.records == 64 * 4
        assert len(lats) == 64 * 4
        # table state persisted across the rebind (flow memory), while
        # batch counters restarted.  Counts may exceed the record/batch
        # quotient by a deadline split (at 2e5 pps a 64-record batch
        # takes 320 us to fill, so a slow-host scheduling hiccup can
        # flush a partial batch) — but a NON-restarted counter would
        # carry rep1's batches too, which the upper bounds exclude.
        assert 4 <= rep2.batches <= 6
        assert 3 <= rep1.batches <= 5
        # the clock epoch persists with the flow memory: re-anchoring
        # would time-shift every persisted expiry (engine.reset_stream)
        assert eng.batcher.t0_ns == t0_anchor
        assert eng._t0_auto is False


class TestTransferGuard:
    """The engine's host↔device boundary is EXPLICIT (device_put in,
    device_get out), so the whole serving loop — dispatch, sink,
    report — runs under ``jax.transfer_guard("disallow")``.  Any
    *implicit* transfer someone later leaks into the hot path (a numpy
    arg to the jit, a host scalar materializing on device, a stray
    ``int(device_scalar)``) fails these tests in CI rather than
    silently costing a sync per batch in production."""

    @staticmethod
    def _recs(n, seed=23):
        return TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=8, attack_fraction=0.8, seed=seed)
        ).next_records(n)

    def test_loop_clean_under_disallow_guard(self):
        import jax

        cfg = small_cfg(batch=256, pps_threshold=200.0,
                        bps_threshold=1e9)
        recs = self._recs(256 * 16)
        sink = CollectSink()
        eng = Engine(cfg, ArraySource(recs), sink, sink_thread=False)
        with jax.transfer_guard("disallow"):
            rep = eng.run()
        assert rep.records == len(recs)
        assert len(sink.blocked) > 0        # verdicts really flowed
        assert rep.table["tracked"] > 0     # report built under guard

    def test_sharded_loop_clean_under_disallow_guard(self):
        import jax

        from flowsentryx_tpu.parallel import make_mesh

        cfg = small_cfg(batch=256, pps_threshold=200.0,
                        bps_threshold=1e9)
        recs = self._recs(256 * 16)
        sink = CollectSink()
        eng = Engine(cfg, ArraySource(recs), sink, sink_thread=False,
                     mesh=make_mesh(8))
        with jax.transfer_guard("disallow"):
            rep = eng.run()
        assert rep.records == len(recs)
        assert len(sink.blocked) > 0

    def test_engine_readback_depth_defaults_from_config(self):
        cfg = small_cfg(batch=256)
        import dataclasses

        cfg = dataclasses.replace(
            cfg, batch=dataclasses.replace(cfg.batch, readback_depth=3))
        eng = Engine(cfg, ArraySource(self._recs(256)), NullSink(),
                     sink_thread=False)
        assert eng.readback_depth == 3
        eng2 = Engine(cfg, ArraySource(self._recs(256)), NullSink(),
                      sink_thread=False, readback_depth=5)
        assert eng2.readback_depth == 5  # explicit arg still wins


class TestLatencyHist:
    """The HDR log-bucketed latency histogram (engine/metrics.py):
    fixed memory, O(buckets) percentiles, lossless JSON merge — the
    measurement substrate of the seal→verdict plane."""

    def test_percentiles_within_bucket_error(self):
        from flowsentryx_tpu.engine.metrics import LAT_SUB, LatencyHist

        rng = np.random.default_rng(7)
        vals_us = rng.lognormal(5.5, 1.2, 50_000)
        h = LatencyHist()
        for v in vals_us:
            h.add(v * 1e-6)
        for q in (50, 90, 99, 99.9):
            true = float(np.percentile(vals_us, q))
            est = h.percentile_us(q)
            # conservative upper edge: never under-reports beyond
            # interpolation noise, never over by more than 1/SUB
            assert est >= true * (1 - 0.02)
            assert est <= true * (1 + 1 / LAT_SUB + 0.02)
        assert h.percentile_us(100) == round(float(vals_us.max()), 1)

    def test_weighted_add_and_ordering(self):
        from flowsentryx_tpu.engine.metrics import LatencyHist

        h = LatencyHist()
        h.add(100e-6, n=99)
        h.add(10e-3, n=1)
        assert h.n == 100
        assert h.percentile_us(50) < 200
        assert h.percentile_us(99.9) > 5000
        d = h.to_dict()
        chain = [d[k] for k in ("p50", "p90", "p99", "p999", "max")]
        assert all(a <= b for a, b in zip(chain, chain[1:]))

    def test_counts_roundtrip_and_merge(self):
        from flowsentryx_tpu.engine.metrics import LatencyHist

        rng = np.random.default_rng(3)
        a, b = LatencyHist(), LatencyHist()
        for v in rng.lognormal(4, 1, 2000):
            a.add(v * 1e-6)
        for v in rng.lognormal(7, 1, 2000):
            b.add(v * 1e-6)
        # JSON roundtrip is lossless at bucket resolution
        a2 = LatencyHist.from_counts(
            __import__("json").loads(
                __import__("json").dumps(a.to_counts())))
        assert a2.to_dict() == a.to_dict()
        # merge == summing the bucket counts, exactly
        merged = LatencyHist.from_counts(a.to_counts())
        merged.merge(b)
        assert merged.n == a.n + b.n
        assert np.array_equal(merged.counts, a.counts + b.counts)
        assert merged.max_us == max(a.max_us, b.max_us)

    def test_scheme_mismatch_refused(self):
        from flowsentryx_tpu.engine.metrics import LatencyHist

        with pytest.raises(ValueError, match="scheme"):
            LatencyHist.from_counts({"scheme": "linear", "buckets": {}})

    def test_cap_boundary_buckets(self):
        """Values at/above the [1 µs, 67 s] cap land in the LAST
        bucket — never raise, never wrap (ISSUE 12 boundary
        hardening, complementing PR 11's from_counts range check)."""
        from flowsentryx_tpu.engine.metrics import (
            LAT_BUCKETS, LAT_OCTAVES, LatencyHist, _lat_bucket,
            _lat_edge_us,
        )

        cap_us = 1 << LAT_OCTAVES  # one past the top octave's base
        # exactly at the top octave base, just below, and far above
        assert _lat_bucket(float(1 << (LAT_OCTAVES - 1))) < LAT_BUCKETS
        assert _lat_bucket(float(cap_us)) == LAT_BUCKETS - 1
        assert _lat_bucket(float(cap_us) * 1000.0) == LAT_BUCKETS - 1
        assert _lat_bucket(0.0) == 0          # sub-µs floors to 1 µs
        assert _lat_bucket(1.0) == 0
        h = LatencyHist()
        h.add(3600.0)            # an hour: far past the cap
        h.add(cap_us * 1e-6)     # exactly the 2^26 µs cap
        h.add(1e-9)              # sub-µs
        assert h.n == 3
        assert int(h.counts[LAT_BUCKETS - 1]) == 2
        # the top bucket reports the exact max, not a fake edge
        assert h.percentile_us(99) == round(h.max_us, 1)
        # every interior bucket's upper edge is finite and ordered
        edges = [_lat_edge_us(i) for i in range(LAT_BUCKETS - 1)]
        assert all(a < b for a, b in zip(edges, edges[1:]))

    def test_from_counts_max_valid_index(self):
        from flowsentryx_tpu.engine.metrics import (
            LAT_BUCKETS, LAT_SUB, LatencyHist,
        )

        scheme = f"log2x{LAT_SUB}us"
        h = LatencyHist.from_counts({
            "scheme": scheme,
            "buckets": {str(LAT_BUCKETS - 1): 7},
            "n": 7, "sum_us": 7e8, "max_us": 1e8,
        })
        assert int(h.counts[LAT_BUCKETS - 1]) == 7
        assert h.percentile_us(50) == round(1e8, 1)  # top bucket → max
        for bad in (LAT_BUCKETS, -1):
            with pytest.raises(ValueError, match="outside"):
                LatencyHist.from_counts({
                    "scheme": scheme, "buckets": {str(bad): 1}})

    def test_recorder_counts_negatives_and_misses(self):
        from flowsentryx_tpu.engine.metrics import LatencyRecorder

        r = LatencyRecorder()
        r.record(1e-3, 5e-4, 1e-5, 4e-4, 1e-4, n=10, budget_s=2e-3)
        assert r.negatives == 0 and r.slo_miss_records == 0
        r.record(3e-3, -1e-6, 1e-5, 4e-4, 1e-4, n=4, budget_s=2e-3)
        assert r.negatives == 1
        assert r.slo_miss_records == 4
        r.record(1.0, 0, 0, 0, 0, n=0, budget_s=1e-9)  # warm: no-op
        assert r.total.n == 14
        d = r.to_dict(slo_us=2000)
        assert d["slo"]["miss_records"] == 4


class TestPulseTraffic:
    """Pulse-wave arrival process (engine/traffic.py): one schedule
    function shared by the synthetic clock and the open-loop paced
    generator, steady case bit-identical to the historical stream."""

    def test_steady_schedule_matches_historical(self):
        from flowsentryx_tpu.engine.traffic import pulse_offsets_ns

        o = pulse_offsets_ns(np.arange(5), 1e6, 0.0, 1.0)
        assert list(o) == [1000, 2000, 3000, 4000, 5000]

    def test_pulse_compresses_into_on_window_at_same_mean_rate(self):
        from flowsentryx_tpu.engine.traffic import pulse_offsets_ns

        # 1 Mpps mean, 1 ms period, 25% duty: 1000 records per period,
        # all inside the first 250 us of each period
        p = pulse_offsets_ns(np.arange(3000), 1e6, 1e-3, 0.25)
        assert p[999] <= 250_000
        assert p[1000] >= 1_000_000
        assert abs(int(p[2999]) - 3_000_000 + 750_000) < 2
        # mean rate preserved: 3000 records span 3 periods
        assert p[2999] < 3_000_000

    def test_pulse_param_validation(self):
        from flowsentryx_tpu.engine import PacedSource
        from flowsentryx_tpu.engine.traffic import pulse_offsets_ns

        with pytest.raises(ValueError, match="duty_cycle"):
            pulse_offsets_ns(np.arange(2), 1e6, 1e-3, 0.0)
        with pytest.raises(ValueError, match="burst_period_s"):
            pulse_offsets_ns(np.arange(2), 1e6, -1.0, 0.5)
        with pytest.raises(ValueError, match="duty_cycle"):
            TrafficGen(TrafficSpec(duty_cycle=1.5))
        # a period holding < 1 record would silently multiply the
        # offered mean (clamping to 1/period); refused EAGERLY at
        # every construction seam that shares the schedule
        with pytest.raises(ValueError, match="fewer than one"):
            pulse_offsets_ns(np.arange(2), 100.0, 1e-3, 0.25)
        pool = TrafficGen(TrafficSpec(seed=1)).next_records(16)
        with pytest.raises(ValueError, match="fewer than one"):
            PacedSource(pool, rate_pps=100.0, total=8,
                        burst_period_s=1e-3, duty_cycle=0.25)
        with pytest.raises(ValueError, match="fewer than one"):
            TrafficGen(TrafficSpec(rate_pps=100.0, burst_period_s=1e-3,
                                   duty_cycle=0.25)).next_records(0)

    def test_trafficgen_steady_bit_identical_to_pre_pulse(self):
        a = TrafficGen(TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI,
                                   seed=5)).next_records(1024)
        b = TrafficGen(TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI,
                                   seed=5, burst_period_s=0.0,
                                   duty_cycle=1.0)).next_records(1024)
        assert (a == b).all()

    def test_trafficgen_pulse_timestamps(self):
        gen = TrafficGen(TrafficSpec(
            scenario=Scenario.UDP_FLOOD_MULTI, seed=5, rate_pps=1e6,
            burst_period_s=1e-3, duty_cycle=0.25))
        # across two polls the schedule is continuous (index-based)
        r1, r2 = gen.next_records(600), gen.next_records(600)
        ts = np.concatenate([r1["ts_ns"], r2["ts_ns"]]).astype(np.int64)
        ts -= 1_000_000_000
        assert ts[999] <= 250_000 and ts[1000] >= 1_000_000
        assert (np.diff(ts) >= 0).all()

    def test_paced_source_pulse_schedule_and_pop(self):
        from flowsentryx_tpu.engine import PacedSource

        pool = TrafficGen(TrafficSpec(seed=1)).next_records(512)
        src = PacedSource(pool, rate_pps=2e5, total=400,
                          burst_period_s=4e-3, duty_cycle=0.25)
        import time as _t

        got = []
        while not src.exhausted():
            r = src.poll(10_000)
            if len(r):
                got.append(r)
            _t.sleep(0.0005)
        recs = np.concatenate(got)
        assert len(recs) == 400
        sch = src.pop_scheduled(400)
        # the ts_ns stamps ARE the schedule (offset from t_start)
        rel = recs["ts_ns"].astype(np.int64) / 1e9
        np.testing.assert_allclose(sch - src.t_start, rel, atol=1e-6)
        # within each 800-record period, records land in the on-window
        per = int(2e5 * 4e-3)
        assert (np.diff(sch) >= -1e-9).all()
        off = (sch - src.t_start) % 4e-3
        assert (off <= 1e-3 + 1e-6).sum() == len(off)  # all in 25% duty


class TestSloServing:
    """Latency-budget serving (``Engine(slo_us=N)`` / ``fsx serve
    --slo-us``): parity gates (the policy bounds COALESCING only —
    results stay byte-identical), the warm EWMA seed, the policy
    helpers, the budget-bounded deadline flush, and the degradation
    behavior under a breached budget."""

    @staticmethod
    def _recs(n_batches, batch=256, seed=17, n_attack=32):
        return TrafficGen(
            TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                        n_attack_ips=n_attack, attack_fraction=0.8,
                        seed=seed)
        ).next_records(batch * n_batches)

    @staticmethod
    def _run(recs, warm=False, tweak=None, **kw):
        import jax

        cfg = small_cfg(batch=256, pps_threshold=200.0,
                        bps_threshold=1e9)
        sink = CollectSink()
        kw.setdefault("readback_depth", 4)
        eng = Engine(cfg, ArraySource(recs.copy()), sink,
                     sink_thread=False, **kw)
        if warm:
            eng.warm()
            eng.reset_stream(ArraySource(recs.copy()))
        if tweak is not None:
            tweak(eng)
        with jax.transfer_guard("disallow"):
            rep = eng.run()
        return rep, sink, eng

    def test_slo_zero_is_todays_path(self):
        """slo_us=0 must be EXACTLY the throughput-tuned engine: no
        EWMA bookkeeping, no slo report block — while the latency
        measurement plane itself is always on."""
        recs = self._recs(6)
        rep, _, eng = self._run(recs, mega_n="auto")
        assert eng.slo_us == 0 and eng._rung_ewma_s == {}
        assert rep.dispatch["slo"] is None
        assert rep.latency is not None
        assert rep.latency["seal_to_verdict"]["n"] == rep.records
        assert "slo" not in rep.latency

    def test_slo_negative_refused(self):
        with pytest.raises(ValueError, match="slo_us"):
            Engine(small_cfg(), ArraySource(self._recs(1)), NullSink(),
                   slo_us=-1)

    def test_slo_parity_byte_identical_single_device(self):
        """slo on vs off vs singles over one deterministic stream:
        byte-identical stats, blacklist (keys AND untils), and final
        table under the transfer guard."""
        import jax

        recs = self._recs(14)
        rep1, sink1, eng1 = self._run(recs)
        repa, sinka, _ = self._run(recs, mega_n="auto")
        reps, sinks, engs = self._run(recs, mega_n="auto", warm=True,
                                      slo_us=250_000)
        assert reps.records == repa.records == rep1.records
        assert reps.stats == repa.stats == rep1.stats
        assert sinks.blocked == sinka.blocked == sink1.blocked
        for a, b in zip(jax.tree_util.tree_leaves(eng1.table),
                        jax.tree_util.tree_leaves(engs.table)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # a quarter-second budget never binds on this drain: the warm
        # EWMA table exists and the dispatch pattern still coalesced
        assert reps.dispatch["slo"]["rung_ewma_ms"]
        assert any(int(g) > 1 for g in reps.dispatch["group_hist"])

    def test_slo_parity_mesh(self):
        """The sharded half of the parity gate: a binding budget over
        the meshed ladder keeps results byte-identical."""
        import jax

        from flowsentryx_tpu.parallel import make_mesh

        recs = self._recs(10, batch=256)
        cfg = small_cfg(batch=256, pps_threshold=200.0,
                        bps_threshold=1e9)

        def run(**kw):
            sink = CollectSink()
            eng = Engine(cfg, ArraySource(recs.copy()), sink,
                         mesh=make_mesh(8), sink_thread=False,
                         readback_depth=4, **kw)
            with jax.transfer_guard("disallow"):
                rep = eng.run()
            return rep, sink

        rep0, sink0 = run(mega_n="auto")
        rep1, sink1 = run(mega_n="auto", slo_us=2000)
        assert rep0.stats == rep1.stats
        assert sink0.blocked == sink1.blocked
        assert rep1.dispatch["slo"]["slo_us"] == 2000

    def test_slo_greedy_flush_skips_unaffordable_rungs(self):
        """THE deterministic degradation proof, driven through the
        real greedy-flush path: a sub-top pending backlog whose
        coalesced rungs all carry unaffordable EWMAs (planted, ample
        headroom) must dispatch as singles — skip climbing — while
        the control flushes the same backlog through rung 4.  The
        dual: a backlog already PAST its budget gets no cap (the
        greedy flush at full amortization is the recovery path;
        forced singles under saturation measured a ~50x p99
        spiral)."""
        import time as _t

        from flowsentryx_tpu.engine.engine import _Stamps

        def seed_pending(eng, n):
            warm = np.zeros(
                (eng.cfg.batch.max_batch + 1,
                 schema.COMPACT_RECORD_WORDS), np.uint32)
            now = _Stamps(*[_t.perf_counter()] * 3)
            eng._pending = [(warm.copy(), now) for _ in range(n)]

        def mk(**kw):
            return Engine(small_cfg(batch=256),
                          ArraySource(self._recs(1)), NullSink(),
                          sink_thread=False, **kw)

        ctl = mk(mega_n="auto")
        seed_pending(ctl, 5)
        ctl._drain_pending(short=True)
        assert {int(g): n for g, n in ctl._group_hist.items()} \
            == {4: 1, 1: 1}
        eng = mk(mega_n="auto", slo_us=10_000_000)
        eng._rung_ewma_s.update({2: 9e9, 4: 9e9, 8: 9e9})
        seed_pending(eng, 5)
        eng._drain_pending(short=True)
        assert {int(g): n for g, n in eng._group_hist.items()} == {1: 5}
        # already-late: no cap — the flush coalesces like the control
        late = mk(mega_n="auto", slo_us=1)
        late._rung_ewma_s.update({2: 9e9, 4: 9e9, 8: 9e9})
        seed_pending(late, 5)
        late._pending = [(r, _Stamps(*[t.t_enqueue - 1.0] * 3))
                         for r, t in late._pending]
        late._drain_pending(short=True)
        assert {int(g): n for g, n in late._group_hist.items()} \
            == {4: 1, 1: 1}

    def test_slo_existing_top_rung_backlog_stays_uncapped(self):
        """An EXISTING top-rung backlog dispatches at full
        amortization whatever the budget: step time is sub-linear in
        group size, so the largest rung finishes every record of a
        backlog soonest — capping it only delays the tail and
        collapses capacity (the saturated-drain regression the first
        policy cut measured)."""
        import time as _t

        eng = Engine(small_cfg(batch=256), ArraySource(self._recs(1)),
                     NullSink(), sink_thread=False, mega_n="auto",
                     slo_us=1000)
        eng._rung_ewma_s.update({2: 9e9, 4: 9e9, 8: 9e9})
        warm = np.zeros((257, schema.COMPACT_RECORD_WORDS), np.uint32)
        from flowsentryx_tpu.engine.engine import _Stamps

        now = _Stamps(*[_t.perf_counter()] * 3)
        eng._pending = [(warm.copy(), now) for _ in range(8)]
        eng._drain_pending(short=True)
        assert {int(g): n for g, n in eng._group_hist.items()} == {8: 1}

    def test_warm_seeds_rung_ewma(self):
        recs = self._recs(2)
        eng = Engine(small_cfg(batch=256), ArraySource(recs), NullSink(),
                     sink_thread=False, mega_n="auto", slo_us=10_000)
        assert eng._rung_ewma_s == {}
        eng.warm()
        assert set(eng._rung_ewma_s) == {1, 2, 4, 8}
        assert all(v > 0 for v in eng._rung_ewma_s.values())
        # a rebind keeps the seed (it is a property of the compiled
        # graphs, not the stream)
        eng.reset_stream(ArraySource(self._recs(1)))
        assert set(eng._rung_ewma_s) == {1, 2, 4, 8}

    def test_slo_cap_and_pressed_policy(self):
        """The policy helpers, driven with a hand-set EWMA table."""
        import time as _t

        eng = Engine(small_cfg(batch=256), ArraySource(self._recs(1)),
                     NullSink(), sink_thread=False, mega_n="auto",
                     slo_us=10_000)  # 10 ms budget
        eng._rung_ewma_s = {1: 0.0005, 2: 0.001, 4: 0.003, 8: 0.02}
        now = _t.perf_counter()
        # fresh record: 8 needs 20 ms > 10 ms budget -> capped at 4
        assert eng._slo_cap(now) == 4
        # 8 ms old: only the 1 ms rung (2) still fits
        assert eng._slo_cap(now - 0.008) == 2
        # 9.8 ms old: positive headroom but nothing fits -> singles
        assert eng._slo_cap(now - 0.0098) == 1
        # 11 ms old: ALREADY LATE -> no cap (greedy-flush recovery at
        # full amortization; singles would collapse drain capacity)
        assert eng._slo_cap(now - 0.011) == 8
        # pressed: ewma(top 8 = 20 ms) >= headroom (10 ms) is true
        # even for a fresh record here (top rung unaffordable)
        assert eng._slo_pressed(now)
        eng._rung_ewma_s[8] = 0.001
        assert not eng._slo_pressed(now)
        assert eng._slo_pressed(now - 0.0095)
        assert eng._slo_pressed(now - 0.011)  # late: flush, never hold

    def test_deadline_flush_only_into_idle_pipe(self):
        """The engine.py idle-pipe deadline-flush rule, tested
        DIRECTLY (it was previously only documented in a comment):
        the flush fires only when the pipe is fully drained — never
        mid-flight, including work queued to the sink channel."""
        import dataclasses

        cfg = small_cfg(batch=256)
        cfg = dataclasses.replace(
            cfg, batch=dataclasses.replace(cfg.batch, deadline_us=1))
        eng = Engine(cfg, ArraySource(self._recs(1)), NullSink(),
                     sink_thread=False)
        gen = TrafficGen(TrafficSpec(seed=2))
        eng.batcher.add(gen.next_records(10))  # partial fill
        import time as _t

        _t.sleep(0.001)  # 1 us deadline: long expired
        assert eng.batcher.flush_due()
        assert eng._deadline_flush_due()  # idle pipe: fires
        # in-flight work (dispatch-staged entry) blocks the flush
        from flowsentryx_tpu.engine.engine import _InFlight, _Stamps

        eng._inflight.append(_InFlight(out=None,
                                       stamps=_Stamps(0.0, 0.0, 0.0),
                                       n_records=1))
        assert eng._busy_depth() == 1
        assert not eng._deadline_flush_due()  # never mid-flight
        eng._inflight.clear()
        # work queued to the sink channel is STILL a busy pipe
        eng._chan.submit(("single", None, 0.0, 1, 1, 0.0), 1)
        assert eng._busy_depth() == 1
        assert not eng._deadline_flush_due()
        eng._chan.reset()
        assert eng._deadline_flush_due()

    def test_deadline_flush_slo_budget_bound(self):
        """SLO mode bounds batcher residency by the budget even when
        deadline_us is far larger — but still only into an idle
        pipe."""
        import dataclasses
        import time as _t

        cfg = small_cfg(batch=256)
        cfg = dataclasses.replace(
            cfg, batch=dataclasses.replace(cfg.batch,
                                           deadline_us=50_000))
        eng = Engine(cfg, ArraySource(self._recs(1)), NullSink(),
                     sink_thread=False, slo_us=5_000)
        eng._rung_ewma_s = {1: 0.001}
        gen = TrafficGen(TrafficSpec(seed=2))
        eng.batcher.add(gen.next_records(10))
        # fresh fill: age < 4ms flush point -> not due (deadline far)
        assert not eng._deadline_flush_due()
        _t.sleep(0.006)
        # age ~6ms >= budget - ewma(1) = 4ms -> budget flush fires
        assert not eng.batcher.flush_due()
        assert eng._deadline_flush_due()
        # the budget/2 floor: an inflated single-step estimate (>=
        # the whole budget) must NOT degenerate into flush-on-any-age
        eng2 = Engine(cfg, ArraySource(self._recs(1)), NullSink(),
                      sink_thread=False, slo_us=5_000)
        eng2._rung_ewma_s = {1: 9.0}
        eng2.batcher.add(gen.next_records(10))
        assert not eng2._deadline_flush_due()  # fresh: floored
        _t.sleep(0.003)
        assert eng2._deadline_flush_due()      # past budget/2 = 2.5ms
        from flowsentryx_tpu.engine.engine import _InFlight, _Stamps

        eng._inflight.append(_InFlight(out=None,
                                       stamps=_Stamps(0.0, 0.0, 0.0),
                                       n_records=1))
        assert not eng._deadline_flush_due()  # idle-pipe rule dominates

    def test_slo_report_miss_accounting(self):
        recs = self._recs(8)
        rep, _, _ = self._run(recs, mega_n="auto", slo_us=1)
        lat = rep.latency
        assert lat["slo"]["slo_us"] == 1
        # a 1 us budget is missed by every record of a real drain
        assert lat["slo"]["miss_records"] == rep.records
        assert lat["slo"]["miss_fraction"] == 1.0
        assert lat["negatives"] == 0

    def test_latency_stage_decomposition_populated(self):
        recs = self._recs(6)
        rep, _, _ = self._run(recs, mega_n="auto")
        lat = rep.latency
        assert lat["seal_to_verdict"]["n"] == rep.records
        for s in ("staged_wait", "upload", "compute", "sink"):
            assert lat["stages"][s]["n"] == rep.records
        chain = [lat["seal_to_verdict"][k]
                 for k in ("p50", "p90", "p99", "p999", "max")]
        assert all(a <= b for a, b in zip(chain, chain[1:]))
        assert chain[0] > 0

