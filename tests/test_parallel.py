"""Sharded-step tests on the 8-device virtual CPU mesh."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flowsentryx_tpu.core.config import FsxConfig, LimiterConfig, TableConfig
from flowsentryx_tpu.core.schema import Verdict, make_stats, make_table
from flowsentryx_tpu.models import get_model
from flowsentryx_tpu.ops import fused
from flowsentryx_tpu.parallel import make_mesh, step as pstep
from tests.test_fused import ML_COLD, ML_HOT, build_batch

CFG = FsxConfig(
    limiter=LimiterConfig(pps_threshold=100.0, bps_threshold=1e9),
    table=TableConfig(capacity=1 << 12, probes=8, stale_s=1e6),
)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(8)


@pytest.fixture(scope="module")
def env(mesh):
    spec = get_model(CFG.model.name)
    params = spec.init()
    sharded = pstep.make_sharded_step(CFG, spec.classify_batch, mesh, donate=False)
    single = fused.make_jitted_step(CFG, spec.classify_batch, donate=False)
    return sharded, single, params


class TestShardedStep:
    def test_matches_single_device_verdicts(self, mesh, env):
        sharded, single, params = env
        entries = [(1000 + i, 3, 100, 0.1, ML_COLD) for i in range(30)]
        entries.append((7777, 120, 100, 0.1, ML_COLD))   # rate flood
        entries.append((8888, 4, 100, 0.1, ML_HOT))      # ML hit
        batch = build_batch(entries, batch_size=256)

        t_s = pstep.make_sharded_table(CFG, mesh)
        t_1 = make_table(CFG.table.capacity)
        st_s, st_1 = make_stats(), make_stats()

        t_s, st_s, out_s = sharded(t_s, st_s, params, batch)
        t_1, st_1, out_1 = single(t_1, st_1, params, batch)

        np.testing.assert_array_equal(
            np.asarray(out_s.verdict), np.asarray(out_1.verdict)
        )
        for a, b in zip(st_s, st_1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_stale_reads_counts_a_batch_once_across_shards(self, mesh, env):
        """Each shard's probe decides on its own keys whether it reads
        `last_seen`; the psum'd flags count a batch once.  A table full
        of live foreign keys: 30 new keys spread over the 8 shards
        read on several, an empty batch on none."""
        from flowsentryx_tpu.core.schema import stat_value

        sharded, single, params = env
        cap = CFG.table.capacity
        full = jnp.arange(cap, dtype=jnp.uint32) + (1 << 30)
        t_s = pstep.make_sharded_table(CFG, mesh)
        t_s = t_s._replace(key=jax.device_put(full, t_s.key.sharding))
        t_1 = make_table(cap)._replace(key=full)
        st_s, st_1 = make_stats(), make_stats()
        new = build_batch([(1000 + i, 3, 100, 0.1, ML_COLD)
                           for i in range(30)], batch_size=256)
        for batch in (new, build_batch([], batch_size=256), new):
            t_s, st_s, _ = sharded(t_s, st_s, params, batch)
            t_1, st_1, _ = single(t_1, st_1, params, batch)
        assert stat_value(st_s.stale_reads) == 2 == stat_value(st_s.batches)
        assert stat_value(st_1.stale_reads) == 2

    def test_state_persists_and_blacklist_works_sharded(self, mesh, env):
        sharded, _, params = env
        table = pstep.make_sharded_table(CFG, mesh)
        stats = make_stats()

        flood = build_batch([(4242, 150, 100, 0.1, ML_COLD)])
        table, stats, out = sharded(table, stats, params, flood)
        assert (np.asarray(out.verdict)[:150] == int(Verdict.DROP_RATE)).all()

        again = build_batch([(4242, 5, 100, 1.0, ML_COLD)])
        table, stats, out2 = sharded(table, stats, params, again)
        assert (np.asarray(out2.verdict)[:5] == int(Verdict.DROP_BLACKLIST)).all()

    def test_flows_land_on_distinct_shards(self, mesh, env):
        """Many flows spread across devices: table occupancy must appear
        in multiple shards (ownership by hash top-bits)."""
        sharded, _, params = env
        table = pstep.make_sharded_table(CFG, mesh)
        stats = make_stats()
        entries = [(10_000 + i, 1, 100, 0.1, ML_COLD) for i in range(128)]
        table, stats, _ = sharded(table, stats, params,
                                  build_batch(entries, batch_size=256))
        keys = np.asarray(table.key)
        local = CFG.table.capacity // 8
        shard_counts = [
            int((keys[i * local:(i + 1) * local] != 0).sum()) for i in range(8)
        ]
        # a few flows may lose same-slot arbitration in their first batch
        # (bounded error by design; they land on the next batch)
        assert int(np.sum(shard_counts)) >= 120
        assert sum(c > 0 for c in shard_counts) >= 4  # hash spreads owners

        # second sighting of the same flows: all must now be tracked
        entries2 = [(10_000 + i, 1, 100, 0.3, ML_COLD) for i in range(128)]
        table, stats, _ = sharded(table, stats, params,
                                  build_batch(entries2, batch_size=256))
        assert int((np.asarray(table.key) != 0).sum()) == 128

    def test_same_key_same_shard_across_batches(self, mesh, env):
        sharded, _, params = env
        table = pstep.make_sharded_table(CFG, mesh)
        stats = make_stats()
        b1 = build_batch([(31337, 10, 100, 0.1, ML_COLD)])
        table, stats, _ = sharded(table, stats, params, b1)
        occ1 = np.flatnonzero(np.asarray(table.key) == 31337)
        b2 = build_batch([(31337, 10, 100, 0.4, ML_COLD)])
        table, stats, _ = sharded(table, stats, params, b2)
        occ2 = np.flatnonzero(np.asarray(table.key) == 31337)
        np.testing.assert_array_equal(occ1, occ2)  # no state migration


def _hash_u32_np(k: np.ndarray) -> np.ndarray:
    """numpy twin of ops.hashtable.hash_u32 (murmur3 finalizer)."""
    k = k.astype(np.uint32)
    k ^= k >> np.uint32(16)
    k = (k * np.uint32(0x85EBCA6B)).astype(np.uint32)
    k ^= k >> np.uint32(13)
    k = (k * np.uint32(0xC2B2AE35)).astype(np.uint32)
    k ^= k >> np.uint32(16)
    return k


def _random_batch(b: int, n_ips: int, seed: int):
    rng = np.random.default_rng(seed)
    from flowsentryx_tpu.core.schema import FeatureBatch

    return FeatureBatch(
        key=jnp.asarray(rng.integers(1, n_ips + 1, b).astype(np.uint32)),
        feat=jnp.asarray(rng.uniform(0, 3000, (b, 8)).astype(np.float32)),
        pkt_len=jnp.asarray(rng.integers(64, 1500, b).astype(np.float32)),
        ts=jnp.asarray(np.sort(rng.uniform(0, 0.01, b)).astype(np.float32)),
        valid=jnp.asarray(np.ones(b, bool)),
    )


class TestOwnerRouting:
    """The owner-routed aggregation path (flows partial-aggregated per
    slice, routed to their hash owner, merged, verdicts routed back)."""

    def test_cross_slice_flows_match_single_device(self, mesh):
        """Flows spanning several devices' batch slices exercise the
        partial-merge path; verdicts and stats must still be identical
        to the single-device step on a big random batch."""
        spec = get_model(CFG.model.name)
        params = spec.init()
        # emit_score=True: scores are opt-in debug/parity outputs now —
        # this test compares them across the two paths
        sharded = pstep.make_sharded_step(CFG, spec.classify_batch, mesh,
                                          donate=False, emit_score=True)
        single = fused.make_jitted_step(CFG, spec.classify_batch,
                                        donate=False, emit_score=True)
        batch = _random_batch(1024, n_ips=200, seed=7)  # ~5 pkts/flow,
        # scattered positions → nearly every flow spans multiple slices

        t_s = pstep.make_sharded_table(CFG, mesh)
        t_1 = make_table(CFG.table.capacity)
        st_s, st_1 = make_stats(), make_stats()
        t_s, st_s, out_s = sharded(t_s, st_s, params, batch)
        t_1, st_1, out_1 = single(t_1, st_1, params, batch)

        np.testing.assert_array_equal(np.asarray(out_s.verdict),
                                      np.asarray(out_1.verdict))
        np.testing.assert_allclose(np.asarray(out_s.score),
                                   np.asarray(out_1.score), rtol=1e-6)
        for a, b in zip(st_s, st_1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(out_s.route_drop) == 0

    def test_untracked_is_summed_once_across_owners(self, mesh):
        """`GlobalStats.untracked` (ISSUE 39; counted where the table
        ages rows out): two new flows of one batch picking one row
        happens more or less often by the layout (one table or eight
        shards), so the count differs between the two; in each it is
        the flows less the rows they took — a flow has one owner, and
        the owners' counts ride the one stats psum."""
        import dataclasses

        from flowsentryx_tpu.core.schema import stat_value

        cfg = dataclasses.replace(CFG, table=dataclasses.replace(
            CFG.table, evict_ttl_s=1e6, evict_every=64))
        spec = get_model(cfg.model.name)
        params = spec.init()
        sharded = pstep.make_sharded_step(cfg, spec.classify_batch, mesh,
                                          donate=False)
        single = fused.make_jitted_step(cfg, spec.classify_batch,
                                        donate=False)
        batch = _random_batch(1024, n_ips=200, seed=7)
        flows = len(np.unique(np.asarray(batch.key)))
        for step, table in ((sharded, pstep.make_sharded_table(cfg, mesh)),
                            (single, make_table(cfg.table.capacity))):
            table, stats, _ = step(table, make_stats(), params, batch)
            rows = int(np.count_nonzero(np.asarray(table.key)))
            assert 0 < stat_value(stats.untracked) == flows - rows
            assert stat_value(stats.evicted) == 0

    def test_adversarial_owner_skew_fails_open(self, mesh):
        """Keys aimed at one owner (ownership is a public hash) overflow
        the per-owner routing capacity: overflowed flows must PASS
        (fail-open, kernel limiter stands alone underneath) and be
        counted in route_drop — never silently mis-verdicted."""
        spec = get_model(CFG.model.name)
        params = spec.init()
        sharded = pstep.make_sharded_step(CFG, spec.classify_batch, mesh,
                                          donate=False)

        # distinct keys all owned by device 0: hash top-3-bits == 0.
        # B=1024 → local_b=128 > C=64, so 8 slices × 64 overflow.
        cand = np.arange(1, 400_000, dtype=np.uint32)
        owned0 = cand[(_hash_u32_np(cand) >> np.uint32(29)) == 0][:1024]
        assert len(owned0) == 1024
        from flowsentryx_tpu.core.schema import FeatureBatch
        b = 1024
        batch = FeatureBatch(
            key=jnp.asarray(owned0),
            feat=jnp.zeros((b, 8), jnp.float32),
            pkt_len=jnp.full((b,), 100.0, jnp.float32),
            ts=jnp.asarray(np.linspace(0, 0.001, b, dtype=np.float32)),
            valid=jnp.ones((b,), bool),
        )
        table = pstep.make_sharded_table(CFG, mesh)
        stats = make_stats()
        table, stats, out = sharded(table, stats, params, batch)

        drop = int(out.route_drop)
        assert drop == 8 * 64  # every slice overflows its C=64 bucket
        # every packet (routed or overflowed) passes: benign features,
        # per-flow rate 1 pps — and overflow must never DROP
        assert (np.asarray(out.verdict) == int(Verdict.PASS)).all()
        # overflowed flows skipped their table update this batch: at
        # most the routed 64 per slice landed state (some lose slot
        # arbitration — 512 keys cram into owner-0's 512-row shard),
        # and ALL of it lands in owner 0's shard rows
        keys = np.asarray(table.key)
        local_rows = CFG.table.capacity // 8
        occupied = np.flatnonzero(keys != 0)
        assert 0 < len(occupied) <= 8 * 64
        assert (occupied < local_rows).all()  # nothing outside shard 0

    def test_salt_defeats_precomputed_owner_skew(self, mesh):
        """The same attack trace that overflows owner routing under the
        public (salt=0) hash must route cleanly once the boot-time salt
        is in: precomputed collisions no longer land (VERDICT r4 #7)."""
        import dataclasses

        spec = get_model(CFG.model.name)
        params = spec.init()
        cfg_salted = dataclasses.replace(
            CFG, table=dataclasses.replace(CFG.table, salt=0xA5F00D01))
        sharded = pstep.make_sharded_step(cfg_salted, spec.classify_batch,
                                          mesh, donate=False)

        # the OLD attack trace: keys whose UNSALTED hash top bits == 0
        cand = np.arange(1, 400_000, dtype=np.uint32)
        owned0 = cand[(_hash_u32_np(cand) >> np.uint32(29)) == 0][:1024]
        from flowsentryx_tpu.core.schema import FeatureBatch
        b = 1024
        batch = FeatureBatch(
            key=jnp.asarray(owned0),
            feat=jnp.zeros((b, 8), jnp.float32),
            pkt_len=jnp.full((b,), 100.0, jnp.float32),
            ts=jnp.asarray(np.linspace(0, 0.001, b, dtype=np.float32)),
            valid=jnp.ones((b,), bool),
        )
        table = pstep.make_sharded_table(cfg_salted, mesh)
        stats = make_stats()
        table, stats, out = sharded(table, stats, params, batch)

        assert int(out.route_drop) == 0  # collisions dispersed
        # the salted owner spread puts rows in MANY shards, not just 0
        keys = np.asarray(table.key)
        local_rows = CFG.table.capacity // 8
        shards_hit = {int(r) // local_rows
                      for r in np.flatnonzero(keys != 0)}
        assert len(shards_hit) >= 4
        # and the salted step stays correct: parity vs the salted
        # single-device step on the same trace
        single = fused.make_jitted_step(cfg_salted, spec.classify_batch,
                                        donate=False)
        t1, s1, out1 = single(make_table(CFG.table.capacity), make_stats(),
                              params, batch)
        np.testing.assert_array_equal(np.asarray(out.verdict),
                                      np.asarray(out1.verdict))

    def test_route_drop_zero_under_uniform_traffic(self, mesh, env):
        sharded, _, params = env
        table = pstep.make_sharded_table(CFG, mesh)
        stats = make_stats()
        batch = _random_batch(1024, n_ips=100_000, seed=11)  # ~all distinct
        table, stats, out = sharded(table, stats, params, batch)
        assert int(out.route_drop) == 0


class TestMesh:
    def test_power_of_two_enforced(self):
        with pytest.raises(ValueError, match="power of two"):
            make_mesh(3)

    def test_too_many_devices(self):
        with pytest.raises(ValueError, match="requested"):
            make_mesh(512)


class TestShardedMegaStep:
    def test_matches_sequential_sharded_steps(self, mesh):
        """The sharded mega-step (lax.scan carrying the SHARDED
        table/stats through N shard-mapped steps) must produce
        byte-identical trajectories to N sequential sharded dispatches
        — the multi-device twin of the fused megastep parity test."""
        import dataclasses

        from flowsentryx_tpu.core import schema
        from flowsentryx_tpu.core.config import BatchConfig

        cfg = dataclasses.replace(
            CFG, batch=BatchConfig(max_batch=128))
        spec = get_model(cfg.model.name)
        params = spec.init()
        quant = schema.wire_quant_for(params)
        single = pstep.make_sharded_compact_step(
            cfg, spec.classify_batch, mesh, donate=False, **quant)
        mega = pstep.make_sharded_compact_megastep(
            cfg, spec.classify_batch, mesh, n_chunks=4, donate=False,
            **quant)

        rng = np.random.default_rng(9)
        raws = []
        for i in range(4):
            buf = np.zeros(128, dtype=schema.FLOW_RECORD_DTYPE)
            buf["saddr"] = rng.integers(1, 200, 128).astype(np.uint32)
            buf["pkt_len"] = rng.integers(64, 1500, 128)
            buf["ts_ns"] = (i * 128 + np.arange(128)) * 50_000
            buf["feat"] = rng.integers(0, 1 << 22, (128, 8))
            raws.append(schema.encode_compact(buf, 128, t0_ns=0, **quant))
        stacked = jnp.asarray(np.stack(raws))

        t1 = pstep.make_sharded_table(cfg, mesh)
        s1 = make_stats()
        verdicts = []
        for r in raws:
            t1, s1, o = single(t1, s1, params, r)
            verdicts.append(np.asarray(o.verdict))
        t2, s2, outs = mega(pstep.make_sharded_table(cfg, mesh),
                            make_stats(), params, stacked)
        np.testing.assert_array_equal(np.asarray(t2.key),
                                      np.asarray(t1.key))
        np.testing.assert_array_equal(np.asarray(t2.state),
                                      np.asarray(t1.state))
        for a, b in zip(s2, s1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(outs.verdict), np.stack(verdicts))
        # per-chunk route_drop stacks to [N]
        assert np.asarray(outs.route_drop).shape == (4,)
