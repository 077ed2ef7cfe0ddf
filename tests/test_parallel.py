"""Sharded-step tests on the 8-device virtual CPU mesh."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flowsentryx_tpu.core.config import FsxConfig, LimiterConfig, TableConfig
from flowsentryx_tpu.core.schema import (
    Verdict, make_stats, make_table, stat_value,
)
from flowsentryx_tpu.models import get_model
from flowsentryx_tpu.ops import fused, hashtable
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


def _assert_same_stats(sharded, single):
    """Every counter alike but ``owner_rows``: the sharded step's own,
    which one device passes through at 0."""
    for f in sharded._fields:
        if f != "owner_rows":
            np.testing.assert_array_equal(np.asarray(getattr(sharded, f)),
                                          np.asarray(getattr(single, f)), f)
    assert stat_value(single.owner_rows) == 0 < stat_value(
        sharded.owner_rows)


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
        _assert_same_stats(st_s, st_1)

    def test_stale_reads_counts_a_batch_once_across_shards(self, mesh, env):
        """Each shard's probe decides on its own keys whether it reads
        `last_seen`; the psum'd flags count a batch once.  A table full
        of live foreign keys: 30 new keys spread over the 8 shards
        read on several, an empty batch on none."""
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
        _assert_same_stats(st_s, st_1)
        assert int(out_s.route_drop) == 0

    def test_untracked_is_summed_once_across_owners(self, mesh):
        """`GlobalStats.untracked` (ISSUE 39; counted where the table
        ages rows out): two new flows of one batch picking one row
        happens more or less often by the layout (one table or eight
        shards), so the count differs between the two; in each it is
        the flows less the rows they took — a flow has one owner, and
        the owners' counts ride the one stats psum."""
        import dataclasses

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


class TestOwnerRungs:
    """The owner side runs the flow core on the smallest of n·C/4,
    9·n·C/16 and n·C rows that holds the flows it received, and gives
    what the full-size pass gives, bit for bit.  The rehearsal's shapes:
    4 devices, 1,024-record batches, C = 128, rungs 128 / 288 / 512."""

    N, B = 4, 1024

    def test_the_merged_flows_are_a_prefix(self):
        """`merge_partials`: however the padding rows lie among the
        received ones, flow i sits at row i and the flows are
        `[0, n_own)`; each sums its partials."""
        from flowsentryx_tpu.ops import agg

        rng = np.random.default_rng(3)
        rn = 512
        for n_keys, filled in ((1, 7), (90, 300), (400, rn)):
            keys = rng.choice(np.arange(1, 5000, dtype=np.uint32), n_keys,
                              replace=False)
            r = np.zeros((rn, pstep.ROUTE_WORDS), np.uint32)
            r[:, 0] = agg.INVALID_KEY
            rows = rng.choice(rn, filled, replace=False)
            k = np.concatenate([keys, rng.choice(keys, filled - n_keys)])
            r[rows, 0] = k
            r[rows, 1] = np.float32(2.0).view(np.uint32)
            mfa, _ = jax.jit(pstep.merge_partials)(jnp.asarray(r))
            valid = np.asarray(mfa.rep_valid)
            assert valid.tolist() == [True] * n_keys + [False] * (rn - n_keys)
            np.testing.assert_array_equal(np.asarray(mfa.rep_key)[:n_keys],
                                          np.sort(keys))
            counts = np.unique(k, return_counts=True)[1]
            np.testing.assert_array_equal(np.asarray(mfa.rep_pkts)[:n_keys],
                                          2.0 * counts)

    @staticmethod
    def full_size_owner_pass(cfg, table, mfa, ml_count, now):
        """The owner side as it was before the rungs: the flow core
        over all n·C received rows."""
        t, dec = fused.flow_step(cfg, table, mfa, mfa.rep_valid, ml_count,
                                 now)
        return t, dec, jnp.uint32(mfa.rep_key.shape[0])

    def expected_rungs(self, keys, salt):
        """Each owner's rung for one batch: the distinct keys it owns,
        less those a source device could not send it (rank ≥ C among
        that owner's keys of its slice, in key order)."""
        c = pstep.route_capacity(self.B, self.N)
        owner = np.asarray(hashtable.hash_u32(
            jnp.asarray(keys), salt)) >> np.uint32(30)
        got = [set() for _ in range(self.N)]
        local_b = self.B // self.N
        for s in range(self.N):
            ks, first = np.unique(keys[s * local_b:(s + 1) * local_b],
                                  return_index=True)
            os_ = owner[s * local_b + first]
            for o in range(self.N):
                got[o] |= set(ks[os_ == o][:c].tolist())
        rungs = pstep.owner_rungs(self.N * c)
        return [min(L for L in rungs if L >= len(g)) for g in got]

    def batches(self, salt):
        """Owners that receive a rung's rows exactly and one flow more
        (128, 129, 288, 289); a handful of flows; about 200 an owner; a
        skew that fills C from every source onto owner 0; and the
        handful again, now matched in the table, one of them a rate
        flood.  Later batches hold a few invalid records at the end."""
        from flowsentryx_tpu.core.schema import FeatureBatch

        rng = np.random.default_rng(5)
        cand = np.arange(1, 200_000, dtype=np.uint32)
        owner = np.asarray(hashtable.hash_u32(
            jnp.asarray(cand), salt)) >> np.uint32(30)
        few = rng.choice(np.arange(1, 50, dtype=np.uint32), self.B)
        edges = np.concatenate([cand[owner == o][:n] for o, n in
                                enumerate((128, 129, 288, 289))])
        edges = np.concatenate([edges, rng.choice(edges,
                                                  self.B - len(edges))])
        keysets = [
            rng.permutation(edges),
            few,
            rng.choice(np.arange(1000, 1800, dtype=np.uint32), self.B),
            rng.permutation(cand[owner == 0][:self.B]),
            np.where(np.arange(self.B) % 3 == 0, np.uint32(7), few),
        ]
        out = []
        for i, keys in enumerate(keysets):
            valid = np.arange(self.B) < self.B - 9 * i
            out.append((keys[valid], FeatureBatch(
                key=jnp.asarray(keys),
                feat=jnp.asarray(rng.uniform(0, 3000, (self.B, 8))
                                 .astype(np.float32)),
                pkt_len=jnp.asarray(rng.integers(64, 1500, self.B)
                                    .astype(np.float32)),
                ts=jnp.asarray((0.01 * i + np.sort(
                    rng.uniform(0, 0.01, self.B))).astype(np.float32)),
                valid=jnp.asarray(valid))))
        return out

    @pytest.mark.parametrize("aging", [False, True], ids=["plain", "aging"])
    def test_every_rung_gives_the_full_size_pass_bit_for_bit(
            self, aging, monkeypatch):
        import dataclasses

        cfg = dataclasses.replace(CFG, table=dataclasses.replace(
            CFG.table, capacity=1 << 14, salt=0x5EED,
            **(dict(evict_ttl_s=1e6, evict_every=64) if aging else {})))
        spec = get_model(cfg.model.name)
        params = spec.init()
        mesh = make_mesh(self.N)
        batches = self.batches(cfg.table.salt)

        def serve():
            step = pstep.make_sharded_step(cfg, spec.classify_batch, mesh,
                                           donate=False, emit_score=True)
            table, stats, outs = pstep.make_sharded_table(cfg, mesh), \
                make_stats(), []
            for _, batch in batches:
                table, stats, out = step(table, stats, params, batch)
                outs.append(out)
            return table, stats, outs

        t, st, outs = serve()
        monkeypatch.setattr(pstep, "owner_flow_step",
                            self.full_size_owner_pass)
        t_ref, st_ref, outs_ref = serve()

        rungs = [self.expected_rungs(k, cfg.table.salt) for k, _ in batches]
        assert pstep.owner_rungs(512) == (128, 288, 512)
        assert {L for r in rungs for L in r} == {128, 288, 512}
        assert rungs[0] == [128, 288, 288, 512]
        assert max(rungs[2]) == 288 and rungs[3][0] == 512
        np.testing.assert_array_equal(np.asarray(t.key),
                                      np.asarray(t_ref.key))
        np.testing.assert_array_equal(np.asarray(t.state),
                                      np.asarray(t_ref.state))
        assert int(np.count_nonzero(np.asarray(t.key))) > 0
        for f in st._fields:
            if f != "owner_rows":
                np.testing.assert_array_equal(np.asarray(getattr(st, f)),
                                              np.asarray(getattr(st_ref, f)),
                                              f)
        assert stat_value(st.owner_rows) == sum(map(sum, rungs))
        assert stat_value(st_ref.owner_rows) == len(batches) * self.N * 512
        assert stat_value(st.dropped_rate) > 0  # the flood blocked
        assert int(outs[3].route_drop) > 0      # the skew overflowed
        for out, ref in zip(outs, outs_ref):
            for f in out._fields:
                a, b = getattr(out, f), getattr(ref, f)
                assert (a is None) == (b is None), f
                if a is not None:
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b), f)


def _slice_case(name: str, b: int, rng):
    """``(key, pkt_len, ts, valid, mal)`` of one device's slice."""
    key = rng.integers(1, b // 4, b).astype(np.uint32)
    valid = rng.random(b) < 0.9
    if name == "key_zero":
        # 0 is sanitised to 0xFFFFFFFE and so shares its flow; a valid
        # 0xFFFFFFFF shares its sort run with the invalid rows
        key = rng.choice(np.array([0, 0xFFFFFFFE, 0xFFFFFFFF, 5, 9],
                                  np.uint32), b)
    elif name == "all_invalid":
        valid[:] = False
    elif name == "one_source":
        key[:], valid[:] = 4242, True
    return (key, rng.integers(64, 1501, b).astype(np.float32),
            rng.uniform(0, 1, b).astype(np.float32), valid,
            (rng.random(b) < 0.3) & valid)


class TestSliceAggregation:
    """The sharded step's local aggregation (`aggregate_slice`, one sort
    and a segmented scan) and its way back (`slice_verdicts`,
    `fail_open_records`) against the two-stage form: `agg.aggregate`,
    the malicious counts scattered through `inv`, and
    `resolve_record_verdicts`."""

    B = 256

    @pytest.mark.parametrize(
        "name", ["key_zero", "all_invalid", "one_source", "random"])
    def test_matches_the_two_stage_form_bit_for_bit(self, name):
        from flowsentryx_tpu.ops import agg

        rng = np.random.default_rng(sum(map(ord, name)))
        cols = _slice_case(name, self.B, rng)
        key, plen, ts, valid, mal = map(jnp.asarray, cols)
        fa, ml, runs = jax.jit(pstep.aggregate_slice)(key, plen, ts, valid,
                                                      mal)
        old = agg.aggregate(key, plen, ts, valid)
        ml_old = (jnp.zeros((self.B,), jnp.float32)
                  .at[old.inv].add(mal.astype(jnp.float32)))

        at = np.flatnonzero(np.asarray(fa.rep_valid))
        at_old = np.flatnonzero(np.asarray(old.rep_valid))
        n = len(np.unique(np.where(cols[0] == 0, 0xFFFFFFFE,
                                   cols[0])[cols[3]]))
        assert len(at) == len(at_old) == n
        for new_col, old_col in ((fa.rep_key, old.rep_key),
                                 (fa.rep_pkts, old.rep_pkts),
                                 (fa.rep_bytes, old.rep_bytes),
                                 (fa.rep_ts, old.rep_ts), (ml, ml_old)):
            np.testing.assert_array_equal(
                np.asarray(new_col)[at].view(np.uint32),
                np.asarray(old_col)[at_old].view(np.uint32))
        assert fa.inv is None

        # a verdict a flow, ML_RECORD_GATE among the codes, at each
        # form's places of the flows; padding reads PASS
        codes = rng.choice([int(v) for v in Verdict]
                           + [fused.ML_RECORD_GATE], n)
        codes[: min(n, 2)] = fused.ML_RECORD_GATE
        flow_v = np.zeros(self.B, np.int32)
        flow_v[at] = codes
        flow_v_old = np.zeros(self.B, np.int32)
        flow_v_old[at_old] = codes
        got = jax.jit(pstep.slice_verdicts)(runs, jnp.asarray(flow_v))
        want = fused.resolve_record_verdicts(jnp.asarray(flow_v_old),
                                             old.inv, mal, valid)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

        # route_drop: the records of the flows that overflowed
        over = rng.random(n) < 0.5
        overflow = np.zeros(self.B, bool)
        overflow[at[over]] = True
        overflow_old = np.zeros(self.B, bool)
        overflow_old[at_old[over]] = True
        drop = jax.jit(pstep.fail_open_records)(fa, jnp.asarray(overflow))
        want_drop = jnp.sum(jnp.where(
            valid, jnp.asarray(overflow_old)[old.inv].astype(jnp.uint32),
            jnp.uint32(0)))
        assert drop.dtype == jnp.uint32
        assert int(drop) == int(want_drop)
        assert (int(drop) > 0) == bool(over.any())

    def test_the_stage_holds_one_sort_and_no_gather_or_scatter(self, mesh,
                                                               env):
        """What the stage was (an argsort, a key gather, the `inv`
        scatter, three payload gathers, four segment reductions and the
        malicious counts' scatter-add) cannot come back unseen, nor the
        two gathers through `inv` that took verdicts and overflow back
        to the records: `fsx.aggregate` holds one four-operand sort and
        `fsx.emit` no gather."""
        from flowsentryx_tpu.audit.graph import iter_staged_eqns

        sharded, _, params = env
        staged = list(iter_staged_eqns(jax.make_jaxpr(sharded)(
            pstep.make_sharded_table(CFG, mesh), make_stats(), params,
            build_batch([(1001, 5, 100, 0.1, ML_COLD)], batch_size=256))))
        aggregate = [e for stage, e in staged if stage == "aggregate"]
        emit = [e.primitive.name for stage, e in staged if stage == "emit"]
        sorts = [e for e in aggregate if e.primitive.name == "sort"]
        assert [len(e.invars) for e in sorts] == [4]
        assert not [e for e in aggregate if e.primitive.name.startswith(
            ("gather", "scatter", "dynamic"))]
        assert "sort" in emit
        assert not [p for p in emit if p.startswith("gather")]
