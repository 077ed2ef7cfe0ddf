"""Tests for the ops layer: limiters, batch aggregation, hash table."""

import functools

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from flowsentryx_tpu.core.config import LimiterConfig, LimiterKind, TableConfig
from flowsentryx_tpu.core.schema import NUM_TABLE_COLS, IpTableState, TableCol
from flowsentryx_tpu.engine import table as table_plan
from flowsentryx_tpu.ops import agg, hashtable, limiters


def _win(n, start=0.0, pps=0.0, bps=0.0, prev_pps=0.0, prev_bps=0.0):
    f = lambda v: jnp.full((n,), v, jnp.float32)
    return limiters.WindowState(f(start), f(pps), f(bps), f(prev_pps), f(prev_bps))


def _bucket(n, tokens=0.0, ts=0.0, tok_bytes=0.0):
    f = lambda v: jnp.full((n,), v, jnp.float32)
    return limiters.BucketState(f(tokens), f(ts), f(tok_bytes))


CFG = LimiterConfig(pps_threshold=100.0, bps_threshold=1e6, window_s=1.0,
                    bucket_rate_pps=100.0, bucket_burst=200.0)


class TestFixedWindow:
    def test_accumulates_within_window(self):
        st = _win(1, start=0.0, pps=50.0)
        st, over = limiters.fixed_window(CFG, st, jnp.array([40.0]), jnp.array([0.0]),
                                         jnp.array([0.5]))
        assert float(st.win_pps[0]) == 90.0 and not bool(over[0])
        st, over = limiters.fixed_window(CFG, st, jnp.array([20.0]), jnp.array([0.0]),
                                         jnp.array([0.9]))
        assert float(st.win_pps[0]) == 110.0 and bool(over[0])

    def test_window_reset_counts_first_delta(self):
        # reference bug fsx_kern.c:245-250: reset seeded 0; must seed delta
        st = _win(1, start=0.0, pps=99.0)
        st, over = limiters.fixed_window(CFG, st, jnp.array([7.0]), jnp.array([0.0]),
                                         jnp.array([1.5]))
        assert float(st.win_pps[0]) == 7.0
        assert float(st.win_start[0]) == 1.5
        assert not bool(over[0])

    def test_bytes_threshold(self):
        st = _win(1)
        _, over = limiters.fixed_window(CFG, st, jnp.array([1.0]),
                                        jnp.array([2e6]), jnp.array([0.1]))
        assert bool(over[0])

    def test_vectorized_independent_rows(self):
        st = _win(3, pps=99.0)
        d = jnp.array([0.0, 5.0, 0.0])
        st, over = limiters.fixed_window(CFG, st, d, jnp.zeros(3), jnp.full((3,), 0.5))
        assert list(np.asarray(over)) == [False, True, False]


class TestSlidingWindow:
    def test_boundary_burst_caught(self):
        # 90 pkts at t=0.95 then 90 more at t=1.05: fixed window would see
        # 90 and 90 (both under 100); sliding sees ~90*0.95+90 = 175 > 100.
        st = _win(1, start=0.0)
        st, over1 = limiters.sliding_window(CFG, st, jnp.array([90.0]),
                                            jnp.array([0.0]), jnp.array([0.95]))
        assert not bool(over1[0])
        st, over2 = limiters.sliding_window(CFG, st, jnp.array([90.0]),
                                            jnp.array([0.0]), jnp.array([1.05]))
        assert bool(over2[0])
        assert float(st.prev_pps[0]) == 90.0  # rolled into prev bucket

    def test_long_idle_clears_history(self):
        st = _win(1, start=0.0, pps=90.0, prev_pps=90.0)
        st, over = limiters.sliding_window(CFG, st, jnp.array([10.0]),
                                           jnp.array([0.0]), jnp.array([5.0]))
        assert not bool(over[0])
        assert float(st.prev_pps[0]) == 0.0

    def test_steady_rate_under_threshold_never_flags(self):
        st = _win(1, start=0.0)
        flagged = False
        for i in range(20):
            t = jnp.array([i * 0.25])
            st, over = limiters.sliding_window(CFG, st, jnp.array([20.0]),
                                               jnp.array([0.0]), t)
            flagged = flagged or bool(over[0])
        assert not flagged  # 80 pps steady < 100 threshold


class TestTokenBucket:
    def test_fresh_flow_gets_full_burst(self):
        st = _bucket(1)
        st, over = limiters.token_bucket(CFG, st, jnp.array([150.0]),
                                         jnp.array([0.0]), jnp.array([10.0]))
        assert not bool(over[0])  # burst 200 covers 150
        assert float(st.tokens[0]) == pytest.approx(50.0)

    def test_drain_then_refill(self):
        st = _bucket(1, tokens=10.0, ts=0.0)
        st, over = limiters.token_bucket(CFG, st, jnp.array([50.0]),
                                         jnp.array([0.0]), jnp.array([0.0]))
        assert bool(over[0]) and float(st.tokens[0]) == 0.0
        # 1 s later: refilled 100 tokens
        st, over = limiters.token_bucket(CFG, st, jnp.array([50.0]),
                                         jnp.array([0.0]), jnp.array([1.0]))
        assert not bool(over[0]) and float(st.tokens[0]) == pytest.approx(50.0)

    def test_burst_cap(self):
        st = _bucket(1, tokens=0.0, ts=0.0)
        st, _ = limiters.token_bucket(CFG, st, jnp.array([0.0]),
                                      jnp.array([0.0]), jnp.array([100.0]))
        assert float(st.tokens[0]) == 200.0  # capped at burst

    def test_byte_dimension_limits_bandwidth(self):
        """The spec's bandwidth limit (README.md:153-162): byte credit
        governs independently of packet credit."""
        import dataclasses

        cfg = dataclasses.replace(CFG, bucket_rate_bps=1000.0,
                                  bucket_burst_bytes=10_000.0)
        # plenty of packet tokens, byte bucket drained to 1000
        st = _bucket(1, tokens=200.0, ts=0.0, tok_bytes=1000.0)
        st, over = limiters.token_bucket(cfg, st, jnp.array([1.0]),
                                         jnp.array([1500.0]), jnp.array([0.0]))
        assert bool(over[0])  # 1500 B demand vs 1000 B credit
        # the refused batch drained the clamped balance to 0 (batch
        # aggregate semantics; the per-packet kernel twin keeps it —
        # the documented divergence the property suite reseeds across);
        # 3 s later: +3000 B -> covered, 1500 left
        st, over = limiters.token_bucket(cfg, st, jnp.array([1.0]),
                                         jnp.array([1500.0]), jnp.array([3.0]))
        assert not bool(over[0])
        assert float(st.tok_bytes[0]) == pytest.approx(1500.0)

    def test_byte_dimension_disabled_when_zero_depth(self):
        import dataclasses

        cfg = dataclasses.replace(CFG, bucket_rate_bps=0.0,
                                  bucket_burst_bytes=0.0)
        st = _bucket(1, tokens=200.0, ts=0.0, tok_bytes=0.0)
        st, over = limiters.token_bucket(cfg, st, jnp.array([1.0]),
                                         jnp.array([1e9]), jnp.array([0.0]))
        assert not bool(over[0])  # bytes ignored entirely
        assert float(st.tok_bytes[0]) == 0.0

    def test_new_flow_byte_bucket_starts_full(self):
        import dataclasses

        cfg = dataclasses.replace(CFG, bucket_rate_bps=1000.0,
                                  bucket_burst_bytes=10_000.0)
        st = _bucket(1, tokens=0.0, ts=0.0, tok_bytes=0.0)
        st, over = limiters.token_bucket(
            cfg, st, jnp.array([1.0]), jnp.array([9000.0]),
            jnp.array([0.0]), is_new=jnp.array([True]))
        assert not bool(over[0])  # full 10 kB burst on first sight
        assert float(st.tok_bytes[0]) == pytest.approx(1000.0)


class TestApplyLimiter:
    @pytest.mark.parametrize("kind", list(LimiterKind))
    def test_dispatch(self, kind):
        cfg = LimiterConfig(kind=kind, pps_threshold=10.0,
                            bucket_rate_pps=10.0, bucket_burst=20.0)
        dec = limiters.apply_limiter(cfg, _win(2), _bucket(2),
                                     jnp.array([5.0, 500.0]),
                                     jnp.array([0.0, 0.0]),
                                     jnp.array([0.5, 0.5]))
        assert not bool(dec.over_limit[0])
        assert bool(dec.over_limit[1])


class TestAggregate:
    def test_groups_duplicates(self):
        key = jnp.array([10, 20, 10, 10, 30, 20], jnp.uint32)
        plen = jnp.array([100.0, 50.0, 100.0, 100.0, 25.0, 50.0])
        ts = jnp.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
        valid = jnp.ones((6,), bool)
        fa = agg.aggregate(key, plen, ts, valid)

        got = {}
        for i in range(6):
            if bool(fa.rep_valid[i]):
                got[int(fa.rep_key[i])] = (
                    float(fa.rep_pkts[i]), float(fa.rep_bytes[i]), float(fa.rep_ts[i])
                )
        assert got == {10: (3.0, 300.0, 4.0), 20: (2.0, 100.0, 6.0),
                       30: (1.0, 25.0, 5.0)}

    def test_inv_broadcasts_back(self):
        key = jnp.array([10, 20, 10, 30], jnp.uint32)
        fa = agg.aggregate(key, jnp.ones(4), jnp.zeros(4), jnp.ones((4,), bool))
        rep_of_packet = np.asarray(fa.rep_key)[np.asarray(fa.inv)]
        np.testing.assert_array_equal(rep_of_packet, [10, 20, 10, 30])

    def test_invalid_packets_excluded(self):
        key = jnp.array([10, 10, 10], jnp.uint32)
        valid = jnp.array([True, False, True])
        fa = agg.aggregate(key, jnp.full((3,), 100.0), jnp.zeros(3), valid)
        idx = int(np.asarray(fa.inv)[0])
        assert float(fa.rep_pkts[idx]) == 2.0
        assert float(fa.rep_bytes[idx]) == 200.0

    def test_all_invalid(self):
        fa = agg.aggregate(jnp.array([1, 2], jnp.uint32), jnp.ones(2),
                           jnp.zeros(2), jnp.zeros((2,), bool))
        assert not bool(fa.rep_valid.any())

    def test_single_source_flood(self):
        b = 2048
        key = jnp.full((b,), 0xC0A80001, jnp.uint32)  # 192.168.0.1
        fa = agg.aggregate(key, jnp.full((b,), 64.0),
                           jnp.linspace(0, 0.001, b), jnp.ones((b,), bool))
        assert int(fa.rep_valid.sum()) == 1
        i = int(np.asarray(fa.rep_valid).argmax())
        assert float(fa.rep_pkts[i]) == b


class TestRunScans:
    """The two passes the fused step makes over its sorted runs
    (``ops/fused.py``), against a loop."""

    @pytest.mark.parametrize("b,head_share", [
        (1, 1.0), (2, 0.5), (255, 0.3), (256, 0.0), (256, 1.0),
        (1000, 0.05), (2048, 0.6)])
    def test_scan_runs_against_a_loop(self, b, head_share):
        from flowsentryx_tpu.ops import fused

        rng = np.random.default_rng([b, int(head_share * 100)])
        head = rng.random(b) < head_share
        head[0] = True
        add = rng.integers(0, 1500, b).astype(np.float32)
        mx = rng.normal(size=b).astype(np.float32)
        mx[rng.random(b) < 0.2] = -np.inf  # an invalid record's time
        got_add, got_max = fused.scan_runs(
            jnp.asarray(head), jnp.asarray(np.stack([add, mx])),
            maxed=(False, True))
        want_add, want_max = add.copy(), mx.copy()
        for i in range(1, b):
            if not head[i]:
                want_add[i] += want_add[i - 1]
                want_max[i] = max(want_max[i], want_max[i - 1])
        # whole numbers under 2^24: exact in any order of addition
        np.testing.assert_array_equal(np.asarray(got_add), want_add)
        np.testing.assert_array_equal(np.asarray(got_max), want_max)

    def test_a_sum_rounds_at_its_run_not_at_the_batch(self):
        """Why the runs are not read off two whole-batch prefix sums:
        behind 2^24 of other runs a run of three halves still sums to
        1.5."""
        from flowsentryx_tpu.ops import fused

        col = np.array([2.0 ** 24, 2.0 ** 24, 0.5, 0.5, 0.5], np.float32)
        head = np.array([True, False, True, False, False])
        (got,) = fused.scan_runs(jnp.asarray(head), jnp.asarray(col)[None],
                                 maxed=(False,))
        assert float(got[-1]) == 1.5
        prefix = np.cumsum(col, dtype=np.float32)
        assert float(prefix[-1] - prefix[1]) != 1.5

    @pytest.mark.parametrize("b,tail_share", [
        (1, 1.0), (2, 0.5), (255, 0.3), (256, 0.0), (256, 1.0),
        (2048, 0.6)])
    def test_spread_from_tails_against_a_loop(self, b, tail_share):
        from flowsentryx_tpu.ops import fused

        rng = np.random.default_rng([b, int(tail_share * 100)])
        tail = rng.random(b) < tail_share
        tail[-1] = True
        code = rng.choice([0, 1, 2, 3, fused.ML_RECORD_GATE], b).astype(
            np.int32)
        got = np.asarray(fused.spread_from_tails(jnp.asarray(tail),
                                                 jnp.asarray(code)))
        want = code.copy()
        for i in range(b - 2, -1, -1):
            if not tail[i]:
                want[i] = want[i + 1]
        np.testing.assert_array_equal(got, want)

    def test_spread_from_tails_refuses_a_batch_its_word_cannot_hold(self):
        from flowsentryx_tpu.ops import fused

        with pytest.raises(ValueError, match="do not fit"):
            fused.spread_from_tails(np.zeros((1 << 24) + 1, bool),
                                    np.zeros(1, np.int32))
        assert fused.ML_RECORD_GATE < 1 << fused._CODE_BITS


class TestHashTable:
    CFG4 = TableConfig(capacity=1 << 10, probes=4, stale_s=30.0)

    def _fresh(self, cap):
        return (jnp.zeros((cap,), jnp.uint32), jnp.zeros((cap,), jnp.float32))

    @staticmethod
    def _table(tk, seen):
        """The table the probe takes, built from a key and a `last_seen`
        vector.  Every other column holds NaN: a probe that read the
        wrong column would find nothing stale and nothing live."""
        state = jnp.full((tk.shape[0], NUM_TABLE_COLS), jnp.nan, jnp.float32)
        return IpTableState(
            key=tk, state=state.at[:, int(TableCol.LAST_SEEN)].set(seen))

    def test_insert_then_find(self):
        tk, seen = self._fresh(1 << 10)
        keys = jnp.array([111, 222, 333, agg.INVALID_KEY], jnp.uint32)
        valid = jnp.array([True, True, True, False])
        a1 = hashtable.assign_slots(self._table(tk, seen), keys, valid,
                                    jnp.float32(1.0), self.CFG4)
        assert list(np.asarray(a1.inserted)) == [True, True, True, False]
        assert not bool(a1.found.any())
        # caller scatters keys (as the fused step does)
        tk = tk.at[a1.slot].set(jnp.where(a1.tracked, keys, tk[a1.slot]))
        seen = seen.at[a1.slot].set(jnp.where(a1.tracked, 1.0, seen[a1.slot]))
        a2 = hashtable.assign_slots(self._table(tk, seen), keys, valid,
                                    jnp.float32(2.0), self.CFG4)
        assert list(np.asarray(a2.found)) == [True, True, True, False]
        np.testing.assert_array_equal(np.asarray(a2.slot[:3]), np.asarray(a1.slot[:3]))

    def test_no_duplicate_slots_among_tracked(self, rng):
        # tiny table forces collisions; arbitration must keep winners unique
        cfg = TableConfig(capacity=16, probes=2, stale_s=30.0)
        tk, seen = self._fresh(16)
        keys = jnp.asarray(rng.integers(1, 2**31, 64).astype(np.uint32))
        valid = jnp.ones((64,), bool)
        a = hashtable.assign_slots(self._table(tk, seen), keys, valid,
                                   jnp.float32(1.0), cfg)
        slots = np.asarray(a.slot)[np.asarray(a.tracked)]
        assert len(slots) == len(set(slots.tolist()))
        assert len(slots) <= 16

    def test_stale_reclamation(self):
        cfg = TableConfig(capacity=2, probes=2, stale_s=5.0)
        tk = jnp.array([0, 999], jnp.uint32)   # slot 1 occupied by key 999
        seen = jnp.array([0.0, 1.0], jnp.float32)
        key = jnp.array([12345], jnp.uint32)
        # at t=3 (999 fresh): key lands in the empty slot 0 or loses
        a_fresh = hashtable.assign_slots(self._table(tk, seen), key,
                                         jnp.array([True]),
                                         jnp.float32(3.0), cfg)
        # at t=20 (999 stale): key must be tracked somewhere
        a_stale = hashtable.assign_slots(self._table(tk, seen), key,
                                         jnp.array([True]),
                                         jnp.float32(20.0), cfg)
        assert bool(a_stale.tracked[0])
        assert bool(a_fresh.tracked[0])  # capacity-2, probes=2 covers both slots

    def test_found_beats_stale_reclaimer(self, rng):
        # Fill a 2-slot table with keys A,B (both stale).  Rep batch has
        # B (a match) plus new keys that want B's slot as stale.  B must
        # keep its slot.
        cfg = TableConfig(capacity=2, probes=2, stale_s=1.0)
        tk = jnp.array([777, 888], jnp.uint32)
        seen = jnp.zeros((2,), jnp.float32)
        keys = jnp.array([888, 555, 666], jnp.uint32)
        a = hashtable.assign_slots(self._table(tk, seen), keys,
                                   jnp.ones((3,), bool),
                                   jnp.float32(100.0), cfg)
        assert bool(a.found[0]) and bool(a.tracked[0])
        b_slot = int(a.slot[0])
        assert int(tk[b_slot]) == 888
        others = np.asarray(a.slot[1:])[np.asarray(a.tracked[1:])]
        assert b_slot not in others.tolist()

    def test_full_table_fails_open(self):
        cfg = TableConfig(capacity=2, probes=2, stale_s=1e9)
        tk = jnp.array([777, 888], jnp.uint32)  # full, never stale
        seen = jnp.full((2,), 1e9, jnp.float32)
        keys = jnp.array([111, 222, 333], jnp.uint32)
        a = hashtable.assign_slots(self._table(tk, seen), keys,
                                   jnp.ones((3,), bool),
                                   jnp.float32(2e9), cfg)
        assert not bool(a.tracked.any())  # untracked, not mis-tracked

    @staticmethod
    def _ring(key, cfg):
        """[R, P] probe ring of each key: the host twin of the device's
        probe sequence (`engine/table.py`, pinned by test_table.py)."""
        return table_plan._global_candidates(key, table_plan.TablePlan.of(cfg))

    @classmethod
    def _probe_by_column(cls, tk, seen, key, valid, now, cfg):
        """`probe_slots` as it read `last_seen` before it took the
        table: from a `[capacity]` column vector, written out in numpy."""
        p = cfg.probes
        slots = cls._ring(key, cfg)
        cand_key, cand_seen = tk[slots], seen[slots]
        match = cand_key == key[:, None]
        empty = cand_key == 0
        stale = ~match & ~empty & (np.float32(now) - cand_seen
                                   > np.float32(cfg.stale_s))
        idx = np.arange(p, dtype=np.int32)[None, :]
        score = np.where(match, idx, np.where(
            empty, p + idx, np.where(stale, 2 * p + idx, 4 * p)))
        best = score.argmin(axis=1)
        rows = np.arange(key.shape[0])
        return hashtable.ProbeResult(
            slot=slots[rows, best],
            found=valid & (score[rows, best] < p),
            usable=valid & (score[rows, best] < 4 * p),
            # staleness can decide only for a valid key with neither a
            # match nor an empty slot among its probes
            read_seen=(valid & ~(match | empty).any(axis=1)).any())

    def test_probe_from_the_matrix_matches_the_column_form(self):
        """One seeded batch whose 4 probes a key meet matches, empties,
        stale rows and live foreign rows: the gather at
        `(slot, LAST_SEEN)` of the state matrix selects bit for bit
        what the `[capacity]` column form selects."""
        rng = np.random.default_rng(30)
        cap = 64
        cfg = TableConfig(capacity=cap, probes=4, stale_s=30.0, salt=0xFEED)
        # residents sit on a probe of their own ring, any of the four
        resident = rng.choice(1 << 20, 56, replace=False).astype(np.uint32) + 1
        tk = np.zeros(cap, np.uint32)
        for k, ring in zip(resident, self._ring(resident, cfg)):
            free = ring[tk[ring] == 0]
            if free.size:
                tk[rng.choice(free)] = k
        # at now = 100 an occupied row is stale below 70 s, live above
        seen = np.where(tk != 0, rng.choice([10.0, 69.5, 70.5, 99.0], cap),
                        0.0).astype(np.float32)
        key = np.concatenate([tk[tk != 0][:24],
                              rng.integers(1 << 20, 1 << 30, 40)]
                             ).astype(np.uint32)
        valid = rng.random(key.shape[0]) < 0.9
        now = jnp.float32(100.0)
        want = self._probe_by_column(tk, seen, key, valid, now, cfg)
        got = hashtable.probe_slots(
            self._table(jnp.asarray(tk), jnp.asarray(seen)),
            jnp.asarray(key), jnp.asarray(valid), now, cfg)
        for name in hashtable.ProbeResult._fields:
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          getattr(want, name), err_msg=name)
        # the batch really holds every kind of outcome
        found, usable = np.asarray(got.found), np.asarray(got.usable)
        picked = tk[np.asarray(got.slot)]
        assert found.sum() >= 16                            # matches
        assert (usable & ~found & (picked == 0)).any()      # empties
        assert (usable & ~found & (picked != 0)).any()      # stale reclaim
        assert (valid & ~usable).any()                      # all live foreign

    #: at NOW an occupied row is stale when last seen before 70 s
    NOW = 100.0
    PROBE_CFG = TableConfig(capacity=64, probes=4, stale_s=30.0, salt=0xFEED)

    def _probe_case(self, case):
        """``(tk, seen, key, valid, lone)`` of one batch against a
        64-row table.  ``lone`` is the row of the key whose 4 probes all
        hold live or stale foreign keys (None where the case has no
        such single key)."""
        rng = np.random.default_rng(38)
        cfg = self.PROBE_CFG
        cap = cfg.capacity
        tk = np.zeros(cap, np.uint32)
        seen = np.zeros(cap, np.float32)
        fresh = iter(rng.permutation(1 << 20)[:4096].astype(np.uint32)
                     + (1 << 20))

        def occupy(rows, last_seen):
            for r, t in zip(rows, last_seen):
                tk[r], seen[r] = next(fresh), t

        if case == "full_table":
            occupy(range(cap), rng.choice([10.0, 69.5, 70.5, 99.0], cap))
            key = np.array([next(fresh) for _ in range(40)], np.uint32)
            return tk, seen, key, rng.random(40) < 0.9, None
        # 24 residents, each on a probe of its own ring
        for k in [next(fresh) for _ in range(24)]:
            ring = self._ring(np.array([k], np.uint32), cfg)[0]
            free = ring[tk[ring] == 0]
            if free.size:
                r = rng.choice(free)
                tk[r], seen[r] = k, rng.choice([10.0, 99.0])
        lone_key = next(fresh)
        ring = self._ring(np.array([lone_key], np.uint32), cfg)[0]
        if case != "none_needs":
            # the lone key's ring: foreign keys on all 4 probes.  One
            # stale candidate, or two that tie in everything but place
            ages = ([99.0, 10.0, 99.0, 10.0] if case == "stale_tie"
                    else [99.0, 99.0, 10.0, 99.0])
            occupy(ring, ages)
        # everyone else in the batch has a match or an empty probe
        others = []
        while len(others) < 24:
            k = next(fresh)
            if (tk[self._ring(np.array([k], np.uint32), cfg)[0]] == 0).any():
                others.append(k)
        key = np.concatenate([tk[tk != 0][:15], [lone_key],
                              others]).astype(np.uint32)
        valid = np.ones(key.shape[0], bool)
        valid[[3, 20]] = False
        if case == "none_needs":
            return tk, seen, key, valid, None
        if case == "only_an_invalid_row_needs":
            valid[15] = False
        return tk, seen, key, valid, 15

    @staticmethod
    def _same_decisions(got, want, msg):
        """`found`, `usable` and `read_seen` everywhere, `slot` where
        `usable` (an unusable row's slot is parked by every caller)."""
        for name in ("found", "usable", "read_seen"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)), getattr(want, name),
                err_msg=f"{msg}: {name}")
        u = np.asarray(want.usable)
        np.testing.assert_array_equal(np.asarray(got.slot)[u], want.slot[u],
                                      err_msg=f"{msg}: slot")

    @pytest.mark.parametrize("how", ["eager", "jit", "scan", "assign_slots"])
    @pytest.mark.parametrize("case,reads", [
        ("none_needs", False),
        ("one_needs", True),
        ("only_an_invalid_row_needs", False),
        ("full_table", True),
        ("stale_tie", True),
    ])
    def test_probe_reads_last_seen_only_where_it_decides(self, case, reads,
                                                         how):
        """`last_seen` is read by a batch in which some VALID key has
        neither a match nor an empty slot among its probes, and by no
        other; either way the decisions are the column form's, which
        always reads it."""
        cfg, now = self.PROBE_CFG, jnp.float32(self.NOW)
        tk, seen, key, valid, lone = self._probe_case(case)
        table = self._table(jnp.asarray(tk), jnp.asarray(seen))
        want = self._probe_by_column(tk, seen, key, valid, now, cfg)
        assert bool(want.read_seen) is reads
        if case in ("one_needs", "stale_tie"):
            # the lone key's earliest stale candidate wins: probe 2, or
            # probe 1 of the tied 1 and 3
            ring = self._ring(key[lone:lone + 1], cfg)[0]
            assert want.usable[lone] and not want.found[lone]
            assert want.slot[lone] == ring[1 if case == "stale_tie" else 2]
        if case == "full_table":
            assert not want.found.any()
            assert want.usable.any() and (valid & ~want.usable).any()
        probe = functools.partial(hashtable.probe_slots, cfg=cfg)
        if how == "eager":
            self._same_decisions(
                probe(table, jnp.asarray(key), jnp.asarray(valid), now),
                want, case)
        elif how == "jit":
            self._same_decisions(
                jax.jit(probe)(table, jnp.asarray(key), jnp.asarray(valid),
                               now), want, case)
        elif how == "scan":
            # a second batch: the keys moved on by one row under an
            # all-true mask, so the conditional is taken batch by batch
            key2, valid2 = np.roll(key, 1), np.ones_like(valid)
            want2 = self._probe_by_column(tk, seen, key2, valid2, now, cfg)
            if case == "only_an_invalid_row_needs":
                assert want2.read_seen and not want.read_seen

            def body(tbl, kv):
                return tbl, probe(tbl, kv[0], kv[1], now)

            _, got = jax.lax.scan(
                body, table, (jnp.asarray(np.stack([key, key2])),
                              jnp.asarray(np.stack([valid, valid2]))))
            for i, w in enumerate((want, want2)):
                self._same_decisions(jax.tree.map(lambda a: a[i], got), w,
                                     f"{case}, batch {i}")
        else:
            a = hashtable.assign_slots(table, jnp.asarray(key),
                                       jnp.asarray(valid), now, cfg)
            assert bool(a.read_seen) is reads
            tracked = np.asarray(a.tracked)
            # arbitration leaves one winner a claimed slot, a finder
            # before a claimant
            assert not (tracked & ~want.usable).any()
            assert tracked.sum() == np.unique(want.slot[want.usable]).size
            np.testing.assert_array_equal(np.asarray(a.slot)[tracked],
                                          want.slot[tracked])
            np.testing.assert_array_equal(np.asarray(a.found),
                                          want.found & tracked)
            np.testing.assert_array_equal(np.asarray(a.inserted),
                                          ~want.found & tracked)

    def test_hash_avalanche(self):
        # sequential keys must not map to sequential slots
        ks = jnp.arange(1, 1025, dtype=jnp.uint32)
        hs = np.asarray(hashtable.hash_u32(ks)) & 1023
        assert len(set(hs.tolist())) > 600  # good dispersion

    def test_salt_relocates_and_disperses(self, rng):
        """The boot-time salt must (a) move slot positions — so an
        unsalted precomputation is useless — while (b) keeping
        find-after-insert exact under the same salt, and (c) dispersing
        keys crafted to collide under salt=0."""
        import dataclasses

        cfg0 = self.CFG4
        cfg_s = dataclasses.replace(cfg0, salt=0xDEADBEEF)
        keys = jnp.asarray(rng.integers(1, 2**31, 64).astype(np.uint32))
        valid = jnp.ones((64,), bool)
        tk, seen = self._fresh(1 << 10)
        a0 = hashtable.assign_slots(self._table(tk, seen), keys, valid,
                                    jnp.float32(1.0), cfg0)
        a_s = hashtable.assign_slots(self._table(tk, seen), keys, valid,
                                     jnp.float32(1.0), cfg_s)
        # (a) layouts differ almost everywhere
        same = np.asarray(a0.slot) == np.asarray(a_s.slot)
        assert same.mean() < 0.1
        # (b) salted insert→find round-trips (scatter winners only: an
        # untracked row's slot is garbage and must not clobber a write)
        slot_w = jnp.where(a_s.tracked, a_s.slot, 1 << 10)
        tk2 = tk.at[slot_w].set(keys, mode="drop")
        seen2 = seen.at[slot_w].set(1.0, mode="drop")
        a2 = hashtable.assign_slots(self._table(tk2, seen2), keys, valid,
                                    jnp.float32(2.0), cfg_s)
        tr = np.asarray(a_s.tracked)
        assert np.asarray(a2.found)[tr].all()
        # (c) keys that all collide to bucket 0 under salt=0 spread out
        # once salted (the precomputed-collision attack on table slots)
        cand = np.arange(1, 400_000, dtype=np.uint32)
        h0 = np.asarray(hashtable.hash_u32(jnp.asarray(cand))) & 1023
        crafted = jnp.asarray(cand[h0 == 0][:64])
        hs = np.asarray(hashtable.hash_u32(crafted, cfg_s.salt)) & 1023
        assert len(set(hs.tolist())) > 48  # near-uniform again
