"""End-to-end tests of the fused micro-batch step (single device)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flowsentryx_tpu.core.config import (
    BatchConfig, FsxConfig, LimiterConfig, LimiterKind, ModelConfig, TableConfig,
)
from flowsentryx_tpu.core.schema import (
    FeatureBatch, Verdict, make_stats, make_table, stat_value,
)
from flowsentryx_tpu.audit.graph import (
    iter_eqns, iter_platform_eqns, platform_branches,
)
from flowsentryx_tpu.models import get_model
from flowsentryx_tpu.ops import fused

CFG = FsxConfig(
    limiter=LimiterConfig(pps_threshold=100.0, bps_threshold=1e9, block_s=10.0),
    table=TableConfig(capacity=1 << 12, probes=8, stale_s=1e6),
    model=ModelConfig(name="logreg_int8", threshold=0.5, ml_block_s=10.0),
)

#: features that make the golden int8 model score 1.0 (huge IAT/len std
#: feed the +106 weights; in_scale ≈ 9.4e5 so small features quantize to 0)
ML_HOT = [0.0, 0.0, 5e6, 0.0, 0.0, 0.0, 5e6, 0.0]
#: features the golden model scores exactly 0.5 (all quantize to zero)
ML_COLD = [80.0, 100.0, 10.0, 100.0, 100.0, 1000.0, 500.0, 2000.0]


def build_batch(entries, batch_size=256):
    """entries: list of (key, n_packets, pkt_len, t, feat)."""
    key, plen, ts, feat = [], [], [], []
    for k, n, ln, t, f in entries:
        for i in range(n):
            key.append(k)
            plen.append(ln)
            ts.append(t + i * 1e-6)
            feat.append(f)
    n = len(key)
    assert n <= batch_size
    pad = batch_size - n
    return FeatureBatch(
        key=jnp.asarray(np.array(key + [0] * pad, np.uint32)),
        feat=jnp.asarray(np.array(feat + [[0.0] * 8] * pad, np.float32)),
        pkt_len=jnp.asarray(np.array(plen + [0] * pad, np.float32)),
        ts=jnp.asarray(np.array(ts + [0] * pad, np.float32)),
        valid=jnp.asarray(np.array([True] * n + [False] * pad)),
    )


def make_env(cfg=CFG):
    spec = get_model(cfg.model.name)
    step = fused.make_jitted_step(cfg, spec.classify_batch, donate=False)
    return step, make_table(cfg.table.capacity), make_stats(), spec.init()


def two_stage_step(cfg, spec, params):
    """The aggregate → assign_slots → core composition that both steps
    ran before their one sort, as ``step(table, stats, batch) -> (table,
    stats, verdict, block_key, block_until)``: the reference twin of
    ``make_step``.  Its flows lie at the front in KEY order, so its
    block arrays agree with the fused step's as sets, not by place."""
    from flowsentryx_tpu.ops import agg

    def step(table, stats, batch):
        fa = agg.aggregate(batch.key, batch.pkt_len, batch.ts,
                           batch.valid)
        now = jnp.max(jnp.where(batch.valid, batch.ts, 0.0))
        score = spec.classify_batch(params, batch.feat)
        mal = (score > cfg.model.threshold) & batch.valid
        ml_count = fused.ml_flow_count(cfg, score, batch.valid,
                                       fa.inv)
        all_flows = jnp.ones_like(fa.rep_valid)
        table, dec = fused.flow_step(cfg, table, fa, all_flows,
                                     ml_count, now)
        verdict = fused.resolve_record_verdicts(
            dec.flow_verdict, fa.inv, mal, batch.valid)
        return (table, fused.update_stats(stats, verdict, batch.valid, dec),
                verdict,
                jnp.where(dec.newly_blocked, fa.rep_key, agg.INVALID_KEY),
                jnp.where(dec.newly_blocked, dec.new_blocked_until, 0.0))

    return step


class TestFusedStep:
    def test_benign_passes(self):
        step, table, stats, params = make_env()
        batch = build_batch([(1001, 5, 100, 0.1, ML_COLD), (1002, 3, 200, 0.1, ML_COLD)])
        table, stats, out = step(table, stats, params, batch)
        v = np.asarray(out.verdict)[:8]
        assert (v == int(Verdict.PASS)).all()
        assert stat_value(stats.allowed) == 8 and stats.dropped == 0

    def test_flood_rate_limited_and_blacklisted(self):
        step, table, stats, params = make_env()
        flood = build_batch([(2001, 150, 100, 0.1, ML_COLD)])
        table, stats, out = step(table, stats, params, flood)
        v = np.asarray(out.verdict)[:150]
        assert (v == int(Verdict.DROP_RATE)).all()
        assert stat_value(stats.dropped_rate) == 150
        # newly-blacklisted writeback contains the key with ~10s expiry
        keys = np.asarray(out.block_key)
        until = np.asarray(out.block_until)
        hit = keys != 0xFFFFFFFF
        assert list(np.unique(keys[hit])) == [2001]
        assert until[hit].max() > 10.0

        # next batch, 1s later: flow is blacklisted outright
        again = build_batch([(2001, 5, 100, 1.2, ML_COLD)])
        table, stats, out2 = step(table, stats, params, again)
        assert (np.asarray(out2.verdict)[:5] == int(Verdict.DROP_BLACKLIST)).all()

        # after expiry (>10s) and calm rate: flow passes again
        later = build_batch([(2001, 5, 100, 20.0, ML_COLD)])
        table, stats, out3 = step(table, stats, params, later)
        assert (np.asarray(out3.verdict)[:5] == int(Verdict.PASS)).all()

    def test_ml_detection_votes_then_blacklists(self):
        """The young-flow vote (ModelConfig.vote_k/vote_m; the fix for
        the round-4 serve run, whose record PR 23 deleted): a new flow's malicious-scoring records DROP per record
        (fail-closed — a rotating spoofed flood must not sail through)
        but the flow is NOT blacklisted until the vote carries;
        sustained malicious evidence past maturity blacklists."""
        step, table, stats, params = make_env()
        # batch 1: 4 hot records from a NEW flow = exactly vote_k —
        # the records drop, but NO blacklist entry lands (pre-vote
        # behavior condemned the source for ml_block_s on the spot)
        batch = build_batch([(3001, 4, 100, 0.1, ML_HOT), (3002, 4, 100, 0.1, ML_COLD)])
        table, stats, out = step(table, stats, params, batch)
        v = np.asarray(out.verdict)
        assert (v[:4] == int(Verdict.DROP_ML)).all()
        assert (v[4:8] == int(Verdict.PASS)).all()
        keys = np.asarray(out.block_key)
        assert 3001 not in keys[keys != 0xFFFFFFFF]  # dropped, not blocked

        # batch 2: the flow is mature (rec_seen=4 >= vote_k); 2 more
        # hot records = vote_m votes -> ML drop + blacklist writeback
        b2 = build_batch([(3001, 2, 100, 0.3, ML_HOT)])
        table, stats, out2 = step(table, stats, params, b2)
        assert (np.asarray(out2.verdict)[:2] == int(Verdict.DROP_ML)).all()
        keys = np.asarray(out2.block_key)
        assert 3001 in keys[keys != 0xFFFFFFFF]

        # batch 3: blacklisted outright for ml_block_s
        again = build_batch([(3001, 2, 100, 0.5, ML_COLD)])
        table, stats, out3 = step(table, stats, params, again)
        assert (np.asarray(out3.verdict)[:2] == int(Verdict.DROP_BLACKLIST)).all()

    def test_ml_young_mis_scores_never_block_recovered_flow(self):
        """A benign flow whose ONLY malicious-looking records are its
        young ones (the failure of the round-4 serve run) loses those
        records — per-record fail-closed — but is NEVER blacklisted,
        and its mature traffic flows untouched."""
        step, table, stats, params = make_env()
        b1 = build_batch([(3101, 3, 100, 0.1, ML_HOT)])   # young mis-scores
        table, stats, o1 = step(table, stats, params, b1)
        assert (np.asarray(o1.verdict)[:3] == int(Verdict.DROP_ML)).all()
        keys = np.asarray(o1.block_key)
        assert 3101 not in keys[keys != 0xFFFFFFFF]  # no condemnation
        # mature records score benign: they pass, and no blacklist entry
        # ever lands (the r4 failure was DROP_BLACKLIST from here on)
        for t in (0.3, 0.5, 0.7):
            b = build_batch([(3101, 4, 100, t, ML_COLD)])
            table, stats, o = step(table, stats, params, b)
            assert (np.asarray(o.verdict)[:4] == int(Verdict.PASS)).all()

    def test_ml_dense_burst_blocks_first_batch_even_tracked(self):
        """The batch-local burst rule applies to tracked flows too: a
        single batch carrying > vote_k records with >= vote_m scored
        malicious is a dense flood, not a young benign flow — youth
        grants no immunity window to line-rate attacks."""
        step, table, stats, params = make_env()
        flood = build_batch([(3201, 40, 100, 0.1, ML_HOT)])
        table, stats, out = step(table, stats, params, flood)
        assert (np.asarray(out.verdict)[:40] == int(Verdict.DROP_ML)).all()

    def test_ml_vote_decays_and_resets_on_block(self):
        """An isolated borderline mis-score long ago must not leave a
        flow permanently one record from a block (votes decay with
        vote_decay_s half-life), and a fired block consumes the votes
        (re-blocking after TTL needs vote_m fresh records)."""
        import dataclasses

        cfg = dataclasses.replace(
            CFG, model=dataclasses.replace(CFG.model, vote_decay_s=1.0,
                                           ml_block_s=0.5))
        step, table, stats, params = make_env(cfg)

        def blocked_keys(out):
            keys = np.asarray(out.block_key)
            return set(keys[keys != 0xFFFFFFFF].tolist())

        # mature the flow benignly
        table, stats, _ = step(table, stats, params,
                               build_batch([(3401, 5, 100, 0.1, ML_COLD)]))
        # one mature mis-score: the record drops (fail-closed) but
        # 1 vote < vote_m -> NO blacklist entry
        table, stats, o1 = step(table, stats, params,
                                build_batch([(3401, 1, 100, 0.2, ML_HOT)]))
        assert 3401 not in blocked_keys(o1)
        # 10 half-lives later another single mis-score: the old vote
        # decayed to ~0.001 — still ~1 vote, must NOT blacklist (an
        # undecayed vote would have carried it over vote_m)
        table, stats, o2 = step(table, stats, params,
                                build_batch([(3401, 1, 100, 10.2, ML_HOT)]))
        assert 3401 not in blocked_keys(o2)
        # two quick mis-scores: 2 votes -> blacklisted; votes then reset
        table, stats, o3 = step(table, stats, params,
                                build_batch([(3401, 2, 100, 10.4, ML_HOT)]))
        assert (np.asarray(o3.verdict)[:2] == int(Verdict.DROP_ML)).all()
        assert 3401 in blocked_keys(o3)
        # after the 0.5 s TTL, a single borderline record drops but does
        # NOT re-blacklist (the block consumed the votes)
        table, stats, o4 = step(table, stats, params,
                                build_batch([(3401, 1, 100, 11.5, ML_HOT)]))
        assert 3401 not in blocked_keys(o4)

    @pytest.mark.parametrize("cap,probes,salt", [
        (64, 4, 0),            # tiny table: heavy collisions/fail-opens
        (16, 2, 0xBEEF),       # tinier still, salted, short probes
        (1 << 12, 8, 0xA5A5),  # roomy: mostly inserts/finds
    ])
    def test_single_sort_step_matches_two_stage_composition(
            self, cap, probes, salt):
        """The production single-sort pipeline (make_step) must be
        decision-identical to the legacy aggregate→assign_slots→core
        composition the sharded path still uses — across random
        traffic, slot collisions, zero/invalid keys, salts, probe
        counts, and repeat batches against evolving table state."""
        import dataclasses

        cfg = dataclasses.replace(
            CFG, table=TableConfig(capacity=cap, probes=probes,
                                   stale_s=1e6, salt=salt))
        spec = get_model(cfg.model.name)
        params = spec.init()
        step = fused.make_jitted_step(cfg, spec.classify_batch,
                                      donate=False)

        legacy_step = two_stage_step(cfg, spec, params)

        rng = np.random.default_rng(3)
        t1, s1 = make_table(cap), make_stats()
        t2, s2 = make_table(cap), make_stats()
        b = 256
        for i in range(6):
            batch = FeatureBatch(
                # keys from a pool of 200 vs a cap-row table: tiny
                # caps force collisions, stale reclaims, and full-table
                # fail-opens; the roomy cap is mostly inserts/finds;
                # some zero keys and invalid rows either way
                key=jnp.asarray(np.where(rng.random(b) < 0.05, 0,
                                         rng.integers(1, 200, b))
                                .astype(np.uint32)),
                feat=jnp.asarray(
                    rng.uniform(0, 3e6, (b, 8)).astype(np.float32)),
                pkt_len=jnp.asarray(
                    rng.integers(64, 1500, b).astype(np.float32)),
                ts=jnp.asarray(np.sort(
                    rng.uniform(i, i + 0.5, b)).astype(np.float32)),
                valid=jnp.asarray(rng.random(b) < 0.95),
            )
            t1, s1, out = step(t1, s1, params, batch)
            t2, s2, v2, *_ = legacy_step(t2, s2, batch)
            np.testing.assert_array_equal(np.asarray(out.verdict),
                                          np.asarray(v2), f"batch {i}")
            for a, c in zip(s1, s2):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
            # table state equal as SETS of rows (arbitration ties may
            # place different winners, but with identical priorities
            # the occupied (key -> counters) mapping must agree)
            np.testing.assert_array_equal(np.asarray(t1.key),
                                          np.asarray(t2.key), f"batch {i}")
            np.testing.assert_allclose(np.asarray(t1.win_pps),
                                       np.asarray(t2.win_pps), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(t1.ml_votes),
                                       np.asarray(t2.ml_votes), rtol=1e-6)

    def test_megastep_matches_sequential_steps(self):
        """The N-in-one-dispatch mega-step (lax.scan over stacked wire
        buffers) must produce byte-identical table/stats/verdict
        trajectories to N sequential single-step dispatches."""
        import dataclasses

        from flowsentryx_tpu.core import schema

        cfg = dataclasses.replace(
            CFG, table=TableConfig(capacity=1 << 10),
            batch=BatchConfig(max_batch=128))
        spec = get_model(cfg.model.name)
        params = spec.init()
        quant = schema.wire_quant_for(params)
        single = fused.make_jitted_compact_step(
            cfg, spec.classify_batch, donate=False, **quant)
        mega = fused.make_jitted_compact_megastep(
            cfg, spec.classify_batch, n_chunks=4, donate=False, **quant)

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

        t1, s1 = make_table(1 << 10), make_stats()
        verdicts = []
        for r in raws:
            t1, s1, o = single(t1, s1, params, r)
            verdicts.append(np.asarray(o.verdict))
        t2, s2, outs = mega(make_table(1 << 10), make_stats(), params,
                            stacked)
        np.testing.assert_array_equal(np.asarray(t2.key), np.asarray(t1.key))
        np.testing.assert_array_equal(np.asarray(t2.state),
                                      np.asarray(t1.state))
        for a, b in zip(s2, s1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(outs.verdict), np.stack(verdicts))

    def test_ml_record_gate_drops_only_malicious_records(self):
        """One borderline record must not drop its flow's whole batch:
        the ML_RECORD_GATE resolves per record, so a mature flow with
        1 hot + 5 cold records in a batch loses exactly the hot one
        (and is not blacklisted — 1 vote < vote_m)."""
        step, table, stats, params = make_env()
        # mature the flow benignly first
        table, stats, _ = step(table, stats, params,
                               build_batch([(3501, 5, 100, 0.1, ML_COLD)]))
        mixed = build_batch([(3501, 1, 100, 0.3, ML_HOT),
                             (3501, 5, 100, 0.3001, ML_COLD)])
        table, stats, out = step(table, stats, params, mixed)
        v = np.asarray(out.verdict)[:6]
        assert (v == [int(Verdict.DROP_ML)] + [int(Verdict.PASS)] * 5).all()
        keys = np.asarray(out.block_key)
        assert 3501 not in keys[keys != 0xFFFFFFFF]

    def test_ml_legacy_knob_restores_immediate_block(self):
        """vote_k=0, vote_m=1 must reproduce the pre-vote semantics."""
        import dataclasses

        cfg = dataclasses.replace(
            CFG, model=dataclasses.replace(CFG.model, vote_k=0, vote_m=1))
        step, table, stats, params = make_env(cfg)
        batch = build_batch([(3301, 4, 100, 0.1, ML_HOT)])
        table, stats, out = step(table, stats, params, batch)
        assert (np.asarray(out.verdict)[:4] == int(Verdict.DROP_ML)).all()
        assert stat_value(stats.dropped_ml) == 4

    def test_state_persists_across_batches(self):
        # 60 pkts then 60 pkts in the same window must exceed pps=100
        step, table, stats, params = make_env()
        b1 = build_batch([(4001, 60, 100, 0.1, ML_COLD)])
        table, stats, o1 = step(table, stats, params, b1)
        assert (np.asarray(o1.verdict)[:60] == int(Verdict.PASS)).all()
        b2 = build_batch([(4001, 60, 100, 0.5, ML_COLD)])
        table, stats, o2 = step(table, stats, params, b2)
        assert (np.asarray(o2.verdict)[:60] == int(Verdict.DROP_RATE)).all()

    def test_empty_batch_noop(self):
        # A fully-masked batch is a TRUE no-op: batches stays 0 too, so
        # Engine.warm()'s compile trigger leaves every counter
        # untouched and `fsx serve --mega` reports batch counts that
        # match its own dispatch count (update_stats_from_counts gates
        # the bump on n_valid > 0).
        step, table, stats, params = make_env()
        empty = build_batch([])
        t2, s2, out = step(table, stats, params, empty)
        assert stat_value(s2.allowed) == 0 and s2.dropped == 0
        assert stat_value(s2.batches) == 0
        np.testing.assert_array_equal(np.asarray(t2.key), np.asarray(table.key))

    def test_interleaved_flows_independent(self):
        step, table, stats, params = make_env()
        entries = [(5000 + i, 2, 100, 0.1, ML_COLD) for i in range(20)]
        entries.append((6666, 120, 100, 0.1, ML_COLD))  # flood
        batch = build_batch(entries)
        table, stats, out = step(table, stats, params, batch)
        v = np.asarray(out.verdict)
        key = np.asarray(batch.key)
        assert (v[key == 6666] == int(Verdict.DROP_RATE)).all()
        assert (v[(key != 6666) & np.asarray(batch.valid)] == int(Verdict.PASS)).all()

    def test_ml_verdict_survives_full_table(self):
        # Attack: fill the table so new flows can't get slots, then send
        # malicious traffic.  ML detection needs no table state and must
        # still drop (regression: over_ml was gated on asg.tracked) —
        # via the batch-local vote (> vote_k records, >= vote_m of them
        # malicious, in one batch), since an untracked flow carries no
        # vote history.
        cfg = FsxConfig(table=TableConfig(capacity=2, probes=2, stale_s=1e9))
        step, table, stats, params = make_env(cfg)
        table = table._replace(
            key=jnp.array([111, 222], jnp.uint32),
        ).with_columns(
            last_seen=jnp.full((2,), 1e9, jnp.float32),  # never stale
        )
        batch = build_batch([(999, 8, 100, 0.1, ML_HOT)])
        table, stats, out = step(table, stats, params, batch)
        assert (np.asarray(out.verdict)[:8] == int(Verdict.DROP_ML)).all()
        # and the kernel writeback still carries the key
        assert 999 in np.asarray(out.block_key).tolist()
        # an untracked trickle (<= vote_k records) that scores malicious
        # gets its RECORDS dropped — fail-closed per record, so a full
        # table can't shield a slow attack — but is NOT blacklisted
        # (blocking on unvoted evidence is what the round-4 serve run did)
        b2 = build_batch([(998, 2, 100, 0.2, ML_HOT)])
        table, stats, out2 = step(table, stats, params, b2)
        assert (np.asarray(out2.verdict)[:2] == int(Verdict.DROP_ML)).all()
        assert 998 not in np.asarray(out2.block_key).tolist()
        # and an untracked BENIGN-scoring trickle passes untouched
        b3 = build_batch([(997, 2, 100, 0.3, ML_COLD)])
        table, stats, out3 = step(table, stats, params, b3)
        assert (np.asarray(out3.verdict)[:2] == int(Verdict.PASS)).all()

    def test_spoofed_zero_saddr_tracked(self):
        # saddr 0.0.0.0 must not collide with the empty-slot sentinel
        step, table, stats, params = make_env()
        flood = build_batch([(0, 150, 100, 0.1, ML_COLD)])
        table, stats, out = step(table, stats, params, flood)
        assert (np.asarray(out.verdict)[:150] == int(Verdict.DROP_RATE)).all()
        assert 0 not in np.asarray(out.block_key).tolist()  # never emit key 0

    def test_token_bucket_config_end_to_end(self):
        cfg = FsxConfig(
            limiter=LimiterConfig(kind=LimiterKind.TOKEN_BUCKET,
                                  bucket_rate_pps=10.0, bucket_burst=20.0),
            table=TableConfig(capacity=1 << 12),
        )
        step, table, stats, params = make_env(cfg)
        batch = build_batch([(7001, 50, 100, 0.5, ML_COLD)])
        table, stats, out = step(table, stats, params, batch)
        assert (np.asarray(out.verdict)[:50] == int(Verdict.DROP_RATE)).all()


class TestCompactWire:
    """The 16 B/record host→device wire format (schema.encode_compact):
    verdict/score parity with the 48 B path and field fidelity."""

    def _records(self, rng, n=512, feat_hi=1 << 28):
        from flowsentryx_tpu.core import schema

        buf = np.zeros(n, dtype=schema.FLOW_RECORD_DTYPE)
        buf["saddr"] = rng.integers(1, 1 << 12, n).astype(np.uint32)
        buf["pkt_len"] = rng.integers(64, 9000, n)
        buf["ts_ns"] = 5_000_000_000 + np.sort(
            rng.integers(0, 60_000, n)
        ).astype(np.uint64) * 1000
        buf["flags"] = rng.integers(0, 32, n)
        buf["feat"] = np.where(
            rng.random((n, 8)) < 0.5,
            rng.integers(0, 4096, (n, 8)),
            rng.integers(0, feat_hi, (n, 8)),
        ).astype(np.uint32)
        return buf

    def test_model_mode_bit_exact_verdicts(self, rng):
        from flowsentryx_tpu.core import schema

        buf = self._records(rng)
        n = len(buf)
        spec = get_model(CFG.model.name)
        params = spec.init()
        qa = schema.model_quant_args(params)
        t0 = 4_999_000_000
        raw = schema.encode_raw(buf, n, t0)
        comp = schema.encode_compact(buf, n, t0, **qa)

        # emit_score=True: the [B] f32 score output is opt-in now (the
        # serving loop never fetches it); this parity test compares it
        sr = jax.jit(fused.make_raw_step(CFG, spec.classify_batch,
                                         emit_score=True))
        sc = jax.jit(fused.make_compact_step(CFG, spec.classify_batch,
                                             emit_score=True, **qa))
        tb, st = make_table(CFG.table.capacity), make_stats()
        _, _, o_r = sr(tb, st, params, raw)
        _, _, o_c = sc(tb, st, params, comp)
        # "model" wire quantization == the classifier's own input
        # observer, so scores must be IDENTICAL, not merely close
        np.testing.assert_array_equal(
            np.asarray(o_r.score), np.asarray(o_c.score)
        )
        np.testing.assert_array_equal(
            np.asarray(o_r.verdict), np.asarray(o_c.verdict)
        )

    def test_field_fidelity(self, rng):
        from flowsentryx_tpu.core import schema

        buf = self._records(rng)
        n = len(buf)
        t0 = 4_999_000_000
        full = schema.decode_records(buf, n, t0)
        comp = schema.encode_compact(buf, n, t0, feat_mode="minifloat")
        dec = jax.jit(
            lambda r: schema.decode_compact(r, feat_mode="minifloat")
        )(comp)
        assert (np.asarray(dec.key)[:n] == buf["saddr"]).all()
        # pkt_len: 8-byte units, round-to-nearest
        assert np.abs(np.asarray(dec.pkt_len)[:n] - buf["pkt_len"]).max() <= 4
        # ts: µs wire resolution + f32 recombination ≪ 1 s windows
        assert np.abs(
            np.asarray(dec.ts)[:n] - np.asarray(full.ts)[:n]
        ).max() < 5e-5
        # flags round-trip
        assert (
            np.asarray(schema.compact_flags(comp))[:n] == buf["flags"]
        ).all()
        assert np.asarray(dec.valid).sum() == n

    def test_minifloat_relative_error_bound(self):
        from flowsentryx_tpu.core import schema

        f = np.concatenate([
            np.arange(0, 1 << 16, dtype=np.uint32),
            np.random.default_rng(3).integers(
                0, 0xFFFFFFFF, 200_000
            ).astype(np.uint32),
            np.array([0xFFFFFFFF, 0, 1, 7, 8, 15, 16], np.uint32),
        ])
        q = schema.quantize_feat_minifloat(f)
        assert q.max() <= 255
        qf = q.astype(np.int64)
        val = np.where(qf < 8, qf, (8 + qf % 8) * (2.0 ** (qf // 8 - 1)))
        rel = np.abs(val - f) / np.maximum(f, 1)
        assert rel.max() <= 0.0625 + 1e-9

    def test_log1p_artifact_roundtrip(self, rng):
        from flowsentryx_tpu.core import schema
        from flowsentryx_tpu.models import logreg

        params = logreg.make_params(
            w_int8=[10, -80, 106, -9, -85, -52, 106, -45],
            bias=0.1, w_scale=0.01, in_scale=22.18 / 255.0,
            out_scale=0.05, out_zp=90, log1p=True,
        )
        qa = schema.model_quant_args(params)
        assert qa["log1p"] is True
        buf = self._records(rng)
        n = len(buf)
        raw = schema.encode_raw(buf, n, 4_999_000_000)
        comp = schema.encode_compact(buf, n, 4_999_000_000, **qa)
        dec_full = jax.jit(lambda r: schema.decode_raw(r))(raw)
        dec_comp = jax.jit(lambda r: schema.decode_compact(r, **qa))(comp)
        s_full = np.asarray(
            logreg.classify_batch(params, dec_full.feat)
        )[:n]
        s_comp = np.asarray(
            logreg.classify_batch(params, dec_comp.feat)
        )[:n]
        # log-domain wire step == the model's own observer step; scores
        # agree except for ±1-ulp rounding at quant boundaries
        assert (s_full == s_comp).mean() > 0.99
        assert np.abs(s_full - s_comp).max() <= 1.5 / 256.0


def test_token_bucket_fresh_flow_gets_full_burst():
    """A new flow at stream start (engine-anchored clock, now ≈ 0) must
    begin with a FULL bucket — the kernel twin's implicit semantics
    (boot-relative clock ⇒ clamped refill fills fresh entries).  Caught
    live: benign single-packet sources were rate-dropped at t≈0."""
    cfg = FsxConfig(
        limiter=LimiterConfig(kind=LimiterKind.TOKEN_BUCKET,
                              bucket_rate_pps=10.0, bucket_burst=20.0),
        table=TableConfig(capacity=1 << 12),
    )
    step, table, stats, params = make_env(cfg)
    # 5 packets at t=0.0005s from a brand-new source: within burst → PASS
    batch = build_batch([(4242, 5, 100, 0.0005, ML_COLD)])
    table, stats, out = step(table, stats, params, batch)
    assert (np.asarray(out.verdict)[:5] == int(Verdict.PASS)).all()


class TestBatchesWrapEviction:
    """The rolling eviction sweep vs a wrapping ``batches`` counter
    (ISSUE 12): the window offset arithmetic reads ``stats.batches[0]``
    — the (lo, hi) pair's LO word, which wraps uint32 by design — so
    the sweep must stay in bounds and keep full-cycle coverage when it
    does."""

    CAP, EVERY, TTL = 256, 8, 5.0

    def _tcfg(self):
        return TableConfig(capacity=self.CAP, evict_ttl_s=self.TTL,
                           evict_every=self.EVERY)

    def _idle_table(self):
        from flowsentryx_tpu.core import schema

        table = schema.make_table(self.CAP)
        return table._replace(
            key=jnp.arange(1, self.CAP + 1, dtype=jnp.uint32))

    def _stats_at(self, batches_lo: int):
        from flowsentryx_tpu.core import schema

        stats = schema.make_stats()
        return stats._replace(batches=jnp.asarray(
            [batches_lo & 0xFFFFFFFF, batches_lo >> 32], jnp.uint32))

    def _sweep(self, batches_lo: int):
        table, stats = self._idle_table(), self._stats_at(batches_lo)
        new_table, n = fused.evict_idle_epoch(
            self._tcfg(), table, stats, jnp.float32(100.0))
        freed = np.flatnonzero(np.asarray(new_table.key) == 0)
        return freed, int(n)

    def test_window_in_bounds_across_the_wrap(self):
        chunk = fused.evict_window(self.CAP, self.EVERY)
        for b in [0, 1, self.EVERY - 1, (1 << 32) - 2, (1 << 32) - 1,
                  (1 << 32), (1 << 32) + 3, 123456789]:
            freed, n = self._sweep(b)
            assert n == chunk, b                      # whole window swept
            assert len(freed) == chunk, b
            assert freed.min() >= 0 and freed.max() < self.CAP, b
            # one contiguous window, never out-of-bounds parking
            assert freed.max() - freed.min() == chunk - 1, b

    def test_full_cycle_coverage_holds_across_the_wrap(self):
        # evict_every consecutive batches STRADDLING the uint32 wrap
        # must still visit every row exactly one full cycle's worth
        # (power-of-two evict_every: 2^32 % evict_every == 0, so the
        # residue sequence continues seamlessly through the wrap —
        # the property this test pins against a future non-pow2 epoch)
        assert self.EVERY & (self.EVERY - 1) == 0
        covered = set()
        start = (1 << 32) - self.EVERY // 2  # half before, half after
        for b in range(start, start + self.EVERY):
            freed, _ = self._sweep(b)
            covered.update(int(i) for i in freed)
        assert covered == set(range(self.CAP))

    def test_blacklisted_rows_survive_the_wrap_epoch(self):
        from flowsentryx_tpu.core import schema

        table = self._idle_table()
        # row guaranteed inside the wrap-batch window: sweep at
        # batches = 2^32 - 1 covers offset ((2^32-1) % 8) * 32
        off = (((1 << 32) - 1) % self.EVERY) * \
            fused.evict_window(self.CAP, self.EVERY)
        table = table._replace(state=table.state.at[
            off, schema.TableCol.BLOCKED_UNTIL].set(1e9))
        stats = self._stats_at((1 << 32) - 1)
        new_table, n = fused.evict_idle_epoch(
            self._tcfg(), table, stats, jnp.float32(100.0))
        assert int(np.asarray(new_table.key)[off]) == off + 1  # kept
        assert int(n) == fused.evict_window(self.CAP, self.EVERY) - 1


class TestSweepFormsAgree:
    """The aging sweep has two forms, and `fused.evict_idle_epoch`
    lets `jax.lax.platform_dependent` choose one where the program is
    lowered (ISSUE 40): the TPU's slices the window out and back in,
    the other gathers it and scatters the victims.  Both are called
    directly here, on XLA:CPU, and held to a numpy sweep of the same
    window bit for bit."""

    CAP, TTL, NOW = 256, 5.0, 60.0

    def _table(self, seed=7):
        """Every kind of row in every window: empty, fresh, idle, and
        idle with a block that has run out; every column non-zero."""
        from flowsentryx_tpu.core import schema

        rng = np.random.default_rng(seed)
        state = rng.uniform(1.0, 9.0, (self.CAP, schema.NUM_TABLE_COLS)
                            ).astype(np.float32)
        state[:, schema.TableCol.LAST_SEEN] = rng.uniform(
            self.NOW - 4 * self.TTL, self.NOW, self.CAP)
        state[:, schema.TableCol.BLOCKED_UNTIL] = rng.uniform(
            0.0, self.NOW - 1.0, self.CAP)
        key = rng.integers(1, 1 << 32, self.CAP, dtype=np.uint32)
        key[rng.random(self.CAP) < 0.25] = 0
        return key, state

    @staticmethod
    def _numpy_sweep(key, state, window, now, ttl):
        from flowsentryx_tpu.core.schema import TableCol

        idle = (np.float32(now) - state[window, TableCol.LAST_SEEN]
                > np.float32(ttl))
        live = state[window, TableCol.BLOCKED_UNTIL] > np.float32(now)
        victim = (key[window] != 0) & idle & ~live
        key, state = key.copy(), state.copy()
        key[window] = np.where(victim, 0, key[window])
        state[window] = np.where(victim[:, None], np.float32(0.0),
                                 state[window])
        return key, state, victim

    @pytest.mark.parametrize("case,every,batches,now", [
        ("middle", 8, 3, NOW),
        # 256 rows in 7 windows of 37: the last would start at 222
        ("ragged-last", 7, 6, NOW),
        ("live-block", 8, 5, NOW),
        ("all-empty", 8, 2, NOW),
        ("now-zero", 8, 3, 0.0),
    ], ids=lambda v: v if isinstance(v, str) else None)
    def test_both_forms_equal_the_numpy_sweep(self, case, every, batches,
                                              now):
        from flowsentryx_tpu.core import schema

        tcfg = TableConfig(capacity=self.CAP, evict_ttl_s=self.TTL,
                           evict_every=every)
        chunk = fused.evict_window(self.CAP, every)
        unclamped = (batches % every) * chunk
        base = min(unclamped, self.CAP - chunk)
        blocked = base + np.arange(3, chunk, 5)
        key, state = self._table()
        if case == "live-block":
            # idle for four TTLs, blocked for another minute: stays
            key[blocked] |= 1
            state[blocked, schema.TableCol.LAST_SEEN] = now - 4 * self.TTL
            state[blocked, schema.TableCol.BLOCKED_UNTIL] = now + 60.0
        if case == "all-empty":
            key[base:base + chunk] = 0
        want_key, want_state, victim = self._numpy_sweep(
            key, state, slice(base, base + chunk), now, self.TTL)
        # each case is what its name says
        assert (base < unclamped) == (case == "ragged-last")
        if case in ("all-empty", "now-zero"):
            assert not victim.any()
        else:
            assert 0 < victim.sum() < chunk
        if case == "live-block":
            assert not victim[blocked - base].any()

        table = schema.IpTableState(key=jnp.asarray(key),
                                    state=jnp.asarray(state))
        stats = make_stats()._replace(
            batches=jnp.asarray([batches, 0], jnp.uint32))
        for form in (fused._sweep_by_slice, fused._sweep_by_scatter):
            got, n = jax.jit(
                lambda t, s, clock, form=form: form(
                    tcfg, t, fused._sweep_offset(tcfg, self.CAP, s), clock)
            )(table, stats, jnp.float32(now))
            assert int(n) == victim.sum(), form.__name__
            np.testing.assert_array_equal(np.asarray(got.key), want_key,
                                          err_msg=form.__name__)
            # bit for bit: -0.0 and 0.0 are told apart
            np.testing.assert_array_equal(
                np.asarray(got.state).view(np.uint32),
                want_state.view(np.uint32), err_msg=form.__name__)


class TestStepNeverTakesATableColumn:
    """The step may touch the table only by gather and scatter (ISSUE
    30).  `table.last_seen` is `state[:, LAST_SEEN]`: handed to the
    probe it made every step copy a whole column out of the state
    matrix, 256 MB at 2^26 rows, 73 % of the step on the chip.  Read
    from the jaxpr, so it holds whatever the backend would fuse away.
    The one exception is the aging sweep's window where the program is
    lowered for a TPU (ISSUE 40): a `dynamic_slice` and a
    `dynamic_update_slice` of the table inside the `tpu` branch of a
    `jax.lax.platform_dependent`, and nowhere else."""

    #: unlike every other dimension of the traced step (batch 256,
    #: 8 probes, 12 columns, a 256-row eviction window)
    CAP = 1 << 12

    @classmethod
    def _table_sized_primitives(cls, fn, *args):
        """Names of the primitives with a table-sized operand, and of
        those with a table-sized result, anywhere in the traced graph
        (sub-jaxprs walked)."""
        eqns = [eqn for _, eqn in iter_eqns(jax.make_jaxpr(fn)(*args))]
        return ({e.primitive.name for e in eqns if cls._sized(e.invars)},
                {e.primitive.name for e in eqns if cls._sized(e.outvars)})

    @classmethod
    def _sized(cls, variables):
        return any(cls.CAP in getattr(v.aval, "shape", ())
                   for v in variables)

    @classmethod
    def _table_sized_primitives_by_platform(cls, fn, *args):
        """The same two sets, apart for the equations inside a `tpu`
        branch of a `platform_dependent` and for all the others (what
        every backend lowers, and the `default` branch); and the
        platforms of every conditional that returns something
        table-sized."""
        used = {True: (set(), set()), False: (set(), set())}
        writers = []
        for _, e, platforms in iter_platform_eqns(jax.make_jaxpr(fn)(*args)):
            read_by, written_by = used[platforms == ("tpu",)]
            if cls._sized(e.invars):
                read_by.add(e.primitive.name)
            if cls._sized(e.outvars):
                written_by.add(e.primitive.name)
                if e.primitive.name == "cond":
                    writers.append(platform_branches(e))
        return used[False], used[True], writers

    #: the probe's conditional reads the table (its branches gather
    #: from it) and returns `[R, P]`: only a scatter returns a table
    READ_BY = {"gather", "scatter", "cond"}
    WRITTEN_BY = {"scatter"}
    #: under aging, one more writer: the switch on `platform_index`
    #: that hands the table to the backend's form of the sweep.  It is
    #: resolved at lowering, so no program holds a conditional for it
    #: (`audit/graph.py::check_inplace`).  The slices stay in its
    #: `tpu` branch.
    WRITTEN_BY_AGING = WRITTEN_BY | {"cond"}
    TPU_BRANCH = ({"dynamic_slice", "dynamic_update_slice"},
                  {"dynamic_update_slice"})
    SWEEP_SWITCH = (("tpu",), ("default",))

    def _cfg(self, kind, evict_ttl_s=0.0):
        return FsxConfig(
            limiter=LimiterConfig(kind=kind),
            table=TableConfig(capacity=self.CAP, probes=8,
                              evict_ttl_s=evict_ttl_s, evict_every=16),
            batch=BatchConfig(max_batch=256))

    @pytest.mark.parametrize("evict_ttl_s", [0.0, 5.0],
                             ids=["no-evict", "evict"])
    @pytest.mark.parametrize("kind", list(LimiterKind),
                             ids=lambda k: k.value)
    def test_make_step(self, kind, evict_ttl_s):
        cfg = self._cfg(kind, evict_ttl_s)
        spec = get_model(cfg.model.name)
        args = (fused.make_step(cfg, spec.classify_batch),
                make_table(self.CAP), make_stats(), spec.init(),
                build_batch([(1001, 5, 100, 0.1, ML_COLD)]))
        if not evict_ttl_s:
            assert (self._table_sized_primitives(*args)
                    == (self.READ_BY, self.WRITTEN_BY))
            return
        anywhere, tpu_only, writers = (
            self._table_sized_primitives_by_platform(*args))
        assert anywhere == (self.READ_BY, self.WRITTEN_BY_AGING)
        assert tpu_only == self.TPU_BRANCH
        # key column and state matrix leave through the one switch; a
        # `lax.cond` on a traced predicate would read None here
        assert writers == [self.SWEEP_SWITCH]

    @pytest.mark.parametrize("kind", list(LimiterKind),
                             ids=lambda k: k.value)
    def test_flow_step_of_the_sharded_path(self, kind):
        from flowsentryx_tpu.ops import agg

        cfg = self._cfg(kind)
        b = build_batch([(1001, 5, 100, 0.1, ML_COLD)])

        def two_stage(table):
            fa = agg.aggregate(b.key, b.pkt_len, b.ts, b.valid)
            ones = jnp.ones_like(fa.rep_valid)
            return fused.flow_step(cfg, table, fa, ones,
                                   jnp.zeros_like(fa.rep_pkts),
                                   jnp.float32(0.1))

        assert (self._table_sized_primitives(two_stage, make_table(self.CAP))
                == (self.READ_BY, self.WRITTEN_BY))

    def test_the_guard_sees_a_column_view(self):
        """What the guard is for: the probe's old read."""
        read_by, _ = self._table_sized_primitives(
            lambda table, slots: table.last_seen[slots],
            make_table(self.CAP), jnp.zeros((256, 8), jnp.int32))
        assert read_by - self.READ_BY  # slice/squeeze of the whole column


class TestStaleReads:
    """`GlobalStats.stale_reads` (ISSUE 38): non-empty batches whose
    probe read `last_seen`, i.e. held a valid key with neither a match
    nor an empty slot among its probes."""

    TINY = TableConfig(capacity=16, probes=2, stale_s=1e6, salt=0xBEEF)

    @staticmethod
    def _full(capacity):
        """A table whose every row holds a live foreign key."""
        t = make_table(capacity)
        return t._replace(
            key=jnp.arange(capacity, dtype=jnp.uint32) + (1 << 30))

    def test_to_dict_names_it(self):
        assert make_stats().to_dict()["stale_reads"] == 0

    @pytest.mark.parametrize("table,entries,reads", [
        ("empty", [(1001, 5, 100, 0.1, ML_COLD)], 0),
        ("full", [(1001, 5, 100, 0.1, ML_COLD)], 1),
        # the one key that would need it sits in rows the mask hides
        ("full", [], 0),
    ], ids=["room", "full-table", "full-table-invalid-rows-only"])
    def test_one_step_counts_a_read(self, table, entries, reads):
        cfg = FsxConfig(table=self.TINY,
                        batch=BatchConfig(max_batch=256, verdict_k=64))
        step, t, stats, params = make_env(cfg)
        if table == "full":
            t = self._full(16)
        batch = build_batch(entries)
        if not entries:
            batch = batch._replace(key=jnp.full((256,), 4242, jnp.uint32))
        _, stats, _ = step(t, stats, params, batch)
        assert stats.to_dict()["stale_reads"] == reads
        assert stats.to_dict()["batches"] == (1 if entries else 0)

    def test_the_megastep_counts_batches_not_groups(self):
        """Eight chunks against a full table: four hold a new key,
        two hold nothing, two hold only a resident key."""
        from flowsentryx_tpu.core import schema

        b = 256
        cfg = FsxConfig(table=self.TINY,
                        batch=BatchConfig(max_batch=b, verdict_k=64))
        spec = get_model(cfg.model.name)
        params = spec.init()
        quant = schema.wire_quant_for(params)
        mega = fused.make_jitted_compact_megastep(
            cfg, spec.classify_batch, n_chunks=8, donate=False, **quant)
        from flowsentryx_tpu.ops import hashtable

        # a resident sits on the first probe of its own ring
        resident = 4242
        home = hashtable.probe_slots(
            make_table(16), jnp.array([resident], jnp.uint32),
            jnp.array([True]), jnp.float32(0.0), self.TINY).slot[0]
        table = self._full(16)
        table = table._replace(key=table.key.at[home].set(resident))
        raws = []
        for i, keys in enumerate([[7], [], [resident], [8, 9], [],
                                  [resident], [10], [11]]):
            buf = np.zeros(len(keys), dtype=schema.FLOW_RECORD_DTYPE)
            buf["saddr"] = np.asarray(keys, np.uint32)
            buf["pkt_len"] = 100
            buf["ts_ns"] = (i + 1) * 1_000_000
            raws.append(schema.encode_compact(buf, b, t0_ns=0, **quant))
        _, stats, _ = mega(table, make_stats(), params,
                           jnp.asarray(np.stack(raws)))
        d = stats.to_dict()
        assert (d["stale_reads"], d["batches"]) == (4, 6)

    def test_fsx_serve_report_carries_it(self):
        from flowsentryx_tpu.engine import ArraySource, CollectSink, Engine
        from tests.test_spans import flood, small_cfg

        rep = Engine(small_cfg(), ArraySource(flood(256 * 4)), CollectSink(),
                     sink_thread=False).run()
        assert rep.stats["stale_reads"] == 0 < rep.stats["batches"]


class TestUntracked:
    """`GlobalStats.untracked` (ISSUE 39): flows of a batch that ended
    it with no row — none of their probes usable, or their slot taken
    by another new flow of the same batch."""

    CAP = 1 << 12
    #: aging on (the counter is compiled in with the sweep), with a
    #: TTL nothing in these batches reaches
    TCFG = TableConfig(capacity=CAP, probes=8, stale_s=1e6, salt=0xBEEF,
                       evict_ttl_s=1e6, evict_every=64)

    def _cfg(self, tcfg=TCFG):
        return FsxConfig(table=tcfg,
                         batch=BatchConfig(max_batch=1024, verdict_k=64))

    @staticmethod
    def _filled(capacity, share, seed=5):
        """A table of which `share` of the rows hold a live foreign key."""
        rng = np.random.default_rng(seed)
        key = np.zeros(capacity, np.uint32)
        rows = rng.choice(capacity, int(share * capacity), replace=False)
        key[rows] = (1 << 30) + np.arange(len(rows), dtype=np.uint32)
        return make_table(capacity)._replace(key=jnp.asarray(key))

    def test_to_dict_names_it(self):
        assert make_stats().to_dict()["untracked"] == 0

    @pytest.mark.parametrize("share,flows,lo,hi", [
        (0.0, 24, 0, 0), (0.6, 600, 6, 150), (1.0, 600, 600, 600),
    ], ids=["empty", "sixty-per-cent", "full"])
    def test_it_is_the_flows_less_the_rows_they_took(self, share, flows,
                                                     lo, hi):
        """New one-record flows: on an empty table every one of a few
        takes a row, at 60 % about 0.6^8 of them find no slot (and some
        lose theirs to another new flow), on a full one none finds any."""
        step, _, stats, params = make_env(self._cfg())
        t = self._filled(self.CAP, share)
        batch = build_batch([(5000 + i, 1, 100, 0.1, ML_COLD)
                             for i in range(flows)], batch_size=1024)
        rows_before = int(np.count_nonzero(np.asarray(t.key)))
        t, stats, _ = step(t, stats, params, batch)
        took = int(np.count_nonzero(np.asarray(t.key))) - rows_before
        d = stats.to_dict()
        assert d["untracked"] == flows - took
        assert lo <= d["untracked"] <= hi
        # a flow with no row still gets its verdicts
        assert d["allowed"] == flows

    def test_it_adds_up_over_batches(self):
        """The same 600 flows twice: the second time those with a row
        find it, those that lost theirs to another flow take the next
        empty one, and those whose probes are all taken go without
        again."""
        step, _, stats, params = make_env(self._cfg())
        t = self._filled(self.CAP, 0.6)
        rows_before = int(np.count_nonzero(np.asarray(t.key)))
        batch = build_batch([(5000 + i, 1, 100, 0.1, ML_COLD)
                             for i in range(600)], batch_size=1024)
        t, stats, _ = step(t, stats, params, batch)
        first = stats.to_dict()["untracked"]
        t, stats, _ = step(t, stats, params, batch)
        took = int(np.count_nonzero(np.asarray(t.key))) - rows_before
        second = stats.to_dict()["untracked"] - first
        assert 0 < second == 600 - took < first

    def test_a_table_with_no_aging_stages_no_count(self):
        """Like `evicted`: with `evict_ttl_s` 0 the step is the one it
        was before the counter, which stays a passthrough (the
        benchmark's `c5-l34-1m.saturate` was found to be tipped over by
        the two small operations it adds: PERF.md section 6, PR 39)."""
        import dataclasses

        cfg = self._cfg(dataclasses.replace(self.TCFG, evict_ttl_s=0.0))
        step, _, stats, params = make_env(cfg)
        batch = build_batch([(5000 + i, 1, 100, 0.1, ML_COLD)
                             for i in range(600)], batch_size=1024)
        t, stats, _ = step(self._filled(self.CAP, 1.0), stats, params, batch)
        assert stats.to_dict()["untracked"] == 0
        assert stats.to_dict()["allowed"] == 600
        text = jax.jit(fused.make_step(cfg, get_model(
            cfg.model.name).classify_batch)).lower(
                make_table(self.CAP), make_stats(), params, batch).as_text()
        aged = jax.jit(fused.make_step(self._cfg(), get_model(
            cfg.model.name).classify_batch)).lower(
                make_table(self.CAP), make_stats(), params, batch).as_text()
        assert "fsx.evict" not in text and len(text) < len(aged)

    def test_an_empty_batch_counts_nothing(self):
        step, _, stats, params = make_env(self._cfg())
        _, stats, _ = step(self._filled(self.CAP, 1.0), stats, params,
                           build_batch([], batch_size=1024))
        assert stats.to_dict()["untracked"] == 0

    def test_fsx_serve_report_carries_it(self):
        from flowsentryx_tpu.engine import ArraySource, CollectSink, Engine
        from tests.test_spans import flood, small_cfg

        rep = Engine(small_cfg(), ArraySource(flood(256 * 4)), CollectSink(),
                     sink_thread=False).run()
        assert rep.stats["untracked"] == 0 < rep.stats["batches"]


class TestFlowsAtRunTails:
    """ISSUE 36: the fused step reduces each run where the sort left it
    and keeps the flows at their runs' last positions.  Held against
    the two-stage composition (:func:`two_stage_step`) on the batches
    that lean on each property of the new form, and by the jaxpr."""

    LIM = LimiterConfig(pps_threshold=8.0, bps_threshold=1e9, block_s=10.0)
    ROOMY = TableConfig(capacity=1 << 12, probes=8, stale_s=1e6,
                        salt=0xA5A5)
    #: two probes a key in sixteen rows: every insert is contested
    TINY = TableConfig(capacity=16, probes=2, stale_s=1e6, salt=0xBEEF)
    #: the same, with rows that go stale between two batches
    TINY_STALE = TableConfig(capacity=16, probes=2, stale_s=1.0,
                             salt=0xBEEF)

    SCENARIOS = ["one_key", "all_distinct", "mixed_runs",
                 "invalid_interleaved", "key_zero", "new_keys_contest",
                 "found_and_new_share", "full_table", "small_verdict_k"]

    _steps: dict = {}

    @classmethod
    def _env(cls, tcfg, verdict_k, b):
        """(cfg, jitted fused step, jitted two-stage step, params), one
        compile a (table, verdict_k, batch) shape."""
        key = (tcfg, verdict_k, b)
        if key not in cls._steps:
            cfg = FsxConfig(limiter=cls.LIM, table=tcfg, model=CFG.model,
                            batch=BatchConfig(max_batch=b,
                                              verdict_k=verdict_k))
            spec = get_model(cfg.model.name)
            params = spec.init()
            cls._steps[key] = (
                cfg,
                fused.make_jitted_step(cfg, spec.classify_batch,
                                       donate=False),
                jax.jit(two_stage_step(cfg, spec, params)), params)
        return cls._steps[key]

    @staticmethod
    def _batch(rng, keys, t0, valid=None):
        keys = np.asarray(keys, np.uint32)
        b = len(keys)
        return FeatureBatch(
            key=jnp.asarray(keys),
            feat=jnp.asarray(rng.uniform(0, 3e6, (b, 8)).astype(np.float32)),
            pkt_len=jnp.asarray(
                rng.integers(64, 1500, b).astype(np.float32)),
            # NOT sorted: a run's newest record may be any of its records
            ts=jnp.asarray(rng.uniform(t0, t0 + 0.5, b).astype(np.float32)),
            valid=jnp.asarray(np.ones(b, bool) if valid is None else valid))

    @staticmethod
    def _probe(tcfg, table, keys, now):
        from flowsentryx_tpu.ops import hashtable

        keys = jnp.asarray(np.asarray(keys, np.uint32))
        pr = hashtable.probe_slots(table, keys, jnp.ones(keys.shape, bool),
                                   jnp.float32(now), tcfg)
        return (np.asarray(pr.slot), np.asarray(pr.found),
                np.asarray(pr.usable))

    def _scenario(self, name, b, rng):
        """(table config, verdict_k, [(keys, valid, t0), ...])."""
        pad = lambda k: np.resize(np.asarray(k, np.uint32), b)  # noqa: E731
        if name == "one_key":
            return self.ROOMY, 64, [(np.full(b, 77), None, 0.0),
                                    (np.full(b, 77), None, 0.6)]
        if name == "all_distinct":
            k = rng.permutation(b) + 1000
            return self.ROOMY, 64, [(k, None, 0.0), (k[::-1], None, 0.6)]
        if name == "mixed_runs":
            k = np.repeat(np.arange(5000, 5000 + b),
                          rng.integers(1, 40, b))[:b]
            return self.ROOMY, 64, [(rng.permutation(k), None, 0.0),
                                    (rng.permutation(k), None, 0.6)]
        if name == "invalid_interleaved":
            k = rng.integers(1, b // 4, b)
            return self.ROOMY, 64, [(k, rng.random(b) < 0.6, 0.0),
                                    (k, rng.random(b) < 0.3, 0.6)]
        if name == "key_zero":
            # 0 is remapped to 0xFFFFFFFE and shares its flow
            k = rng.choice([0, 0xFFFFFFFE, 5, 6, 7], b)
            return self.ROOMY, 64, [(k, rng.random(b) < 0.9, 0.0),
                                    (k, None, 0.6)]
        if name == "new_keys_contest":
            # keys whose first probe is the same row of an empty table
            cand = np.arange(1, 400)
            slot, _, _ = self._probe(self.TINY, make_table(16), cand, 0.0)
            groups = [cand[slot == s] for s in range(16)]
            assert sum(len(g) >= 2 for g in groups) >= 8
            k = np.concatenate([g[:3] for g in groups])
            return self.TINY, 64, [(rng.permutation(pad(k)), None, 0.0)]
        if name == "found_and_new_share":
            # batch 1 fills the sixteen rows; 100 s on every row is
            # stale, so a row's owner (found) and a newcomer (its
            # reclaimer) pick the same row, and the owner must win
            return self.TINY_STALE, 64, [
                (pad(np.arange(1, 121)), None, 0.0),
                (rng.permutation(pad(np.arange(1, 241))), None, 100.0)]
        if name == "full_table":
            return self.TINY, 64, [
                (pad(np.arange(1, 121)), None, 0.0),
                (rng.permutation(pad(np.arange(200, 260))), None, 0.6)]
        if name == "small_verdict_k":
            # runs of 10 records pass 8 pps: about b // 10 blocks, k = 4
            k = np.repeat(np.arange(9000, 9000 + b // 10), 10)
            return self.ROOMY, 4, [(rng.permutation(pad(k)), None, 0.0)]
        raise KeyError(name)

    def _expected_order(self, cfg, table, block_key, now):
        """The fused step's flows lie in the order of its one sort:
        (slot-priority, key)."""
        keys = block_key[block_key != 0xFFFFFFFF]
        slot, found, usable = self._probe(cfg.table, table, keys, now)
        pri = np.where(usable, slot.astype(np.int64) * 2 + ~found,
                       2 * cfg.table.capacity)
        return keys[np.lexsort((keys, pri))]

    def _check_batch(self, cfg, table, out, ref, note):
        """One batch's outputs of the fused step against the two-stage
        reference's; returns the blocks in the fused step's order."""
        t1, s1 = out[0], out[1]
        t2, s2, v2, bk2, bu2 = ref
        o = out[2]
        np.testing.assert_array_equal(np.asarray(o.verdict),
                                      np.asarray(v2), note)
        for a, c in zip(s1, s2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c), note)
        np.testing.assert_array_equal(np.asarray(t1.key), np.asarray(t2.key),
                                      note)
        np.testing.assert_allclose(np.asarray(t1.state),
                                   np.asarray(t2.state), rtol=1e-6,
                                   err_msg=note)
        bk1, bu1 = np.asarray(o.block_key), np.asarray(o.block_until)
        bk2, bu2 = np.asarray(bk2), np.asarray(bu2)
        hit1, hit2 = bk1 != 0xFFFFFFFF, bk2 != 0xFFFFFFFF
        assert sorted(zip(bk1[hit1], bu1[hit1])) \
            == sorted(zip(bk2[hit2], bu2[hit2])), note
        assert (bu1[~hit1] == 0).all(), note
        np.testing.assert_array_equal(
            bk1[hit1], self._expected_order(cfg, table, bk2, float(o.now)),
            note)
        return bk1[hit1], bu1[hit1]

    @staticmethod
    def _check_wire(wire, k, keys, untils, now, note):
        wire = np.asarray(wire)
        want = np.full(k, 0xFFFFFFFF, np.uint32)
        want[:min(k, len(keys))] = keys[:k]
        np.testing.assert_array_equal(wire[:k], want, note)
        np.testing.assert_array_equal(
            wire[k:2 * k].view(np.float32)[:min(k, len(keys))],
            untils[:k], note)
        assert wire[2 * k] == len(keys), note
        assert wire[2 * k + 1] == (len(keys) > k), note
        assert wire[2 * k + 3:].view(np.float32)[0] == now, note

    @pytest.mark.parametrize("b", [256, 2048])
    @pytest.mark.parametrize("name", SCENARIOS)
    def test_step_matches_two_stage_composition(self, name, b):
        rng = np.random.default_rng([b, self.SCENARIOS.index(name)])
        tcfg, k, batches = self._scenario(name, b, rng)
        cfg, step, ref_step, params = self._env(tcfg, k, b)
        t1, s1 = make_table(tcfg.capacity), make_stats()
        t2, s2 = make_table(tcfg.capacity), make_stats()
        blocks = 0
        for i, (keys, valid, t0) in enumerate(batches):
            batch = self._batch(rng, keys, t0, valid)
            before = t1
            t1, s1, out = step(t1, s1, params, batch)
            ref = ref_step(t2, s2, batch)
            t2, s2 = ref[0], ref[1]
            bk, bu = self._check_batch(cfg, before, (t1, s1, out), ref,
                                       f"{name} batch {i}")
            self._check_wire(out.wire, k, bk, bu, float(out.now),
                             f"{name} batch {i}")
            blocks += len(bk)
        if name == "small_verdict_k":
            assert blocks > 4 * k
            assert np.asarray(out.wire)[2 * k + 1] == 1
        if name in ("one_key", "mixed_runs"):
            assert blocks > 0
        reads = (stat_value(s1.stale_reads), stat_value(s2.stale_reads))
        if name in ("found_and_new_share", "full_table"):
            # the second batch met a table with no empty row: its new
            # keys are the first for which staleness decides
            assert (np.asarray(before.key) != 0).all()
            assert reads == (1, 1) and stat_value(s1.batches) == 2
        else:
            # (at 2,048 records the 4,096-row table is half full, and a
            # key that lost its row in batch 0 may find its 8 probes
            # taken in batch 1)
            assert reads[0] == reads[1] and (b > 256 or reads[0] == 0)

    def test_the_shared_row_goes_to_its_owner(self):
        """What ``found_and_new_share`` is there for, spelled out: of a
        stale row's owner and a newcomer that would reclaim it, in one
        batch, the owner keeps the row."""
        b = 256
        cfg, step, _, params = self._env(self.TINY_STALE, 64, b)
        rng = np.random.default_rng(5)
        table, stats, _ = step(make_table(16), make_stats(), params,
                               self._batch(rng, np.resize(
                                   np.arange(1, 121, dtype=np.uint32), b),
                                   0.0))
        owners = np.asarray(table.key)
        assert (owners != 0).all()
        cand = np.arange(121, 400)
        slot, found, usable = self._probe(cfg.table, table, cand, 100.0)
        assert usable.all() and not found.any()  # every row is stale
        new = cand[:8]
        keys = np.resize(np.concatenate([owners[slot[:8]], new]), b)
        t2, _, _ = step(table, stats, params,
                        self._batch(rng, keys.astype(np.uint32), 100.0))
        np.testing.assert_array_equal(np.asarray(t2.key), owners)

    @pytest.mark.parametrize("b", [256, 2048])
    def test_megastep_over_eight_such_batches(self, b):
        """The scan of the step over a stacked group, a scenario a
        chunk: verdicts, table, stats, each chunk's fallback arrays and
        the one merged wire against eight two-stage steps."""
        from flowsentryx_tpu.core import schema

        k = 4
        cfg = FsxConfig(limiter=self.LIM, table=self.ROOMY, model=CFG.model,
                        batch=BatchConfig(max_batch=b, verdict_k=k))
        spec = get_model(cfg.model.name)
        params = spec.init()
        quant = schema.wire_quant_for(params)
        mega = fused.make_jitted_compact_megastep(
            cfg, spec.classify_batch, n_chunks=8, donate=False, **quant)
        ref_step = jax.jit(two_stage_step(cfg, spec, params))
        rng = np.random.default_rng(b)
        raws = []
        for i, name in enumerate(self.SCENARIOS[:5] + ["small_verdict_k",
                                                      "mixed_runs",
                                                      "one_key"]):
            keys, valid, _ = self._scenario(name, b, rng)[2][0]
            n = b if valid is None else int(b * 0.7)  # a part-filled chunk
            buf = np.zeros(b, dtype=schema.FLOW_RECORD_DTYPE)
            buf["saddr"] = np.asarray(keys, np.uint32)
            buf["pkt_len"] = rng.integers(64, 1500, b)
            buf["ts_ns"] = (i * b + rng.permutation(b)) * 20_000
            buf["feat"] = rng.integers(0, 1 << 22, (b, 8))
            raws.append(schema.encode_compact(buf[:n], b, t0_ns=0, **quant))
        t1, s1, outs = mega(make_table(cfg.table.capacity), make_stats(),
                            params, jnp.asarray(np.stack(raws)))
        t2, s2 = make_table(cfg.table.capacity), make_stats()
        keys, untils = [], []
        for i, raw in enumerate(raws):
            before = t2
            ref = ref_step(t2, s2, schema.decode_compact(raw, **quant))
            t2, s2 = ref[0], ref[1]
            one = jax.tree.map(lambda a: a[i], outs._replace(wire=None))
            bk, bu = self._check_batch(
                cfg, before, (t2, s2, one), ref, f"chunk {i}")
            keys.append(bk[:k])  # a chunk's own wire holds k
            untils.append(bu[:k])
        np.testing.assert_array_equal(np.asarray(t1.key), np.asarray(t2.key))
        np.testing.assert_allclose(np.asarray(t1.state),
                                   np.asarray(t2.state), rtol=1e-6)
        for a, c in zip(s1, s2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        wire = np.asarray(outs.wire)
        keys, untils = np.concatenate(keys), np.concatenate(untils)
        total = int((np.asarray(outs.block_key) != 0xFFFFFFFF).sum())
        np.testing.assert_array_equal(wire[:k], keys[:k])
        np.testing.assert_array_equal(wire[k:2 * k].view(np.float32),
                                      untils[:k])
        assert wire[2 * k] == total > k and wire[2 * k + 1] == 1

    @pytest.mark.parametrize("kind", list(LimiterKind),
                             ids=lambda k: k.value)
    def test_aggregate_holds_no_gather_and_no_scatter(self, kind):
        """Beside PR 30's guard: what the stage was (six gathers, nine
        segment scatters and one ``.at[order].set`` behind a sort that
        had already made every run contiguous) cannot come back, and
        the step pays for exactly one sort more than it did: the one
        that puts the verdicts back in the batch's order."""
        from flowsentryx_tpu.audit.graph import iter_staged_eqns

        cfg = FsxConfig(limiter=LimiterConfig(kind=kind),
                        table=TableConfig(capacity=1 << 12, probes=8),
                        batch=BatchConfig(max_batch=256, verdict_k=64))
        spec = get_model(cfg.model.name)
        staged = [(stage, eqn.primitive.name) for stage, eqn in
                  iter_staged_eqns(jax.make_jaxpr(
                      fused.make_step(cfg, spec.classify_batch))(
                          make_table(1 << 12), make_stats(), spec.init(),
                          build_batch([(1001, 5, 100, 0.1, ML_COLD)])))]
        in_aggregate = {p for stage, p in staged if stage == "aggregate"}
        assert "sort" in in_aggregate
        assert not {p for p in in_aggregate
                    if p.startswith(("gather", "scatter", "dynamic"))}
        assert [stage for stage, p in staged if p == "sort"] \
            == ["aggregate", "emit"]

    @pytest.mark.parametrize("kind", list(LimiterKind),
                             ids=lambda k: k.value)
    def test_probe_holds_one_gather_outside_its_reading_branch(self, kind):
        """ISSUE 38: the stage gathers the candidates' keys, and their
        `last_seen` only inside the one conditional's reading branch;
        the winner's score and slot come by select, not by the two
        `take_along_axis` gathers."""
        from flowsentryx_tpu.audit.graph import iter_staged_eqns

        cfg = FsxConfig(limiter=LimiterConfig(kind=kind),
                        table=TableConfig(capacity=1 << 12, probes=8),
                        batch=BatchConfig(max_batch=256, verdict_k=64))
        spec = get_model(cfg.model.name)
        probe = [eqn for stage, eqn in iter_staged_eqns(jax.make_jaxpr(
            fused.make_step(cfg, spec.classify_batch))(
                make_table(1 << 12), make_stats(), spec.init(),
                build_batch([(1001, 5, 100, 0.1, ML_COLD)])))
                 if stage == "probe"]
        conds = [e for e in probe if e.primitive.name == "cond"]
        assert len(conds) == 1
        gathers = [sorted(e.primitive.name for _, e in iter_eqns(branch)
                          if e.primitive.name == "gather")
                   for branch in conds[0].params["branches"]]
        assert sorted(gathers) == [[], ["gather"]]
        # `probe` holds the branches' equations too
        assert [e.primitive.name for e in probe].count("gather") == 2
        assert not [e for e in probe if e.primitive.name == "pjit"
                    and "take_along_axis" in e.params["name"]]
        # every result of the conditional is [B, P]: never the table
        assert [v.aval.shape for v in conds[0].outvars] == [(256, 8)]
