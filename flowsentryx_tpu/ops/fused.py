"""The fused per-micro-batch pipeline step.

One ``jit``-compiled program per config that does everything the
reference's per-packet XDP fast path does (``fsx_kern.c:97-346``:
blacklist check → counter update → threshold check → verdict) *plus*
the ML scoring the reference never wired up — for a whole micro-batch
at once:

    aggregate by flow → slot assignment → blacklist gate →
    limiter transition → int8 classifier → verdict → state scatter →
    stats reduction

Design notes (why this shape is the TPU-fast shape):

* Everything is a gather/arith/scatter dataflow over static shapes —
  XLA fuses the limiter math into the table gathers, and the classifier
  matmul rides the MXU while the VPU does the bookkeeping.
* State transitions happen once per (flow, batch) on aggregated deltas,
  not per packet (see :mod:`flowsentryx_tpu.ops.agg`).
* The returned table/stats are new pytrees; callers jit with
  ``donate_argnums`` so XLA updates HBM in place (no copy of the 1M-row
  table per batch).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from flowsentryx_tpu.core.config import FsxConfig
from flowsentryx_tpu.core.schema import (
    V4_MAPPED_PREFIX, GlobalStats, IpTableState, TableCol, Verdict,
    key_words, u64_add,
)
from flowsentryx_tpu.ops import agg, hashtable, limiters


#: What a 128-bit key of all zeros (``::``, the empty-slot sentinel) is
#: served as: the 32-bit step's 0 -> 0xFFFFFFFE, widened.
ZERO_KEY128_SUB = np.array([0xFFFFFFFE, 0xFFFFFFFF, 0xFFFFFFFF,
                            0xFFFFFFFF], np.uint32)


def is_v4_mapped_key(key: jnp.ndarray) -> jnp.ndarray:
    """Whether each ``[R, 4]`` key lies in ``::ffff:0:0/96`` (an IPv4
    source; ``core/schema.py`` ``V4_MAPPED_PREFIX``)."""
    return ((key[:, 0] == np.uint32(V4_MAPPED_PREFIX[0]))
            & (key[:, 1] == np.uint32(V4_MAPPED_PREFIX[1]))
            & (key[:, 2] == np.uint32(V4_MAPPED_PREFIX[2])))


class StepOutput(NamedTuple):
    verdict: jnp.ndarray   # [B] uint8 Verdict codes, per packet
    score: Any             # [B] f32 classifier probability per packet when
    #                        the step was built with ``emit_score=True``
    #                        (latency/debug/parity paths); None otherwise —
    #                        the serving loop never reads scores, so the
    #                        default build doesn't materialize the [B] f32
    block_key: jnp.ndarray  # [B] uint32 keys newly blacklisted (INVALID_KEY
    #                        pad).  Full-array FALLBACK: fetched by the host
    #                        only when the compact wire overflowed (or
    #                        verdict_k=0); stays on device otherwise.
    block_until: jnp.ndarray  # [B] f32 absolute expiry for block_key entries
    now: jnp.ndarray       # [] f32 newest valid timestamp in the batch —
    #                        the device-clock reading the host side (stats,
    #                        expiry math) uses without re-reducing anything
    # numpy scalar default, NOT jnp: a module-level concrete jax.Array
    # would initialize a backend at import (see agg.INVALID_KEY note).
    route_drop: Any = np.uint32(0)  # [] packets fail-opened because their
    #                        flow overflowed owner routing (sharded step
    #                        only; always 0 single-device — see
    #                        parallel/step.py module docstring)
    wire: Any = None       # [2*verdict_k + 4] uint32 compact verdict wire
    #                        (:func:`pack_verdict_wire`) — the ONE buffer
    #                        the steady-state sink fetches per batch.
    #                        None when cfg.batch.verdict_k == 0.


#: Internal flow-verdict sentinel (never leaves a step): the flow
#: failed the ML vote but had malicious-scoring records — the
#: per-packet assembly translates it record-by-record (malicious
#: records DROP_ML, the flow's other records PASS).
ML_RECORD_GATE = 100


def gate_record_verdicts(
    per_pkt: jnp.ndarray,        # [B] int32 flow verdict of each record
    mal: jnp.ndarray,            # [B] bool: record scored malicious
    valid: jnp.ndarray,          # [B] bool
) -> jnp.ndarray:
    """Record verdicts from each record's flow verdict: the
    :data:`ML_RECORD_GATE` sentinel is translated record by record,
    and an invalid row passes."""
    gated = per_pkt == ML_RECORD_GATE
    per_pkt = jnp.where(
        gated, jnp.where(mal, int(Verdict.DROP_ML), int(Verdict.PASS)),
        per_pkt)
    return jnp.where(valid, per_pkt, int(Verdict.PASS))


def resolve_record_verdicts(
    flow_verdict: jnp.ndarray,   # [R] int32 (may carry ML_RECORD_GATE)
    inv: jnp.ndarray,            # [B] packet -> flow segment
    mal: jnp.ndarray,            # [B] bool: record scored malicious
    valid: jnp.ndarray,          # [B] bool
) -> jnp.ndarray:
    """Broadcast flow verdicts to packets by ``inv`` (the two-stage
    composition's form), then :func:`gate_record_verdicts`."""
    return gate_record_verdicts(flow_verdict[inv], mal, valid)


class FlowDecision(NamedTuple):
    """Per-flow outcome of the table+limiter core."""

    flow_verdict: jnp.ndarray      # [R] int32 Verdict codes
    new_blocked_until: jnp.ndarray  # [R] f32
    newly_blocked: jnp.ndarray     # [R] bool
    tracked: jnp.ndarray           # [R] bool
    read_seen: jnp.ndarray         # [] bool: SlotAssignment.read_seen
    untracked: Any                 # [] uint32: owned flows left with no
    #                                row (``GlobalStats.untracked``); None
    #                                where the table does not age rows out


def flow_step(
    cfg: FsxConfig,
    table: IpTableState,
    fa: agg.FlowAgg,
    flow_mask: jnp.ndarray,
    ml_count: jnp.ndarray,
    now: jnp.ndarray,
) -> tuple[IpTableState, FlowDecision]:
    """Table + limiter + blacklist core over aggregated flows.

    ``flow_mask`` restricts which flows this invocation owns — all-true
    on a single device; the hash-ownership mask under ``shard_map``
    (each device updates only flows whose slots live in its table
    shard).  ``ml_count`` is the per-flow COUNT of records the
    classifier scored malicious this batch, computed by the caller
    (score sharding differs between the local and distributed paths);
    the young-flow vote (``ModelConfig.vote_k``/``vote_m``) decides
    whether that evidence blocks.  Its two halves carry the fused
    step's stage names, ``fsx.probe`` and ``fsx.update``: a caller
    must not hold it inside another ``fsx.*`` scope."""
    with jax.named_scope("fsx.probe"):
        asg = hashtable.assign_slots(
            table, fa.rep_key, fa.rep_valid & flow_mask, now, cfg.table)
    with jax.named_scope("fsx.update"):
        return _flow_core(cfg, table, fa, asg, flow_mask, ml_count, now)


def _flow_core(
    cfg: FsxConfig,
    table: IpTableState,
    fa: agg.FlowAgg,
    asg: "hashtable.SlotAssignment",
    flow_mask: jnp.ndarray,
    ml_count: jnp.ndarray,
    now: jnp.ndarray,
) -> tuple[IpTableState, FlowDecision]:
    """Everything after slot resolution: blacklist gate, limiter, ML
    vote, verdicts, state scatter.  Shared by the sort-per-stage path
    (:func:`flow_step`, used sharded) and the single-sort fused step
    (:func:`make_step`)."""
    lim = cfg.limiter
    mdl = cfg.model
    slot = asg.slot

    # Gather per-flow state: ONE [R, 12] row gather (48 B contiguous
    # per flow — a single HBM transaction, the point of the matrix
    # layout).  Slots claimed via insert (empty or stale reclaim) start
    # from zeroed state — a reclaimed slot must not leak the previous
    # flow's counters.
    C = TableCol
    with jax.named_scope("gather"):
        rows = jnp.where(asg.inserted[:, None], 0.0, table.state[slot])

    win = limiters.WindowState(
        win_start=rows[:, C.WIN_START],
        win_pps=rows[:, C.WIN_PPS],
        win_bps=rows[:, C.WIN_BPS],
        prev_pps=rows[:, C.PREV_PPS],
        prev_bps=rows[:, C.PREV_BPS],
    )
    bucket = limiters.BucketState(
        tokens=rows[:, C.TOKENS], tok_ts=rows[:, C.TOK_TS],
        tok_bytes=rows[:, C.TOK_BYTES],
    )
    blocked_until = rows[:, C.BLOCKED_UNTIL]
    rec_seen = rows[:, C.REC_SEEN]
    ml_votes = rows[:, C.ML_VOTES]
    last_seen = rows[:, C.LAST_SEEN]

    eligible = fa.rep_valid & flow_mask

    # 1. blacklist gate (fsx_kern.c:189-216): still-valid entries drop
    #    the whole flow; expired entries simply stop matching (the
    #    reference's delete becomes a no-op compare).
    already_blocked = asg.tracked & (blocked_until > fa.rep_ts)

    # 2. limiter transition on aggregated deltas (needs a slot: only
    #    tracked flows carry limiter state)
    with jax.named_scope("limiter"):
        dec = limiters.apply_limiter(
            lim, win, bucket, fa.rep_pkts, fa.rep_bytes, fa.rep_ts,
            is_new=asg.inserted,
        )
    over_rate = asg.tracked & dec.over_limit & ~already_blocked

    # 3. ML verdict with the young-flow vote (first records
    #    carry no variance/IAT mass and mis-score, so votes only count
    #    once the flow has shown vote_k records; blocking needs vote_m
    #    votes AND fresh malicious evidence this batch).  The vote
    #    state lives in the table, but the verdict must still apply to
    #    flows that lost slot arbitration or found a full table —
    #    otherwise an attacker could disable detection by filling the
    #    table — so untracked flows vote batch-locally: enough records
    #    in THIS batch to be past the young phase, vote_m of them
    #    malicious (floods qualify; a benign trickle never does).
    ml_hit = ml_count > 0
    mature = rec_seen >= mdl.vote_k
    # Vote decay (half-life vote_decay_s): an isolated borderline
    # mis-score long ago must not leave a benign flow permanently one
    # record from a block.  dt uses the flow's own last activity;
    # inserted flows carry no votes, so their garbage dt is harmless.
    if mdl.vote_decay_s > 0:
        dt = jnp.maximum(fa.rep_ts - last_seen, 0.0)
        ml_votes = ml_votes * jnp.exp2(-dt / mdl.vote_decay_s)
    votes_new = jnp.minimum(
        ml_votes + jnp.where(mature, ml_count, 0.0), jnp.float32(1e6))
    # The batch-local burst rule applies to EVERY flow, tracked or not:
    # a single batch carrying > vote_k records with >= vote_m scored
    # malicious is a dense flood, not a young benign flow (interactive
    # sources emit a handful of records per batch) — without it, a
    # tracked source sending <= vote_k records total, or rotating IPs
    # each batch, would never mature into blockability.
    burst = (fa.rep_pkts > mdl.vote_k) & (ml_count >= mdl.vote_m)
    vote_ok = jnp.where(asg.tracked, (votes_new >= mdl.vote_m) | burst,
                        burst)
    over_ml = eligible & ml_hit & vote_ok & ~already_blocked & ~over_rate
    # Flows that score malicious but fail the vote: drop the RECORDS
    # that scored malicious (fail-closed per record — the ML verdict
    # applies to the packet regardless of flow age or table state, or
    # a rotating spoofed-source flood whose every source sends
    # <= vote_k records would sail through untouched) but do NOT
    # blacklist.  The vote gates the heavy hammer only: the
    # failure was benign SOURCES being condemned for ml_block_s on
    # their first records' mis-scores.  The flow-level verdict here is
    # the ML_RECORD_GATE sentinel; the per-packet assembly translates
    # it record-by-record (a flow's benign-scoring records PASS — one
    # borderline record must not drop its whole batch).
    ml_drop_only = (eligible & ml_hit & ~vote_ok
                    & ~already_blocked & ~over_rate)

    # 4. blacklist writeback (fsx_kern.c:317-325: now + block time).
    #    The device-table scatter below only persists it for tracked
    #    flows (it needs a slot); the kernel-map writeback in StepOutput
    #    carries it for ALL newly-blocked flows, tracked or not.
    new_blocked_until = jnp.where(
        over_rate, fa.rep_ts + lim.block_s,
        jnp.where(over_ml, fa.rep_ts + cfg.model.ml_block_s, blocked_until),
    )

    flow_verdict = jnp.where(
        already_blocked, int(Verdict.DROP_BLACKLIST),
        jnp.where(over_rate, int(Verdict.DROP_RATE),
                  jnp.where(over_ml, int(Verdict.DROP_ML),
                            jnp.where(ml_drop_only, ML_RECORD_GATE,
                                      int(Verdict.PASS)))),
    ).astype(jnp.int32)

    # 5. scatter state back (tracked flows only).  Untracked reps are
    #    routed out of bounds and dropped: arbitration losers share a
    #    slot index with the winner, and scatter order with duplicate
    #    indices is unspecified — a loser writing anything (even the old
    #    value) could clobber the winner's update.
    safe_slot = jnp.where(asg.tracked, slot, table.key.shape[0])

    # one [R, 12] row build + ONE matrix scatter (the gather's mirror);
    # a fired block consumes the votes: re-blocking after the TTL
    # expires requires vote_m FRESH malicious records
    new_rows = jnp.stack(
        [
            fa.rep_ts,                             # LAST_SEEN
            dec.window.win_start,                  # WIN_START
            dec.window.win_pps,                    # WIN_PPS
            dec.window.win_bps,                    # WIN_BPS
            dec.window.prev_pps,                   # PREV_PPS
            dec.window.prev_bps,                   # PREV_BPS
            dec.bucket.tokens,                     # TOKENS
            dec.bucket.tok_ts,                     # TOK_TS
            dec.bucket.tok_bytes,                  # TOK_BYTES
            rec_seen + fa.rep_pkts,                # REC_SEEN
            jnp.where(over_ml, 0.0, votes_new),    # ML_VOTES
            new_blocked_until,                     # BLOCKED_UNTIL
        ],
        axis=1,
    )
    with jax.named_scope("scatter"):
        new_table = IpTableState(
            key=table.key.at[safe_slot].set(fa.rep_key, mode="drop"),
            state=table.state.at[safe_slot].set(new_rows, mode="drop"),
        )

    return new_table, FlowDecision(
        flow_verdict=flow_verdict,
        new_blocked_until=new_blocked_until,
        newly_blocked=over_rate | over_ml,
        tracked=asg.tracked,
        read_seen=asg.read_seen,
        # counted where the aging sweep is compiled in, as ``evicted``
        # is: a table with no aging stages the graph it always staged
        # (GlobalStats.untracked says what that graph was found to
        # hold still)
        untracked=(jnp.sum(eligible & ~asg.tracked).astype(jnp.uint32)
                   if cfg.table.evict_ttl_s > 0 else None),
    )


def ml_flow_count(
    cfg: FsxConfig, score: jnp.ndarray, valid: jnp.ndarray, inv: jnp.ndarray
) -> jnp.ndarray:
    """Per-flow COUNT of records scoring over the decision threshold —
    the vote evidence :func:`flow_step` weighs against
    ``ModelConfig.vote_m`` (a bool "any malicious" can't distinguish
    one borderline young record from a sustained attack)."""
    mal_pkt = (score > cfg.model.threshold) & valid
    return (
        jnp.zeros_like(score)
        .at[inv].add(mal_pkt.astype(jnp.float32))
    )


#: Verdict classes in the order :func:`count_verdicts` /
#: :func:`update_stats_from_counts` use — one slot per GlobalStats
#: packet counter.
STAT_VERDICT_ORDER = (
    Verdict.PASS, Verdict.DROP_BLACKLIST, Verdict.DROP_RATE, Verdict.DROP_ML,
)


def count_verdicts(verdict: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """``[4]`` uint32 packet counts in :data:`STAT_VERDICT_ORDER`."""
    return jnp.stack([
        jnp.sum(valid & (verdict == int(code))).astype(jnp.uint32)
        for code in STAT_VERDICT_ORDER
    ])


def update_stats_from_counts(
    stats: GlobalStats, counts: jnp.ndarray, read_seen: jnp.ndarray,
    untracked: Any,
) -> GlobalStats:
    """Fold a ``[4]`` count vector (:data:`STAT_VERDICT_ORDER`) plus one
    batch into the u64 counters — shared by the single-device step
    (local counts) and the sharded step (psum'd counts).  ``read_seen``
    is the batch's ``ProbeResult.read_seen`` and ``untracked`` its
    ``FlowDecision.untracked`` (only a valid key can set either, so an
    empty batch never does; ``None``, a table with no aging, leaves
    the counter a donated passthrough, as ``evicted`` is there).

    ``batches`` bumps only for a NON-EMPTY batch: the verdict classes
    partition the valid records, so ``counts.sum()`` is ``n_valid``, and
    an all-masked dispatch — exactly ``Engine.warm()``'s compile
    trigger — must leave every counter untouched (warm's documented
    contract; unconditional bumping skewed ``fsx serve --mega`` reports
    by 1 + mega_n device batches vs the report's own batch count)."""
    return GlobalStats(
        allowed=u64_add(stats.allowed, counts[0]),
        dropped_blacklist=u64_add(stats.dropped_blacklist, counts[1]),
        dropped_rate=u64_add(stats.dropped_rate, counts[2]),
        dropped_ml=u64_add(stats.dropped_ml, counts[3]),
        batches=u64_add(stats.batches,
                        (counts.sum() > 0).astype(jnp.uint32)),
        # the sharded step's own count (parallel/step.py); one device
        # passes it through
        owner_rows=stats.owner_rows,
        # eviction is accounted at the sweep site (evict_idle_epoch's
        # callers), not from the verdict counts; a pure passthrough here
        # keeps disabled-eviction graphs — and their donation aliasing —
        # identical to the pre-eviction era
        evicted=stats.evicted,
        stale_reads=u64_add(stats.stale_reads, read_seen),
        untracked=(stats.untracked if untracked is None
                   else u64_add(stats.untracked, untracked)),
        # the 128-bit step's counts, added by make_step where the table
        # keys by 128 bits; a passthrough here, so the 32-bit graph is
        # the one it was
        v6_records=stats.v6_records,
        v6_blocks=stats.v6_blocks,
    )


def update_stats(
    stats: GlobalStats, verdict: jnp.ndarray, valid: jnp.ndarray,
    dec: FlowDecision,
) -> GlobalStats:
    """Per-packet counters (successor of the reference's racy
    allowed/dropped bumps, ``fsx_kern.c:210,332,342``) and the two
    per-batch ones the flow core reports."""
    return update_stats_from_counts(stats, count_verdicts(verdict, valid),
                                    dec.read_seen, dec.untracked)


# -- in-step aging: the rolling idle-flow eviction sweep --------------------
#
# The reference gets flow-table aging for free from BPF LRU maps; the
# dense device table only ever RECLAIMED stale slots when a new flow
# happened to probe them, so under sustained flow churn occupancy grew
# monotonically toward capacity and every probe sequence degraded with
# it.  The eviction sweep bounds occupancy in-graph: each batch, the
# step OPENS by sweeping one ``ceil(capacity / evict_every)``-row
# WINDOW — the window base advancing with the batch counter, so every
# row is re-examined once per ``evict_every`` batches (one full aging
# cycle) — freeing slots idle longer than ``evict_ttl_s`` (still-valid
# blacklist entries exempt: a blocked source must keep dropping until
# its TTL expires, exactly like the kernel map entry).
#
# Why a rolling window and not an every-N-batches whole-table pass
# under ``lax.cond``: XLA:CPU materializes a conditional's operands and
# results as fresh buffers, so a cond carrying a [4M, 12] table COPIES
# ~400 MB per batch whether or not the sweep branch fires — measured
# 60x off the no-eviction drain rate.  (What a conditional RETURNS is
# what is copied: the probe's, whose branches only read the table and
# return ``[R, P]``, is handed it by reference —
# ``audit/graph.py::check_inplace``.)  The window form costs
# ``capacity/evict_every`` rows read and written per batch, adds no
# whole-table latency spike on epoch batches, and keeps the exact same
# guarantee: a row idle past the ttl is freed within one cycle of
# crossing it.
#
# HOW the window is read and written is the one thing here that the
# backend decides, and the two backends' needs are opposite, so there
# are two forms and ``jax.lax.platform_dependent`` picks one where the
# program is LOWERED (no configuration field, no environment variable,
# no device name: a compile for a described v5e from a CPU host takes
# the TPU's form, which ``jax.default_backend()`` would get wrong):
#
# * XLA:CPU, and anything not named: a GATHER over ``off + arange``
#   and a victim-only drop-mode SCATTER (``_sweep_by_scatter``).  A
#   dynamic-OFFSET slice touching the donated table defeats XLA:CPU's
#   in-place buffer reuse for the whole donated chain, and the step
#   falls off the in-place cliff: the full-table-copy signature,
#   whatever the window (~250 ms/step at 4M rows even at a 1-row
#   window when this was written; on JAX 0.9.0 the compact step at
#   2^22 rows, batch 2,048, costs 1.5 ms with this form and 29.9 ms
#   with the slice form, at a 128-row and an 8,192-row window alike:
#   ISSUE 40).  Scatters on the donated buffers are the hot path's own
#   proven-in-place mechanism; with drop-mode parking for the
#   non-victim lanes the write volume is the evicted rows only.
# * the TPU: ``dynamic_slice`` the window, ``where`` the victims to
#   EMPTY_KEY / 0.0, ``dynamic_update_slice`` it back
#   (``_sweep_by_slice``).  There the reverse holds: a drop-mode
#   scatter walks every index of the window whatever it frees, at the
#   ~0.09 us an index the step's other scatters cost, twice (key
#   column, row matrix), behind a sort of the (index, key) pairs the
#   compiler puts in front of a scatter it cannot prove unique: 23.4
#   ms a batch for a 2^17-row window of a 2^26-row table, 76 % of the
#   step (PERF_LEDGER, PR 39), where the window is 12 contiguous column
#   runs of the chip's layout and the slices update the donated table
#   in place.
#
# The victim rule is written once (``_sweep_victims``) and both forms
# are held to the numpy reference bit for bit (tests/test_fused.py);
# ``audit/graph.py::check_inplace`` walks each branch under its own
# platform's rules.
#
# Everything stays inside the staged graph: no new D2H (the verdict
# wire is unchanged), no new collectives (each shard sweeps its own
# rows; the count rides the existing stats psum).  Sweeping at step
# START (before slot probing) means freed slots are claimable by the
# same batch's inserts, and the sweep depends only on (incoming table,
# incoming batch count, batch clock) — which is what makes the
# reference-sweep parity test exact.


def evict_window(capacity: int, evict_every: int) -> int:
    """Rows swept per batch: one full pass every ``evict_every``
    batches.  When the division is ragged the last window re-sweeps a
    few tail rows (the base is clamped to keep the window in bounds) —
    idempotent, so merely redundant.  Sizing rule: size by CYCLE TIME —
    pick ``evict_every`` so one full pass (``evict_every`` batches)
    takes about ``ttl/4`` at your batch rate — and then look at what
    the window costs on YOUR backend.  On XLA:CPU the sweep costs ~0.2
    µs/row single-device and ~1 µs/row under shard_map, so the window
    should land in the tens-to-hundreds of rows, where the per-batch
    overhead vanishes: at the 10 Mpps design rate a 4M table with
    ``evict_every=32768`` cycles in ~7 s with a 128-row window (the
    TABLESCALE_r12 bench setting).  On the TPU the window is a slice
    and costs what its bytes cost: a 2^17-row window (2^26 rows,
    ``evict_every=512``) is measured in PERF.md §5
    (``step.stage_evict_ms.tput``), not derived from the CPU's rule."""
    return -(-capacity // evict_every)


def _sweep_offset(tcfg, cap: int, stats: GlobalStats) -> jnp.ndarray:
    """``[] uint32`` first row of this batch's window.  Unsigned, and
    clamped only where the clamp can bite, for the TPU's sake: a slice
    at a signed start is lowered behind a ``start < 0`` select, and
    that select or a ``minimum`` hides from the chip's compiler that
    the start is a multiple of the window.  Knowing it, it blanks the
    victims inside the in-place ``dynamic-update-slice``; not knowing,
    it slices the window into a temporary and copies it back (0.084
    against 0.049 ms a batch at a 2^17-row window: PERF.md §6, PR 40)."""
    chunk = evict_window(cap, tcfg.evict_every)
    off = (stats.batches[0] % np.uint32(tcfg.evict_every)) * np.uint32(chunk)
    if chunk * tcfg.evict_every != cap:
        # clamp so a ragged last window re-sweeps tail rows instead of
        # parking out of bounds (which would leave them unswept forever)
        off = jnp.minimum(off, np.uint32(cap - chunk))
    return off


def _sweep_victims(tcfg, keys: jnp.ndarray, rows: jnp.ndarray,
                   now: jnp.ndarray) -> jnp.ndarray:
    """``[chunk] bool``: occupied, idle past the ttl, block not live."""
    C = TableCol
    idle = now - rows[:, C.LAST_SEEN] > tcfg.evict_ttl_s
    live_block = rows[:, C.BLOCKED_UNTIL] > now
    return (keys != hashtable.EMPTY_KEY) & idle & ~live_block


def _sweep_by_scatter(tcfg, table: IpTableState, off: jnp.ndarray,
                      now: jnp.ndarray) -> tuple[IpTableState, jnp.ndarray]:
    """The sweep where a dynamic-offset slice would copy the table
    (XLA:CPU): gather the window, scatter the victims."""
    cap = table.key.shape[0]
    idx = off.astype(jnp.int32) + jnp.arange(
        evict_window(cap, tcfg.evict_every), dtype=jnp.int32)
    victim = _sweep_victims(tcfg, table.key[idx], table.state[idx], now)
    # victim-only scatter: non-victim lanes park at row `cap` and drop
    vidx = jnp.where(victim, idx, jnp.int32(cap))
    return IpTableState(
        key=table.key.at[vidx].set(jnp.uint32(hashtable.EMPTY_KEY),
                                   mode="drop"),
        state=table.state.at[vidx].set(0.0, mode="drop"),
    ), jnp.sum(victim).astype(jnp.uint32)


def _sweep_by_slice(tcfg, table: IpTableState, off: jnp.ndarray,
                    now: jnp.ndarray) -> tuple[IpTableState, jnp.ndarray]:
    """The sweep where a scatter walks every index (the TPU): slice the
    window out, blank the victims, slice it back in."""
    chunk = evict_window(table.key.shape[0], tcfg.evict_every)
    keys = jax.lax.dynamic_slice_in_dim(table.key, off, chunk)
    rows = jax.lax.dynamic_slice_in_dim(table.state, off, chunk)
    victim = _sweep_victims(tcfg, keys, rows, now)
    return IpTableState(
        key=jax.lax.dynamic_update_slice_in_dim(
            table.key,
            jnp.where(victim, jnp.uint32(hashtable.EMPTY_KEY), keys),
            off, 0),
        state=jax.lax.dynamic_update_slice_in_dim(
            table.state, jnp.where(victim[:, None], 0.0, rows), off, 0),
    ), jnp.sum(victim).astype(jnp.uint32)


def evict_idle_epoch(
    tcfg,
    table: IpTableState,
    stats: GlobalStats,
    now: jnp.ndarray,
) -> tuple[IpTableState, jnp.ndarray]:
    """One rolling-sweep step (module comment above).

    Returns ``(table, [] uint32 evicted-count-this-window)``.  Callers
    gate on ``tcfg.evict_ttl_s > 0`` STATICALLY — a disabled config
    must stage the pre-eviction graph, not a sweep that never frees.

    Warm/empty batches carry ``now == 0``, making the sweep a no-op by
    construction (``0 - last_seen`` can never exceed a positive ttl),
    so ``warm()``'s state-preservation contract holds without a
    valid-count input here."""
    return jax.lax.platform_dependent(
        table, _sweep_offset(tcfg, table.key.shape[0], stats), now,
        tpu=functools.partial(_sweep_by_slice, tcfg),
        default=functools.partial(_sweep_by_scatter, tcfg))


# -- compact verdict wire ---------------------------------------------------
#
# The steady-state device→host readback.  A sunk batch used to fetch the
# full [B] block arrays (8 B/record — 16 KB at B=2048) just to find the
# handful of newly-blocked flows; line-rate planes keep the feedback
# channel tiny (Taurus) and bound what crosses the device boundary per
# window (SpliDT).  The wire packs everything the sink needs into ONE
# fixed uint32 buffer: one readback per batch, O(K) bytes:
#
#     [0 : K]          newly-blocked keys, INVALID_KEY padded
#     [K : 2K]         matching blacklist expiries (f32 bitcast)
#     [2K]             true count of newly-blocked flows (may exceed K)
#     [2K + 1]         overflow flag: count > K — the host must fall back
#                      to the full block_key/block_until fetch for this
#                      batch so no block is ever lost
#     [2K + 2]         route_drop (sharded fail-opens; 0 single-device)
#     [2K + 3]         batch device clock "now" (f32 bitcast)
#
# With 128-bit keys (``TableConfig.key_bits``) a key takes 4 words: the
# keys fill ``[0 : 4K]`` (key i at ``4i .. 4i+3``), the expiries
# ``[4K : 5K]``, and the four scalars follow at ``5K``: ``[5K + 4]``
# words in all.  The width is the configuration's; the wire does not
# state it.
#
# Host-side decode lives in engine/writeback.py (numpy, no jax needed at
# decode time).

#: Trailing scalar words of the verdict wire (count, overflow,
#: route_drop, now).
VERDICT_WIRE_SCALARS = 4


def verdict_wire_words(k_max: int, key_words: int = 1) -> int:
    """uint32 words in a verdict wire built for ``k_max`` slots of
    ``key_words``-word keys."""
    return (key_words + 1) * k_max + VERDICT_WIRE_SCALARS


def _real_row(key: jnp.ndarray) -> jnp.ndarray:
    """Where a key (``[R]`` or ``[R, 4]``) is not the INVALID_KEY pad."""
    return (key != agg.INVALID_KEY if key.ndim == 1
            else jnp.any(key != agg.INVALID_KEY, axis=1))


def compact_blocklist(
    block_key: jnp.ndarray,   # [R] uint32, INVALID_KEY padded
    block_until: jnp.ndarray,  # [R] f32
    k_max: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Order-preserving device-side compaction of a padded block array
    into ``([k_max] keys, [k_max] untils, [] true count)``.

    Entries past ``k_max`` are parked out of the buffer (the count still
    reflects them, which is how callers detect overflow).  Order
    preservation matters: duplicate keys across merged buffers resolve
    last-wins downstream, exactly like the kernel blacklist map.
    ``[R, 4]`` keys compact to ``[k_max, 4]``."""
    nb = _real_row(block_key)
    pos = jnp.cumsum(nb.astype(jnp.int32)) - 1
    idx = jnp.where(nb & (pos < k_max), pos, k_max)  # park tail + invalid
    ck = (jnp.full((k_max + 1,) + block_key.shape[1:], agg.INVALID_KEY,
                   jnp.uint32)
          .at[idx].set(block_key)[:k_max])
    cu = (jnp.zeros((k_max + 1,), jnp.float32)
          .at[idx].set(block_until)[:k_max])
    return ck, cu, jnp.sum(nb).astype(jnp.uint32)


def pack_verdict_wire(
    block_key: jnp.ndarray,
    block_until: jnp.ndarray,
    now: jnp.ndarray,
    route_drop: Any,
    k_max: int,
) -> jnp.ndarray:
    """Build the ``[2*k_max + 4]`` uint32 compact verdict wire
    (``[5*k_max + 4]`` for ``[R, 4]`` keys)."""
    bits = jax.lax.bitcast_convert_type
    ck, cu, count = compact_blocklist(block_key, block_until, k_max)
    scalars = jnp.stack([
        count,
        (count > k_max).astype(jnp.uint32),
        jnp.asarray(route_drop).astype(jnp.uint32),
        bits(jnp.asarray(now, jnp.float32), jnp.uint32),
    ])
    if ck.ndim == 2:
        ck = ck.reshape(-1)
    return jnp.concatenate([ck, bits(cu, jnp.uint32), scalars])


def merge_verdict_wires(wires: jnp.ndarray, key_words: int = 1
                        ) -> jnp.ndarray:
    """Fold a ``[N, 2K+4]`` stack of per-chunk verdict wires (a megastep
    scan's outputs) into ONE wire, so a mega dispatch still costs a
    single O(K) readback.

    Counts/route_drop sum, ``now`` maxes, and the key/until slots
    re-compact in chunk order (last-wins per key downstream).  The
    merged overflow derives from the summed TRUE counts: any lost entry
    — a chunk's own overflow or more than K total across chunks —
    implies total > K, so the flag is exact.  ``key_words`` is the
    width of a key in the wires (4 for 128-bit keys)."""
    bits = jax.lax.bitcast_convert_type
    kw = key_words
    k = (wires.shape[1] - VERDICT_WIRE_SCALARS) // (kw + 1)
    keys = wires[:, :kw * k].reshape(-1)
    if kw > 1:
        keys = keys.reshape(-1, kw)
    untils = bits(wires[:, kw * k:(kw + 1) * k], jnp.float32).reshape(-1)
    s0 = (kw + 1) * k
    count = jnp.sum(wires[:, s0]).astype(jnp.uint32)
    rd = jnp.sum(wires[:, s0 + 2]).astype(jnp.uint32)
    now = jnp.max(bits(wires[:, s0 + 3], jnp.float32))
    ck, cu, _ = compact_blocklist(keys, untils, k)
    scalars = jnp.stack([
        count, (count > k).astype(jnp.uint32), rd, bits(now, jnp.uint32),
    ])
    if ck.ndim == 2:
        ck = ck.reshape(-1)
    return jnp.concatenate([ck, bits(cu, jnp.uint32), scalars])


#: The stages of the fused step, in order: each is a
#: ``jax.named_scope("fsx.<stage>")`` in :func:`make_step` (``decode``
#: also wraps the wire decode of the raw/compact steps, ``emit`` the
#: megastep's wire merge; ``fsx.evict`` exists only where the aging
#: sweep is compiled in).  ``aggregate`` holds the step's one sort by
#: (slot-priority, key) and the scan over its runs; ``emit`` the sort
#: that undoes it for the verdicts.  The names reach the device trace
#: as each operation's scope; the benchmark's ``step.stage_*`` metrics
#: read them.
STEP_SCOPES = ("decode", "classify", "probe", "aggregate", "update", "emit")


def scan_runs(head: jnp.ndarray, cols: jnp.ndarray, maxed: tuple
              ) -> jnp.ndarray:
    """Segmented inclusive scan along the rows of ``cols`` (``[K, B]``:
    ``K`` columns of a batch whose runs are contiguous).  ``head``
    (``[B]``) marks each run's first position; row ``k`` of the result
    holds, at position ``i``, the maximum (where ``maxed[k]``) or the
    sum of its column from the run's head to ``i`` — so the value at a
    run's LAST position is the run's total.

    ``ceil(log2 B)`` doubling steps of one shifted read each: after the
    step of distance ``d`` a position holds its run's values over the
    last ``2d`` positions, and ``reached`` says that window already
    holds the run's head.  Positions ``i < d`` have always reached
    (position 0 is a head), so what the rotation wraps round to them
    is never read.  A sum only ever adds values of its own run, so it
    rounds where the run's own total does — not at the batch's, as the
    difference of two whole-batch ``f32`` prefix sums would (16,384
    records of 1,500 B pass 2^24).  One stacked array, not an array a
    column: every boot traces these steps for every program, and each
    ``jnp`` call in them is a ``jit`` of its own to trace."""
    b = head.shape[0]
    pick_max = np.asarray(maxed)[:, None]

    def rotated(x, d):
        return jax.lax.concatenate(
            [jax.lax.slice_in_dim(x, b - d, b, axis=1),
             jax.lax.slice_in_dim(x, 0, b - d, axis=1)], 1)

    reached = head[None, :]
    d = 1
    while d < b:
        back = rotated(cols, d)
        cols = jnp.where(reached, cols,
                         jnp.where(pick_max, jnp.maximum(back, cols),
                                   back + cols))
        reached = reached | rotated(reached, d)
        d *= 2
    return cols


#: Bits of a flow verdict code in :func:`spread_from_tails`' packed
#: word (the codes are Verdict's four and ML_RECORD_GATE).
_CODE_BITS = 7


def spread_from_tails(tail: jnp.ndarray, code: jnp.ndarray) -> jnp.ndarray:
    """Each position's run-tail ``code`` (``[B]`` int32 in
    ``[0, 2^7)``): the flow verdict, which the fused step holds at a
    run's last position, handed to every record of the run.

    One reverse running minimum: a tail holds ``position << 7 | code``
    and every other position the largest int32, so the minimum over
    ``[i, B)`` is the nearest tail at or after ``i`` — ``i``'s own
    run's, runs being contiguous — and its low bits are that tail's
    code.  Integer, so exact."""
    b = tail.shape[0]
    if b << _CODE_BITS > np.iinfo(np.int32).max:
        raise ValueError(f"batch of {b} records: position and verdict "
                         "code do not fit one int32")
    packed = jnp.where(
        tail, (jnp.arange(b, dtype=jnp.int32) << _CODE_BITS) | code,
        np.iinfo(np.int32).max)
    return jax.lax.cummin(packed, reverse=True) & ((1 << _CODE_BITS) - 1)


def make_step(
    cfg: FsxConfig,
    classify_batch: Callable[[Any, jnp.ndarray], jnp.ndarray],
    emit_score: bool = False,
) -> Callable[..., tuple[IpTableState, GlobalStats, StepOutput]]:
    """Build the (single-device) fused step for a static config + scorer.

    Returns ``step(table, stats, params, batch) -> (table, stats, out)``,
    a pure function ready for ``jit``.  ``out.wire`` (the compact
    verdict buffer, sized by ``cfg.batch.verdict_k``) feeds the daemon's
    writeback into the kernel blacklist map (the reference's
    ``blacklist_v4`` ingress, ``fsx_kern.c:64-70``), closing the north
    star's verdict loop; the full ``block_key``/``block_until`` arrays
    stay on device as the overflow fallback.  ``emit_score=True`` adds
    the ``[B]`` f32 score output (latency/debug/parity paths only — the
    serving loop never reads it).  The multi-device variant is
    :func:`flowsentryx_tpu.parallel.step.make_sharded_step`.
    """

    def step(
        table: IpTableState,
        stats: GlobalStats,
        params: Any,
        batch,
    ) -> tuple[IpTableState, GlobalStats, StepOutput]:
        # SINGLE-SORT pipeline (VERDICT r4 #4: the two sort passes —
        # aggregation's key sort + slot arbitration's sort — dominated
        # the step).  Slots are probed PER PACKET first (equal keys
        # compute equal slots, so this costs the same [B, P] gather the
        # per-flow probe did on the padded rep array), then ONE
        # multi-key ``lax.sort`` by (slot-priority, key) yields BOTH
        # groupings at once: equal keys form contiguous runs (the
        # aggregation), and runs sharing a slot are adjacent with
        # found-first priority (the arbitration — the slot group's
        # first run wins).  The sort carries every per-record column
        # the flows need as a payload, and a run is reduced where it
        # lies, by a segmented scan (:func:`scan_runs`): the stage
        # holds no gather and no scatter (on the chip one of either
        # costs ten to twenty such sorts).  THE FLOWS LIVE AT THEIR
        # RUNS' LAST POSITIONS, in run order, under ``rep_valid``; every
        # other position is padding.  Nothing downstream wants them at the
        # front: ``_flow_core`` is elementwise under its masks and
        # ``compact_blocklist`` keeps order.  A record's verdict comes
        # back the same way: spread over the run in sorted order
        # (:func:`spread_from_tails`), then one sort keyed on the
        # first sort's permutation puts it in the batch's order.  The
        # sharded step aggregates its slice the same way, with no slot
        # key (parallel/step.py::aggregate_slice); parity with the
        # two-stage composition is pinned by tests/test_fused.py.
        # The ``fsx.<stage>`` scopes (STEP_SCOPES) are metadata only:
        # the compiled program is the same with or without them.
        # A 128-bit key (``TableConfig.key_bits``, ``[B, 4]``, ``kw``
        # 4) takes the same path word for word: the probe hashes and
        # compares all four words, the sort orders by all four after
        # the slot key and a run breaks where any word does, so equal
        # 32-bit hashes or folds of two sources never merge their flows.
        b = batch.key.shape[0]
        kw = key_words(cfg.table.key_bits)
        with jax.named_scope("fsx.decode"):
            now = jnp.max(jnp.where(batch.valid, batch.ts, 0.0))
        # In-step aging epoch (evict_idle_epoch): sweep BEFORE probing
        # so freed slots are claimable by this very batch's inserts.
        # Statically absent when disabled — the pre-eviction graph.
        n_evicted = None
        if cfg.table.evict_ttl_s > 0:
            with jax.named_scope("fsx.evict"):
                table, n_evicted = evict_idle_epoch(cfg.table, table,
                                                    stats, now)
        with jax.named_scope("fsx.classify"):
            score = classify_batch(params, batch.feat)  # [B] f32, MXU path
            mal = (score > cfg.model.threshold) & batch.valid

        # --- per-packet probe + slot selection (the ONE probe-math
        # copy, shared with assign_slots — cross-path slot decisions
        # must stay bit-identical) ---
        n = table.key.shape[0]
        with jax.named_scope("fsx.probe"):
            # key sanitization (agg.aggregate's contract): 0 must not
            # masquerade as the empty-slot sentinel; invalid rows park
            # at INVALID_KEY, which sorts past every real key
            if kw == 1:
                key = jnp.where(batch.key == 0, jnp.uint32(0xFFFFFFFE),
                                batch.key)
                key = jnp.where(batch.valid, key, agg.INVALID_KEY)
            else:
                zero = jnp.all(batch.key == 0, axis=1, keepdims=True)
                key = jnp.where(zero, ZERO_KEY128_SUB, batch.key)
                key = jnp.where(batch.valid[:, None], key, agg.INVALID_KEY)
            pr = hashtable.probe_slots(table, key, batch.valid, now,
                                       cfg.table)
            slot, found, usable = pr.slot, pr.found, pr.usable

        with jax.named_scope("fsx.aggregate"):
            # --- the one sort: (slot-priority, key), carrying the
            # record's length, its time, and one word of its place in
            # the batch and its two flags (an operand costs the chip's
            # compiler 2.4 s at 16,384 records).  Two keys and stable:
            # records of one key keep the batch's order
            slot_pri = jnp.where(
                usable, slot * 2 + (~found).astype(jnp.int32),
                jnp.int32(2 * n))
            tag = (jnp.arange(b, dtype=jnp.int32) * 4
                   + 2 * mal.astype(jnp.int32)
                   + batch.valid.astype(jnp.int32))
            if kw == 1:
                sp_s, key_s, tag_s, len_s, ts_s = jax.lax.sort(
                    (slot_pri, key, tag, batch.pkt_len, batch.ts),
                    num_keys=2)
            else:
                sp_s, *key_cols, tag_s, len_s, ts_s = jax.lax.sort(
                    (slot_pri, *(key[:, i] for i in range(kw)), tag,
                     batch.pkt_len, batch.ts), num_keys=1 + kw)
                key_s = jnp.stack(key_cols, axis=1)
            order = tag_s >> 2
            valid_s = (tag_s & 1) > 0
            mal_s = (tag_s & 2) > 0
            # probe_slots' three answers, read back off the sorted
            # key: equal keys probed equal slots
            usable_s = sp_s != 2 * n
            found_s = usable_s & ((sp_s & 1) == 0)
            slot_s = jnp.minimum(sp_s >> 1, n - 1)

            one = jnp.ones((1,), bool)
            differs = key_s[1:] != key_s[:-1]
            if kw > 1:
                differs = jnp.any(differs, axis=1)
            key_head = jnp.concatenate([one, differs])
            tail = jnp.concatenate([key_head[1:], one])
            # arbitration: a flow wins iff its first packet opens its
            # slot group (the found-first bit in slot_pri already
            # ordered the groups; parked rows share slot_pri 2n but
            # usable=False).  Read at the run's head, so it rides the
            # scan to the tail
            slot_head = jnp.concatenate(
                [one, (sp_s[1:] >> 1) != (sp_s[:-1] >> 1)])
            runs = scan_runs(
                key_head,
                jnp.stack([valid_s.astype(jnp.float32),
                           jnp.where(valid_s, len_s, 0.0),
                           mal_s.astype(jnp.float32),
                           jnp.where(valid_s, ts_s, -jnp.inf),
                           (key_head & slot_head).astype(jnp.float32)]),
                maxed=(False, False, False, True, True))
            rep_valid = tail & (runs[0] > 0)
            pkts, bytes_, ml_count, ts_max, won = jnp.where(
                rep_valid, runs, 0.0)
            rep_winner = won > 0

            fa = agg.FlowAgg(
                rep_key=jnp.where(rep_valid if kw == 1
                                  else rep_valid[:, None],
                                  key_s, agg.INVALID_KEY),
                rep_pkts=pkts, rep_bytes=bytes_, rep_ts=ts_max,
                rep_valid=rep_valid, inv=None)
            asg = hashtable.SlotAssignment(
                slot=slot_s,
                found=found_s & rep_winner,
                inserted=usable_s & ~found_s & rep_winner,
                tracked=usable_s & rep_winner,
                read_seen=pr.read_seen,
            )
        with jax.named_scope("fsx.update"):
            all_flows = jnp.ones_like(rep_valid)
            new_table, dec = _flow_core(cfg, table, fa, asg, all_flows,
                                        ml_count, now)

        with jax.named_scope("fsx.emit"):
            # flow verdict -> its run's records (sorted order) ->
            # the batch's order: ``order`` is a permutation, so a sort
            # by it is the inverse of the first, and a Verdict code
            # rides in the two bits under it
            verdict_s = gate_record_verdicts(
                spread_from_tails(tail, dec.flow_verdict), mal_s, valid_s)
            verdict = jax.lax.sort(order * 4 + verdict_s,
                                   is_stable=False) & 3
            new_stats = update_stats(stats, verdict, batch.valid, dec)
            if n_evicted is not None:
                new_stats = new_stats._replace(
                    evicted=u64_add(new_stats.evicted, n_evicted))

            if kw > 1:
                # the v6 counters: records and new blocks whose source
                # lies outside ::ffff:0:0/96
                v6_s = valid_s & ~is_v4_mapped_key(key_s)
                new_stats = new_stats._replace(
                    v6_records=u64_add(new_stats.v6_records,
                                       jnp.sum(v6_s).astype(jnp.uint32)),
                    v6_blocks=u64_add(
                        new_stats.v6_blocks,
                        jnp.sum(dec.newly_blocked & v6_s
                                ).astype(jnp.uint32)))
            block_key = jnp.where(dec.newly_blocked if kw == 1
                                  else dec.newly_blocked[:, None],
                                  fa.rep_key, agg.INVALID_KEY)
            block_until = jnp.where(dec.newly_blocked,
                                    dec.new_blocked_until, 0.0)
            k_max = cfg.batch.verdict_k
            out = StepOutput(
                # uint8 pack: 4 verdict classes; the [B] int32 was 4x
                # the bytes for readers (parity tests, offline
                # analysis) that only ever compare against small codes
                verdict=verdict.astype(jnp.uint8),
                score=score if emit_score else None,
                block_key=block_key,
                block_until=block_until,
                now=now,
                wire=(pack_verdict_wire(block_key, block_until, now,
                                        np.uint32(0), k_max)
                      if k_max else None),
            )
        return new_table, new_stats, out

    return step


def make_raw_step(
    cfg: FsxConfig,
    classify_batch: Callable[[Any, jnp.ndarray], jnp.ndarray],
    emit_score: bool = False,
) -> Callable[..., tuple[IpTableState, GlobalStats, StepOutput]]:
    """Fused step taking the RAW ring wire format (``[B+1, 12]`` uint32,
    :func:`~flowsentryx_tpu.core.schema.encode_raw`) instead of a decoded
    :class:`FeatureBatch`.

    This is the production hot path: the host's per-packet work drops to
    one memcpy, the batch crosses the host↔device link as a single
    contiguous buffer, and all field extraction / casts fuse into the
    step's first gathers on device.  ``step(table, stats, params, raw)``.
    """
    from flowsentryx_tpu.core import schema

    base = make_step(cfg, classify_batch, emit_score=emit_score)

    def step(table, stats, params, raw):
        with jax.named_scope("fsx.decode"):
            batch = schema.decode_raw(raw)
        return base(table, stats, params, batch)

    return step


def make_jitted_raw_step(cfg: FsxConfig, classify_batch,
                         donate: bool = True,
                         emit_score: bool = False):
    """``jit``-compiled :func:`make_raw_step`, table+stats donated."""
    step = make_raw_step(cfg, classify_batch, emit_score=emit_score)
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def make_compact_step(
    cfg: FsxConfig,
    classify_batch: Callable[[Any, jnp.ndarray], jnp.ndarray],
    emit_score: bool = False,
    **quant,
) -> Callable[..., tuple[IpTableState, GlobalStats, StepOutput]]:
    """Fused step over the COMPACT 16 B wire format
    (:func:`~flowsentryx_tpu.core.schema.encode_compact`).

    The host→device hop is the bandwidth-critical seam (at 10 Mpps the
    48 B record needs 480 MB/s of PCIe/link); this step takes the
    quantized 16 B record instead — 3× fewer wire bytes — and fuses the
    dequant into the batch's first device-side ops.  ``**quant`` are
    the wire-quantizer kwargs (``schema.model_quant_args(params)`` for
    bit-exact ``model`` mode; default model-independent minifloat).
    Verdict parity with the 48 B path is tested in tests/test_fused.py.
    """
    from flowsentryx_tpu.core import schema

    base = make_step(cfg, classify_batch, emit_score=emit_score)

    def step(table, stats, params, raw):
        with jax.named_scope("fsx.decode"):
            batch = schema.decode_compact(raw, **quant)
        return base(table, stats, params, batch)

    return step


def make_jitted_compact_step(
    cfg: FsxConfig,
    classify_batch,
    donate: bool = True,
    emit_score: bool = False,
    **quant,
):
    """``jit``-compiled :func:`make_compact_step` with donation (twin of
    :func:`make_jitted_raw_step`)."""
    step = make_compact_step(cfg, classify_batch, emit_score=emit_score,
                             **quant)
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def pow2_group_sizes(mega_n: int) -> tuple[int, ...]:
    """The adaptive-coalescing ladder: every power-of-two group size
    in ``[2, mega_n]``, LARGEST first (the dispatch loop picks the
    first size the backlog fills, so order encodes preference).

    Power-of-two rungs keep the staged-variant count logarithmic in
    ``mega_n`` (each size is its own compiled scan artifact, audited
    and cached like any other variant) while guaranteeing any backlog
    ``b`` dispatches in at most ``popcount(b)`` groups + singles —
    the fixed-``mega_n`` policy's worst case was ``b`` singles the
    moment ``b < mega_n``."""
    sizes: list[int] = []
    g = 2
    while g <= mega_n:
        sizes.append(g)
        g *= 2
    return tuple(reversed(sizes))


def rung_for_volume(volume: int, sizes: tuple[int, ...]) -> int:
    """THE ladder rung-selection policy: the largest rung of ``sizes``
    (largest-first, :func:`pow2_group_sizes` order) that ``volume``
    sealed batches fill, else 1 (singles).  One copy shared by the
    engine's backlog dispatch (``Engine._rung_for``) and the
    predictive governor's pre-warm sizing (``engine/predict.py``) —
    the forecast must pre-warm exactly the rung the backlog will
    dispatch through, so the two callers cannot be allowed to drift."""
    return next((s for s in sizes if s <= volume), 1)


def make_jitted_compact_megastep(
    cfg: FsxConfig,
    classify_batch,
    n_chunks: int,
    donate: bool = True,
    **quant,
):
    """N micro-batches in ONE dispatch: a ``lax.scan`` over the leading
    axis of a ``[N, B+1, 4]`` stacked compact wire buffer, carrying
    (table, stats) through the chain — the "persistent on-device loop"
    prototype (SURVEY.md §7.4.1).

    One jit call amortizes the fixed dispatch cost over ``n_chunks``
    batches, which is the difference between dispatch-bound and
    compute-bound throughput wherever per-dispatch overhead rivals the
    step time (what that overhead is on the chip: not measured yet,
    ROADMAP speed item 4c).  Latency trade: records wait for the
    whole group to fill before the dispatch, so the engine reserves
    mega-dispatch for load regimes where the group fills faster than
    one dispatch turnaround.

    Returns ``mega(table, stats, params, raws) -> (table, stats, outs)``
    where outs fields are stacked ``[N, B]`` (``now``/``route_drop``:
    ``[N]``) — EXCEPT ``outs.wire``, which is the N chunks' compact
    verdict wires merged into ONE (:func:`merge_verdict_wires`), so a
    mega dispatch still costs a single O(verdict_k) readback.
    """
    base = make_compact_step(cfg, classify_batch, **quant)
    return wrap_megastep(base, n_chunks, (0, 1) if donate else (),
                         key_words(cfg.table.key_bits))


def make_compact_megastep_family(
    cfg: FsxConfig,
    classify_batch,
    sizes: tuple[int, ...],
    donate: bool = True,
    **quant,
) -> dict:
    """One jitted megastep per group size, sharing ONE traced base step
    (``{n: mega_n}``, keys sorted descending).  The adaptive dispatch
    ladder (:func:`pow2_group_sizes`) compiles each rung once at boot;
    sharing the base step keeps the N traces from re-staging the whole
    fused pipeline per size."""
    base = make_compact_step(cfg, classify_batch, **quant)
    kw = key_words(cfg.table.key_bits)
    return {
        n: wrap_megastep(base, n, (0, 1) if donate else (), kw)
        for n in sorted(sizes, reverse=True)
    }


def wrap_megastep(base, n_chunks: int, donate_argnums: tuple,
                  key_words: int = 1):
    """Shared mega-dispatch wrapper: ``lax.scan`` of ``base`` over a
    ``[N, ...]`` stacked wire group, carrying (table, stats).  Both the
    single-device and the sharded mega factories build on this, so the
    chunk-count guard and scan-carry logic cannot drift.  The N per-chunk
    compact verdict wires merge into ONE after the scan (the engine's
    group sink fetches one O(verdict_k) buffer per mega entry, not
    ``[N, 2K+4]`` stacks).  ``key_words`` is the width of the wires'
    keys (:func:`merge_verdict_wires`)."""

    def mega(table, stats, params, raws):
        if raws.shape[0] != n_chunks:
            raise ValueError(
                f"mega-step compiled for {n_chunks} chunks, got a "
                f"[{raws.shape[0]}, ...] group (any other leading dim "
                "would silently recompile)")

        def body(carry, raw):
            tbl, st = carry
            tbl, st, out = base(tbl, st, params, raw)
            return (tbl, st), out

        (table, stats), outs = jax.lax.scan(body, (table, stats), raws)
        if outs.wire is not None:
            with jax.named_scope("fsx.emit"):
                outs = outs._replace(
                    wire=merge_verdict_wires(outs.wire, key_words))
        return table, stats, outs

    return jax.jit(mega, donate_argnums=donate_argnums)


def make_jitted_step(cfg: FsxConfig, classify_batch,
                     donate: bool = True,
                     emit_score: bool = False):
    """``jit`` the fused step, donating table+stats so the 1M-row state
    updates in place in HBM instead of being copied per batch
    (``donate=False`` is for references and the static passes)."""
    step = make_step(cfg, classify_batch, emit_score=emit_score)
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())
