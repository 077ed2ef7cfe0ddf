"""Multi-device fused step: IP-hash-sharded state + owner-routed flows.

This is the scale-out analog of SURVEY.md §2.3's parallelism table:

* **"Sequence parallelism" analog** — the per-IP state table shards by
  IP hash across the mesh's ``ip`` axis.  A flow's owner device is
  given by the *top* hash bits, its slot within the owner's shard by
  the *low* bits — ownership and probing use disjoint bits, and a key's
  owner never changes, so limiter state never migrates between devices.
* **Data parallelism** — each device parses, scores, and locally
  aggregates its ``B/n`` slice of the packet batch (classifier matmul,
  and the one-device step's one sort and segmented scan,
  :func:`aggregate_slice`, all shrink with the mesh).
* **Flow routing** — local per-flow partial aggregates are routed to
  their owner device with one ``all_to_all`` (ICI); the owner merges
  partials (a flow's packets may land on several devices' slices),
  runs the table+limiter+ML core once per flow — on the smallest of
  a quarter, nine sixteenths or all of its ``n·C`` received rows that
  holds the flows (:func:`owner_flow_step`) — and routes per-flow
  verdicts back with a second ``all_to_all``.  Nothing per-flow is
  replicated — this is what makes the step *scale* instead of merely
  not serialize (the round-3 design re-sorted the full batch on every
  device, so per-device work stayed O(B) no matter the mesh size).
* **Collectives per step, by scope** — under ``fsx.route``, the 2
  ``all_to_all`` (flow partials out, verdicts back) with the send
  packing and the unpacking round them; under ``fsx.decode``, 1
  ``pmax`` (the batch clock); under ``fsx.emit``, 1 ``psum`` (stat
  counts) and, with a compact verdict wire, 2 ``all_gather`` and 1
  ``psum`` of the shards' blocks.  ``fsx.merge`` is the owner-side sort
  and segment reductions; the local work keeps the one-device step's
  names (``fsx.classify``, ``fsx.aggregate``; ``fsx.probe`` and
  ``fsx.update`` inside :func:`~flowsentryx_tpu.ops.fused.flow_step`;
  ``fsx.evict`` where the table ages rows out).  No ``fsx.*`` scope
  holds another: a device trace files an operation under its first.

Routing capacity: each device sends at most ``C ≈ 2·(B/n)/n`` flows to
each owner — 2× the uniform-hash expectation.  Ownership hashing mixes
in the boot-time random salt (``TableConfig.salt``), so an attacker
cannot precompute a spoofed-source flood that lands every flow on one
owner.  Overflow remains possible in principle (natural skew at tiny
batch/mesh ratios, or a disclosed salt) and is handled
fail-open, the framework-wide discipline (SURVEY.md §5.3): overflowed
flows PASS this batch, skip their limiter update, and are counted in
``StepOutput.route_drop`` — visible, bounded, and backstopped by the
in-kernel limiter, which stands alone by design.

Everything runs under ``jax.shard_map`` over a
:func:`~flowsentryx_tpu.parallel.mesh.make_mesh` mesh; the same code
compiles for 8 virtual CPU devices (tests) or a v5e pod slice.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flowsentryx_tpu.core.config import FsxConfig
from flowsentryx_tpu.parallel import layout
from flowsentryx_tpu.core.schema import (
    IpTableState, Verdict, make_table, u64_add,
)
from flowsentryx_tpu.ops import agg, fused, hashtable

#: Re-export (the historical home): placement now derives from the
#: declarative partition rules in :mod:`flowsentryx_tpu.parallel.layout`.
shard_table = layout.shard_table

#: u32 words of one routed flow partial (key, packets, bytes, newest
#: time, malicious count); a verdict comes back as one int32.
ROUTE_WORDS = 5


def route_capacity(batch: int, n_dev: int) -> int:
    """``C``: flows one device may send each owner in a step — 2× the
    uniform-hash expectation ``(batch / n_dev) / n_dev``, floored at 64
    so tiny test batches don't route at capacity 1, and never more than
    a device's slice.  Each ``all_to_all`` moves ``n_dev × C`` rows a
    device."""
    local_b = batch // n_dev
    return min(local_b, max(64, -(-2 * local_b // n_dev)))


def empty_table_fn(capacity: int, mesh: Mesh):
    """The jitted maker of an empty ``capacity``-row table, row-sharded
    as it is made: each device fills its own shard, so a table that no
    one device can hold (``c5-l34-mesh4``: 2^28 rows, 18.3 GB) is never
    whole on one."""
    shardings = IpTableState(*(layout.sharding_for(mesh, f"table.{f}")
                               for f in IpTableState._fields))
    return jax.jit(functools.partial(make_table, capacity),
                   out_shardings=shardings)


def make_sharded_table(cfg: FsxConfig, mesh: Mesh) -> IpTableState:
    """Fresh empty table of ``cfg.table.capacity`` rows, row-sharded."""
    return empty_table_fn(cfg.table.capacity, mesh)()


class SliceRuns(NamedTuple):
    """A device's slice in its aggregation sort's order: what hands each
    flow's verdict back to its records (:func:`slice_verdicts`)."""

    order: jnp.ndarray  # [b] int32: slice position of each sorted record
    tail: jnp.ndarray   # [b] bool: last position of each key's run
    mal: jnp.ndarray    # [b] bool: the record scored malicious
    valid: jnp.ndarray  # [b] bool


def aggregate_slice(key, pkt_len, ts, valid, mal
                    ) -> tuple[agg.FlowAgg, jnp.ndarray, SliceRuns]:
    """The one-device step's aggregation (``fused.make_step``'s
    ``fsx.aggregate``) on a device's ``[b]`` slice, where no table
    decides a slot: one stable sort by the sanitised key (0 →
    ``0xFFFFFFFE``, an invalid row → ``INVALID_KEY``, as
    :func:`agg.aggregate`) carrying the record's length, time, and one
    word of its place and two flags, then one :func:`fused.scan_runs`
    over the runs.  No gather and no scatter.

    Returns ``(flows, [b] f32 malicious records of each flow, runs)``.
    The flows lie at their runs' last positions, in key order, under
    ``rep_valid``, padding between (``inv`` is ``None``); they, their
    counts and their order among themselves are :func:`agg.aggregate`'s
    bit for bit while a run's bytes stay under 2^24 (whole numbers)."""
    b = key.shape[0]
    key = jnp.where(key == 0, jnp.uint32(0xFFFFFFFE), key)
    key = jnp.where(valid, key, agg.INVALID_KEY)
    tag = (jnp.arange(b, dtype=jnp.int32) * 4
           + 2 * mal.astype(jnp.int32) + valid.astype(jnp.int32))
    key_s, tag_s, len_s, ts_s = jax.lax.sort((key, tag, pkt_len, ts),
                                             num_keys=1)
    valid_s = (tag_s & 1) > 0
    mal_s = (tag_s & 2) > 0
    one = jnp.ones((1,), bool)
    head = jnp.concatenate([one, key_s[1:] != key_s[:-1]])
    tail = jnp.concatenate([head[1:], one])
    runs = fused.scan_runs(
        head,
        jnp.stack([valid_s.astype(jnp.float32),
                   jnp.where(valid_s, len_s, 0.0),
                   mal_s.astype(jnp.float32),
                   jnp.where(valid_s, ts_s, -jnp.inf)]),
        maxed=(False, False, False, True))
    rep_valid = tail & (runs[0] > 0)
    pkts, bytes_, ml_count, ts_max = jnp.where(rep_valid, runs, 0.0)
    fa = agg.FlowAgg(rep_key=jnp.where(rep_valid, key_s, agg.INVALID_KEY),
                     rep_pkts=pkts, rep_bytes=bytes_, rep_ts=ts_max,
                     rep_valid=rep_valid, inv=None)
    return fa, ml_count, SliceRuns(order=tag_s >> 2, tail=tail, mal=mal_s,
                                   valid=valid_s)


def slice_verdicts(runs: SliceRuns, rep_verdict: jnp.ndarray) -> jnp.ndarray:
    """Each record's verdict, in the slice's order, from its flow's
    verdict at the run's tail (``[b]`` int32, codes under 128,
    ``ML_RECORD_GATE`` among them): spread over the run, gated record
    by record, then one sort keyed on the aggregation sort's
    permutation puts it back, the code in the two bits under the
    place."""
    verdict_s = fused.gate_record_verdicts(
        fused.spread_from_tails(runs.tail, rep_verdict), runs.mal,
        runs.valid)
    return jax.lax.sort(runs.order * 4 + verdict_s, is_stable=False) & 3


def fail_open_records(fa: agg.FlowAgg, overflow: jnp.ndarray) -> jnp.ndarray:
    """``[]`` uint32: the records of the flows that ``overflow`` (``[b]``
    bool, at the flows' places) marks, counted at each flow.  A flow
    holds at most the slice's ``b`` records: the clip states the bound
    that ``fsx ranges`` cannot read through the scan's float sums."""
    b = overflow.shape[0]
    pkts = jnp.clip(fa.rep_pkts, 0, b).astype(jnp.uint32)
    return jnp.sum(jnp.where(overflow, pkts, 0))


def merge_partials(r: jnp.ndarray) -> tuple[agg.FlowAgg, jnp.ndarray]:
    """The owner's merge (scope ``fsx.merge``): the ``[R, ROUTE_WORDS]``
    partials it received, one per (source device, flow), summed by key
    into ``(flows, malicious counts)``.  ``inv`` maps each received row
    to its flow.  The keys' sort puts ``INVALID_KEY`` last and the
    segment reductions put flow ``i`` at index ``i``, so the flows are
    the prefix ``[0, n_own)`` of the ``R`` rows (test-pinned)."""
    bits = jax.lax.bitcast_convert_type
    rn = r.shape[0]
    ks = agg.segment_by_key(r[:, 0])
    seg, sk = ks.seg, ks.sorted_key
    fvalid = sk != agg.INVALID_KEY

    def seg_sum(v):
        return jax.ops.segment_sum(
            jnp.where(fvalid, v[ks.order], 0.0), seg, num_segments=rn)

    def seg_max(v, fill):
        return jax.ops.segment_max(
            jnp.where(fvalid, v[ks.order], fill), seg, num_segments=rn)

    m_pkts = seg_sum(bits(r[:, 1], jnp.float32))
    m_bytes = seg_sum(bits(r[:, 2], jnp.float32))
    m_ts = seg_max(bits(r[:, 3], jnp.float32), -jnp.inf)
    m_ml = seg_sum(bits(r[:, 4], jnp.float32))  # vote-count merge
    m_key = jax.ops.segment_max(sk, seg, num_segments=rn)
    m_valid = m_pkts > 0
    return agg.FlowAgg(rep_key=jnp.where(m_valid, m_key, agg.INVALID_KEY),
                       rep_pkts=m_pkts, rep_bytes=m_bytes,
                       rep_ts=jnp.where(m_valid, m_ts, 0.0),
                       rep_valid=m_valid, inv=ks.inv), m_ml


def owner_rungs(rn: int) -> tuple[int, ...]:
    """The row counts an owner may run the flow core on, smallest first,
    of its ``rn = n·C`` received rows: a quarter; nine sixteenths, since
    ``C`` is twice the uniform share and a full batch of distinct flows
    gives an owner about ``rn / 2`` of them, here with an eighth more;
    and all of them."""
    return tuple(sorted({-(-rn // 4), -(-9 * rn // 16), rn}))


def owner_flow_step(cfg: FsxConfig, table: IpTableState, mfa: agg.FlowAgg,
                    ml_count: jnp.ndarray, now: jnp.ndarray):
    """:func:`~flowsentryx_tpu.ops.fused.flow_step` over the merged flows'
    rows alone: the smallest of :func:`owner_rungs` that holds them, and
    not all ``n·C``, which are sized for the worst skew and mostly
    padding.  Returns ``(table, decision over all n·C rows, [] uint32
    rows run)``.

    Rows past the last flow cannot change a flow's outcome (the probe
    masks them, the slot arbitration parks them after a stable sort, the
    core scatters tracked rows only), so table, verdicts and counters
    are the full-size pass's bit for bit; the decision's rows past the
    rung read what a padding row reads there (``PASS``, 0, ``False``).

    Each rung is a loop of 0 or 1 trips that carries the table, so the
    shard is updated in place whichever runs: a conditional would copy
    the table it returns every batch (``ops/fused.py``'s note on
    conditionals, ``audit/graph.py::check_inplace``)."""
    rn = mfa.rep_key.shape[0]
    rungs = owner_rungs(rn)
    aging = cfg.table.evict_ttl_s > 0
    with jax.named_scope("fsx.merge"):
        # the end of the last flow: n_own where the flows are a prefix
        end = jnp.max(jnp.where(mfa.rep_valid,
                                jnp.arange(1, rn + 1, dtype=jnp.int32), 0))
        pick = sum((end > L).astype(jnp.int32) for L in rungs[:-1])
        trips = [(pick == i).astype(jnp.int32) for i in range(len(rungs))]
        rows = jnp.asarray(rungs, jnp.uint32)[pick]
        heads = [(jax.tree.map(lambda x, L=L: x[:L], mfa._replace(inv=None)),
                  ml_count[:L]) for L in rungs]
        dec = fused.FlowDecision(  # the rows no rung runs on
            flow_verdict=jnp.full((rn,), int(Verdict.PASS), jnp.int32),
            new_blocked_until=jnp.zeros((rn,), jnp.float32),
            newly_blocked=jnp.zeros((rn,), bool),
            tracked=jnp.zeros((rn,), bool),
            read_seen=jnp.zeros((), bool),
            untracked=jnp.zeros((), jnp.uint32) if aging else None)

    def rung(head, carry):
        # a trip sets the count to its bound: no counter arithmetic,
        # and no trip count the compiler can read off the loop
        _, n, t, _ = carry
        fa, ml = head
        t, dec = fused.flow_step(cfg, t, fa, fa.rep_valid, ml, now)
        with jax.named_scope("fsx.update"):
            tail = (0, rn - fa.rep_key.shape[0])
            return n, n, t, dec._replace(
                flow_verdict=jnp.pad(dec.flow_verdict, tail,
                                     constant_values=int(Verdict.PASS)),
                new_blocked_until=jnp.pad(dec.new_blocked_until, tail),
                newly_blocked=jnp.pad(dec.newly_blocked, tail),
                tracked=jnp.pad(dec.tracked, tail))

    for head, n in zip(heads, trips):
        _, _, table, dec = jax.lax.while_loop(
            lambda c: c[0] < c[1], functools.partial(rung, head),
            (jnp.int32(0), n, table, dec))
    return table, dec, rows


def make_sharded_step(
    cfg: FsxConfig,
    classify_batch: Callable[[Any, jnp.ndarray], jnp.ndarray],
    mesh: Mesh,
    donate: bool = True,
    emit_score: bool = False,
):
    """Build the jitted multi-device step.

    Signature matches the single-device
    :func:`~flowsentryx_tpu.ops.fused.make_jitted_step`:
    ``step(table, stats, params, batch) -> (table, stats, out)`` — the
    engine swaps one for the other based on mesh size.  ``table`` must
    be sharded with :func:`shard_table`; batch/params/stats replicated.
    """
    axis = mesh.axis_names[0]
    n_dev = int(mesh.devices.size)
    k_bits = n_dev.bit_length() - 1  # n_dev = 2**k_bits (validated by make_mesh)
    if cfg.table.capacity % n_dev:
        raise ValueError("table capacity must divide by device count")
    local_tbl = dataclasses.replace(
        cfg.table, capacity=cfg.table.capacity // n_dev, shards=1)
    local_cfg = dataclasses.replace(cfg, table=local_tbl)

    def device_step(table_shard, stats, params, batch):
        b = batch.feat.shape[0]
        if b % n_dev:
            raise ValueError(
                f"batch size {b} must divide by the {n_dev}-device mesh "
                "(pad the batch; decode_records already pads to a static size)"
            )
        local_b = b // n_dev
        # Per-source→owner routing capacity: 2× the uniform-hash
        # expectation, floored so tiny test batches don't route at
        # capacity 1 (module docstring: overflow is fail-open+counted).
        C = route_capacity(b, n_dev)

        with jax.named_scope("fsx.decode"):
            d = jax.lax.axis_index(axis)

            def sl(x):
                return jax.lax.dynamic_slice_in_dim(x, d * local_b, local_b)

            key_l, len_l = sl(batch.key), sl(batch.pkt_len)
            ts_l, valid_l = sl(batch.ts), sl(batch.valid)
            feat_l = sl(batch.feat)
            now = jax.lax.pmax(jnp.max(jnp.where(valid_l, ts_l, 0.0)), axis)

        # In-step aging epoch, the shard-local way: each device sweeps
        # its OWN table rows (an elementwise pass — nothing crosses the
        # mesh), gated by the replicated batch counter so every shard
        # fires the same epochs; the per-shard count rides the existing
        # stats psum below.  Statically absent when disabled.
        n_evict_l = None
        if cfg.table.evict_ttl_s > 0:
            with jax.named_scope("fsx.evict"):
                table_shard, n_evict_l = fused.evict_idle_epoch(
                    cfg.table, table_shard, stats, now)

        # --- local slice work: classifier + per-flow aggregation -----------
        with jax.named_scope("fsx.classify"):
            score_l = classify_batch(params, feat_l)             # [local_b]
            mal_l = (score_l > cfg.model.threshold) & valid_l
        with jax.named_scope("fsx.aggregate"):
            # ml_l: per-local-flow COUNT of malicious records (vote
            # evidence; owner-side merge SUMS partials so a flow
            # spanning slices votes with its full record count)
            fa, ml_l, runs_l = aggregate_slice(key_l, len_l, ts_l, valid_l,
                                               mal_l)

        # --- route local flow partials to their owner ----------------------
        bits = jax.lax.bitcast_convert_type
        with jax.named_scope("fsx.route"):
            h1 = hashtable.hash_u32(fa.rep_key, cfg.table.salt)
            owner = ((h1 >> (32 - k_bits)).astype(jnp.int32) if k_bits
                     else jnp.zeros_like(h1, jnp.int32))
            # rank of each flow within its owner bucket: one small sort
            # by owner + a cummax gives position-within-run
            ko = agg.segment_by_key(jnp.where(fa.rep_valid, owner, n_dev))
            idx = jnp.arange(local_b, dtype=jnp.int32)
            run_start = jax.lax.cummax(jnp.where(ko.heads, idx, 0))
            rank = (jnp.zeros((local_b,), jnp.int32)
                    .at[ko.order].set(idx - run_start))

            routed = fa.rep_valid & (rank < C)
            overflow = fa.rep_valid & ~routed
            flat = jnp.where(routed, owner * C + rank, n_dev * C)  # park tail

            def scatter_send(vals, fill):
                ext = jnp.full((n_dev * C + 1,), fill, vals.dtype)
                ext = ext.at[flat].set(jnp.where(routed, vals, fill))
                return ext[: n_dev * C]

            send = jnp.stack(
                [
                    scatter_send(fa.rep_key, agg.INVALID_KEY),
                    scatter_send(bits(fa.rep_pkts, jnp.uint32), jnp.uint32(0)),
                    scatter_send(bits(fa.rep_bytes, jnp.uint32), jnp.uint32(0)),
                    scatter_send(bits(fa.rep_ts, jnp.uint32), jnp.uint32(0)),
                    scatter_send(bits(ml_l, jnp.uint32), jnp.uint32(0)),
                ],
                axis=1,
            ).reshape(n_dev, C, ROUTE_WORDS)
            recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
            r = recv.reshape(n_dev * C, ROUTE_WORDS)              # [R, 5]

        # --- owner side: merge per-source partials, run the flow core ------
        # A flow's packets may have landed on several source devices;
        # each contributed one partial (≤ n_dev duplicates per key).
        with jax.named_scope("fsx.merge"):
            mfa, m_ml = merge_partials(r)
        # fsx.probe and fsx.update, inside, on the rows that hold flows
        new_shard, dec, owner_rows_l = owner_flow_step(
            local_cfg, table_shard, mfa, m_ml, now)

        # --- route per-flow verdicts back to the source devices ------------
        with jax.named_scope("fsx.route"):
            back = jax.lax.all_to_all(
                dec.flow_verdict[mfa.inv].reshape(n_dev, C), axis,
                split_axis=0, concat_axis=0,
            )  # back[o, c] = verdict of my local flow with (owner o, rank c)
            rep_verdict = jnp.where(
                routed,
                back[jnp.clip(owner, 0, n_dev - 1), jnp.clip(rank, 0, C - 1)],
                int(Verdict.PASS),  # overflow: fail-open this batch (counted)
            )

        with jax.named_scope("fsx.emit"):
            # the ML_RECORD_GATE sentinel rides the verdict all_to_all
            # and resolves per record HERE, where the local slice's
            # scores live
            verdict_l = slice_verdicts(runs_l, rep_verdict)

            # --- stats: local counts, one psum -----------------------------
            route_drop_l = fail_open_records(fa, overflow)
            # each shard's probe decides on its own keys whether it reads
            # last_seen; a batch counts once in stale_reads if any did.
            # That joins the ONE existing scalar psum, and where the table
            # ages rows out so do the eviction count and the owners'
            # counts of flows left with no row (a flow has one owner, so
            # they add up); the owners' row counts join it too — the
            # audited collective census does not grow
            count_parts = [
                fused.count_verdicts(verdict_l, valid_l),
                route_drop_l[None].astype(jnp.uint32),
                dec.read_seen[None].astype(jnp.uint32),
                owner_rows_l[None],
            ]
            if n_evict_l is not None:
                count_parts += [n_evict_l[None], dec.untracked[None]]
            counts = jax.lax.psum(jnp.concatenate(count_parts), axis)
            new_stats = fused.update_stats_from_counts(
                stats, counts[:4], counts[5] > 0,
                None if n_evict_l is None else counts[8])
            # an empty batch (warm()'s) leaves every counter as it was
            new_stats = new_stats._replace(owner_rows=u64_add(
                stats.owner_rows,
                jnp.where(counts[:4].sum() > 0, counts[6], jnp.uint32(0))))
            if n_evict_l is not None:
                new_stats = new_stats._replace(
                    evicted=u64_add(new_stats.evicted, counts[7]))

            blk_key = jnp.where(dec.newly_blocked, mfa.rep_key,
                                agg.INVALID_KEY)                  # owner-side
            blk_until = jnp.where(dec.newly_blocked,
                                  dec.new_blocked_until, 0.0)
            # Compact verdict wire, the sharded way: each owner shard
            # compacts ITS newly-blocked flows (a flow blocks only on its
            # owner, so shards never duplicate keys), one all_gather moves
            # the K-slot buffers — O(n·K) over ICI, tiny next to the two
            # batch all_to_alls — and a second compaction folds them into
            # ONE replicated wire.  route_drop and the batch clock ride
            # the same buffer, so the host's steady-state readback is a
            # single O(K) fetch with no extra scalar round trips.
            # Overflow (total > K) is exact from the psum'd true counts: a
            # shard losing entries locally implies total > K.
            k_max = cfg.batch.verdict_k
            if k_max:
                lk, lu, lcount = fused.compact_blocklist(blk_key, blk_until,
                                                         k_max)
                gk = jax.lax.all_gather(lk, axis)              # [n_dev, K]
                gu = jax.lax.all_gather(lu, axis)
                total = jax.lax.psum(lcount, axis)
                ck, cu, _ = fused.compact_blocklist(
                    gk.reshape(-1), gu.reshape(-1), k_max)
                wire = jnp.concatenate([
                    ck, bits(cu, jnp.uint32),
                    jnp.stack([total, (total > k_max).astype(jnp.uint32),
                               counts[4],
                               bits(now, jnp.uint32)]),
                ])
            else:
                wire = None

            out = fused.StepOutput(
                verdict=verdict_l.astype(jnp.uint8),              # P(axis)→[B]
                score=score_l if emit_score else None,            # P(axis)→[B]
                block_key=blk_key,
                block_until=blk_until,
                now=now,
                route_drop=counts[4],
                wire=wire,
            )
        return new_shard, new_stats, out

    # in/out placement comes from the declarative rule table
    # (parallel/layout.py) — the one layout declaration the engine's
    # H2D path and the checkpoint restore path also derive from
    table_specs = layout.table_specs(axis)
    stats_specs = layout.stats_specs()
    out_specs = fused.StepOutput(
        verdict=P(axis), score=P(axis) if emit_score else None,
        block_key=P(axis), block_until=P(axis),
        now=P(), route_drop=P(),
        wire=P() if cfg.batch.verdict_k else None,
    )

    sharded = jax.shard_map(
        device_step,
        mesh=mesh,
        in_specs=(table_specs, stats_specs, P(), P()),
        out_specs=(table_specs, stats_specs, out_specs),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def _make_sharded_wire_step(cfg, classify_batch, mesh, donate, decode,
                            emit_score=False):
    """Shared wrapper: replicated wire buffer → on-device ``decode`` →
    the shard-mapped step.  The wire enters as ONE contiguous H2D
    transfer (tiny next to the sharded state); all field extraction
    fuses into the jit."""
    base = make_sharded_step(cfg, classify_batch, mesh, donate=False,
                             emit_score=emit_score)

    def step(table, stats, params, raw):
        with jax.named_scope("fsx.decode"):
            batch = decode(raw)
        return base(table, stats, params, batch)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_sharded_raw_step(
    cfg: FsxConfig,
    classify_batch: Callable[[Any, jnp.ndarray], jnp.ndarray],
    mesh: Mesh,
    donate: bool = True,
    emit_score: bool = False,
):
    """Sharded step over the RAW ring wire format — the multi-device
    twin of :func:`~flowsentryx_tpu.ops.fused.make_jitted_raw_step`,
    with the same ``step(table, stats, params, raw)`` signature, so the
    serving :class:`~flowsentryx_tpu.engine.engine.Engine` swaps it in
    whenever its mesh spans more than one device."""
    from flowsentryx_tpu.core import schema

    return _make_sharded_wire_step(cfg, classify_batch, mesh, donate,
                                   schema.decode_raw,
                                   emit_score=emit_score)


def make_sharded_compact_step(
    cfg: FsxConfig,
    classify_batch: Callable[[Any, jnp.ndarray], jnp.ndarray],
    mesh: Mesh,
    donate: bool = True,
    emit_score: bool = False,
    **quant,
):
    """Sharded step over the COMPACT 16 B wire format — the multi-device
    twin of :func:`~flowsentryx_tpu.ops.fused.make_jitted_compact_step`.
    ``**quant`` are the wire-quantizer kwargs
    (:func:`~flowsentryx_tpu.core.schema.wire_quant_for`); the batch
    enters replicated and dequantizes on device before the shard-mapped
    step, so the multi-chip engine keeps the 3× wire-byte saving."""
    from flowsentryx_tpu.core import schema

    return _make_sharded_wire_step(
        cfg, classify_batch, mesh, donate,
        functools.partial(schema.decode_compact, **quant),
        emit_score=emit_score,
    )


def make_sharded_compact_megastep(
    cfg: FsxConfig,
    classify_batch: Callable[[Any, jnp.ndarray], jnp.ndarray],
    mesh: Mesh,
    n_chunks: int,
    donate: bool = True,
    **quant,
):
    """N micro-batches in ONE dispatch over the device mesh — the
    multi-device twin of
    :func:`~flowsentryx_tpu.ops.fused.make_jitted_compact_megastep`.

    A ``lax.scan`` carries the SHARDED (table, stats) through N
    shard-mapped steps inside one jit: the per-dispatch fixed cost is
    paid once per group while every chunk still runs the full
    owner-routed all_to_all/psum pipeline, so trajectory parity with N
    sequential sharded dispatches holds by construction (test-pinned).
    Outs fields stack to ``[N, ...]`` exactly like the single-device
    megastep, which is what the serving engine's group sink expects.
    Donation matches the module's table-only policy (the replicated
    stats output cannot alias a single-device input buffer anyway).
    """
    base = make_sharded_compact_step(cfg, classify_batch, mesh,
                                     donate=False, **quant)
    return fused.wrap_megastep(base, n_chunks, (0,) if donate else ())


def make_sharded_compact_megastep_family(
    cfg: FsxConfig,
    classify_batch: Callable[[Any, jnp.ndarray], jnp.ndarray],
    mesh: Mesh,
    sizes: tuple[int, ...],
    donate: bool = True,
    **quant,
) -> dict:
    """One jitted sharded megastep per group size over ONE shard-mapped
    base step — the multi-device twin of
    :func:`~flowsentryx_tpu.ops.fused.make_compact_megastep_family`.
    The adaptive engine dispatches the largest rung its backlog fills;
    every rung carries the full owner-routed collective pipeline per
    chunk, so per-rung parity with sequential sharded dispatches holds
    exactly as for the single fixed size."""
    base = make_sharded_compact_step(cfg, classify_batch, mesh,
                                     donate=False, **quant)
    return {
        n: fused.wrap_megastep(base, n, (0,) if donate else ())
        for n in sorted(sizes, reverse=True)
    }
