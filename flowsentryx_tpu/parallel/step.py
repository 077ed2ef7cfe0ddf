"""Multi-device fused step: IP-hash-sharded state + owner-routed flows.

This is the scale-out analog of SURVEY.md §2.3's parallelism table:

* **"Sequence parallelism" analog** — the per-IP state table shards by
  IP hash across the mesh's ``ip`` axis.  A flow's owner device is
  given by the *top* hash bits, its slot within the owner's shard by
  the *low* bits — ownership and probing use disjoint bits, and a key's
  owner never changes, so limiter state never migrates between devices.
* **Data parallelism** — each device parses, scores, and locally
  aggregates its ``B/n`` slice of the packet batch (sort, classifier
  matmul, and segment ops all shrink with the mesh).
* **Flow routing** — local per-flow partial aggregates are routed to
  their owner device with one ``all_to_all`` (ICI); the owner merges
  partials (a flow's packets may land on several devices' slices),
  runs the table+limiter+ML core once per flow, and routes per-flow
  verdicts back with a second ``all_to_all``.  Nothing per-flow is
  replicated — this is what makes the step *scale* instead of merely
  not serialize (the round-3 design re-sorted the full batch on every
  device, so per-device work stayed O(B) no matter the mesh size).
* **Collectives per step** — 2 ``all_to_all`` (flow partials out,
  verdicts back) + 1 ``pmax`` (batch clock) + 1 ``psum`` (stat counts).

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
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flowsentryx_tpu.core.config import FsxConfig
from flowsentryx_tpu.parallel import layout
from flowsentryx_tpu.core.schema import (
    IpTableState, Verdict, make_table,
)
from flowsentryx_tpu.ops import agg, fused, hashtable

#: Re-export (the historical home): placement now derives from the
#: declarative partition rules in :mod:`flowsentryx_tpu.parallel.layout`.
shard_table = layout.shard_table


def make_sharded_table(cfg: FsxConfig, mesh: Mesh) -> IpTableState:
    """Fresh empty table of ``cfg.table.capacity`` rows, row-sharded."""
    return shard_table(make_table(cfg.table.capacity), mesh)


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
    local_tbl = dataclasses.replace(cfg.table, capacity=cfg.table.capacity // n_dev)
    local_cfg = dataclasses.replace(cfg, table=local_tbl)

    def device_step(table_shard, stats, params, batch):
        d = jax.lax.axis_index(axis)
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
        C = min(local_b, max(64, -(-2 * local_b // n_dev)))

        def sl(x):
            return jax.lax.dynamic_slice_in_dim(x, d * local_b, local_b)

        key_l, len_l = sl(batch.key), sl(batch.pkt_len)
        ts_l, valid_l = sl(batch.ts), sl(batch.valid)
        feat_l = jax.lax.dynamic_slice_in_dim(batch.feat, d * local_b, local_b)

        # --- local slice work: classifier + per-flow aggregation -----------
        score_l = classify_batch(params, feat_l)                 # [local_b]
        fa = agg.aggregate(key_l, len_l, ts_l, valid_l)
        mal_l = (score_l > cfg.model.threshold) & valid_l
        # per-local-flow COUNT of malicious records (vote evidence;
        # owner-side merge SUMS partials so a flow spanning slices
        # votes with its full record count)
        ml_l = (jnp.zeros((local_b,), jnp.float32)
                .at[fa.inv].add(mal_l.astype(jnp.float32)))
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

        # --- route local flow partials to their owner ----------------------
        h1 = hashtable.hash_u32(fa.rep_key, cfg.table.salt)
        owner = ((h1 >> (32 - k_bits)).astype(jnp.int32) if k_bits
                 else jnp.zeros_like(h1, jnp.int32))
        # rank of each flow within its owner bucket: one small sort by
        # owner + a cummax gives position-within-run
        ko = agg.segment_by_key(jnp.where(fa.rep_valid, owner, n_dev))
        idx = jnp.arange(local_b, dtype=jnp.int32)
        run_start = jax.lax.cummax(jnp.where(ko.heads, idx, 0))
        rank = (jnp.zeros((local_b,), jnp.int32)
                .at[ko.order].set(idx - run_start))

        routed = fa.rep_valid & (rank < C)
        overflow = fa.rep_valid & ~routed
        flat = jnp.where(routed, owner * C + rank, n_dev * C)    # park tail

        def scatter_send(vals, fill):
            ext = jnp.full((n_dev * C + 1,), fill, vals.dtype)
            ext = ext.at[flat].set(jnp.where(routed, vals, fill))
            return ext[: n_dev * C]

        bits = jax.lax.bitcast_convert_type
        send = jnp.stack(
            [
                scatter_send(fa.rep_key, agg.INVALID_KEY),
                scatter_send(bits(fa.rep_pkts, jnp.uint32), jnp.uint32(0)),
                scatter_send(bits(fa.rep_bytes, jnp.uint32), jnp.uint32(0)),
                scatter_send(bits(fa.rep_ts, jnp.uint32), jnp.uint32(0)),
                scatter_send(bits(ml_l, jnp.uint32), jnp.uint32(0)),
            ],
            axis=1,
        ).reshape(n_dev, C, 5)
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
        r = recv.reshape(n_dev * C, 5)                           # [R, 5]

        # --- owner side: merge per-source partials, run the flow core ------
        # A flow's packets may have landed on several source devices;
        # each contributed one partial (≤ n_dev duplicates per key).
        ks = agg.segment_by_key(r[:, 0])
        seg, sk = ks.seg, ks.sorted_key
        rn = n_dev * C
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
        m_key = jnp.where(m_valid, m_key, agg.INVALID_KEY)
        m_ts = jnp.where(m_valid, m_ts, 0.0)
        inv2 = ks.inv                                            # entry→flow

        mfa = agg.FlowAgg(rep_key=m_key, rep_pkts=m_pkts, rep_bytes=m_bytes,
                          rep_ts=m_ts, rep_valid=m_valid, inv=inv2)
        new_shard, dec = fused.flow_step(
            local_cfg, table_shard, mfa, m_valid, m_ml, now
        )

        # --- route per-flow verdicts back to the source devices ------------
        back = jax.lax.all_to_all(
            dec.flow_verdict[inv2].reshape(n_dev, C), axis,
            split_axis=0, concat_axis=0,
        )  # back[o, c] = verdict of my local flow with (owner o, rank c)
        rep_verdict = jnp.where(
            routed,
            back[jnp.clip(owner, 0, n_dev - 1), jnp.clip(rank, 0, C - 1)],
            int(Verdict.PASS),  # overflow: fail-open this batch (counted)
        )
        # the ML_RECORD_GATE sentinel rides the verdict all_to_all and
        # resolves per record HERE, where the local slice's scores live
        verdict_l = fused.resolve_record_verdicts(rep_verdict, fa.inv,
                                                  mal_l, valid_l)

        # --- stats: local counts, one psum ---------------------------------
        route_drop_l = jnp.sum(
            jnp.where(valid_l, overflow[fa.inv].astype(jnp.uint32),
                      jnp.uint32(0))
        )
        # each shard's probe decides on its own keys whether it reads
        # last_seen; a batch counts once in stale_reads if any did.
        # That joins the ONE existing scalar psum, and where the table
        # ages rows out so do the eviction count and the owners' counts
        # of flows left with no row (a flow has one owner, so they add
        # up) — the audited collective census does not grow
        count_parts = [
            fused.count_verdicts(verdict_l, valid_l),
            route_drop_l[None].astype(jnp.uint32),
            dec.read_seen[None].astype(jnp.uint32),
        ]
        if n_evict_l is not None:
            count_parts += [n_evict_l[None], dec.untracked[None]]
        counts = jax.lax.psum(jnp.concatenate(count_parts), axis)
        new_stats = fused.update_stats_from_counts(
            stats, counts[:4], counts[5] > 0,
            None if n_evict_l is None else counts[7])
        if n_evict_l is not None:
            from flowsentryx_tpu.core.schema import u64_add

            new_stats = new_stats._replace(
                evicted=u64_add(new_stats.evicted, counts[6]))

        blk_key = jnp.where(dec.newly_blocked, m_key,
                            agg.INVALID_KEY)                      # owner-side
        blk_until = jnp.where(dec.newly_blocked,
                              dec.new_blocked_until, 0.0)
        # Compact verdict wire, the sharded way: each owner shard
        # compacts ITS newly-blocked flows (a flow blocks only on its
        # owner, so shards never duplicate keys), one all_gather moves
        # the K-slot buffers — O(n·K) over ICI, tiny next to the two
        # batch all_to_alls — and a second compaction folds them into
        # ONE replicated wire.  route_drop and the batch clock ride the
        # same buffer, so the host's steady-state readback is a single
        # O(K) fetch with no extra scalar round trips.  Overflow
        # (total > K) is exact from the psum'd true counts: a shard
        # losing entries locally implies total > K.
        k_max = cfg.batch.verdict_k
        if k_max:
            lk, lu, lcount = fused.compact_blocklist(blk_key, blk_until,
                                                     k_max)
            gk = jax.lax.all_gather(lk, axis)              # [n_dev, K]
            gu = jax.lax.all_gather(lu, axis)
            total = jax.lax.psum(lcount, axis)
            ck, cu, _ = fused.compact_blocklist(
                gk.reshape(-1), gu.reshape(-1), k_max)
            bits2 = jax.lax.bitcast_convert_type
            wire = jnp.concatenate([
                ck, bits2(cu, jnp.uint32),
                jnp.stack([total, (total > k_max).astype(jnp.uint32),
                           counts[4],
                           bits2(now, jnp.uint32)]),
            ])
        else:
            wire = None

        out = fused.StepOutput(
            verdict=verdict_l.astype(jnp.uint8),                  # P(axis)→[B]
            score=score_l if emit_score else None,                # P(axis)→[B]
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
        return base(table, stats, params, decode(raw))

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
    import functools

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
