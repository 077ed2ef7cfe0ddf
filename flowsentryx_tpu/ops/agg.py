"""Per-flow aggregation of a packet micro-batch — sort + segment ops.

The reference touches its per-IP map once *per packet*
(``fsx_kern.c:225-284``): at 10 Mpps that is 10M random map operations
per second.  The TPU plane instead aggregates each micro-batch by
source key first, so the state table is touched once per *(flow,
batch)*: a 2048-packet batch from a single-source flood becomes ONE
state transition.

``jnp.unique`` is not jittable (dynamic output shape); the jittable
equivalent is the classic sort → segment-boundary → ``segment_sum``
pattern with a static segment count equal to the batch size:

    keys   [B]  → sort → run heads → segment ids [B]
    reps   [B]  (padded: at most B distinct flows; tail is invalid)
    inv    [B]  maps each packet back to its flow's segment

Everything is fixed-shape, fuses under ``jit``, and shards cleanly.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: Sentinel sorted past every real key (source 0xFFFFFFFF =
#: 255.255.255.255 is never a legitimate unicast source).
#:
#: A numpy scalar, NOT ``jnp.uint32``: a module-level concrete
#: ``jax.Array`` starts a backend — and takes the chip — in whatever
#: process merely imports this module.  numpy scalars fold into the HLO
#: as literals.
INVALID_KEY = np.uint32(0xFFFFFFFF)


class FlowAgg(NamedTuple):
    """Micro-batch aggregated by flow key.

    ``rep_*`` arrays are ``[B]``-shaped and ``rep_valid`` says which
    entries are flows: that mask is the contract, not a position.
    :func:`aggregate` packs its flows to the front in key order and
    gives ``inv``, ``[B]`` mapping each input packet position to its
    flow's entry, so per-flow decisions broadcast back to packets as
    ``decision[inv]``.  The fused step
    (:func:`flowsentryx_tpu.ops.fused.make_step`) leaves each flow at
    the last position of its run in ITS sort's order, padding between,
    and hands verdicts back along the runs itself: its ``inv`` is
    ``None``.  Padding entries read ``INVALID_KEY`` and zeros.
    """

    rep_key: jnp.ndarray    # [B] uint32, INVALID_KEY padded
    rep_pkts: jnp.ndarray   # [B] f32: packets of this flow in the batch
    rep_bytes: jnp.ndarray  # [B] f32: bytes of this flow in the batch
    rep_ts: jnp.ndarray     # [B] f32: newest timestamp of this flow
    rep_valid: jnp.ndarray  # [B] bool
    inv: Any                # [B] int32: packet -> entry (None: fused step)


class KeySegments(NamedTuple):
    """Sort-based grouping of a key vector — the one copy of the
    sort → run-heads → segment-ids pattern this module and the
    owner-routed sharded step (parallel/step.py) both build on."""

    order: jnp.ndarray   # [B] int: argsort permutation (stable)
    sorted_key: jnp.ndarray  # [B]: keys in sorted order
    heads: jnp.ndarray   # [B] bool: True at each run start (sorted order)
    seg: jnp.ndarray     # [B] int32: segment id per sorted position
    inv: jnp.ndarray     # [B] int32: original position -> segment id


def segment_by_key(k: jnp.ndarray) -> KeySegments:
    """Group equal keys into contiguous segments via one stable sort."""
    order = jnp.argsort(k)  # stable; INVALID_KEY pads sort to the tail
    sk = k[order]
    heads = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    seg = (jnp.cumsum(heads) - 1).astype(jnp.int32)
    inv = jnp.zeros(k.shape, jnp.int32).at[order].set(seg)
    return KeySegments(order=order, sorted_key=sk, heads=heads, seg=seg,
                       inv=inv)


def aggregate(
    key: jnp.ndarray,
    pkt_len: jnp.ndarray,
    ts: jnp.ndarray,
    valid: jnp.ndarray,
) -> FlowAgg:
    """Group a ``[B]`` packet batch by source key (jit-safe, static shapes)."""
    b = key.shape[0]
    # Key sanitization: 0 is the hash table's empty-slot sentinel — a
    # spoofed saddr 0.0.0.0 must not masquerade as "empty" (it would
    # land state in slots that still look free and get clobbered).
    # Remap to 0xFFFFFFFE (255.255.255.254, not a legitimate unicast
    # source either) so such floods are tracked like any other key.
    key = jnp.where(key == 0, jnp.uint32(0xFFFFFFFE), key)
    k = jnp.where(valid, key, INVALID_KEY)

    ks = segment_by_key(k)
    order, sk, seg = ks.order, ks.sorted_key, ks.seg

    sv = valid[order]
    pkts = jax.ops.segment_sum(sv.astype(jnp.float32), seg, num_segments=b)
    bytes_ = jax.ops.segment_sum(
        jnp.where(sv, pkt_len[order], 0.0), seg, num_segments=b
    )
    ts_max = jax.ops.segment_max(
        jnp.where(sv, ts[order], -jnp.inf), seg, num_segments=b
    )

    # representative key per segment: the key at each segment head
    rep_key = jax.ops.segment_max(sk, seg, num_segments=b)
    # untouched segments (beyond the number of distinct keys) come back 0
    rep_valid = pkts > 0
    rep_key = jnp.where(rep_valid, rep_key, INVALID_KEY)
    ts_max = jnp.where(rep_valid, ts_max, 0.0)

    inv = ks.inv  # packet -> segment mapping in ORIGINAL order

    return FlowAgg(
        rep_key=rep_key,
        rep_pkts=pkts,
        rep_bytes=bytes_,
        rep_ts=ts_max,
        rep_valid=rep_valid,
        inv=inv,
    )
