"""The declared input-range registry: what the prover may assume.

Every flattened input of a staged step variant gets a seed interval
here.  The discipline is *weakest workable assumption*: a seed narrower
than the dtype must be a contract something actually enforces —

* **wire record rows** are attacker-controlled bytes: every record
  word seeds FULL u32 (the prover derives field ranges from the
  decode's own masks/shifts, exactly as the BPF verifier re-derives
  packet bounds from the mask-before-add discipline);
* **wire metadata rows** are written by our own encoders under
  documented contracts: ``n_valid <= max_batch``
  (:func:`~flowsentryx_tpu.core.schema.encode_compact` /
  ``encode_raw``), and timestamp HI words bounded by the deployment
  horizon (:data:`~flowsentryx_tpu.core.schema.RANGE_DEPLOY_HORIZON_S`
  — the one place the registry and the runtime share named
  ``RANGE_*`` constants, so the prover's assumptions cannot drift from
  the code's clips);
* **table / stats state** seeds full dtype range (keys are arbitrary
  folded sources; counters wrap by design at their (lo, hi) pair);
* **quantized artifact scalars** seed their struct contracts
  (``in_zp``/``out_zp`` are quint8 zero-points in [0, 255]).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.ranges import interval as iv
from flowsentryx_tpu.ranges.interval import IVal

U32_MAX = (1 << 32) - 1

#: Quantized-artifact integer leaves with contracts narrower than
#: their dtype (LogRegParams docstring: quint8 observers).
PARAM_LEAF_RANGES: dict[str, tuple[int, int]] = {
    "in_zp": (0, 255),
    "out_zp": (0, 255),
    "log1p": (0, 1),
}


def _obj_full(shape, lo, hi) -> IVal:
    lo_a = np.empty(shape, dtype=object)
    hi_a = np.empty(shape, dtype=object)
    lo_a[...] = lo
    hi_a[...] = hi
    return IVal(lo_a, hi_a)


def wire_seed(shape: tuple, wire: str, max_batch: int) -> IVal:
    """Per-element seed of one wire buffer argument.

    ``shape`` may carry a leading group axis (``[N, B+1, w]`` mega
    groups); the per-row contract is tiled across it.  Record rows: full u32.  Metadata row (row B):
    the encoder contracts above."""
    words = shape[-1]
    rows = shape[-2]
    b = rows - 1
    horizon_ns = schema.RANGE_DEPLOY_HORIZON_S * 10 ** 9
    horizon_us = horizon_ns // 1000
    base = _obj_full((rows, words), 0, U32_MAX)
    # metadata row: n_valid is our own encoder's min(len, B)
    base.hi[b, 0] = min(max_batch, b)
    if wire == schema.WIRE_COMPACT16:
        # words 1/2: base_rel_us split u64 — the HI word carries
        # (horizon_us >> 32), the LO word genuinely spans u32
        base.hi[b, 2] = horizon_us >> 32
    else:
        # raw48 metadata words 1/2: t0_ns split u64; record word 1 is
        # the per-record ts_ns HI word — both bounded by the horizon
        base.hi[b, 2] = horizon_ns >> 32
        base.hi[:b, 1] = horizon_ns >> 32
    if len(shape) > 2:
        lead = tuple(shape[:-2])
        lo = np.broadcast_to(base.lo, lead + base.lo.shape)
        hi = np.broadcast_to(base.hi, lead + base.hi.shape)
        return iv.guard_cap(IVal(lo, hi))
    return iv.guard_cap(base)


def param_seeds(params: Any) -> list[IVal]:
    """Seeds for the flattened params leaves, keyed by leaf name."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path).strip(".[]'\"").split(".")[-1]
        dtype = np.asarray(leaf).dtype
        rng = PARAM_LEAF_RANGES.get(name)
        if rng is not None and iv.is_int_dtype(dtype):
            out.append(iv.scalar(*rng))
        else:
            out.append(iv.top_for(dtype))
    return out


def variant_seeds(in_avals: list, wire: str, max_batch: int,
                  params: Any) -> list[IVal]:
    """Seeds aligned with a staged variant's flattened inputs:
    ``table.key, table.state, stats.* (6), params leaves, wire
    buffer(s)`` — the :data:`~flowsentryx_tpu.audit.runner.CARRY_NAMES`
    order the whole audit suite shares."""
    n_carry = 2 + len(schema.GlobalStats._fields)
    pseeds = param_seeds(params)
    seeds: list[IVal] = []
    for i, aval in enumerate(in_avals):
        if i < n_carry:
            seeds.append(iv.top_for(aval.dtype))
        elif i < n_carry + len(pseeds):
            seeds.append(pseeds[i - n_carry])
        else:
            seeds.append(wire_seed(tuple(aval.shape), wire, max_batch))
    return seeds
