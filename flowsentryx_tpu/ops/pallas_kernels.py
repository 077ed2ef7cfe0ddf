"""Hand-written Pallas TPU kernels for the hot ops.

Two kernels, each with an XLA-composed twin elsewhere in the tree (the
twin is the correctness oracle and the fallback on non-TPU backends):

* :func:`score_int8` — the classifier's fused quantize → int8 dot →
  requant → quantized-sigmoid pipeline in ONE VPU pass over the batch
  (twin: :func:`flowsentryx_tpu.models.logreg.classify_batch_int8_matmul`).
  With K=8, N=1 the "matmul" is really a row reduction; doing it on the
  VPU in the same pass as both quantizations means the batch is read
  from VMEM exactly once and nothing round-trips through HBM between
  stages.  All intermediate values are ≤ 255·127·8 < 2^18, exactly
  representable in f32, so f32 arithmetic reproduces the int32 path
  bit-for-bit.
* :func:`table_summary` — operational scan over the device-resident
  per-IP state table (tracked/blocked/stale counts): one streamed pass
  through the [N]-row arrays with the grid pipelining HBM→VMEM blocks,
  reading key/blocked/last_seen together instead of three separate
  XLA reductions.

Kernels run in Mosaic on TPU and in interpreter mode elsewhere (CPU
tests exercise the same code path; ``interpret`` auto-detects).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flowsentryx_tpu.core.schema import NUM_FEATURES, IpTableState, TableCol
from flowsentryx_tpu.models.logreg import LogRegParams


def _interpret() -> bool:
    """Mosaic needs a real TPU; everywhere else run the interpreter."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Fused int8 scoring
# ---------------------------------------------------------------------------

TILE_B = 512  # batch rows per grid step (f32 sublane 8 × 64 — ample)

#: Layout of the scalar-parameter vector handed to the kernel.
_P_IN_SCALE, _P_IN_ZP, _P_WSCALE, _P_BIAS, _P_OUT_SCALE, _P_OUT_ZP, _P_LOG1P = range(7)


def _score_kernel(x_ref, w_ref, p_ref, out_ref):
    x = x_ref[:]                      # [TILE_B, 8] f32
    p = p_ref[:]                      # [1, 8] f32 scalar params
    log_domain = p[0, _P_LOG1P] > 0
    x = jnp.where(log_domain, jnp.log1p(x), x)

    # 1. input quantization (quint8 affine; f32 domain, exact)
    in_zp = p[0, _P_IN_ZP]
    q_x = jnp.clip(jnp.round(x / p[0, _P_IN_SCALE]) + in_zp, 0.0, 255.0)

    # 2. "matmul": K=8, N=1 → row reduction on the VPU.  (q_x - zp)·w
    #    with |acc| < 2^18 — exact in f32.
    acc = jnp.sum((q_x - in_zp) * w_ref[:], axis=1, keepdims=True)  # [TB,1]

    # 3. dequant + bias, then output requantization (quint8 affine)
    y = acc * (p[0, _P_IN_SCALE] * p[0, _P_WSCALE]) + p[0, _P_BIAS]
    q_y = jnp.clip(
        jnp.round(y / p[0, _P_OUT_SCALE]) + p[0, _P_OUT_ZP], 0.0, 255.0
    )
    y_dq = (q_y - p[0, _P_OUT_ZP]) * p[0, _P_OUT_SCALE]

    # 4. quantized sigmoid: fixed qparams scale 1/256, zp 0 (torch)
    prob = jax.nn.sigmoid(y_dq)
    out_ref[:] = jnp.clip(jnp.round(prob * 256.0), 0.0, 255.0) * (1.0 / 256.0)


@jax.jit
def score_int8(params: LogRegParams, x: jnp.ndarray) -> jnp.ndarray:
    """Pallas twin of ``classify_batch_int8_matmul``: ``[B, 8] → [B]``.

    Pads the batch to a TILE_B multiple (scores of the zero padding are
    discarded), runs one fused VPU pass per tile.
    """
    b = x.shape[0]
    bp = ((b + TILE_B - 1) // TILE_B) * TILE_B
    x = jnp.pad(x.astype(jnp.float32), ((0, bp - b), (0, 0)))

    w = params.w_int8.astype(jnp.float32).reshape(1, NUM_FEATURES)
    p = jnp.zeros((1, 8), jnp.float32)
    p = p.at[0, _P_IN_SCALE].set(params.in_scale.astype(jnp.float32))
    p = p.at[0, _P_IN_ZP].set(params.in_zp.astype(jnp.float32))
    p = p.at[0, _P_WSCALE].set(params.w_scale.astype(jnp.float32))
    p = p.at[0, _P_BIAS].set(params.bias.astype(jnp.float32))
    p = p.at[0, _P_OUT_SCALE].set(params.out_scale.astype(jnp.float32))
    p = p.at[0, _P_OUT_ZP].set(params.out_zp.astype(jnp.float32))
    p = p.at[0, _P_LOG1P].set(params.log1p.astype(jnp.float32))

    out = pl.pallas_call(
        _score_kernel,
        out_shape=jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        grid=(bp // TILE_B,),
        in_specs=[
            pl.BlockSpec((TILE_B, NUM_FEATURES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, NUM_FEATURES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((TILE_B, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(x, w, p)
    return out[:b, 0]


# ---------------------------------------------------------------------------
# Table summary scan
# ---------------------------------------------------------------------------

_CHUNK = 8 * 128  # one f32 tile per grid step


def _summary_kernel(key_ref, blocked_ref, seen_ref, now_ref, out_ref):
    """Accumulates per-LANE partials (Mosaic forbids scalar VMEM stores;
    row-wide vector adds are the natural VPU shape anyway).  Rows of the
    [4, 128] output: 0=tracked 1=blocked 2=stale as lane-partial sums,
    3=per-lane max last_seen.  The host wrapper reduces over lanes."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    now = now_ref[0, 0]
    stale_s = now_ref[0, 1]
    key = key_ref[:]                        # [8, 128]
    tracked = key != 0
    blocked = tracked & (blocked_ref[:] > now)
    stale = tracked & (now - seen_ref[:] > stale_s)

    out_ref[0, :] += jnp.sum(tracked.astype(jnp.float32), axis=0)
    out_ref[1, :] += jnp.sum(blocked.astype(jnp.float32), axis=0)
    out_ref[2, :] += jnp.sum(stale.astype(jnp.float32), axis=0)
    out_ref[3, :] = jnp.maximum(
        out_ref[3, :], jnp.max(jnp.where(tracked, seen_ref[:], 0.0), axis=0)
    )


@functools.partial(jax.jit, static_argnames=("stale_s",))
def _table_summary_device(
    key: jnp.ndarray,
    blocked_until: jnp.ndarray,
    last_seen: jnp.ndarray,
    now: jnp.ndarray,
    stale_s: float,
) -> jnp.ndarray:
    n = key.shape[0]
    rows = n // 128
    shape2d = (rows, 128)
    block = (8, 128)
    nowv = jnp.stack([now.astype(jnp.float32), jnp.float32(stale_s)]).reshape(1, 2)

    lanes = pl.pallas_call(
        _summary_kernel,
        out_shape=jax.ShapeDtypeStruct((4, 128), jnp.float32),
        grid=(rows // 8,),
        in_specs=[
            pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((4, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(
        key.reshape(shape2d),
        blocked_until.reshape(shape2d),
        last_seen.reshape(shape2d),
        nowv,
    )
    # Lane reduction: 3 sums + 1 max over the 128 partials.  Count sums
    # go through int32 — per-lane partials are exact in f32 (each lane
    # accumulates <= capacity/128 <= 2^25/128 = 2^18 unit increments),
    # but summing 128 of them in f32 would lose exactness past 2^24
    # total, where the XLA twin (integer sum) stays exact.
    counts = jnp.sum(lanes[:3].astype(jnp.int32), axis=1)  # [3] exact
    return counts, jnp.max(lanes[3])


@functools.partial(jax.jit, static_argnames=("stale_s", "use_pallas"))
def _table_summary(key, state, now, stale_s, use_pallas):
    """Column extraction + dispatch under ONE jit, so the host-side
    caller never materializes slice constants eagerly (the engine's
    transfer-guard contract).  A ``[capacity, 4]`` key (a table keyed
    by 128 bits) is tracked where any word is set: its words' max."""
    if key.ndim == 2:
        key = jnp.max(key, axis=1)
    blocked_until = state[..., int(TableCol.BLOCKED_UNTIL)]
    last_seen = state[..., int(TableCol.LAST_SEEN)]
    fn = _table_summary_device if use_pallas else _table_summary_xla
    return fn(key, blocked_until, last_seen, now, stale_s)


@functools.partial(jax.jit, static_argnames=("stale_s",))
def _table_summary_xla(key, blocked_until, last_seen, now, stale_s):
    """XLA twin of the summary kernel (correctness oracle + fallback)."""
    tracked = key != 0
    counts = jnp.stack(
        [
            jnp.sum(tracked, dtype=jnp.int32),
            jnp.sum(tracked & (blocked_until > now), dtype=jnp.int32),
            jnp.sum(tracked & (now - last_seen > stale_s), dtype=jnp.int32),
        ]
    )
    return counts, jnp.max(jnp.where(tracked, last_seen, 0.0))


def table_summary(
    table: IpTableState, now: float, stale_s: float = 30.0
) -> dict:
    """Operational counters over the live state table, one device pass.

    Successor of the stats display the reference only planned
    (``README.md:143-146``) — but over the DEVICE table, so the engine
    can report tracked/blocked/stale flow counts without hauling 40 MB
    to the host.  Tables smaller than one kernel chunk (or misaligned)
    fall back to the XLA-composed reduction — same answer, no Pallas.
    """
    # device_put, not jnp.float32: the clock scalar's H2D hop stays an
    # EXPLICIT transfer, so report building runs clean under
    # jax.transfer_guard("disallow") (the engine's CI guard); same for
    # the result fetch below.  Column extraction happens INSIDE the jit
    # (_table_summary) for the same reason — the eager column-view
    # properties materialize their slice indices host-side.  A SHARDED
    # table needs the scalar replicated over its mesh up front, or the
    # jit reshards it (an implicit D2D hop) on entry.
    sh = getattr(table.key, "sharding", None)
    sharded = isinstance(sh, jax.sharding.NamedSharding)
    if sharded:
        dst = jax.sharding.NamedSharding(sh.mesh,
                                         jax.sharding.PartitionSpec())
        now_dev = jax.device_put(np.float32(now), dst)
    else:
        now_dev = jax.device_put(np.float32(now))
    # Pallas only on a REAL TPU: interpret-mode emulation walks the
    # grid step by step, which at production capacities turns a
    # per-report scan into tens of seconds (measured ~100 s at 4M rows
    # on CPU — it silently dominated every engine run's report).  And
    # only for a table on ONE device: the TPU compiler refuses to
    # partition a Mosaic kernel over a mesh ("wrap the call in a
    # shard_map"; tests/test_chip_compile.py keeps that on record).
    # The XLA twin is the same answer at memory-bandwidth speed
    # everywhere else.
    counts, newest = _table_summary(
        table.key, table.state, now_dev,
        float(stale_s),
        use_pallas=(not table.capacity % _CHUNK and not sharded
                    and not _interpret()),
    )
    counts = jax.device_get(counts)  # noqa: host half, outside any jit
    return {
        "tracked": int(counts[0]),
        "blocked": int(counts[1]),
        "stale": int(counts[2]),
        "newest_seen_s": float(jax.device_get(newest)),  # noqa: same
    }
