"""The device-resident drain ring: a persistent deep-scan serving loop.

Every dispatch mode before this one — singles, fixed ``--mega N``, the
adaptive ladder — shares one shape: Python pushes ONE group to the
device, the device computes, and the per-dispatch fixed cost (Python
bookkeeping + the XLA launch) is paid once per group.  The drain ring inverts the granularity:
the device consumes a whole STAGING RING of arena slices per host
round-trip, so the steady-state loop is pull-based from the device's
point of view — the accelerator never waits on the host between the
megasteps of a round (Taurus and FENXI reach line rate on exactly this
principle: the data plane's accelerator is always fed).

Shape of one device-loop round (``ring_depth`` R slots of ``n_chunks``
C micro-batches each)::

    slots (R separate device buffers, uploaded one-by-one while the
           PREVIOUS round computes — the double-buffered H2D half)
      └─ jnp.stack → [R, C, B+1, words]        (device-side, no host copy)
           └─ lax.scan over slots              (the ring)
                └─ lax.scan over chunks        (the megastep)
                     └─ the fused step         (ops/fused.py)

carrying (table, stats) on-device across ALL R·C batches, and emitting
ONE folded ``[2K+4]``-word compact verdict wire PER RING SLOT
(:func:`~flowsentryx_tpu.ops.fused.merge_verdict_wires` — the same fold
the megastep uses, applied once per slot instead of once per dispatch),
so the sink harvests verdicts at ring granularity: one
``[R, 2K+4]`` fetch per round, R·C batches amortized.

Why slots stay SEPARATE jit arguments instead of one ``[R, C, ...]``
host buffer: each slot is its own ``device_put``, issued by the engine
the moment that slot's arena rows fill — while the previous round is
still computing.  One contiguous buffer would serialize the whole
round's H2D behind the staging of its last batch; R separate uploads
overlap staging with compute slot-by-slot (the engine's
``EngineReport.dispatch["device_loop"]["h2d"]`` measures the overlap).
The ``jnp.stack`` that reassembles them runs ON DEVICE, inside the jit.

The base step is traced ONCE (the inner scan body), so compile cost
stays at one megastep regardless of ring depth — a Python-unrolled
ring would re-stage the full fused pipeline R times.

TRACED-REGION PURITY: everything in this module runs inside ``jit``.
No ``jax.device_get``, no ``pure_callback``/``io_callback``/
``debug_callback``, no host round-trip of any kind may appear here —
``fsx audit`` proves it statically on the staged graph, and
``scripts/lint.py``'s ``device_loop_purity`` stage catches it at
review speed.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from flowsentryx_tpu.ops import fused


class RingOutput(NamedTuple):
    """One device-loop round's outputs.

    ``wire`` is the round's whole steady-state readback: R per-slot
    merged compact wires in ONE buffer, fetched by the sink as a single
    D2H transfer.  The stacked block/verdict arrays stay on device —
    ``block_key``/``block_until`` exist only as the overflow fallback
    (a slot whose merged wire overflowed pays the full fetch for the
    round, so no block is ever lost), exactly like the megastep."""

    wire: Any         # [R, 2K+4] uint32 — one merged verdict wire per slot
    block_key: Any    # [R, C, B] uint32 overflow fallback (stays on device)
    block_until: Any  # [R, C, B] f32
    verdict: Any      # [R, C, B] uint8 (parity/debug; never fetched hot)
    now: Any          # [] f32 — round device clock (per-slot now rides
    #                   each slot's wire; this is their max)


def ring_round_batches(ring_depth: int, n_chunks: int) -> int:
    """Micro-batches consumed by one device-loop round."""
    return int(ring_depth) * int(n_chunks)


def wrap_device_loop(
    base: Callable[..., tuple],
    ring_depth: int,
    n_chunks: int,
    donate_argnums: tuple,
):
    """Build the jitted drain-ring loop over an (unjitted single-device
    or jitted shard-mapped) base step.

    ``loop(table, stats, params, *slots) -> (table, stats, RingOutput)``
    with exactly ``ring_depth`` slot arguments, each a
    ``[n_chunks, B+1, words]`` staged wire group (an uploaded arena
    slice).  Both the single-device and the sharded factories build on
    this wrapper — the ring/chunk guards and the per-slot wire fold
    cannot drift between them (the ``wrap_megastep`` discipline)."""
    if ring_depth < 1:
        raise ValueError(f"ring_depth must be >= 1, got {ring_depth}")
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")

    def loop(table, stats, params, *slots):
        if len(slots) != ring_depth:
            raise ValueError(
                f"device loop compiled for a {ring_depth}-slot ring, got "
                f"{len(slots)} slots (any other count would silently "
                "recompile)")
        for r, raws in enumerate(slots):
            if raws.shape[0] != n_chunks:
                raise ValueError(
                    f"device loop compiled for {n_chunks}-chunk slots, "
                    f"slot {r} is [{raws.shape[0]}, ...]")
        # Device-side reassembly of the R separately-uploaded slots:
        # this stack is a device memcpy inside the jit, not a host copy
        # (the slots crossed H2D one by one, overlapped with the
        # previous round's compute).
        ring = jnp.stack(slots)  # [R, C, B+1, words]

        def chunk_body(carry, raw):
            tbl, st = carry
            tbl, st, out = base(tbl, st, params, raw)
            return (tbl, st), out

        def slot_body(carry, raws):
            carry, outs = jax.lax.scan(chunk_body, carry, raws)
            # one merged wire PER SLOT — the sink's harvest granularity
            return carry, outs._replace(
                wire=fused.merge_verdict_wires(outs.wire))

        (table, stats), outs = jax.lax.scan(slot_body, (table, stats),
                                            ring)
        return table, stats, RingOutput(
            wire=outs.wire,                       # [R, 2K+4]
            block_key=outs.block_key,             # [R, C, B]
            block_until=outs.block_until,
            verdict=outs.verdict,
            now=jnp.max(outs.now),
        )

    return jax.jit(loop, donate_argnums=donate_argnums)


def make_compact_device_loop(
    cfg,
    classify_batch,
    ring_depth: int,
    n_chunks: int,
    donate: bool = True,
    **quant,
):
    """Single-device drain ring over the compact16 wire — the
    device-loop analog of
    :func:`~flowsentryx_tpu.ops.fused.make_jitted_compact_megastep`.
    ``**quant`` are the wire-quantizer kwargs; a compact wire
    (``cfg.batch.verdict_k >= 1``) is REQUIRED — without it every slot's
    readback would be the full ``[C, B]`` block arrays and the ring
    would multiply, not amortize, the D2H budget."""
    if cfg.batch.verdict_k < 1:
        raise ValueError(
            "the device loop needs the compact verdict wire "
            "(batch.verdict_k >= 1): its steady-state readback is one "
            "[ring, 2K+4] buffer per round")
    base = fused.make_compact_step(cfg, classify_batch, **quant)
    return wrap_device_loop(base, ring_depth, n_chunks,
                            (0, 1) if donate else ())


def make_sharded_compact_device_loop(
    cfg,
    classify_batch,
    mesh,
    ring_depth: int,
    n_chunks: int,
    donate: bool = True,
    **quant,
):
    """Multi-device drain ring: the deep scan over the shard-mapped
    compact step — every chunk of every slot still runs the full
    owner-routed all_to_all/psum pipeline, so trajectory parity with
    sequential sharded megasteps holds by construction (test-pinned in
    tests/test_parallel.py).  Donation matches the sharded-step policy:
    table only (replicated stats cannot alias)."""
    from flowsentryx_tpu.parallel import step as pstep

    if cfg.batch.verdict_k < 1:
        raise ValueError(
            "the device loop needs the compact verdict wire "
            "(batch.verdict_k >= 1): its steady-state readback is one "
            "[ring, 2K+4] buffer per round")
    base = pstep.make_sharded_compact_step(cfg, classify_batch, mesh,
                                           donate=False, **quant)
    return wrap_device_loop(base, ring_depth, n_chunks,
                            (0,) if donate else ())


# ---------------------------------------------------------------------------
# ring-depth autotuning (fsx serve --device-loop auto)
# ---------------------------------------------------------------------------

def choose_ring_depth(measurements: list[dict],
                      knee_fraction: float = 0.9) -> tuple[int, dict]:
    """Pick a ring depth from short calibration-drain measurements —
    the policy half of ``--device-loop auto`` (the drive half is
    :func:`flowsentryx_tpu.engine.engine.calibrate_ring_depth`).

    Each measurement is one candidate depth's
    ``EngineReport.dispatch["device_loop"]`` summary:
    ``{"ring", "overlap_fraction", "rounds", "ring_occupancy"}``.

    Policy: depth buys H2D overlap (more uploads issued while a round
    is still in flight) until the pipeline saturates; past the knee it
    only adds in-flight arena slots, device output memory and round
    latency (``readback_depth`` grows with ``ring * chunks``).  So:
    the SHALLOWEST candidate whose measured ``overlap_fraction``
    reaches ``knee_fraction`` of the best observed wins; candidates
    whose calibration never completed a full round (``rounds == 0``)
    measured nothing and are skipped.  If no candidate fired a round —
    a drain too short or a backlog too shallow — the smallest
    candidate is returned with the reason recorded, matching the
    ring's graceful-degradation posture (a shallow ring is the safe
    default, never a refusal: the flags were already validated
    pre-boot).
    """
    detail: dict = {"candidates": measurements,
                    "knee_fraction": knee_fraction}
    fired = [m for m in measurements if m.get("rounds", 0) >= 1]
    if not fired:
        depth = min(m["ring"] for m in measurements)
        detail["reason"] = ("no candidate completed a full round "
                           "during calibration; defaulting shallow")
        return depth, detail
    best = max(m.get("overlap_fraction", 0.0) for m in fired)
    detail["best_overlap"] = best
    if best <= 0.0:
        # no overlap anywhere (e.g. a single-core host where the
        # pipeline worker never runs concurrently): depth buys nothing,
        # keep the ring shallow
        depth = min(m["ring"] for m in fired)
        detail["reason"] = "no H2D overlap measured at any depth"
        return depth, detail
    # non-empty by construction: the best-overlap candidate always
    # clears its own knee (knee_fraction is clamped to <= 1)
    eligible = [m for m in fired
                if m.get("overlap_fraction", 0.0)
                >= min(knee_fraction, 1.0) * best]
    m = min(eligible, key=lambda m: m["ring"])
    detail["reason"] = (
        f"shallowest depth within {knee_fraction:.0%} of the "
        f"best measured overlap ({best})")
    return m["ring"], detail
