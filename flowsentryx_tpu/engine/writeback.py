"""Verdict writeback: the TPU plane's output side of the map seam.

The fused step returns, per batch, the flow keys newly condemned and
their blacklist expiries (``StepOutput.block_key`` / ``block_until``).
A :class:`VerdictSink` carries them back toward the kernel's
``blacklist_map`` — closing the loop the reference never built
(``fsx_load.py:5-12`` intent).  Sinks:

* :class:`NullSink` — benching the compute path alone.
* :class:`CollectSink` — tests/offline analysis: keeps everything.
* :class:`~flowsentryx_tpu.engine.shm.ShmVerdictSink` — production:
  pushes updates into the daemon's verdict ring; the daemon applies
  them to the pinned BPF map (kept with the shm transport).
"""

from __future__ import annotations

from typing import NamedTuple, Protocol

import numpy as np

from flowsentryx_tpu.ops.agg import INVALID_KEY


class BlacklistUpdate(NamedTuple):
    """One batch's newly blocked sources."""

    key: np.ndarray        # [K] uint32 folded source addrs
    until_s: np.ndarray    # [K] f32 expiry, engine-relative seconds


def extract_updates(block_key: np.ndarray, block_until: np.ndarray) -> BlacklistUpdate:
    """Compact a step's padded block arrays to the real updates."""
    block_key = np.asarray(block_key)
    mask = block_key != INVALID_KEY
    return BlacklistUpdate(
        key=block_key[mask], until_s=np.asarray(block_until)[mask]
    )


class VerdictWire(NamedTuple):
    """Host-side view of one decoded compact verdict wire
    (:func:`flowsentryx_tpu.ops.fused.pack_verdict_wire`)."""

    key: np.ndarray      # [count] uint32 newly-blocked keys (in order)
    until_s: np.ndarray  # [count] f32 matching expiries
    count: int           # TRUE newly-blocked count (may exceed len(key))
    overflow: bool       # count > k_max: fall back to the full fetch
    route_drop: int      # sharded routing fail-opens (0 single-device)
    now: float           # batch device clock (t0-relative seconds)


def decode_verdict_wire(wire: np.ndarray) -> VerdictWire:
    """Decode a fetched ``[2K+4]`` uint32 verdict wire (numpy only —
    the layout is self-describing, K = (len - 4) / 2).

    When ``overflow`` is set the key/until slots are INCOMPLETE (the
    device parked the tail): the caller must fetch the full
    ``block_key``/``block_until`` arrays for that batch instead, so a
    block is never lost."""
    wire = np.asarray(wire)
    k = (wire.shape[0] - 4) // 2
    count = int(wire[2 * k])
    n = min(count, k)
    return VerdictWire(
        key=wire[:n],
        until_s=wire[k:k + n].view(np.float32),
        count=count,
        overflow=bool(wire[2 * k + 1]),
        route_drop=int(wire[2 * k + 2]),
        now=float(wire[2 * k + 3:2 * k + 4].view(np.float32)[0]),
    )


def wire_overflowed(wire: np.ndarray) -> bool:
    """Whether a fetched wire — one ``[2K+4]`` buffer or a ``[R, 2K+4]``
    stack of per-slot wires — has any overflow flag set, i.e. the entry
    needs the full block-array fetch (:func:`decode_verdict_wire`)."""
    wire = np.asarray(wire)
    return bool(wire.reshape(-1, wire.shape[-1])[:, -3].any())


class VerdictSink(Protocol):
    def apply(self, update: BlacklistUpdate) -> None: ...


class NullSink:
    def apply(self, update: BlacklistUpdate) -> None:
        pass


class CollectSink:
    """Accumulates updates (last expiry wins per key, like the kernel map)."""

    def __init__(self) -> None:
        self.blocked: dict[int, float] = {}
        self.updates = 0

    def apply(self, update: BlacklistUpdate) -> None:
        self.updates += 1
        # dict.update over zip is the vectorized last-wins write: zip
        # yields pairs in array order, and dict assignment keeps the
        # LAST value per key — the same semantics the per-key loop had
        # and the kernel map's overwrite-on-update gives.
        self.blocked.update(zip(update.key.tolist(),
                                update.until_s.tolist()))
