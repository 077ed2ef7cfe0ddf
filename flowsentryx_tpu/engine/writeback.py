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

    key: np.ndarray        # [K] uint32 source addrs / folds, or [K, 4]
    until_s: np.ndarray    # [K] f32 expiry, engine-relative seconds


def extract_updates(block_key: np.ndarray, block_until: np.ndarray) -> BlacklistUpdate:
    """Compact a step's padded block arrays to the real updates
    (``[R, 4]`` 128-bit keys: a row of all INVALID_KEY is padding)."""
    block_key = np.asarray(block_key)
    mask = block_key != INVALID_KEY
    if mask.ndim == 2:
        mask = mask.any(axis=1)
    return BlacklistUpdate(
        key=block_key[mask], until_s=np.asarray(block_until)[mask]
    )


class VerdictWire(NamedTuple):
    """Host-side view of one decoded compact verdict wire
    (:func:`flowsentryx_tpu.ops.fused.pack_verdict_wire`)."""

    key: np.ndarray      # [count] (or [count, 4]) newly-blocked keys
    until_s: np.ndarray  # [count] f32 matching expiries
    count: int           # TRUE newly-blocked count (may exceed len(key))
    overflow: bool       # count > k_max: fall back to the full fetch
    route_drop: int      # sharded routing fail-opens (0 single-device)
    now: float           # batch device clock (t0-relative seconds)


def decode_verdict_wire(wire: np.ndarray, key_words: int = 1
                        ) -> VerdictWire:
    """Decode a fetched ``[2K+4]`` uint32 verdict wire (numpy only —
    K = (len - 4) / 2), or the ``[5K+4]`` one of 4-word keys
    (``key_words`` 4, a table keyed by 128 bits).

    When ``overflow`` is set the key/until slots are INCOMPLETE (the
    device parked the tail): the caller must fetch the full
    ``block_key``/``block_until`` arrays for that batch instead, so a
    block is never lost."""
    wire = np.asarray(wire)
    kw = key_words
    k = (wire.shape[0] - 4) // (kw + 1)
    s0 = (kw + 1) * k
    count = int(wire[s0])
    n = min(count, k)
    return VerdictWire(
        key=wire[:n] if kw == 1 else wire[:kw * n].reshape(n, kw),
        until_s=wire[kw * k:kw * k + n].view(np.float32),
        count=count,
        overflow=bool(wire[s0 + 1]),
        route_drop=int(wire[s0 + 2]),
        now=float(wire[s0 + 3:s0 + 4].view(np.float32)[0]),
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
    """Accumulates updates (last expiry wins per key, like the kernel
    map); a 128-bit key is held as the tuple of its four words."""

    def __init__(self) -> None:
        self.blocked: dict[int | tuple, float] = {}
        self.updates = 0

    def apply(self, update: BlacklistUpdate) -> None:
        self.updates += 1
        keys = update.key.tolist()
        if update.key.ndim == 2:
            keys = map(tuple, keys)
        # dict.update over zip is the vectorized last-wins write: zip
        # yields pairs in array order, and dict assignment keeps the
        # LAST value per key — the same semantics the per-key loop had
        # and the kernel map's overwrite-on-update gives.
        self.blocked.update(zip(keys, update.until_s.tolist()))
