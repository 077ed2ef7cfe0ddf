"""Preallocated page-aligned staging memory for the dispatch hop.

The sealed-batch → device path used to pay three host copies per
steady-state batch: ``SealedBatchQueue.consume_batch`` copied the
payload out of the shm slot, a mega group re-copied via ``np.stack``,
and ``jax.device_put`` staged the unaligned result once more.  The
arena collapses that to ONE engine-side copy: the engine packs wire
buffers straight from the shm slot VIEWS (:meth:`SealedBatchQueue
.peek_batches`) into arena rows, releases the slots immediately, and
``device_put``\\s the contiguous arena slice — which is the host↔device
boundary itself, not a host copy (on a real accelerator a page-aligned
source is DMA-able without a bounce buffer; that is why the backing
store is an anonymous ``mmap``, page-aligned by construction, rather
than a numpy allocation).

Geometry: ``slots`` independent group buffers of ``group_max`` wire
rows each, ``[slots, group_max, max_batch+1, words]`` u32 overall.  A
group (1..group_max batches) assembles in ONE slot's rows, so any
``rows[a:a+g]`` dispatch slice is contiguous.  Slots recycle
round-robin; the safety rule mirrors ``MicroBatcher.n_buffers``:

    a slot's rows may be overwritten only once every batch staged in
    it has been SUNK — guaranteed structurally by ``slots >=
    readback_depth + 2``, because the engine claims a fresh slot only
    after dispatching everything staged in the current one, and
    ``_reap`` keeps at most ``readback_depth`` dispatched-but-unsunk
    batches (each occupying >= 1 slot) at any time.  The rule is
    :meth:`DispatchArena.safe_slots`; the full derivation is
    docs/CONCURRENCY.md §arena, and ``fsx sync`` proves the bound
    TIGHT by exhaustive interleaving of this class.

This also covers the CPU backend, where ``device_put`` of an aligned
buffer may alias rather than copy: rows stay immutable for the whole
life of the batch they carry, not just until the transfer is enqueued.
"""

from __future__ import annotations

import mmap

import numpy as np


class DispatchArena:
    """Ring of page-aligned ``[group_max, rows, words]`` staging slots.

    :meth:`claim` hands out the next slot index (recycling oldest);
    :meth:`rows` exposes one slot's wire-row array for staging and
    dispatch slicing.  The arena does NOT track per-slot liveness — the
    engine's claim/dispatch/reap discipline (module docstring) is the
    lifetime contract, and the wraparound/mutate-after-release tests
    pin it.
    """

    def __init__(self, slots: int, group_max: int, max_batch: int,
                 words: int):
        if slots < 2:
            raise ValueError(f"arena needs >= 2 slots, got {slots}")
        if group_max < 1:
            raise ValueError(f"group_max must be >= 1, got {group_max}")
        self.slots = slots
        self.group_max = group_max
        self.row_shape = (max_batch + 1, words)
        nbytes = slots * group_max * (max_batch + 1) * words * 4
        # anonymous mmap: page-aligned backing store (a plain np.zeros
        # is only 16/64-byte aligned, which forces the runtime through
        # a bounce buffer on DMA paths)
        self._mm = mmap.mmap(-1, nbytes)
        self.buf = np.frombuffer(self._mm, np.uint32).reshape(
            slots, group_max, max_batch + 1, words)
        # Pre-fault every page NOW: anonymous mmap pages materialize on
        # first write, and an arena left lazy pays its page faults
        # inside the first serving groups' staging memcpys — a boot
        # cost billed to the hot path (measured as a consistently
        # slow first drain window).
        self.buf[...] = 0
        self._cur = -1

    @property
    def nbytes(self) -> int:
        return self.buf.nbytes

    @staticmethod
    def safe_slots(readback_depth: int) -> int:
        """Slot count that keeps the reuse-safety rule:
        ``readback_depth + 2``.

        In one line: at any claim, at most ``readback_depth``
        sunk-pending slots (trickle singles, one slot each, worst
        case) plus the slot of the just-submitted work whose staged
        rows the device has not consumed, plus the overlapped claim
        itself must coexist.  The full derivation lives in
        docs/CONCURRENCY.md §arena, and the bound is not argued but
        MACHINE-CHECKED: ``fsx sync`` (sync/interleave.py) drives this
        class over exhaustive thread interleavings, passing every
        schedule at this bound and printing a staged-copy-overwrite
        counterexample one slot below it.
        """
        return max(readback_depth, 1) + 2

    def claim(self) -> int:
        """Next slot index, recycling the oldest.  Callers claim only
        when nothing staged in the previous slot remains undispatched
        (the module-docstring safety rule)."""
        self._cur = (self._cur + 1) % self.slots
        return self._cur

    def rows(self, slot: int) -> np.ndarray:
        """The ``[group_max, max_batch+1, words]`` row array of one
        slot.  ``rows(s)[a:a+g]`` is the contiguous dispatch slice of a
        g-batch group staged at offset ``a``."""
        return self.buf[slot]

    def info(self) -> dict:
        """Report-facing geometry (EngineReport.dispatch["arena"])."""
        return {
            "slots": self.slots,
            "group_max": self.group_max,
            "row_shape": list(self.row_shape),
            "bytes": int(self.nbytes),
            "page_aligned": True,
        }
