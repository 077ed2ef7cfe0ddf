"""Size- and deadline-triggered micro-batcher.

SURVEY.md §7.2's "micro-batcher (size- and deadline-triggered, e.g.
2048 vectors or 200 µs)".  Records accumulate in a preallocated
wire buffer so a flush is metadata-row update + hand-off — no per-flush
allocation or repacking.  Double-buffered: the engine can have one
buffer in flight on device while the next fills.

Two wire formats (core/schema.py):

* ``raw48`` — records copied verbatim as ``[B+1, 12]`` u32
  (:func:`schema.encode_raw` layout); full fidelity, 48 B/record.
* ``compact16`` — records quantized on the way in as ``[B+1, 4]`` u32
  (:func:`schema.encode_compact` layout); 3× fewer bytes over the
  host→device hop, which is the bandwidth-critical seam at 10 Mpps.
  With ``model``-mode quantizer kwargs the classifier's scores are
  bit-identical to raw48 (the wire carries the model's own input
  quantization).  The compact ts field is a µs delta from the batch
  base, so ``deadline_us`` must stay under its 65 ms range — enforced
  here rather than silently saturating.
"""

from __future__ import annotations

import time

import numpy as np

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import BatchConfig


class MicroBatcher:
    """Accumulates ring records; flushes at ``max_batch`` or ``deadline_us``.

    ``add()`` returns a full wire buffer when the size trigger fires,
    else None; ``flush_due()`` says whether the deadline trigger fires;
    ``take()`` hands off whatever is pending (padded, metadata row set).

    ``n_buffers`` bounds how many sealed buffers may be outstanding at
    once: a buffer is reused after ``n_buffers`` further seals, so the
    engine must have reaped (or at least completed the H2D transfer of)
    a batch within that many seals — the engine sizes this from its
    readback depth.  ``pop_seal_time()`` yields, per sealed buffer, when
    its FIRST record entered the batcher (the honest start of e2e
    latency: batcher residency counts).
    """

    def __init__(
        self,
        cfg: BatchConfig,
        t0_ns: int = 0,
        n_buffers: int = 4,
        wire: str = schema.WIRE_RAW48,
        quant: dict | None = None,
    ):
        self.cfg = cfg
        self.t0_ns = t0_ns
        self.n_buffers = max(2, n_buffers)
        self.wire = wire
        self.quant = dict(quant or {})
        if wire == schema.WIRE_COMPACT16:
            if cfg.deadline_us > 60_000:
                raise ValueError(
                    "compact16 ts field spans 65 ms; deadline_us "
                    f"{cfg.deadline_us} would saturate record deltas"
                )
            words = schema.COMPACT_RECORD_WORDS
        elif wire == schema.WIRE_RAW48:
            words = schema.RECORD_WORDS
        else:
            raise ValueError(f"unknown wire format {wire!r}")
        b = cfg.max_batch
        self._bufs = [
            np.zeros((b + 1, words), np.uint32)
            for _ in range(self.n_buffers)
        ]
        self._cur = 0
        self.fill = 0
        self._first_add_t: float | None = None
        self._base_ns = 0  # compact16: batch base timestamp
        self._seal_times: list[float] = []
        self.batches_emitted = 0
        self.records_emitted = 0
        #: Drains whose preceding poll gap made 16-bit kernel-ts unwrap
        #: ambiguous (see add_precompact) — surfaced in the engine report.
        self.ts_wrap_risk_polls = 0
        self._last_poll_t: float | None = None

    # -- triggers -----------------------------------------------------------

    def add_precompact(self, records: np.ndarray,
                       on_seal=None) -> list[np.ndarray]:
        """Append KERNEL-quantized compact records
        (``schema.COMPACT_RECORD_DTYPE``, from a compact-emit data
        plane): features pass through untouched; only word 3's wrapped
        µs stamp is unwrapped against the host clock and rebased to the
        batch base.  Requires ``wire="compact16"``.  ``on_seal`` as in
        :meth:`add`."""
        if self.wire != schema.WIRE_COMPACT16:
            raise ValueError("add_precompact requires the compact16 wire")
        out: list[np.ndarray] = []
        sealed = on_seal or out.append
        if not len(records):
            return out
        # Staleness heuristic (unwrap_kernel_ts16 aliases silently): the
        # 16-bit µs stamp wraps every 65.536 ms, so any record emitted
        # more than one wrap before this drain is shifted forward by
        # n*65.5 ms with no way to detect it per record.  What IS
        # observable is the drain-opportunity cadence: if the gap since
        # the previous poll (the engine notes empty polls via
        # :meth:`note_poll`; traffic lulls therefore do NOT count)
        # approached the wrap period — engine stall, GC pause — records
        # drained now may have sat in the ring longer than one wrap, so
        # their unwraps are at risk.  Count it so post-stall timing skew
        # is visible in the engine report instead of silently biasing
        # batch bases and limiter windows.
        if self.note_poll() > 0.050:
            self.ts_wrap_risk_polls += 1
        now = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
        ts_ns = schema.unwrap_kernel_ts16(records["w3"], now)
        pos = 0
        b = self.cfg.max_batch
        while pos < len(records):
            if self.fill == 0:
                self._first_add_t = time.perf_counter()
                self._base_ns = int(ts_ns[pos])
            take = min(b - self.fill, len(records) - pos)
            span_ok = (ts_ns[pos : pos + take].astype(np.int64)
                       - self._base_ns) < 65_000_000
            if not span_ok.all():
                take = max(int(span_ok.argmin()), 0)
                if take == 0:
                    sealed(self._seal())
                    continue
            chunk = records[pos : pos + take]
            dt_us = np.clip(
                (ts_ns[pos : pos + take].astype(np.int64) - self._base_ns)
                // 1000, 0, 65535,
            ).astype(np.uint32)
            buf = self._bufs[self._cur]
            rows = buf[self.fill : self.fill + take]
            rows[:, 0] = chunk["w0"]
            rows[:, 1] = chunk["w1"]
            rows[:, 2] = chunk["w2"]
            rows[:, 3] = (chunk["w3"] & np.uint32(0xFFFF)) | (dt_us << 16)
            self.fill += take
            pos += take
            if self.fill == b:
                sealed(self._seal())
        return out

    def add(self, records: np.ndarray,
            on_seal=None) -> list[np.ndarray]:
        """Append records; returns the (possibly several) wire buffers
        completed by this addition.

        One call can seal MORE buffers than ``n_buffers``: compact16
        seals at every 65 ms of record time, so a backlog of slow
        traffic seals once per few records.  The returned list would
        then name buffers a later seal of the same call already reused.
        A holder of few buffers passes ``on_seal`` instead: it is
        handed each buffer the moment it seals (and must be done with
        it on return), and the list stays empty."""
        out: list[np.ndarray] = []
        sealed = on_seal or out.append
        pos = 0
        b = self.cfg.max_batch
        compact = self.wire == schema.WIRE_COMPACT16
        while pos < len(records):
            if self.fill == 0:
                self._first_add_t = time.perf_counter()
                if compact:
                    self._base_ns = int(records["ts_ns"][pos])
            take = min(b - self.fill, len(records) - pos)
            if compact:
                # The compact ts field is a u16 µs delta from the batch
                # base: a batch may not SPAN more than ~65 ms of record
                # time (slow replays / post-stall backlogs would
                # otherwise saturate deltas and inflate apparent rates).
                # Seal early at the span boundary instead.
                span_ok = (
                    records["ts_ns"][pos : pos + take].astype(np.int64)
                    - self._base_ns
                ) < 65_000_000
                if not span_ok.all():
                    take = max(int(span_ok.argmin()), 0)
                    if take == 0:
                        sealed(self._seal())
                        continue
            chunk = records[pos : pos + take]
            buf = self._bufs[self._cur]
            if compact:
                buf[self.fill : self.fill + take] = schema.compact_pack(
                    chunk, self._base_ns, **self.quant
                )
            else:
                buf[self.fill : self.fill + take] = (
                    chunk.view(np.uint32).reshape(take, schema.RECORD_WORDS)
                )
            self.fill += take
            pos += take
            if self.fill == b:
                sealed(self._seal())
        return out

    def note_poll(self) -> float:
        """Record a drain opportunity (a source poll, empty or not) and
        return the gap since the previous one — the cadence input to
        ``add_precompact``'s wrap-risk heuristic.  The engine calls this
        on empty polls so a mere traffic lull is not mistaken for a
        drain stall."""
        t = time.perf_counter()
        gap = 0.0 if self._last_poll_t is None else t - self._last_poll_t
        self._last_poll_t = t
        return gap

    def flush_due(self) -> bool:
        """Deadline trigger: something pending for longer than deadline_us."""
        return (
            self.fill > 0
            and self._first_add_t is not None
            and (time.perf_counter() - self._first_add_t) * 1e6
            >= self.cfg.deadline_us
        )

    def pending_age_s(self) -> float:
        """Age of the oldest UNSEALED record (0.0 when nothing is
        pending) — the engine's SLO mode bounds batcher residency by
        the latency budget with this, on top of ``flush_due``'s fixed
        ``deadline_us`` trigger."""
        if self.fill == 0 or self._first_add_t is None:
            return 0.0
        return time.perf_counter() - self._first_add_t

    def take(self) -> np.ndarray | None:
        """Flush whatever is pending (deadline path); None if empty."""
        return self._seal() if self.fill else None

    def pop_seal_time(self) -> float:
        """First-record-arrival time of the oldest unclaimed sealed batch."""
        return self._seal_times.pop(0)

    # -- internals ----------------------------------------------------------

    def _seal(self) -> np.ndarray:
        buf = self._bufs[self._cur]
        b = self.cfg.max_batch
        meta = buf[b]
        meta[0] = self.fill
        if self.wire == schema.WIRE_COMPACT16:
            base_rel_us = max(0, self._base_ns - self.t0_ns) // 1000
            meta[1] = base_rel_us & 0xFFFFFFFF
            meta[2] = (base_rel_us >> 32) & 0xFFFFFFFF
        else:
            meta[1] = self.t0_ns & 0xFFFFFFFF
            meta[2] = (self.t0_ns >> 32) & 0xFFFFFFFF
        # tail rows beyond fill are stale from an earlier batch; they are
        # masked by n_valid on device, so no need to zero them.
        self.batches_emitted += 1
        self.records_emitted += self.fill
        self._seal_times.append(self._first_add_t or time.perf_counter())
        self.fill = 0
        self._first_add_t = None
        self._cur = (self._cur + 1) % self.n_buffers
        return buf
