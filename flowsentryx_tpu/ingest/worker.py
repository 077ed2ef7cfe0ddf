"""Drain-worker process: one shm ring shard → sealed batches, forever.

Spawned by :class:`~flowsentryx_tpu.ingest.sharded.ShardedIngest` via
``multiprocessing`` (spawn context: never forks a process that may own
jax/XLA threads).  The import chain here is deliberately jax-free —
``core.schema`` + ``engine.batcher`` + ``engine.shm`` are pure numpy —
so a worker boots in well under a second.

Lifecycle (states in ``schema.WSTATE_*``, published through the queue's
control block):

1. **SPAWNING** — open the batch queue, wait for the ring shard.
2. t0 handshake — publish the first record's timestamp as ``FIRST_TS``,
   buffer drained records (bounded), and wait for the engine to publish
   the agreed ``T0`` epoch.  Every worker must seal against one epoch or
   cross-shard flow windows would skew.
3. **RUNNING** — drain → decode/quantize → seal → enqueue.  A full
   queue is backpressure: the worker retries, the ring fills, the
   daemon's drop counters account the loss (fail-open, same policy as
   the kernel ringbuf).  Each sealed batch carries its first record's
   wait in the ring (:class:`CursorLog`).
4. ``STOP`` observed — drain the ring to empty, flush the partial
   batch, publish **DONE**, exit.  Crashes publish **FAILED** (best
   effort) and leave the traceback on stderr; the engine fails open.
"""

from __future__ import annotations

import time
import traceback

import numpy as np

from flowsentryx_tpu.sync import tuning

#: Records a worker will buffer while waiting for the t0 handshake
#: before letting ring backpressure take over (64k records ≈ 3 MB raw48;
#: the handshake resolves in well under a second of traffic).
PENDING_CAP = 1 << 16

#: Idle sleep between empty polls — sync/tuning.py is the documented
#: table (daemon-matched 200 µs).  Also the spin-exhausted sleep of the
#: drain loop's bounded backoff when the queue creator left the
#: ctl-block ``idle_us`` field at 0.
IDLE_SLEEP_S = tuning.IDLE_SLEEP_S


class _Backoff:
    """Bounded spin-then-sleep idle policy for the drain loop.

    A worker that sleeps the moment its ring shard reads empty adds a
    whole scheduler wakeup (≥ the 200 µs sleep, often a multi-ms
    quantum on a loaded host) to the NEXT record's path — at Mpps
    rates the ring is "empty" between every burst, so that latency tax
    lands constantly.  Instead the worker keeps polling (spinning) for
    a bounded ``spin_us`` after the last productive poll, and only
    then falls back to sleeping ``idle_us`` per miss, so a genuinely
    idle shard stops burning its core.  Both parameters come from the
    queue's ctl block (``schema.SHM_SPIN_US_OFFSET`` /
    ``SHM_IDLE_US_OFFSET``), written by the queue creator before the
    worker spawns — tests pin them through
    ``ShardedIngest(spin_us=..., idle_us=...)``.  ``spin_us=0``
    reproduces the pre-backoff sleep-immediately behavior."""

    def __init__(self, spin_us: int, idle_us: int):
        self.spin_s = spin_us / 1e6
        self.idle_s = idle_us / 1e6
        self._idle_since: float | None = None

    def reset(self) -> None:
        """A productive poll: re-arm the spin budget."""
        self._idle_since = None

    def idle(self) -> bool:
        """An empty poll: spin (return False, poll again immediately)
        while the budget lasts, then sleep.  Returns True iff it
        slept (observable for tests)."""
        now = time.perf_counter()
        if self._idle_since is None:
            self._idle_since = now
        if now - self._idle_since < self.spin_s:
            return False
        time.sleep(self.idle_s)
        return True

#: Bounded wait on a full queue once stop was requested (rationale in
#: sync/tuning.py) — the consumer may already be gone and shutdown must
#: not hang.  A give-up is NOT silent: the batch's seq is un-burned (a
#: gap stays a corruption signal) and the loss lands in the queue's
#: ``emit_drop`` counter, surfaced per worker in the engine report's
#: ``ingest`` block.  Module-level (not read from tuning at call time)
#: so tests can monkeypatch the shutdown bound.
EMIT_STOP_TIMEOUT_S = tuning.EMIT_STOP_TIMEOUT_S


def _monotonic_ns() -> int:
    return time.clock_gettime_ns(time.CLOCK_MONOTONIC)


#: Entries of a worker's :class:`CursorLog`: one a look that found the
#: producer cursor moved, so seconds of history at the rate a worker
#: looks; no per-record storage.
CURSOR_LOG_ENTRIES = 1 << 16


class CursorLog:
    """When the ring's producer cursor was seen to move: a fixed ring of
    ``(head, t_ns)`` pairs on CLOCK_MONOTONIC (the clock of the seal
    stamps), appended by :meth:`observe` whenever ``head`` has moved
    since the last look.  The worker looks at every poll, in the emit
    loop's wait on a full queue and after each idle sleep.

    :meth:`published_ns` dates a record as the first entry whose head
    passed its cursor: never before the record was published, and after
    it by at most one observation interval, so a wait computed from it
    is a lower bound.  A cursor older than the log (its entry
    overwritten, or the record already there at the first look) is
    dated by the oldest entry and counted in ``clamped``: the wait is
    then understated by an unknown amount."""

    def __init__(self, ring, entries: int = CURSOR_LOG_ENTRIES):
        if entries < 2 or entries & (entries - 1):
            raise ValueError(f"entries must be a power of two >= 2, "
                             f"got {entries}")
        self._ring = ring
        self._heads = np.zeros(entries, np.int64)
        self._t_ns = np.zeros(entries, np.int64)
        self._mask = entries - 1
        self.n = 0          # entries ever appended
        self.last = -1      # head at the newest entry
        self.observes = 0   # looks at the cursor
        self.clamped = 0

    def observe(self) -> int:
        """Look at the producer cursor, log it if it moved, return it."""
        self.observes += 1
        h = self._ring.head()
        if h != self.last:
            i = self.n & self._mask
            self._heads[i] = h
            self._t_ns[i] = _monotonic_ns()
            self.n += 1
            self.last = h
        return h

    def published_ns(self, cursor: int) -> int:
        """When the record at ``cursor`` was seen published (ns).  A
        cursor no entry has passed yet was published after the last
        look: it is dated now."""
        oldest = max(self.n - self._mask - 1, 0)
        lo, hi = oldest, self.n
        if lo == hi or self.last <= cursor:
            return _monotonic_ns()
        while lo < hi:  # heads rise strictly along the log
            mid = (lo + hi) // 2
            if self._heads[mid & self._mask] > cursor:
                hi = mid
            else:
                lo = mid + 1
        if lo == oldest:
            self.clamped += 1
        return int(self._t_ns[lo & self._mask])


class _Emitter:
    """Seal-side bookkeeping: batch header fields + queue backpressure.
    ``cursor0`` is the ring cursor of the first record the worker took:
    records reach the batcher in ring order, so a batch's first record
    lies at ``cursor0`` + the records sealed before it."""

    def __init__(self, queue, batcher, wire_id: int, max_batch: int,
                 log: CursorLog, cursor0: int):
        self.q = queue
        self.batcher = batcher
        self.wire_id = wire_id
        self.max_batch = max_batch
        self.log = log
        self.cursor0 = cursor0
        self.seq = 0

    def emit(self, buf: np.ndarray, stopping: bool) -> None:
        n = int(buf[self.max_batch, 0])
        first_add_t = self.batcher.pop_seal_time()
        seal_ns = _monotonic_ns()
        fill_dur_us = max(0, int(seal_ns / 1e3 - first_add_t * 1e6))
        published_ns = self.log.published_ns(
            self.cursor0 + self.batcher.records_emitted - n)
        ring_wait_us = max(0, int(first_add_t * 1e6 - published_ns / 1e3))
        self.q.ctl_set("ring_wait_clamped", self.log.clamped)
        self.q.ctl_set("ring_observes", self.log.observes)
        self.seq += 1
        deadline = (time.monotonic() + EMIT_STOP_TIMEOUT_S
                    if stopping else None)
        while not self.q.produce_batch(
            buf,
            seq=self.seq,
            n_records=n,
            wire_id=self.wire_id,
            seal_ns=seal_ns,
            fill_dur_us=fill_dur_us,
            ring_wait_us=ring_wait_us,
        ):
            # Queue full: backpressure.  While stopping the consumer may
            # already be gone — bound the wait so shutdown can't hang.
            if deadline is not None and time.monotonic() > deadline:
                # The batch never entered the stream: un-burn its seq
                # (no consumer ever saw it, so later emits stay
                # consecutive and a gap remains a pure corruption
                # signal) and count the loss where the engine reads it.
                self.seq -= 1
                self.q.ctl_set("emit_drop",
                               self.q.ctl_get("emit_drop") + 1)
                return
            self.q.ctl_set("hbeat", _monotonic_ns())
            time.sleep(IDLE_SLEEP_S)
            # records published while the queue is full are dated here,
            # not when the wait ends
            self.log.observe()


def worker_main(spec: dict) -> None:
    """Entry point of one drain worker (module-level: picklable by the
    spawn context).  ``spec`` carries only plain data — paths, batch
    geometry, wire/quant kwargs."""
    from flowsentryx_tpu.core import schema
    from flowsentryx_tpu.core.config import BatchConfig
    from flowsentryx_tpu.engine.batcher import MicroBatcher
    from flowsentryx_tpu.engine.shm import SealedBatchQueue, ShmRingSource

    q = SealedBatchQueue.wait_for(
        spec["queue_path"], timeout_s=spec.get("timeout_s", 10.0)
    )
    q.ctl_set("wstate", schema.WSTATE_SPAWNING)
    try:
        quant = spec.get("quant") or {}
        if (spec["wire"] == schema.WIRE_COMPACT16
                and quant.get("feat_mode", "minifloat") == "minifloat"):
            # Build the minifloat encode LUT now, while still booting:
            # lazily it would land inside the FIRST seal, a ~0.3 s stall
            # with the ring filling behind it.  The first heartbeat is
            # published only after this, so ``ShardedIngest.wait_ready``
            # means "warmed", not just "spawned".
            schema.quantize_feat_minifloat(np.zeros(8, np.uint32))
        q.ctl_set("hbeat", _monotonic_ns())
        src = ShmRingSource(
            spec["ring_path"], timeout_s=spec.get("timeout_s", 10.0)
        )
        wire = spec["wire"]
        if src.precompact and wire != schema.WIRE_COMPACT16:
            raise ValueError(
                "compact-emit ring shard requires the compact16 wire"
            )
        if (src.ring.dtype == schema.FLOW_RECORD6_DTYPE) != (
                wire == schema.WIRE_COMPACT28):
            raise ValueError(
                f"a {src.ring.record_size} B ring shard cannot be sealed "
                f"to the {wire} wire: 64 B records (fsxd --key-bits 128) "
                f"and compact28 (table.key_bits 128) go together")
        # verdict_k=0: the worker-side config only drives the
        # micro-batcher (fill/deadline); the verdict wire is an
        # engine-side device concern, and the default K could exceed a
        # small max_batch and fail BatchConfig validation here.
        cfg = BatchConfig(
            max_batch=spec["max_batch"], deadline_us=spec["deadline_us"],
            verdict_k=0,
        )
        poll_chunk = 2 * cfg.max_batch
        log = CursorLog(src.ring)
        cursor0 = src.ring.tail()
        emitter = None
        pending: list[np.ndarray] = []
        pending_n = 0
        # Idle policy off the ctl block (0 = worker default: no spin,
        # the daemon-matched 200 µs sleep — a bare queue created by a
        # test keeps the pre-backoff behavior unless it pins values).
        backoff = _Backoff(
            int(q.ctl_get("spin_us")),
            int(q.ctl_get("idle_us")) or int(IDLE_SLEEP_S * 1e6),
        )
        q.ctl_set("wstate", schema.WSTATE_RUNNING)

        def add(batcher, records):
            # on_seal, not the returned list: this batcher has TWO
            # buffers and one chunk of slow record time can seal many
            # (MicroBatcher.add) — a sealed buffer must be in the queue
            # before the next seal can reuse it
            pack = batcher.add_precompact if src.precompact else batcher.add
            pack(records, lambda buf: emitter.emit(buf, stopping))

        while True:
            q.ctl_set("hbeat", _monotonic_ns())
            stopping = bool(q.ctl_get("stop"))
            # Zero-copy drain: pack straight out of the ring slots and
            # release them afterwards — at Mpps rates the consume()
            # memcpy was a fifth of the whole worker budget.
            chunks, n_polled = src.ring.peek(poll_chunk, log.observe())
            if n_polled and q.ctl_get("first_ts") == 0:
                head = chunks[0]
                if src.precompact:
                    ts0 = int(
                        schema.unwrap_kernel_ts16(
                            head["w3"][:1], _monotonic_ns()
                        )[0]
                    )
                else:
                    ts0 = int(head["ts_ns"][0])
                q.ctl_set("first_ts", max(ts0, 1))  # 0 means "unseen"
            if emitter is None:
                # t0 handshake: buffer (bounded) until the engine
                # publishes the shared epoch.
                t0 = q.ctl_get("t0")
                if t0 == 0:
                    if stopping:
                        # epoch never agreed (engine gone?): nothing
                        # sealable — exit clean, leave the ring to the
                        # producer's accounting.
                        q.ctl_set("wstate", schema.WSTATE_DONE)
                        return
                    if n_polled and pending_n < PENDING_CAP:
                        # copy out (peek views die at advance); past the
                        # cap records STAY in the ring, so the loss — if
                        # the handshake stalls that long — lands in the
                        # producer's drop counters, never silently here.
                        pending.extend(c.copy() for c in chunks)
                        pending_n += n_polled
                        src.ring.advance(n_polled)
                    else:
                        time.sleep(IDLE_SLEEP_S)
                    continue
                batcher = MicroBatcher(
                    cfg,
                    t0_ns=t0,
                    n_buffers=2,  # produce_batch copies at seal: 2 suffice
                    wire=wire,
                    quant=spec.get("quant") or None,
                )
                emitter = _Emitter(
                    q, batcher, schema.wire_id_of(wire), cfg.max_batch,
                    log, cursor0,
                )
                for r in pending:
                    add(batcher, r)
                pending = []
            else:
                batcher = emitter.batcher

            sealed_before = batcher.batches_emitted
            if n_polled:
                for c in chunks:
                    add(batcher, c)
                # every record is packed into wire buffers and every
                # sealed buffer is in the queue: the ring slots are dead
                src.ring.advance(n_polled)
            else:
                if src.precompact:
                    batcher.note_poll()
                if batcher.flush_due():
                    took = batcher.take()
                    if took is not None:
                        emitter.emit(took, stopping)
            sealed = batcher.batches_emitted != sealed_before
            if stopping and not n_polled and src.ring.readable() == 0:
                # drain-on-shutdown: ring empty, flush the partial batch
                tail = batcher.take()
                if tail is not None:
                    emitter.emit(tail, stopping=True)
                q.ctl_set("wstate", schema.WSTATE_DONE)
                return
            if not n_polled and not sealed:
                # Empty ring: bounded spin before sleeping (the sleep
                # was the dominant empty-ring wakeup latency at high
                # rates — a burst landing just after the sleep started
                # waited the whole 200 µs plus reschedule).
                if backoff.idle():
                    log.observe()
            else:
                backoff.reset()
    except Exception:
        try:
            q.ctl_set("wstate", schema.WSTATE_FAILED)
        except Exception:
            pass
        traceback.print_exc()
        raise
