"""The dispatch↔worker handoff protocol, as one small real class.

:class:`SinkChannel` is the cv-guarded bounded pipe between the
engine's dispatch thread and its sink worker — the
queue, the dispatched-but-unsunk batch count the ``readback_depth``
backpressure waits on, the stop flag, and the crash slot.  It used to
live as five loose ``Engine`` attributes (``_sinkq``/``_sink_pending``/
``_sink_stop``/``_sink_exc``/``_sink_busy_s``); extracting it buys two
things:

* the protocol's invariants are stated (and enforced by ``fsx sync``)
  in ONE place instead of across a 2000-line engine, and
* the bounded-interleaving model checker
  (:mod:`flowsentryx_tpu.sync.interleave`) can drive the REAL protocol
  object — the nonblocking core below is exactly what the blocking
  wrappers loop over, so a schedule the checker explores is a schedule
  the engine can execute.

THE one crash-propagation path (docs/CONCURRENCY.md §crash): a worker
records its death via :meth:`complete`'s ``exc`` argument (or
:meth:`record_exc` for failures outside any group), and the exception
lands ATOMICALLY with the queue accounting — a backpressure waiter
woken by the completing notify can never observe (pending drained,
crash unset) for work that actually crashed.  The dispatch side
surfaces it loudly through :meth:`check` (a RuntimeError naming the
worker), which every engine poll/reap passes through.  The sink
thread and strict-mode ingest death both funnel through this same
shape, so a dead worker of ANY type reads the
same at the dispatch loop.

Timing constants come from :mod:`flowsentryx_tpu.sync.tuning`.
Jax-free by design.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Callable

from flowsentryx_tpu.sync import tuning


class WorkerCrash(RuntimeError):
    """A worker of the host pipeline died; raised on the DISPATCH thread by
    :meth:`SinkChannel.check` so the engine fails loudly instead of
    serving on with verdicts silently discarded."""


class SinkChannel:
    """Bounded cv-guarded handoff queue with crash-coupled accounting.

    Discipline (the ``fsx sync`` contract registry pins it):

    * ``_q``, ``_stop`` — every access under ``self.cv``;
    * ``_pending``, ``_exc``, ``busy_s`` — writes under ``self.cv``;
      the documented unlocked reads (:attr:`pending`,
      :meth:`crashed`, the report's busy total) are benign on CPython
      — single reference/int loads of values that only the holder of
      the cv advances;
    * ``_pending`` counts BATCHES (chunks), not queue entries — a mega
      entry is ``n_chunks`` batches, and counting it as one would
      silently multiply the configured pipe depth.
    """

    def __init__(self, name: str = "worker"):
        #: Worker name for crash diagnostics ("sink thread",
        #: "ingest worker 3").
        self.name = name
        self.cv = threading.Condition()
        self._q: deque = deque()
        self._pending = 0
        self._stop = False
        self._exc: BaseException | None = None
        self.busy_s = 0.0

    # -- dispatch side ------------------------------------------------------

    def submit(self, item: Any, n_chunks: int) -> None:
        """Enqueue one work item; ``_pending`` rises at SUBMIT time so
        the backpressure bound covers queued-but-unprocessed work too
        (the wire/arena reuse-safety arguments both lean on that)."""
        with self.cv:
            self._q.append(item)
            self._pending += n_chunks
            self.cv.notify_all()

    def submit_many(self, items: list, n_chunks: Callable[[Any], int]) -> None:
        """Enqueue a batch of items under ONE lock acquisition (the
        engine's staged-inflight handoff)."""
        if not items:
            return
        with self.cv:
            for it in items:
                self._q.append(it)
                self._pending += n_chunks(it)
            self.cv.notify_all()

    def wait_below(self, down_to: int,
                   quantum: float = tuning.BACKPRESSURE_WAIT_S,
                   on_wait: Callable[[], None] | None = None) -> None:
        """Block until at most ``down_to`` batches remain pending or
        the worker crashed (the ``readback_depth`` backpressure);
        :meth:`check` after this surfaces the crash.

        ``on_wait`` runs once per wakeup quantum while still over
        depth — the engine's dispatch-watchdog hook (a wedged-but-
        ALIVE worker records no exc, so without it this wait would
        park forever with no diagnostic).  It may raise; the cv is
        released on the way out like any exception under ``with``."""
        with self.cv:
            while self._pending > down_to and self._exc is None:
                self.cv.wait(quantum)
                if on_wait is not None:
                    on_wait()

    @property
    def pending(self) -> int:
        """Submitted-but-uncompleted batches (unlocked benign read —
        the dispatch side's busy-pipe predicate)."""
        return self._pending

    def crashed(self) -> BaseException | None:
        """The recorded worker exception, if any (unlocked benign
        read: transitions None→exc exactly once per run)."""
        return self._exc

    def check(self) -> None:
        """Surface a recorded worker crash as a loud dispatch-side
        error — THE unified worker-death idiom."""
        exc = self._exc
        if exc is not None:
            raise WorkerCrash(
                f"engine {self.name} crashed: "
                f"{type(exc).__name__}: {exc}") from exc

    def request_stop(self) -> None:
        """Ask the worker to drain the queue and exit."""
        with self.cv:
            self._stop = True
            self.cv.notify_all()

    def reset(self) -> None:
        """Re-arm for a new worker (engine thread start).  Must only
        run quiescent — no worker alive.  The queue and pending count
        are CLEARED, not trusted empty: after a worker crash the dead
        run's unsunk groups are still queued, and a fresh worker must
        not sink a crashed stream's stale work into the new run (the
        crash already surfaced loudly; those verdicts are lost either
        way)."""
        with self.cv:
            self._q.clear()
            self._pending = 0
            self._stop = False
            self._exc = None
            self.busy_s = 0.0

    # -- worker side --------------------------------------------------------

    def try_pop(self, coalesce: Callable[[Any], bool] | None = None
                ) -> list | None:
        """Nonblocking pop of the oldest item (plus, with ``coalesce``,
        every consecutive item the predicate accepts — the sink
        thread's ready-group fold).  Returns None when the queue is
        empty; the empty list ``[]`` is never returned.  This is the
        model checker's atomic step; :meth:`pop` is the blocking
        wrapper the real workers run."""
        with self.cv:
            if not self._q:
                return None
            group = [self._q.popleft()]
            if coalesce is not None:
                while self._q and coalesce(self._q[0]):
                    group.append(self._q.popleft())
            return group

    def pop(self, coalesce: Callable[[Any], bool] | None = None,
            quantum: float = tuning.POP_WAIT_S) -> list | None:
        """Blocking pop: wait for work, or return None once stop was
        requested AND the queue drained (the drain-preserving shutdown
        contract — queued work always completes)."""
        with self.cv:
            while not self._q and not self._stop:
                self.cv.wait(quantum)
            if not self._q:
                return None
        # re-enter through the nonblocking core: between the wait and
        # this pop only THIS worker consumes (single-worker protocol),
        # so the queue cannot have emptied.
        return self.try_pop(coalesce)

    def complete(self, n_chunks: int, busy_s: float = 0.0,
                 exc: BaseException | None = None) -> None:
        """Account one finished group — and, when it crashed, record
        the exception ATOMICALLY with the pending decrement: a
        backpressure waiter woken by this notify must never observe
        (pending drained, exc unset) for a group that actually
        crashed.  This is the invariant the model checker's planted
        split-complete negative demonstrates breaking."""
        with self.cv:
            self.busy_s += busy_s
            self._pending -= n_chunks
            if exc is not None:
                self._exc = exc
            self.cv.notify_all()

    def record_exc(self, exc: BaseException) -> None:
        """Record a worker failure that happened OUTSIDE any group
        (the worker loop's outer catch)."""
        with self.cv:
            self._exc = exc
            self.cv.notify_all()

    def drained(self) -> bool:
        """True when nothing is queued (stop-path assertion hook)."""
        with self.cv:
            return not self._q
