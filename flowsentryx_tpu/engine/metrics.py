"""Time-keeping for the serving pipeline: one span type, one store.

The reference's only observability is printk in the packet path
(SURVEY.md §5.1, which it even identifies as a perf bug).  Here every
stage boundary of the serving path is a :class:`Span`:

* entered (``with span:`` / ``with span(seq):``) it is a
  ``jax.profiler.TraceAnnotation`` — a host span on the profiler's own
  clock, in the same trace as the device planes, present exactly when
  a ``jax.profiler`` session is on (``fsx serve --profile``, the
  benchmark's ``--trace 1``) and about a microsecond when none is;
* always, its duration goes into a :class:`LatencyHist` — count, exact
  ``sum_us``, max, 1/16-octave log buckets: FIXED memory, O(buckets)
  percentiles, no per-sample storage.  ``Span.add(seconds, n)`` feeds
  the histogram alone, for durations measured elsewhere (the ingest
  workers' seal stamps, the staging memcpy inside a poll).

:class:`LatencyRecorder` is the per-RECORD seal→verdict plane (ISSUE
11) on the same histograms, with the closed stage chain of ISSUE 29.
Everything is cumulative since boot (or ``reset_stream``): the report's
``spans`` block carries every histogram's mergeable counts, so a
reader gets any window by subtracting two reports, and the per-rank
merge (``supervisor.aggregate``) by adding them.  Numpy-only at import:
``jax.profiler`` is imported where the first span is entered, so the
jax-free consumers (cluster supervisor, ``fsx status``) can import the
histogram half on their sub-second path.
"""

from __future__ import annotations

import time

import numpy as np


#: LatencyHist geometry: 16 linear sub-buckets per power-of-two octave
#: over [1 µs, 2^26 µs ≈ 67 s].  16 sub-buckets bound the relative
#: quantization error of a reported percentile at 1/16 ≈ 6.25 % — the
#: same fidelity class as the compact16 wire's minifloat — for 432
#: int64 buckets ≈ 3.5 KB per histogram, fixed for the life of a serve.
LAT_SUB = 16
LAT_OCTAVES = 27
LAT_BUCKETS = LAT_OCTAVES * LAT_SUB


def _lat_bucket(us: float) -> int:
    """Bucket index of a µs value (scalar; the engine records per
    sunk BATCH, so this is never a per-record hot path).  CEILING to
    whole µs before bucketing: truncation would drop sub-16 µs values
    into buckets whose upper edge is BELOW the true value, breaking
    the conservative-upper-edge percentile guarantee exactly in the
    octaves where the 1 µs truncation step exceeds the sub-bucket
    width."""
    u = max(-int(-us // 1), 1)
    e = u.bit_length() - 1
    if e >= LAT_OCTAVES:
        return LAT_BUCKETS - 1
    sub = ((u - (1 << e)) * LAT_SUB) >> e
    return e * LAT_SUB + sub


def _lat_edge_us(idx: int) -> float:
    """UPPER edge (µs) of bucket ``idx`` — percentiles report the
    conservative edge, so a quoted p99 is never under the true one by
    more than the 1/16 sub-bucket width."""
    e, sub = divmod(idx + 1, LAT_SUB)
    return float((1 << e) * (1.0 + sub / LAT_SUB))


class LatencyHist:
    """HDR-style log-bucketed latency histogram (module docstring).

    ``add(seconds, n)`` charges ``n`` records one latency value (the
    engine's per-record accounting anchors every record of a batch at
    the batch's OLDEST-record stamp — a conservative per-record upper
    bound, matching how ``e2e`` has always been anchored); ``merge``
    sums another histogram in; ``percentile_us`` walks the cumulative
    counts.  ``to_counts()``/``from_counts()`` round-trip the nonzero
    buckets through JSON for the cluster per-rank merge."""

    def __init__(self) -> None:
        self.counts = np.zeros(LAT_BUCKETS, np.int64)
        self.n = 0
        self.sum_us = 0.0
        self.max_us = 0.0

    def add(self, seconds: float, n: int = 1) -> None:
        if n <= 0:
            return
        us = seconds * 1e6
        self.counts[_lat_bucket(us)] += n
        self.n += n
        self.sum_us += us * n
        if us > self.max_us:
            self.max_us = us

    def merge(self, other: "LatencyHist") -> "LatencyHist":
        self.counts += other.counts
        self.n += other.n
        self.sum_us += other.sum_us
        self.max_us = max(self.max_us, other.max_us)
        return self

    def percentile_us(self, q: float) -> float:
        """Value (µs, conservative bucket upper edge) at percentile
        ``q`` — O(buckets) cumulative walk, no sort.  The all-time max
        is exact, so ``q=100`` reports it rather than an edge."""
        if not self.n:
            return 0.0
        if q >= 100.0:
            return round(self.max_us, 1)
        rank = max(int(np.ceil(self.n * q / 100.0)), 1)
        cum = np.cumsum(self.counts)
        idx = int(np.searchsorted(cum, rank))
        # the top bucket holds the >67 s clamp; its "edge" is the max
        if idx >= LAT_BUCKETS - 1:
            return round(self.max_us, 1)
        return round(min(_lat_edge_us(idx), self.max_us), 1)

    def to_dict(self) -> dict:
        """Percentile summary (µs) — the report-facing face."""
        if not self.n:
            return {"n": 0}
        return {
            "n": int(self.n),
            "p50": self.percentile_us(50),
            "p90": self.percentile_us(90),
            "p99": self.percentile_us(99),
            "p999": self.percentile_us(99.9),
            "max": round(self.max_us, 1),
            "mean": round(self.sum_us / self.n, 1),
        }

    def to_counts(self) -> dict:
        """JSON-able mergeable form: nonzero buckets only."""
        nz = np.nonzero(self.counts)[0]
        return {
            "scheme": f"log2x{LAT_SUB}us",
            "buckets": {str(int(i)): int(self.counts[i]) for i in nz},
            "n": int(self.n),
            "sum_us": round(self.sum_us, 1),
            "max_us": round(self.max_us, 1),
        }

    @classmethod
    def from_counts(cls, d: dict) -> "LatencyHist":
        h = cls()
        scheme = d.get("scheme")
        if scheme != f"log2x{LAT_SUB}us":
            raise ValueError(
                f"latency histogram scheme {scheme!r} != "
                f"log2x{LAT_SUB}us — refusing a silent mis-merge")
        for i, c in d.get("buckets", {}).items():
            idx = int(i)
            if not 0 <= idx < LAT_BUCKETS:
                # a negative index would silently wrap into the top
                # octave and skew every merged percentile — the exact
                # mis-merge the scheme check refuses; and IndexError
                # would escape callers' ValueError armor
                raise ValueError(
                    f"latency histogram bucket {idx} outside "
                    f"[0, {LAT_BUCKETS}) — corrupt or foreign counts")
            h.counts[idx] += int(c)
        h.n = int(d.get("n", 0))
        h.sum_us = float(d.get("sum_us", 0.0))
        h.max_us = float(d.get("max_us", 0.0))
        return h


class Span:
    """One named stage of the serving path (module docstring): a host
    span in the profiler's trace while a session is on, and always a
    :class:`LatencyHist` of its durations.

    ``with span:`` times the block; ``with span(seq) as s:`` also tags
    the trace event with the dispatch ordinal of the group it works on
    (one group's spans share ``seq`` across threads) and leaves
    ``s.t0`` / ``s.t1`` / ``s.seconds`` for the caller's own stamps;
    ``span.add(seconds, n)`` counts a duration measured elsewhere.
    Every span has one writing thread at a time (docs/CONCURRENCY.md
    names the owners), so the histogram needs no lock."""

    __slots__ = ("name", "hist", "_open")

    def __init__(self, name: str):
        self.name = name
        self.hist = LatencyHist()
        self._open: _OpenSpan | None = None

    def add(self, seconds: float, n: int = 1) -> None:
        self.hist.add(max(seconds, 0.0), n)

    def __call__(self, seq: int | None = None) -> "_OpenSpan":
        return _OpenSpan(self, seq)

    def __enter__(self) -> "_OpenSpan":
        self._open = _OpenSpan(self, None)
        return self._open.__enter__()

    def __exit__(self, *exc) -> bool:
        return self._open.__exit__(*exc)

    def percentiles_ms(self) -> dict[str, float]:
        """The ``stages_ms`` / ``fill_ms`` / ``queue_ms`` face: all-time
        bucket upper edges (at most 1/16 over), exact max and mean."""
        h = self.hist
        if not h.n:
            return {}
        return {
            "p50": round(h.percentile_us(50) / 1e3, 4),
            "p99": round(h.percentile_us(99) / 1e3, 4),
            "max": round(h.max_us / 1e3, 4),
            "mean": round(h.sum_us / h.n / 1e3, 4),
            "n": int(h.n),
        }


_TraceAnnotation = None


class _OpenSpan:
    """One entry of a :class:`Span`."""

    __slots__ = ("span", "ann", "t0", "t1")

    def __init__(self, span: Span, seq: int | None):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            # deferred: the histogram half of this module stays
            # importable without jax (module docstring)
            from jax.profiler import TraceAnnotation

            _TraceAnnotation = TraceAnnotation
        self.span = span
        self.ann = (_TraceAnnotation(span.name) if seq is None
                    else _TraceAnnotation(span.name, seq=seq))

    def __enter__(self) -> "_OpenSpan":
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.span.hist.add(self.t1 - self.t0)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span_store(hists: dict[str, LatencyHist]) -> dict:
    """The report's ``spans`` block: every histogram's cumulative
    count, exact sum, max and mergeable bucket counts by name.  A
    window is two of these subtracted (``n``, ``sum_us`` and each
    bucket; ``max_us`` is all-time and does not subtract)."""
    return {name: {"n": int(h.n), "sum_us": h.sum_us,
                   "max_us": h.max_us, "hist": h.to_counts()}
            for name, h in hists.items()}


class LatencyRecorder:
    """The engine's per-record latency plane: one total (seal→verdict)
    histogram, the stage decomposition the SLO mode is tuned by, and
    the closed stage chain.

    SLO stages — ``staged_wait`` (seal → launch: batcher/pending/
    arena/sink-queue residency), ``upload`` (the explicit H2D put),
    ``compute`` (the step call's wall — on synchronously-dispatching
    backends like XLA:CPU this IS the compute; on async backends it is
    the enqueue cost — disclosed in the report's ``compute_is_wall``
    flag), and ``sink`` (wire fetch begins → writeback applied: on an
    async backend it holds the sink's wait on the device).

    The chain — ``fill`` (first record → seal), ``queue`` (seal →
    engine dequeue), ``hold`` (dequeue → the launch section picks the
    entry up, less an upload made ahead of it: arena residency and the
    ladder's wait for a rung), then ``upload`` and ``compute`` as
    above, ``device`` (the step call returned → the group's wire is on
    the host: device queue + step + D2H) and ``sink_host`` (wire on
    the host → verdict sunk).  CHAIN sums to the total for every entry
    (test-pinned), so the window sums of the seven say where a
    record's time went with nothing left over.

    An entry is charged from its OLDEST member batch, and all
    histograms weight by the entry's record count; an entry with zero
    valid records (warm) records nothing.

    ``negatives`` counts stage deltas that arrived negative (clock
    inversion between the seal and sink stamps) — the smoke gate pins
    it at 0 every run."""

    CHAIN = ("fill", "queue", "hold", "upload", "compute", "device",
             "sink_host")
    STAGES = ("staged_wait", "upload", "compute", "sink",
              "fill", "queue", "hold", "device", "sink_host")

    def __init__(self) -> None:
        self.total = LatencyHist()
        self.stages = {s: LatencyHist() for s in self.STAGES}
        self.negatives = 0
        self.slo_miss_records = 0

    def record(self, total_s: float, staged_s: float, upload_s: float,
               compute_s: float, sink_s: float, n: int,
               budget_s: float = 0.0, *, fill_s: float = 0.0,
               queue_s: float = 0.0, hold_s: float = 0.0,
               device_s: float = 0.0, sink_host_s: float = 0.0) -> None:
        if n <= 0:
            return
        stages = (staged_s, upload_s, compute_s, sink_s,
                  fill_s, queue_s, hold_s, device_s, sink_host_s)
        self.negatives += sum(v < 0.0 for v in (total_s, *stages))
        self.total.add(max(total_s, 0.0), n)
        for name, v in zip(self.STAGES, stages):
            self.stages[name].add(max(v, 0.0), n)
        if budget_s and total_s > budget_s:
            self.slo_miss_records += n

    def hists(self) -> dict[str, LatencyHist]:
        """The plane's histograms under their ``spans`` names."""
        return {"latency.seal_to_verdict": self.total,
                **{f"latency.{s}": h for s, h in self.stages.items()}}

    def merge(self, other: "LatencyRecorder") -> "LatencyRecorder":
        self.total.merge(other.total)
        for s in self.STAGES:
            self.stages[s].merge(other.stages[s])
        self.negatives += other.negatives
        self.slo_miss_records += other.slo_miss_records
        return self

    def to_dict(self, slo_us: int = 0,
                compute_is_wall: bool = True) -> dict:
        out = {
            "unit": "us",
            "seal_to_verdict": self.total.to_dict(),
            "stages": {s: self.stages[s].to_dict()
                       for s in self.STAGES},
            "compute_is_wall": bool(compute_is_wall),
            "negatives": int(self.negatives),
            "hist": self.total.to_counts(),
        }
        if slo_us:
            n = max(self.total.n, 1)
            out["slo"] = {
                "slo_us": int(slo_us),
                "miss_records": int(self.slo_miss_records),
                "miss_fraction": round(self.slo_miss_records / n, 6),
            }
        return out


class WorkerIngestMetrics:
    """Per-drain-worker spans of the sharded ingest subsystem
    (flowsentryx_tpu/ingest/): ``fill`` is first-record-arrival → seal
    inside the worker (the parallelized decode/assembly stage), ``queue``
    is seal → engine dequeue (sealed-batch queue residency — the
    pipelining debt the engine's dispatch loop imposes).  One sample a
    sealed batch, unweighted (the latency plane's ``fill``/``queue``
    weigh by records and charge an entry's oldest batch).  Surfaced per
    worker in the engine report's ``ingest`` block, and in ``spans``."""

    def __init__(self, worker: int):
        self.worker = worker
        self.fill = Span(f"fsx.ingest.w{worker}.fill")
        self.queue = Span(f"fsx.ingest.w{worker}.queue")

    def to_dict(self) -> dict:
        return {
            "fill_ms": self.fill.percentiles_ms(),
            "queue_ms": self.queue.percentiles_ms(),
        }


class PipelineMetrics:
    """The engine's spans (docs/ENGINE.md §Observability has the
    table).  Each thread's spans are disjoint — ``poll`` alone holds
    parts, ``pop`` and ``stage`` — so a thread's time is the sum of its
    spans, and the rest of its wall is what it did unnamed.

    Dispatch thread: ``poll`` (the sealed loops' ``poll_batches_into``;
    the inline loop's source poll + batcher pack), of which the sealed
    path counts ``pop`` (queue peek + header decode + seq bookkeeping)
    and ``stage`` (the ONE shm-slot-view → dispatch-arena memcpy of
    the zero-copy pipeline; the inline loop's arena pack too) — a
    regression that re-grows a second copy shows up as a ``stage``
    jump, not as undifferentiated ``poll`` noise; ``upload`` (the
    explicit H2D put); ``launch`` (the step call: the enqueue on an
    async backend); ``backpressure`` (the blocking wait for the pipe
    to drain to ``readback_depth``); ``idle`` (the sleeps on an empty
    poll); ``report``.

    Sink section (the sink thread; the dispatch thread in
    single-thread mode): ``wait`` (nothing queued), ``fetch`` (the
    D2H: on an async backend the wait on the device), ``decode``,
    ``apply`` (writeback, gossip publish, book-keeping, ``on_reap``);
    ``e2e`` is first record in → sunk, one sample an in-flight entry.

    ``stages_ms`` is the older face of the same histograms."""

    def __init__(self) -> None:
        self.poll = Span("fsx.dispatch.poll")
        self.pop = Span("fsx.dispatch.pop")
        self.stage = Span("fsx.dispatch.stage")
        self.upload = Span("fsx.dispatch.upload")
        self.launch = Span("fsx.dispatch.launch")
        self.backpressure = Span("fsx.dispatch.backpressure")
        self.idle = Span("fsx.dispatch.idle")
        self.report = Span("fsx.report")
        self.sink_wait = Span("fsx.sink.wait")
        self.fetch = Span("fsx.sink.fetch")
        self.decode = Span("fsx.sink.decode")
        self.apply = Span("fsx.sink.apply")
        self.e2e = Span("fsx.e2e")

    def spans(self) -> tuple[Span, ...]:
        """Every span: the attributes are the spans and nothing else."""
        return tuple(vars(self).values())

    def to_dict(self) -> dict:
        """``EngineReport.stages_ms``, under the names it always had."""
        return {
            name: span.percentiles_ms()
            for name, span in (("fill", self.poll), ("pop", self.pop),
                               ("stage", self.stage),
                               ("dispatch", self.launch),
                               ("readback", self.fetch),
                               ("e2e", self.e2e))
        }
