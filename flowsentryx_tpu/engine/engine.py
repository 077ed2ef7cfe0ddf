"""The serving engine: drain → batch → TPU step → verdict writeback.

The online loop of BASELINE configs 4/5.  TWO threads:

* the **dispatch thread** (the caller of :meth:`Engine.run`) only polls
  the source and enqueues device steps — JAX dispatch is asynchronous,
  so "dispatch batch N, fill batch N+1" overlaps host fill with device
  compute exactly as before;
* a **sink thread** harvests finished step futures, fetches the compact
  verdict wire (one O(verdict_k) D2H buffer per batch, see
  ``ops/fused.py``), and runs writeback/metrics/``on_reap`` — the fixed
  host cost per sunk batch no longer blocks the dispatch loop.

A bounded handoff queue provides backpressure: ``readback_depth`` caps
how many BATCHES may be dispatched-but-unsunk before the dispatch
thread blocks — a pipe bound, not a readback schedule (scheduling
readback BY depth deferred every verdict by depth × batch-fill time,
the r4 open-loop latency collapse).  The sink thread sinks each batch
the moment its wire is ready, oldest first, and coalesces whatever else
already finished into the same group.  A crash in the sink thread fails
the engine loudly on the next dispatch-iteration; shutdown drains the
queue, then joins.  ``sink_thread=False`` restores the single-thread
loop (readiness-reaped, same semantics — parity is test-pinned); the
default is AUTO — threaded only where the host has ≥3 cores, because
on 1-2 core hosts the extra thread merely contends with dispatch and
XLA's own pool.

The blacklist tolerates the remaining small writeback delay by design —
the kernel limiter stands alone during the gap (fail-open, SURVEY.md
§5.3).

How batches reach the device is decided in TWO serving loops —
:meth:`Engine._run_inline` (a record source; the batcher lives in the
engine) and :meth:`Engine._sealed_loop_arena` (a sealed-batch source;
the ingest workers own the batchers) — and both launch one of TWO
kinds of dispatch: ``("single",)`` through :meth:`Engine._dispatch`,
or ``("mega", g)``, one ``lax.scan`` over a staged group of ``g``
batches, through :meth:`Engine._dispatch_group`.
"""

from __future__ import annotations

import threading
import time
from typing import Any, NamedTuple

import jax
# The ONE module-level jax.numpy import for the deep-drain device-side
# concat paths — previously duplicated as function-local imports in
# every branch of the group sink.  Free here: ``import jax`` above has
# already initialized jax.numpy, so there is nothing to defer.
import jax.numpy as jnp
import numpy as np

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import FsxConfig
from flowsentryx_tpu.engine.arena import DispatchArena
from flowsentryx_tpu.engine.batcher import MicroBatcher
from flowsentryx_tpu.engine import health
from flowsentryx_tpu.engine.metrics import (
    LatencyRecorder, PipelineMetrics, span_store,
)
from flowsentryx_tpu.engine.sources import RecordSource
from flowsentryx_tpu.engine.watchdog import DispatchWatchdog
from flowsentryx_tpu.engine.writeback import (
    VerdictSink, decode_verdict_wire, extract_updates, wire_overflowed,
)
from flowsentryx_tpu.models import get_model
from flowsentryx_tpu.ops import fused, pallas_kernels
from flowsentryx_tpu.sync import tuning
from flowsentryx_tpu.sync.channel import SinkChannel


#: ``Engine(mega_n="auto")`` / ``fsx serve --mega auto``: the largest
#: group size of the adaptive power-of-two coalescing ladder.  8 holds
#: the staged-variant count at three scan artifacts (2/4/8) while
#: already amortizing ~8x of the per-dispatch fixed cost.  Where the
#: knee is on the chip has not been measured (ROADMAP speed item 4c).
MEGA_AUTO_MAX = 8


class EngineReport(NamedTuple):
    batches: int
    records: int
    wall_s: float
    records_per_s: float
    stats: dict
    stages_ms: dict
    blocked_sources: int
    table: dict           # live-table summary (pallas single-pass scan)
    #: Precompact drains at risk of 16-bit kernel-ts unwrap aliasing
    #: (drain-gap > 50 ms; see MicroBatcher.add_precompact).  Always 0
    #: outside compact-emit serving.
    ts_wrap_risk_polls: int = 0
    #: Packets fail-opened because their flow overflowed owner routing
    #: in the sharded step (adversarial hash skew; parallel/step.py
    #: module docstring).  Always 0 single-device.
    route_drop: int = 0
    #: Sharded-ingest summary (per-worker batches/records/seq-gaps and
    #: fill/queue p50/p99) when the source is a sealed-batch fleet
    #: (flowsentryx_tpu/ingest/); None on the inline record path.
    ingest: dict | None = None
    #: Verdict-readback accounting: wire mode and size, compact vs
    #: fallback sink counts, D2H bytes per sunk batch, and sink-thread
    #: occupancy (busy fraction of the run wall; None single-threaded).
    readback: dict | None = None
    #: Dispatch-pipeline accounting: coalescing mode and staged group
    #: sizes, per-group-size dispatch histogram, dispatch rate, bytes
    #: staged through the arena and HOST copies per dispatched batch
    #: (the zero-copy pipeline's invariant: 1.0 on the sealed compact16
    #: path — one shm-slot-view → arena memcpy, then the device_put
    #: boundary), plus the arena geometry.  None before the first run.
    dispatch: dict | None = None
    #: Two-tier escalation accounting (kernel-distilled classifier,
    #: flowsentryx_tpu/distill/): band thresholds plus per-band record
    #: counts — kernel drops / suppressed passes / escalations — and
    #: the derived escalation ratio and kernel-drop Hz.  Filled from a
    #: simulated tier (``Engine(kernel_tier=SimKernelTier(...))`` /
    #: ``fsx serve --sim-kernel-tier``; rootless CI path); a real
    #: deployment reads the same split off the kernel stats map
    #: (``fsx status --pin``: dropped_ml / ml_pass / ml_escalated).
    #: None when no kernel tier fronts the engine.
    escalation: dict | None = None
    #: Cluster gossip accounting (``flowsentryx_tpu/cluster/``): rank,
    #: published/merged blacklist digests and wire/drop counters of the
    #: coordinator-less verdict plane.  None outside cluster serving.
    cluster: dict | None = None
    #: Per-record seal→verdict latency plane (engine/metrics.py
    #: LatencyRecorder): HDR log-bucketed percentiles of the
    #: feature→verdict path (p50/p90/p99/p999/max, µs), the
    #: staged-wait / upload / compute / sink stage decomposition, the
    #: mergeable bucket counts (cluster aggregate, ``fsx status
    #: --engine-report``), and — when serving under ``--slo-us`` — the
    #: budget-miss accounting.  Always measured; None only before the
    #: first run.
    latency: dict | None = None
    #: Explicit health ladder (engine/health.py): HEALTHY /
    #: DEGRADED(reasons) / FAILED, derived from the signals this report
    #: already carries — dead/stalled ingest shards, seq gaps, emit
    #: drops, quarantined poisoned batches, corrupt-slot skips, gossip
    #: TX-drop / RX-gap counters, watchdog trips, ``.prev`` restore
    #: fallbacks.  Aggregated worst-of across ranks by the cluster
    #: supervisor; queryable via ``fsx status --engine-report`` and
    #: alertable via ``fsx monitor --alert-degraded``.
    health: dict | None = None
    #: Live-rebalance audit (cluster/rebalance.py): rows shipped /
    #: adopted / dropped-post-flip, handoffs donated/adopted, refused
    #: streams, staged discards, boot-time foreign-row drops.  None
    #: until the first handoff touches this engine.
    rebalance: dict | None = None
    #: Predictive dispatch governor (engine/predict.py): the burst
    #: estimator's period/duty/confidence, pre-warm hit/miss and
    #: early-flush/hold actuation counters, and the budget-pressure
    #: shed counts (anti-entropy ticks / resyncs deferred).  Merged
    #: across ranks by the supervisor
    #: (``DispatchGovernor.merge_reports``).  None unless serving with
    #: ``predict=True`` (``fsx serve --predict``).
    predict: dict | None = None
    #: Boot-latency accounting (ISSUE 20): per-variant compile vs
    #: cache-hit timings from :meth:`Engine.warm`, the persistent AOT
    #: compile-cache counters (hits / misses / corrupt / version_drift
    #: — engine/compile_cache.py), serving-ready and background-fill
    #: walls, import time (``Engine.boot_import_s``, stamped by the
    #: CLI/runner) and time-to-first-verdict.  Aggregated per rank by
    #: the cluster supervisor and alertable via ``fsx monitor
    #: --alert-cold-boot``.  None until warm() runs.
    boot: dict | None = None
    #: Where this engine ran: ``{"platform", "kind", "count"}`` of the
    #: devices its flow table was PLACED on (read off the table's own
    #: shards at construction, not off ``jax.devices()``) — what lets a
    #: printed report show it served on the chip and not on the CPU.
    device: dict | None = None
    #: The span store (engine/metrics.py): ``{name: {"n", "sum_us",
    #: "max_us", "hist"}}`` for every span of the serving path
    #: (``fsx.dispatch.*``, ``fsx.sink.*``, ``fsx.report``, the ingest
    #: workers' ``fsx.ingest.w<k>.*``) and every histogram of the
    #: latency plane (``latency.seal_to_verdict``, ``latency.<stage>``)
    #: — CUMULATIVE since boot or ``reset_stream``, so two reports
    #: subtracted are a window (docs/ENGINE.md §Observability).
    #: ``stages_ms``, ``latency`` and the ingest blocks' ``fill_ms`` /
    #: ``queue_ms`` are percentile views of the same histograms.  The
    #: ``fsx.report`` span of THIS report closes after it is built: it
    #: is in the next one.
    spans: dict | None = None


class _Stamps(NamedTuple):
    """The host stamps of one sealed batch (perf_counter domain): the
    latency plane charges an in-flight entry from its OLDEST member
    batch, so that member's three stamps ride with the entry."""

    t_enqueue: float    # when the batch's first record arrived
    t_seal: float       # when its batcher (worker or inline) sealed it
    t_dequeue: float    # when the engine took it off the sealed queue
    #                     (the inline path has no queue: == t_seal)


def _inline_stamps(t_first: float) -> _Stamps:
    """Stamps of a batch the engine's own batcher just sealed: the
    caller pops it the moment it seals, so seal and dequeue are now."""
    now = time.perf_counter()
    return _Stamps(t_first, now, now)


def _sealed_stamps(sb) -> _Stamps:
    """Stamps of a batch taken off the sealed queue; a source that does
    not stamp the dequeue (a stand-in fleet) dequeued it just now."""
    return _Stamps(sb.t_enqueue, sb.t_seal,
                   sb.t_dequeue or time.perf_counter())


class _InFlight(NamedTuple):
    out: Any            # StepOutput of device futures
    stamps: _Stamps     # of the entry's oldest member batch
    n_records: int      # valid records in the batch (wire meta row)
    n_chunks: int = 1   # batches in this entry (mega_n for a mega dispatch)
    # latency-plane stamps (engine/metrics.py LatencyRecorder): when
    # the launch section picked the entry up, how long its explicit
    # H2D put took, and the step call's own wall — on synchronously-
    # dispatching backends (XLA:CPU scatter custom-calls) the latter
    # IS the compute time; see EngineReport.latency["compute_is_wall"].
    t_launch: float = 0.0
    put_s: float = 0.0
    launch_s: float = 0.0
    t_launched: float = 0.0   # when the step call returned
    #: dispatch ordinal of the entry: the ``seq`` its spans carry
    #: (upload, launch, fetch, decode, apply), across threads
    seq: int = 0


class Engine:
    """Owns the device state (table/stats/params) and runs the loop.

    With ``donate`` (the default) the table updates in place in HBM
    (no 40 MB copy per batch).
    ``readback_depth`` is how many batches may be in flight before the
    oldest verdicts are fetched and sunk (``None`` = the config's
    ``BatchConfig.readback_depth``).

    ``audit`` (``None`` = on when ``FSX_AUDIT=1``) statically audits
    the serving step's graph contracts at boot — dtypes, donation
    aliasing, transfer budget, retrace stability, collectives
    (:mod:`flowsentryx_tpu.audit`) — and raises rather than serve on a
    violated contract.  Results are cached per staged shape, so a
    fleet of engines in one process pays the audit trace once.

    The engine's own host↔device boundary is EXPLICIT: batches enter
    via ``jax.device_put`` and results leave via ``jax.device_get``,
    so tests can run the whole loop under
    ``jax.transfer_guard("disallow")`` and any *implicit* transfer that
    sneaks into the hot path fails loudly in CI.
    """

    def __init__(
        self,
        cfg: FsxConfig,
        source: RecordSource,
        sink: VerdictSink,
        params: Any | None = None,
        donate: bool = True,
        readback_depth: int | None = None,
        t0_ns: int | None = None,
        mesh: Any | None = None,
        wire: str | None = None,
        mega_n: int | str = 0,
        mega_auto: bool = False,
        sink_thread: bool | None = None,
        audit: bool | None = None,
        kernel_tier: Any | None = None,
        gossip: Any | None = None,
        slo_us: int = 0,
        watchdog_s: float | None = None,
        predict: bool = False,
        compile_cache: Any | None = None,
    ):
        #: Boot-latency anchor: everything in EngineReport.boot —
        #: serving-ready, background-fill-done, time-to-first-verdict
        #: — is measured from construction start.
        self._boot_t0 = time.perf_counter()
        self.cfg = cfg
        self.source = source
        self.sink = sink
        #: Cluster verdict-gossip plane (cluster/gossip.py GossipPlane
        #: protocol: ``publish(upd, now)`` from the sink section,
        #: ``tick()`` from the dispatch thread, ``report() -> dict``).
        #: None = single-engine serving, the byte-identical baseline.
        self.gossip = gossip
        #: Simulated kernel tier (distill.SimKernelTier protocol:
        #: ``filter(records) -> records`` + ``report() -> dict``): band-
        #: splits drained records BEFORE the batcher, exactly where the
        #: real XDP stage splits them before the ringbuf.  Record-path
        #: only — sealed-ingest workers and precompact rings deliver
        #: records the tier cannot rescore (quantized / already sealed).
        self.kernel_tier = kernel_tier
        if kernel_tier is not None:
            if getattr(source, "provides_sealed", False):
                raise ValueError(
                    "kernel_tier needs the inline record path; sealed-"
                    "batch ingest bypasses the record stream (run the "
                    "real kernel tier via fsx distill --pin instead)")
            if getattr(source, "precompact", False):
                raise ValueError(
                    "kernel_tier cannot rescore a compact-emit ring: "
                    "records arrive kernel-quantized; the distilled "
                    "bands are defined on raw u32 features")
        #: Compact-verdict-wire slots (cfg.batch.verdict_k; 0 = the
        #: legacy full [B] fetch per batch).
        self.verdict_k = cfg.batch.verdict_k
        #: Latency-budget serving mode (``fsx serve --slo-us N``): the
        #: feature→verdict budget, µs, that the coalescing ladder and
        #: the batcher deadline flush are bounded by (docs/ENGINE.md
        #: §latency).  0 — the default — is the throughput-tuned
        #: engine, BIT-IDENTICAL to every prior PR (test-pinned like
        #: every other mode flag): no EWMA bookkeeping, no policy
        #: checks on the hot path.
        self.slo_us = int(slo_us)
        if self.slo_us < 0:
            raise ValueError(f"slo_us must be >= 0, got {slo_us}")
        self._slo_budget_s = self.slo_us * 1e-6
        #: Warm-measured per-group-size step-time EWMA (seconds), keyed
        #: by dispatched chunk count (1 and each ladder rung).  Seeded by
        #: :meth:`warm`'s timed second pass when SLO mode is on;
        #: refined online by the launch section whenever a launch call
        #: absorbed its compute (synchronous backends).  The
        #: deadline-aware policy reads it advisorily — a stale
        #: estimate can only mis-size a group, never corrupt state.
        self._rung_ewma_s: dict[int, float] = {}
        #: Per-record seal→verdict latency plane (always on; the sink
        #: section is its single writer — sync/contracts.py).
        self._lat = LatencyRecorder()
        #: Run the verdict sink on a dedicated thread (module
        #: docstring); False = single-thread readiness reaping.
        #: None = auto: a sink thread needs
        #: a core to run on — on 1-2 core hosts (CI containers) it just
        #: contends with the dispatch thread and XLA's own pool
        #: (measured: saturated drain ~5-25 % slower), so auto enables
        #: it only where the host has cores to spare.
        if sink_thread is None:
            import os

            try:
                # affinity, not cpu_count: a CI container pinned to 2
                # CPUs of a 64-core host must read as 2, or auto lands
                # in exactly the contention regime it exists to avoid
                n_cpus = len(os.sched_getaffinity(0))
            except AttributeError:  # non-Linux
                n_cpus = os.cpu_count() or 1
            sink_thread = n_cpus >= 3
        self.sink_thread = bool(sink_thread)
        spec = get_model(cfg.model.name)
        self.params = params if params is not None else spec.init()
        # Mesh spanning >1 device: serve through the IP-hash-sharded
        # multi-device step (parallel/step.py) — state rows live
        # sharded across the mesh, the wire batch enters replicated.
        self.mesh = mesh if mesh is not None and mesh.devices.size > 1 else None
        # The wire batch's device placement, made EXPLICIT (class
        # docstring): replicated over the mesh when sharded, default
        # device otherwise.  None = plain device_put.
        if self.mesh is not None:
            from flowsentryx_tpu.parallel import layout as par_layout

            # derived from the declarative partition rules — the same
            # table the shard_map specs and checkpoint restore use
            self._in_sharding = par_layout.replicated(self.mesh)
        else:
            self._in_sharding = None
        # Params go to the device ONCE at boot.  A numpy artifact
        # (load_artifact .npz leaves) passed straight through otherwise
        # re-crosses the host->device link on EVERY dispatch — eight
        # silent H2D transfers per batch of pure overhead.
        self.params = jax.tree.map(self._put, self.params)
        # A compact-emit data plane (fsxd --compact) delivers records
        # the KERNEL already quantized to the minifloat wire: the
        # engine must speak compact16/minifloat end to end, whatever
        # was requested.
        self.precompact = bool(getattr(source, "precompact", False))
        if self.precompact:
            wire = schema.WIRE_COMPACT16
        elif wire is None:
            # Default wire: compact16 only when it is bit-exact (the
            # artifact exposes an input observer, so the wire carries
            # the model's own quantization); raw48 otherwise.  A model
            # without an observer must not be silently degraded to
            # minifloat-quantized features by a constructor default —
            # callers opt into that by passing wire="compact16".
            wire = (schema.WIRE_COMPACT16
                    if hasattr(self.params, "in_scale")
                    else schema.WIRE_RAW48)
        self.wire = wire
        # compact16 quantizes features on the way into the batcher with
        # the model's own input observer when the artifact exposes one
        # (bit-exact scores vs raw48 for identity-transform artifacts;
        # ±1 output quant step for log1p ones), else the minifloat
        # fallback (≤6.25 % per-feature error) — announced, since it
        # changes borderline scores vs the raw48 wire.
        if self.precompact:
            quant = dict(feat_mode="minifloat")
            if hasattr(self.params, "in_scale"):
                import sys

                print(
                    "fsx engine: compact-emit data plane delivers "
                    "kernel-quantized minifloat features (<=6.25% "
                    "relative error); the artifact's own input observer "
                    "is bypassed. Serve a 48B plane for bit-exact "
                    "model-mode quantization.",
                    file=sys.stderr,
                )
        elif wire == schema.WIRE_COMPACT16:
            quant = schema.wire_quant_for(self.params)
        else:
            quant = None
        if (not self.precompact and quant is not None
                and quant.get("feat_mode") == "minifloat"):
            import sys

            print(
                "fsx engine: params expose no input observer; compact16 "
                "wire uses minifloat feature quantization (<=6.25% "
                "relative error). Pass wire='raw48' for full fidelity.",
                file=sys.stderr,
            )
        if self.mesh is not None:
            from flowsentryx_tpu import parallel as par

            if wire == schema.WIRE_COMPACT16:
                self.step = par.make_sharded_compact_step(
                    cfg, spec.classify_batch, self.mesh, donate=donate,
                    **quant,
                )
            else:
                self.step = par.make_sharded_raw_step(
                    cfg, spec.classify_batch, self.mesh, donate=donate
                )
            self.table = par.make_sharded_table(cfg, self.mesh)
        elif wire == schema.WIRE_COMPACT16:
            self.step = fused.make_jitted_compact_step(
                cfg, spec.classify_batch, donate=donate, **quant
            )
            self.table = jax.device_put(schema.make_table(cfg.table.capacity))
        else:
            self.step = fused.make_jitted_raw_step(
                cfg, spec.classify_batch, donate=donate
            )
            self.table = jax.device_put(schema.make_table(cfg.table.capacity))
        table_devs = sorted(
            {s.device for s in self.table.key.addressable_shards},
            key=lambda d: d.id)
        #: EngineReport.device: the devices the table actually landed on.
        self._device = {
            "platform": table_devs[0].platform,
            "kind": table_devs[0].device_kind,
            "count": len(table_devs),
        }
        # _put, not bare device_put: sharded engines need the stats
        # replicated OVER THE MESH from boot — committed to device 0
        # they'd be implicitly resharded (a D2D transfer) on the first
        # dispatch, which the transfer-guard contract forbids.
        self.stats = self._put(schema.make_stats())
        # None = the config's pipe depth (BatchConfig.readback_depth,
        # validated >= 1 at construction); an explicit int overrides.
        if readback_depth is None:
            readback_depth = cfg.batch.readback_depth
        self.readback_depth = readback_depth
        # Mega-dispatch (SURVEY.md §7.4.1 brought into SERVING): when
        # the source backlog holds ≥ a staged group size of sealed
        # batches, they go to the device as ONE lax.scan dispatch — the
        # fixed per-dispatch cost is paid once per group instead of per
        # batch.
        # Purely backlog-triggered: the moment a poll comes back short
        # the pending batches dispatch through the largest staged group
        # they still fill (adaptive mode) or singly, so low-load
        # latency behavior is unchanged.
        #
        # ``mega_n="auto"`` (or ``mega_auto=True`` with an explicit
        # cap) = ADAPTIVE coalescing: stage one megastep per
        # power-of-two group size ≤ the cap (fused.pow2_group_sizes)
        # and let each iteration dispatch the largest rung the
        # instantaneous backlog fills — fixed-``mega_n`` amortization
        # was all-or-nothing (backlog < mega_n ⇒ every batch paid the
        # full per-dispatch tax as a single).
        if mega_n == "auto":
            mega_auto = True
            mega_n = MEGA_AUTO_MAX
        elif isinstance(mega_n, str):
            raise ValueError(
                f"mega_n must be an int or 'auto', got {mega_n!r}")
        self.mega_auto = bool(mega_auto)
        self.mega_n = int(mega_n)
        if self.mega_n < 0:
            raise ValueError(f"mega_n must be >= 0, got {mega_n}")
        if self.mega_auto and self.mega_n < 2:
            raise ValueError(
                "adaptive coalescing needs a group-size cap >= 2 "
                f"(got mega_n={self.mega_n})")
        if self.mega_auto:
            mega_sizes = fused.pow2_group_sizes(self.mega_n)
        elif self.mega_n > 0:
            mega_sizes = (self.mega_n,)
        else:
            mega_sizes = ()
        #: Staged group sizes, largest first — the coalescing ladder.
        self._mega_sizes: tuple[int, ...] = mega_sizes
        self.megasteps: dict[int, Any] = {}
        self.megastep = None
        if mega_sizes:
            if wire != schema.WIRE_COMPACT16:
                raise ValueError("mega_n requires the compact16 wire")
            if self.mesh is not None:
                from flowsentryx_tpu import parallel as par

                self.megasteps = par.make_sharded_compact_megastep_family(
                    cfg, spec.classify_batch, self.mesh, mega_sizes,
                    donate=donate, **quant,
                )
            else:
                self.megasteps = fused.make_compact_megastep_family(
                    cfg, spec.classify_batch, mega_sizes, donate=donate,
                    **quant,
                )
            self.megastep = self.megasteps[max(self.megasteps)]
        # Static graph audit at boot (class docstring): prove the
        # serving variant's dtype/donation/transfer/retrace/collective
        # contracts on the staged jaxpr + executable BEFORE the first
        # batch, and refuse to serve on a violation.  Flag-gated (the
        # audit trace+compile costs seconds) and cached per shape.
        if audit is None:
            import os as _os

            audit = _os.environ.get("FSX_AUDIT", "").lower() in (
                "1", "true", "on")
        if audit:
            from flowsentryx_tpu.audit import boot_audit

            # every staged group size is its own compiled scan
            # artifact: each rung of the adaptive ladder is audited
            # (and the boot cache keyed) individually
            boot_audit(cfg, wire=self.wire, mesh=self.mesh,
                       mega_n=self.mega_n if self._mega_sizes else 0,
                       mega_sizes=self._mega_sizes or None,
                       params=self.params)
        #: Sealed-but-undispatched (raw, stamps) group candidates.
        self._pending: list[tuple[np.ndarray, _Stamps]] = []
        # Sealed-batch sources (flowsentryx_tpu/ingest/ShardedIngest)
        # deliver finished wire buffers instead of raw records: the run
        # loop switches to dequeue → dispatch → reap, and the worker
        # fleet is spawned HERE, after the engine has fixed the wire and
        # quantizer — the workers must seal with exactly the engine's
        # choices or the N=0 inline path and the sharded path would
        # score differently.
        self.sealed = bool(getattr(source, "provides_sealed", False))
        if self.sealed:
            source.start(cfg.batch, self.wire, quant)
        #: u32 words in one wire row (the last axis of every staged
        #: buffer).
        self._words = (schema.COMPACT_RECORD_WORDS
                       if self.wire == schema.WIRE_COMPACT16
                       else schema.RECORD_WORDS)
        self._arena = self._make_arena()
        # dispatch-block accounting (EngineReport.dispatch)
        self._group_hist: dict[int, int] = {}
        self._dispatch_calls = 0
        self._dispatched_chunks = 0
        self._staged_batches = 0
        self._staged_bytes = 0
        # A wire buffer may be reused only after its batch is off the
        # in-flight queue (or, for a pending group member, dispatched):
        # keep more buffers than in-flight batches + the pending group
        # (at most one top-rung group of sealed-but-undispatched
        # batches accumulates before the coalescing policy fires).
        self.batcher = MicroBatcher(
            cfg.batch, t0_ns=t0_ns or 0,
            n_buffers=readback_depth + 2 + self.mega_n,
            wire=wire, quant=quant,
        )
        # t0 anchors the device clock (f32 seconds).  None = auto: take
        # the first record's kernel timestamp, which is the documented
        # contract of decode_raw (a boot-relative bpf_ktime_get_ns can
        # be ~1e6 s, where f32 spacing is far too coarse for 1 s
        # windows — anchoring near the stream start keeps µs precision).
        self._t0_auto = t0_ns is None
        # An explicit t0 must also anchor the sink (the auto-t0 and
        # restore() paths already do this); otherwise a ShmVerdictSink
        # stays at t0_ns=0 and emits until_ns values ~t0 in the past,
        # so the daemon/kernel blacklist never fires.
        if t0_ns is not None and hasattr(sink, "t0_ns"):
            sink.t0_ns = t0_ns
        self.metrics = PipelineMetrics()
        #: Optional per-batch reap hook ``(n_records, t_done) -> None``,
        #: called after a batch's verdicts are fetched AND sunk.  Batches
        #: are reaped in record-FIFO order, so a caller pairing this with
        #: :class:`~flowsentryx_tpu.engine.sources.PacedSource` can pop
        #: ``n_records`` scheduled arrival times per call and obtain
        #: exact per-record arrival→verdict-sunk latencies (the latency
        #: bench's measurement; batch-level ``metrics.e2e`` conflates
        #: queueing with readback-group policy, which is fine for
        #: throughput mode but not for judging the 1 ms budget).
        self.on_reap = None
        self._inflight: list[_InFlight] = []
        self._blocked: set[int] = set()
        self._device_now = 0.0  # newest stream time seen in reaped outputs
        self._route_drop = 0    # routing-overflow fail-opens (sharded step)
        # ready-reap coalescing (see _reap_ready): each sink has a fixed
        # host cost, so cap the sink rate when the pipe is shallow —
        # but never above half the flush deadline, which is the
        # configured latency budget (a fixed floor would silently
        # override small deadline_us values).  Only the single-thread
        # mode needs this: a threaded sink's host cost doesn't block
        # dispatch, and its worker coalesces naturally when behind.
        self._last_sink_t = 0.0
        self._min_sink_gap_s = min(tuning.MIN_SINK_GAP_S,
                                   cfg.batch.deadline_us * 1e-6 / 2)
        # -- sink-thread machinery (module docstring) -------------------
        # The SinkChannel (sync/channel.py) is the ONLY shared state
        # between the dispatch and sink threads: the handoff
        # queue, the dispatched-but-unsunk BATCH count backpressure
        # waits on (chunks, not entries — a mega entry is mega_n
        # batches), the stop flag, and the crash slot a worker death
        # lands in atomically with its accounting.  _check_sink
        # surfaces that crash loudly on the next dispatch-thread reap.
        self._chan = SinkChannel("sink thread")
        self._sink_active = False
        self._sink_thread_obj: threading.Thread | None = None
        # readback accounting (EngineReport.readback)
        self._d2h_bytes = 0
        self._sink_compact = 0
        self._sink_fallback = 0
        self._sunk_batches = 0
        # live artifact hot-swap (watch_artifact / hot_swap)
        self._watch_path: str | None = None
        self._watch_mtime = 0
        self._watch_next = 0.0
        self._hot_swaps = 0
        # -- robustness plane (PR 13; engine/health.py derives the
        # -- ladder, engine/watchdog.py owns the no-progress detector)
        #: restores that fell back to the retained .prev generation
        #: (a DEGRADED reason: flow memory resumed one generation
        #: stale).  Written only in the quiescent restore().
        self._restore_fallbacks = 0
        #: Live-rebalance audit counters (cluster/rebalance.py drives
        #: the quiescent span methods below; engine/health.py folds
        #: the loss-shaped ones — adopt_dropped, staged_discarded,
        #: foreign_dropped — into the DEGRADED ladder).  Written only
        #: between run chunks, read by _build_report: single-thread.
        self._rebalance: dict[str, int] = {}
        #: Dispatch watchdog (engine/watchdog.py): trips when batches
        #: are in flight but nothing sinks for the stall bound —
        #: dumping per-thread stacks and surfacing loudly instead of
        #: letting a drain hang forever.  ``watchdog_s=0`` disables;
        #: None = sync/tuning.py WATCHDOG_STALL_S.  Pure observer on
        #: the null path: it never changes results, only refuses to
        #: hang (test-pinned byte-identical at defaults).
        if watchdog_s is None:
            watchdog_s = tuning.WATCHDOG_STALL_S
        self._watchdog = DispatchWatchdog(watchdog_s)
        #: Predictive dispatch governor (``fsx serve --predict``;
        #: engine/predict.py): forecasts the arrival process from the
        #: per-poll stamps this thread already takes and steers the
        #: flush/pre-warm/shed decisions AROUND the hot path — every
        #: hook below is gated ``if self._gov is not None``, so
        #: ``predict=False`` (the default) stays bit-identical to the
        #: reactive engine (test-pinned like every mode flag).
        #: Dispatch-thread-only state (sync/contracts.py).
        if predict and not self.slo_us:
            # the governor's every actuation is phrased in budget
            # headroom — without --slo-us there is no budget to
            # pre-size against or shed under, only silent no-ops
            raise ValueError(
                "predict=True requires slo_us > 0: the governor "
                "actuates the latency-budget machinery (pre-sizing, "
                "early flush, pressure shedding are all phrased in "
                "budget headroom)")
        if predict:
            from flowsentryx_tpu.engine.predict import DispatchGovernor

            self._gov = DispatchGovernor(
                rung_sizes=self._mega_sizes,
                batch_records=cfg.batch.max_batch)
        else:
            self._gov = None
        # lazily-built masked zero batch for pre-warm dispatches
        # (one allocation, reused; _prewarm_dispatch)
        self._warm_buf: np.ndarray | None = None
        # -- boot-latency engine (ISSUE 20) -----------------------------
        #: Persistent AOT executable store (engine/compile_cache.py):
        #: staged variants lower().compile() once, serialize to disk,
        #: and later boots of the same staged shape (the audit boot
        #: cache's signature discipline, core/signature.py) reload in
        #: tens of ms.  None = no cache (every warm compiles, exactly
        #: the historical path).  Fail-open throughout: the jit
        #: wrappers below stay captured as the fallback, so a cold or
        #: corrupt cache only ever costs the compile it always cost.
        if compile_cache is not None:
            from flowsentryx_tpu.core.signature import staging_signature
            from flowsentryx_tpu.engine.compile_cache import CompileCache

            if isinstance(compile_cache, CompileCache):
                self._cache = compile_cache
            else:
                sig = staging_signature(
                    cfg, wire=self.wire,
                    mesh_devices=(int(self.mesh.devices.size)
                                  if self.mesh is not None else 1),
                    mega_sizes=self._mega_sizes,
                    params=self.params,
                    donate=bool(donate))
                self._cache = CompileCache(compile_cache, sig)
        else:
            self._cache = None
        #: Pristine jit wrappers + abstract arg specs per staged
        #: variant, captured HERE (quiescent, the live device state in
        #: scope) so AOT lowering — including on the background warm
        #: fill thread — never touches launch-section fields.  Keys:
        #: ("single",), ("mega", g).
        self._aot_specs = self._capture_aot_specs()
        #: The READY rung set: the rungs of the coalescing ladder whose
        #: executables are installed and safe to dispatch without an
        #: inline compile.  Defaults to the whole ladder (legacy warm
        #: and un-warmed engines: byte-identical behavior); a tiered
        #: warm shrinks it to the serving tier and the background fill
        #: re-grows it rung by rung — grouping is dispatch-granularity
        #: only, so the SHAPES dispatched change but the results never
        #: do (the PR 5 invariant the partial-ladder parity test pins).
        self._ready_sizes: tuple[int, ...] = self._mega_sizes
        #: Background warm-fill plan + thread (warm(tiered=True)).
        self._warm_plan: tuple = ()
        self._warm_thread_obj: threading.Thread | None = None
        #: Boot-latency block (EngineReport.boot); built by warm(),
        #: extended by the warm fill thread via whole-dict rebinds.
        self._boot: dict | None = None
        #: Wall from construction to the FIRST real verdict sunk
        #: (stamped in the sink section; masked warm batches carry no
        #: records and never trip it).
        self._first_verdict_s: float | None = None
        #: Engine-stack import wall, stamped by the CLI/runner that
        #: measured it (the engine cannot observe its own import).
        self.boot_import_s = 0.0
        #: JAX's own compile/cache counters for this process
        #: (core/runtime.py CompileCounters), stamped by the entry
        #: point that placed the persistent cache; None = not counted.
        self.boot_jax_compiles = None

    def _make_arena(self) -> DispatchArena | None:
        """The dispatch arena (engine/arena.py): page-aligned staging
        rows for the zero-copy pipeline.  Sealed sources memcpy
        shm-slot VIEWS straight into arena rows (the ONE host copy)
        and mega groups assemble contiguously in one slot, so the
        device_put slice needs no np.stack.  Slot count follows the
        reuse safety rule (arena module docstring): readback_depth + 2
        guarantees every batch staged in a slot is SUNK before the
        slot recycles.  Inline engines without grouping never stage,
        so they skip the allocation (None)."""
        if not (self.sealed or self.megasteps):
            return None
        group_max = max(self.megasteps) if self.megasteps else 1
        return DispatchArena(
            slots=DispatchArena.safe_slots(self.readback_depth),
            # sealed singles still batch their queue drains: give
            # the slot a few rows even when no megastep is staged
            group_max=max(group_max, 4) if self.sealed else group_max,
            max_batch=self.cfg.batch.max_batch,
            words=self._words,
        )

    def _capture_aot_specs(self) -> dict:
        """Abstract (ShapeDtypeStruct) argument specs and the pristine
        jit wrapper for every staged variant — the inputs to
        ``wrapper.lower(*specs).compile()``.  Shardings are taken from
        the LIVE arrays (mesh engines lower against the real sharded
        layout; replicated wire entry), so the AOT executable is the
        same artifact the jit path would build."""

        def _abs(t):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=getattr(a, "sharding", None)), t)

        state = (_abs(self.table), _abs(self.stats), _abs(self.params))
        b = self.cfg.batch.max_batch

        def _wire(shape):
            return jax.ShapeDtypeStruct(shape, np.uint32,
                                        sharding=self._in_sharding)

        specs: dict[tuple, tuple] = {
            ("single",): (self.step, (*state, _wire((b + 1, self._words)))),
        }
        for g, fn in self.megasteps.items():
            specs[("mega", g)] = (fn, (*state,
                                       _wire((g, b + 1, self._words))))
        return specs

    # -- pipeline stages ----------------------------------------------------

    def _put(self, a):
        """EXPLICIT H2D: wire buffers/params cross to the device via
        device_put (replicated over the mesh when sharded), never as
        implicit jit-argument transfers — the whole loop runs clean
        under ``jax.transfer_guard("disallow")``."""
        return (jax.device_put(a, self._in_sharding)
                if self._in_sharding is not None else jax.device_put(a))

    def _note_step_s(self, key: int, dt: float, out: Any) -> None:
        """Online refinement of the per-rung step-time EWMA (SLO mode
        only — the default path records nothing).  Only launches whose
        call absorbed the compute count: the output being READY the
        moment the call returns proves the backend executed
        synchronously (XLA:CPU's scatter custom-calls), so ``dt`` is a
        true step time; on async backends the call is a cheap enqueue
        and the warm-pass seed stands unrefined."""
        if not self._slo_budget_s or not self._out_ready(out):
            return
        prev = self._rung_ewma_s.get(key)
        self._rung_ewma_s[key] = (
            dt if prev is None
            else prev + tuning.SLO_EWMA_ALPHA * (dt - prev))

    def _launch_single(self, raw: Any, stamps: _Stamps,
                       n_records: int) -> _InFlight:
        """The step call + accounting of a single-batch dispatch."""
        self._dispatch_calls += 1
        seq = self._dispatch_calls
        with self.metrics.upload(seq) as up:
            dev = self._put(raw)
        with self.metrics.launch(seq) as la:
            self.table, self.stats, out = self.step(
                self.table, self.stats, self.params, dev
            )
        self._dispatched_chunks += 1
        self._group_hist[1] = self._group_hist.get(1, 0) + 1
        self._note_step_s(1, la.seconds, out)
        return _InFlight(out, stamps, n_records,
                         t_launch=up.t0, put_s=la.t0 - up.t0,
                         launch_s=la.seconds, t_launched=la.t1, seq=seq)

    def _pop_stamps(self) -> _Stamps:
        """Stamps of the batch the engine's own batcher sealed next."""
        return _inline_stamps(self.batcher.pop_seal_time())

    def _dispatch(self, raw: np.ndarray, stamps: _Stamps) -> None:
        n_records = int(raw[self.cfg.batch.max_batch, 0])
        self._inflight.append(self._launch_single(raw, stamps,
                                                  n_records))

    def _launch_group(self, raws: np.ndarray, stamps: _Stamps,
                      n_records: int) -> _InFlight:
        """The megastep call + accounting of a group dispatch."""
        g = int(raws.shape[0])
        self._dispatch_calls += 1
        seq = self._dispatch_calls
        with self.metrics.upload(seq) as up:
            dev = self._put(raws)
        with self.metrics.launch(seq) as la:
            self.table, self.stats, out = self.megasteps[g](
                self.table, self.stats, self.params, dev
            )
        self._dispatched_chunks += g
        self._group_hist[g] = self._group_hist.get(g, 0) + 1
        self._note_step_s(g, la.seconds, out)
        return _InFlight(out, stamps, n_records, n_chunks=g,
                         t_launch=up.t0, put_s=la.t0 - up.t0,
                         launch_s=la.seconds, t_launched=la.t1, seq=seq)

    def _dispatch_group(self, raws: np.ndarray, stamps: _Stamps,
                        n_records: int) -> None:
        """One lax.scan dispatch over a CONTIGUOUS ``[g, B+1, words]``
        staged wire group (a dispatch-arena slice — no np.stack copy).

        Queued as ONE in-flight entry whose StepOutput fields are
        stacked ``[g, B]`` (``now``/``route_drop``: ``[g]``) —
        :meth:`_sink_group` ravels, so verdict extraction is unchanged.
        Latency is anchored at the OLDEST member's stamps (the honest
        group latency: earlier members waited for the group)."""
        self._inflight.append(self._launch_group(raws, stamps,
                                                 n_records))

    def _dispatch_mega(self,
                       group: list[tuple[np.ndarray, _Stamps]]) -> None:
        """Group dispatch of INLINE-path pending buffers: stage the
        group's wire buffers into one arena slot (replacing the old
        per-group ``np.stack`` allocation with the arena's reusable
        page-aligned rows) and scan-dispatch the contiguous slice."""
        b = self.cfg.batch.max_batch
        g = len(group)
        rows = self._arena.rows(self._arena.claim())
        with self.metrics.stage:
            for i, (raw, _) in enumerate(group):
                rows[i][...] = raw
        self._staged_batches += g
        self._staged_bytes += int(rows[0].nbytes) * g
        n_records = int(sum(int(raw[b, 0]) for raw, _ in group))
        self._dispatch_group(rows[:g], min(t for _, t in group), n_records)

    def _rung_for(self, backlog: int) -> int:
        """THE coalescing policy, shared by the inline and sealed
        loops so the two paths can never dispatch different group
        shapes for the same backlog: the largest staged rung the
        backlog fills, else 1 (a single).  Delegates to
        :func:`flowsentryx_tpu.ops.fused.rung_for_volume` — the ONE
        copy of the rule, also read by the predictive governor's
        pre-warm sizing (engine/predict.py), so a forecast can never
        pre-warm a rung the backlog dispatch would not pick.

        Ranges over the READY rung set, not the staged ladder: while a
        tiered warm's background fill is still installing executables,
        the greedy flush picks the largest rung that is actually warm
        (grouping is dispatch-granularity only — byte-identity to the
        full ladder is pinned by test), and once the fill completes
        the two sets are equal again (legacy warm: always equal)."""
        return fused.rung_for_volume(backlog, self._ready_sizes)

    def _prewarm_dispatch(self, rung: int) -> None:
        """The governor's pre-warm actuation (engine/predict.py): ONE
        masked zero-valid dispatch through the forecast rung.
        :meth:`warm`'s masking argument makes it result-free — every
        row carries n_valid=0, so table/stats/verdicts are untouched
        and the latency plane ignores the entry (0 records).  The
        observable effects are exactly the point: the rung's EWMA
        refreshes launch-absorbed (so :meth:`_slo_cap` prices the
        incoming burst off a HOT measurement) and the rung's
        executable/arena path is warm when the burst lands.  Reaped
        to empty before returning — the pipe must read idle again
        before real traffic arrives."""
        if self._warm_buf is None:
            self._warm_buf = np.zeros(
                (self.cfg.batch.max_batch + 1, self._words), np.uint32)
        stamps = _inline_stamps(time.perf_counter())
        if rung > 1 and self._arena is not None:
            self._dispatch_mega([(self._warm_buf, stamps)] * rung)
        else:
            self._dispatch(self._warm_buf, stamps)
        self._reap(0)

    # -- latency-budget (SLO) policy ----------------------------------------
    # Two advisory predicates over the warm-measured per-rung step-
    # time EWMA, all no-ops at --slo-us 0.  They bound COALESCING, not
    # results: whatever group shapes they pick, the verdict state is
    # byte-identical (grouping is dispatch-granularity only, the PR 5
    # invariant) — only latency and amortization change.

    def _slo_cap(self, t_oldest: float) -> int:
        """Largest staged rung whose expected completion — the oldest
        pending record's age plus the rung's EWMA step time — still
        fits the budget; 1 when even the smallest rung would breach
        (minimum-work path: a single is the least latency the engine
        can add).  A record ALREADY past its budget gets no cap: a
        late record cannot be saved by a rung choice, and shrinking
        groups exactly when a backlog exists collapses drain capacity
        into a queueing spiral (measured: forced singles under a
        saturating pulse took p99 from ~100 ms to ~700 ms) — the
        budget-exceeded path is the greedy flush at FULL
        amortization, which recovers the backlog fastest and so
        minimizes how many MORE records go late.  A rung without a
        measurement yet is assumed free: the dispatch that follows
        seeds it (self-correcting, and warm() pre-seeds every rung in
        SLO mode anyway)."""
        headroom = self._slo_budget_s - (time.perf_counter() - t_oldest)
        if headroom <= 0.0:
            # the top rung is ALWAYS in the ready set (serving tier of
            # a tiered warm), so the budget-exceeded full-amortization
            # path never waits on the background fill
            return self._mega_sizes[0] if self._mega_sizes else 1
        for s in self._ready_sizes:
            if self._rung_ewma_s.get(s, 0.0) <= headroom:
                return s
        return 1

    def _slo_pressed(self, t_oldest: float) -> bool:
        """Stop holding for a deeper backlog: once a TOP-rung step no
        longer fits the oldest pending record's remaining headroom —
        including a record already late, whose headroom is gone —
        waiting can only make things worse; flush now through
        :meth:`_slo_cap`'s choice (the existing greedy flush IS the
        budget-exceeded path).  Before this point, holding is free (a
        fuller group dispatched within budget is strictly better
        amortization)."""
        top = self._mega_sizes[0] if self._mega_sizes else 1
        headroom = self._slo_budget_s - (time.perf_counter() - t_oldest)
        return self._rung_ewma_s.get(top, 0.0) >= headroom

    def _drain_pending(self, short: bool) -> None:
        """Apply the coalescing ladder to the inline pending list.

        Full TOP-rung groups always dispatch (a deep backlog keeps
        amortization maximal); a short poll — no backlog left behind
        the pending batches — flushes the remainder greedily through
        the largest rung it still fills, then singles.  With a fixed
        ``mega_n`` the ladder is one rung, which reduces to the
        original all-or-nothing policy; adaptive mode
        (``mega_n="auto"``) is where partial backlogs stop paying the
        full per-dispatch tax batch by batch.

        Under ``--slo-us`` the WAITING is budget-bounded (policy block
        above): budget pressure turns the hold-for-backlog into the
        greedy flush — the existing flush IS the budget-exceeded
        path, just entered earlier — and the greedy flush skips
        CLIMBING to a rung whose expected step time the oldest
        record's remaining headroom no longer covers.  Existing
        full-rung backlogs dispatch at full amortization either
        way (the sub-linear-step argument in the SLO note below)."""
        # SLO note: the full-amortization path below (an EXISTING
        # top-rung backlog) deliberately stays un-capped even in
        # budget mode — step time is sub-linear in group size, so for
        # a backlog that already exists the largest rung finishes
        # EVERY record soonest (splitting it only delays the tail and
        # collapses capacity; measured: capping a saturated drain's
        # rungs cost ~35 % throughput and spiralled pulse p99 ~50x).
        # The budget bounds what the engine WAITS for — holds,
        # batcher residency — and the greedy flush's climb.
        slo = self._slo_budget_s
        top = self._mega_sizes[0]
        while len(self._pending) >= top:
            self._dispatch_mega(self._pending[:top])
            del self._pending[:top]
            self._reap(self.readback_depth)
        if not self._pending or not (short or (
                slo and self._slo_pressed(
                        self._pending[0][1].t_enqueue))):
            return
        while self._pending:
            g = self._rung_for(len(self._pending))
            if slo:
                g = min(g, self._slo_cap(
                    self._pending[0][1].t_enqueue))
            if g > 1:
                self._dispatch_mega(self._pending[:g])
                del self._pending[:g]
            else:
                self._dispatch(*self._pending.pop(0))
            self._reap(self.readback_depth)

    @staticmethod
    def _out_ready(out) -> bool:
        """Whether a step output's sink fetch would not block: the
        compact wire is the LAST thing the step computes, so its
        readiness covers the whole output."""
        return (out.wire if out.wire is not None else out.block_key).is_ready()

    def _busy_depth(self) -> int:
        """Batches dispatched but not yet sunk (staging + sink queue +
        in-sink) — the 'pipe is busy' predicate the deadline-flush and
        idle-sleep decisions key on."""
        return sum(g.n_chunks for g in self._inflight) + self._chan.pending

    def _deadline_flush_due(self) -> bool:
        """THE idle-pipe deadline-flush rule (previously inline in
        :meth:`_run_inline`; extracted because it is load-bearing for
        the SLO path and must be testable directly).

        Deadline flush ONLY into an idle pipe: while batches are in
        flight — including batches queued to the sink thread,
        dispatched-but-unsunk is still a busy pipe — an early flush
        cannot reduce latency (the new batch queues behind them
        anyway) but it does burn a full padded step per near-empty
        buffer; the r4 open-loop collapse at tiny loads was exactly
        this flush-faster-than-the-step-drains spiral.  When the pipe
        drains (<= one step time) the deadline fires.

        Under ``--slo-us`` the batcher's residency is ALSO bounded by
        the budget: once the oldest pending record's age plus a
        single-batch EWMA step would land on the budget, the flush
        fires even before ``deadline_us`` — but never into a busy
        pipe; the rule above dominates.  The flush age is floored at
        HALF the budget: when the single-step estimate inflates past
        the budget itself (a throttled host), the naive ``age + step
        >= budget`` fires on any nonzero age and degenerates into
        flush-every-poll — the r4 spiral in budget clothing, measured
        decaying the SLO arm trial over trial; a record that cannot
        make the budget anyway still batches for up to budget/2."""
        if self._busy_depth() != 0:
            return False
        if self.batcher.flush_due():
            return True
        if not self._slo_budget_s:
            return False
        age = self.batcher.pending_age_s()
        if age <= 0.0:
            return False
        if self._gov is not None:
            # Predictive override (engine/predict.py): during a
            # forecast on-window, HOLD the flush so the burst's
            # records coalesce into one dispatch — but only while the
            # governor proves the held records still land inside the
            # budget (hold-safety bound); in the post-burst off-window
            # flush EARLY at the forecast burst end instead of waiting
            # for records to age into the reactive rule — the p99
            # lever.  None = no confident forecast, fall through to
            # the reactive rule below unchanged (the quiescent
            # fallback the confidence gate guarantees).
            d = self._gov.flush_decision(
                time.perf_counter(), age,
                self._rung_ewma_s.get(1, 0.0), self._slo_budget_s)
            if d is not None:
                return d
        return age >= max(
            self._slo_budget_s - self._rung_ewma_s.get(1, 0.0),
            self._slo_budget_s / 2)

    def _check_sink(self) -> None:
        """Propagate a worker crash into the dispatch thread — the
        engine must fail LOUDLY, not serve on with verdicts silently
        discarded (SinkChannel.check is THE unified worker-death
        path; strict-mode ingest death raises the same way)."""
        self._chan.check()

    def _handoff(self) -> None:
        """Move staged in-flight entries to the sink thread's queue."""
        if not self._inflight:
            return
        self._chan.submit_many(self._inflight, lambda g: g.n_chunks)
        self._inflight.clear()

    def _reap(self, down_to: int) -> None:
        """Ensure at most ``down_to`` BATCHES remain dispatched-but-
        unsunk — BLOCKING if needed.  This is the pipeline-depth cap;
        the latency path is :meth:`_reap_ready`.  Counted in batches,
        not queue entries: a mega dispatch is one entry of ``mega_n``
        batches, and letting it count as one would silently multiply
        the configured pipe depth (and its device output memory / tail
        latency) by ``mega_n``.

        Threaded mode: hand entries to the sink thread and wait on the
        pending count (backpressure); single-thread mode: fetch + sink
        here, blocking on device completion."""
        if self._sink_active:
            self._handoff()
            # the watchdog rides the backpressure wait's wakeup
            # quantum: a wedged-but-alive worker (no WorkerCrash to
            # break the wait) must dump stacks and fail loudly instead
            # of parking this wait forever (engine/watchdog.py)
            if self._chan.pending > down_to:
                with self.metrics.backpressure:
                    self._chan.wait_below(
                        down_to,
                        on_wait=lambda: self._watchdog.check(
                            self._chan.pending))
            self._check_sink()
            return
        total = sum(g.n_chunks for g in self._inflight)
        group: list[_InFlight] = []
        while self._inflight and total > down_to:
            g = self._inflight.pop(0)
            total -= g.n_chunks
            group.append(g)
        if group:
            # single-thread mode: the blocking sink IS the wait for the
            # pipe to drain (here alone a span holds the sink
            # section's fetch/decode/apply inside it)
            with self.metrics.backpressure:
                self._sink_group(group)

    def _reap_ready(self) -> None:
        """Sink every batch the device has ALREADY finished, oldest
        first, without blocking on anything unfinished.

        Threaded mode: the sink thread already does exactly this the
        moment futures complete — just hand over anything staged and
        surface a sink crash.  Single-thread mode (the original loop):
        called every iteration, because without it a batch's verdicts
        waited until ``readback_depth`` MORE batches had been
        dispatched — at an offered load L and batch size B that is
        ``depth × B/L`` of pure queueing added to every record (the r4
        open-loop collapse: p99 20×+ the step time at trivial loads).
        Readiness is a local future check, not a device round trip; the
        sink itself has a fixed host cost, so reaps COALESCE — a sink
        happens only when one is due (minimum gap) or the pipe is
        stacking up, and consecutive ready batches go as one group."""
        # every serving loop passes through here each iteration — the
        # one place the artifact watcher's throttled mtime check covers
        # the inline and sealed loops alike (and the dispatch
        # watchdog's no-progress poll, same coverage argument)
        self._maybe_reload_artifact()
        self._watchdog.check(self._busy_depth())
        pressure = 0.0
        if self._gov is not None:
            # governor heartbeat: re-estimate (throttled inside), then
            # measure the SLO headroom of the OLDEST work anywhere on
            # the host side — batcher residency or a staged pending
            # group — as the shed-pressure signal.  Pure host floats;
            # nothing here touches the device path.
            now = time.perf_counter()
            self._gov.update(now)
            age = self.batcher.pending_age_s()
            if self._pending:
                age = max(age, now - self._pending[0][1].t_enqueue)
            pressure = self._gov.pressure(age, self._slo_budget_s)
        if self.gossip is not None:
            # merge peers' gossiped verdicts between dispatches (also
            # on idle iterations — a quiet engine still mitigates what
            # its peers condemn).  RX mailboxes + the plane's own sink
            # are dispatch-thread-owned; the engine sink is not touched
            # here (its producer is the sink section).  Under measured
            # budget pressure the governor defers the plane's
            # anti-entropy pacing (never its verdict publish — that
            # happens in the sink section, untouched here).
            if pressure:
                self.gossip.tick(pressure=pressure)
            else:
                self.gossip.tick()
        if self._sink_active:
            self._handoff()
            self._check_sink()
            return
        if not self._inflight or not self._out_ready(self._inflight[0].out):
            return
        t = time.perf_counter()
        if (len(self._inflight) < 2
                and t - self._last_sink_t < self._min_sink_gap_s):
            return
        group = [self._inflight.pop(0)]
        while self._inflight and self._out_ready(self._inflight[0].out):
            group.append(self._inflight.pop(0))
        self._sink_group(group)

    # -- the sink thread ----------------------------------------------------

    def _start_sink_thread(self) -> None:
        if self._sink_active or not self.sink_thread:
            return
        self._chan.reset()
        self._sink_thread_obj = threading.Thread(
            target=self._sink_worker, name="fsx-sink", daemon=True)
        self._sink_active = True
        self._sink_thread_obj.start()

    def _stop_sink_thread(self) -> None:
        """Drain-preserving shutdown: the worker finishes everything
        queued (each fetch completes — device futures always resolve),
        then exits; join is unbounded by design.  Never raises — the
        caller re-checks ``_check_sink`` after."""
        if not self._sink_active:
            return
        self._chan.request_stop()
        if self._watchdog.tripped:
            # the watchdog hard-tripped: the worker is WEDGED, not
            # draining — an unbounded join here would turn "fail
            # loudly" back into "hang forever".  Bounded join, then
            # abandon the daemon thread; the WatchdogStall propagating
            # through run() is the loud failure.
            self._sink_thread_obj.join(timeout=2.0)
        else:
            self._sink_thread_obj.join()
        self._sink_thread_obj = None
        self._sink_active = False

    def _sink_worker(self) -> None:
        """Sink-thread main: pop the oldest entry (blocking on its
        fetch paces us to the device), coalesce whatever else already
        finished into the same group, fetch + sink, repeat.  FIFO pop
        by a single worker preserves record order for ``on_reap``."""
        try:
            while True:
                with self.metrics.sink_wait:
                    group = self._chan.pop(
                        coalesce=lambda e: self._out_ready(e.out))
                if group is None:
                    return  # stop requested and queue drained
                t0 = time.perf_counter()
                exc: BaseException | None = None
                try:
                    self._sink_group(group)
                except BaseException as e:  # noqa: BLE001
                    exc = e
                # exception recorded ATOMICALLY with the pending
                # decrement (SinkChannel.complete): a backpressure
                # waiter woken by this notify must never observe
                # (pending drained, exc unset) for a group that
                # actually crashed.
                self._chan.complete(sum(g.n_chunks for g in group),
                                    time.perf_counter() - t0, exc)
                if exc is not None:
                    return
        except BaseException as e:  # noqa: BLE001 — surfaced by _check_sink
            self._chan.record_exc(e)

    def _sink_group(self, group: list[_InFlight]) -> None:
        """Fetch + sink a reap group.

        COMPACT path (verdict_k > 0, the steady state): each entry's
        whole sink payload — keys, untils, count, overflow flag,
        route_drop, batch clock — is ONE small device buffer, so the
        fetch is O(verdict_k) bytes per batch instead of two full [B]
        arrays (8 B/record).  An entry whose overflow flag is set falls
        back to the full block-array fetch for THAT batch, so a block
        is never lost.  Small groups fetch wires with plain
        ``np.asarray``; LARGE groups (deep drains, post-stall bursts)
        fetch one device-side stack so the per-readback fixed cost is
        paid per group, not per batch.

        LEGACY path (verdict_k == 0): the full-array fetch, kept as the
        parity/measurement baseline.  Host-side concat for small groups
        (composing a device-side concat cost three extra jit dispatches
        per sink, ~1.5 ms each — measured dominating the paced loop),
        one device-side concat for large ones."""
        if group[0].out.wire is not None:
            self._sink_group_wire(group)
            return
        seq = group[0].seq
        # .reshape(-1) everywhere: a mega-dispatch entry carries stacked
        # [N, B] fields (now/route_drop [N]); single entries are [B]/[].
        with self.metrics.fetch(seq) as fetch:
            # jax.device_get, not np.asarray: the D2H boundary stays
            # EXPLICIT (class docstring / transfer_guard contract)
            if len(group) <= 2:
                keys = np.concatenate(
                    [jax.device_get(g.out.block_key).reshape(-1)
                     for g in group]) \
                    if len(group) > 1 \
                    else jax.device_get(group[0].out.block_key).reshape(-1)
                untils = np.concatenate(
                    [jax.device_get(g.out.block_until).reshape(-1)
                     for g in group]) \
                    if len(group) > 1 \
                    else jax.device_get(group[0].out.block_until).reshape(-1)
            else:
                keys = jax.device_get(jnp.concatenate(
                    [g.out.block_key.reshape(-1) for g in group]))
                untils = jax.device_get(jnp.concatenate(
                    [g.out.block_until.reshape(-1) for g in group]))
            now = float(np.max(jax.device_get(group[-1].out.now)))
            self._d2h_bytes += keys.nbytes + untils.nbytes
            self._sink_fallback += sum(g.n_chunks for g in group)
            # routing-overflow fail-opens (sharded step): single-device
            # steps carry a module-level numpy zero here — free, no
            # device fetch.  Sharded jax scalars: per-batch fetch on the
            # small-group fast path; ONE device-side sum for deep
            # groups (the whole point of that branch is one RPC round
            # trip per group).
            rds = [g.out.route_drop for g in group]
            if all(isinstance(rd, (int, np.integer, np.generic))
                   for rd in rds):
                self._route_drop += sum(int(rd) for rd in rds)
            elif len(group) <= 2:
                # .sum() not int(): a mega entry's route_drop is [N]
                self._route_drop += sum(
                    int(np.sum(jax.device_get(rd))) for rd in rds)
            else:
                self._route_drop += int(jax.device_get(jnp.sum(
                    jnp.concatenate([jnp.ravel(jnp.asarray(rd))
                                     for rd in rds]))))
        with self.metrics.decode(seq):
            upd = extract_updates(keys, untils)
        self._apply_updates(upd, now, group, fetch.t0, fetch.t1)

    def _sink_group_wire(self, group: list[_InFlight]) -> None:
        """The compact-wire sink (see :meth:`_sink_group`).

        An entry's wire is one ``[2K+4]`` buffer, a single's or a mega
        dispatch's merged window.  An overflowed wire falls back to
        the full block-array fetch for the whole entry — the arrays
        cover every chunk in order, so last-wins decode stays exact
        and no block is lost."""
        seq = group[0].seq
        with self.metrics.fetch(seq) as fetch:
            if len(group) <= 2:
                wires = [jax.device_get(g.out.wire) for g in group]
            else:
                wires = jax.device_get(
                    jnp.stack([g.out.wire for g in group]))
            # K_MAX-overflow fallback: a batch (or a group's merged
            # window) condemned more flows than its wire holds — pay
            # the full fetch once rather than lose a single block.
            # Fetched here, with the wires: the fetch span is all of
            # the sink's waiting on the device.
            full = {}
            for i, (g, w) in enumerate(zip(group, wires)):
                self._d2h_bytes += w.nbytes
                if wire_overflowed(w):
                    fk = jax.device_get(g.out.block_key).reshape(-1)
                    fu = jax.device_get(g.out.block_until).reshape(-1)
                    self._d2h_bytes += fk.nbytes + fu.nbytes
                    full[i] = (fk, fu)
        with self.metrics.decode(seq):
            parts_k: list[np.ndarray] = []
            parts_u: list[np.ndarray] = []
            now = 0.0
            for i, w in enumerate(wires):
                vw = decode_verdict_wire(w)
                self._route_drop += vw.route_drop
                now = max(now, vw.now)
                # both counters in batches (an entry's n_chunks), so
                # that they add up to the batches sunk
                if i in full:
                    # the wire slots of the WHOLE entry are discarded:
                    # the full arrays carry every block in the same
                    # chunk order
                    self._sink_fallback += group[i].n_chunks
                    parts_k.append(full[i][0])
                    parts_u.append(full[i][1])
                else:
                    self._sink_compact += group[i].n_chunks
                    parts_k.append(vw.key)
                    parts_u.append(vw.until_s)
            keys = (np.concatenate(parts_k) if len(parts_k) > 1
                    else parts_k[0])
            untils = (np.concatenate(parts_u) if len(parts_u) > 1
                      else parts_u[0])
            upd = extract_updates(keys, untils)
        self._apply_updates(upd, now, group, fetch.t0, fetch.t1)

    def _apply_updates(self, upd, now: float, group: list[_InFlight],
                       t_fetch: float, t_wire: float) -> None:
        """Shared sink tail: writeback, clock/metric bookkeeping, the
        per-record latency plane, and the per-batch reap hook
        (record-FIFO order — both sink modes process groups
        oldest-first on a single thread).  ``t_fetch`` is when the
        group's wire fetch began — the ``sink`` stage's anchor —
        and ``t_wire`` when the wire was on the host, where the
        chain's ``device`` ends and ``sink_host`` begins."""
        with self.metrics.apply(group[0].seq):
            self.sink.apply(upd)
            if self.gossip is not None:
                # republish to every peer engine RIGHT where the local
                # sink applied — the gossip TX mailboxes' single
                # producer is this sink section, whichever thread owns
                # it
                self.gossip.publish(upd, now)
            self._blocked.update(upd.key.tolist())
            self._device_now = max(self._device_now, now)
            self._sunk_batches += sum(g.n_chunks for g in group)
            t_done = time.perf_counter()
            if (self._first_verdict_s is None
                    and any(g.n_records for g in group)):
                # time-to-first-verdict (EngineReport.boot): anchored
                # at construction; masked warm batches carry zero
                # records and never trip it, so this is the first REAL
                # verdict served
                self._first_verdict_s = t_done - self._boot_t0
            self._last_sink_t = t_done
            for g in group:
                st = g.stamps
                self.metrics.e2e.add(t_done - st.t_enqueue)
                # per-record accounting: every record of the entry is
                # charged the entry's OLDEST-record path (a
                # conservative upper bound — earlier members waited
                # for the group, the same anchoring e2e has always
                # used).  hold ends where the step call began, less the
                # upload made ahead of it, so the chain closes exactly.
                self._lat.record(
                    total_s=t_done - st.t_enqueue,
                    staged_s=(g.t_launch - st.t_enqueue
                              if g.t_launch else 0.0),
                    upload_s=g.put_s,
                    compute_s=g.launch_s,
                    sink_s=t_done - t_fetch,
                    n=g.n_records,
                    budget_s=self._slo_budget_s,
                    fill_s=st.t_seal - st.t_enqueue,
                    queue_s=st.t_dequeue - st.t_seal,
                    hold_s=(g.t_launched - g.launch_s - g.put_s
                            - st.t_dequeue),
                    device_s=t_wire - g.t_launched,
                    sink_host_s=t_done - t_wire,
                )
                if self.on_reap is not None:
                    self.on_reap(g.n_records, t_done)
        # a completed sink group is the watchdog's progress signal —
        # one float store, whichever thread owns the sink section
        self._watchdog.note_progress()

    def warm(self, tiered: bool = False) -> None:
        """Stage every serving executable with zero-fill batches.

        A long-lived server pays the multi-second compile once at boot;
        a benchmark or test that skips this charges it to the first
        measured window instead (and, fed by a live ring, drops the
        seconds of records that arrive meanwhile).  The batch's meta
        row carries n_valid=0, so every row is masked — table, stats,
        and verdicts are unchanged.  Call before attaching a live
        stream; must not be called with batches in flight.

        With a persistent compile cache configured
        (``Engine(compile_cache=dir)``; engine/compile_cache.py) each
        variant is AOT-installed first: a cache hit deserializes the
        executable in tens of ms and the ladder below pays no compile;
        a miss compiles once via ``lower().compile()`` and publishes
        the entry for the next boot.  Fail-open at every step — the
        jit wrappers stay captured as the fallback path.

        ``tiered=True`` is the boot-latency mode: only the SERVING
        TIER — singles plus the top rung, the shapes every drain
        starts from — warms in the foreground.  The engine is serving
        the moment this returns; a background thread
        (:meth:`_warm_worker`) fills the remaining rungs AOT-only —
        it never dispatches — and publishes each executable with one
        reference rebind, growing the ready set until the full ladder
        is live.  Byte-identity to
        a full-ladder warm is pinned by test: grouping is
        dispatch-granularity only."""
        if (self._warm_thread_obj is not None
                and self._warm_thread_obj.is_alive()):
            raise RuntimeError(
                "warm() called while a background warm fill is active "
                "— warm_fill_join() first (nothing else may touch the "
                "staged executables while the fill thread installs)")
        self._warm_thread_obj = None
        serving_sizes = self._mega_sizes
        fill_plan: list[tuple] = []
        if tiered and self._mega_sizes:
            serving_sizes = self._mega_sizes[:1]
            fill_plan = [("mega", g) for g in self._mega_sizes[1:]]
        boot: dict[str, Any] = {
            "tiered": bool(fill_plan),
            "variants": {},
            "fill_pending": [self._variant_label(n) for n in fill_plan],
        }
        # AOT install (cache load or lower().compile()) BEFORE the
        # dispatch ladder: installed executables replace the jit
        # wrappers on self.step/self.megasteps, so the ladder below
        # triggers no compile on a warm cache.  Without a
        # cache the ladder itself is the compile trigger, exactly the
        # historical path (tiered mode still AOT-compiles so the
        # background fill has executables to install).
        if self._cache is not None or fill_plan:
            names: list[tuple] = [("single",)]
            names += [("mega", g) for g in serving_sizes]
            for name in names:
                exe, entry = self._aot_build(name)
                if exe is not None:
                    self._aot_install(name, exe)
                boot["variants"][self._variant_label(name)] = entry
        warm = np.zeros((self.cfg.batch.max_batch + 1, self._words),
                        np.uint32)
        # ONE dispatch ladder, run once to compile every staged
        # variant (each ladder rung is its own XLA artifact) and — in
        # SLO mode — a second, TIMED time to seed the per-rung step-time EWMA with
        # compile-free launch→sunk walls (backend-agnostic: the reap
        # blocks on the fetch, so the measure covers the compute the
        # launch call alone would hide on async backends).  Masked
        # batches run the full fused graph, so the costs are the
        # served ones; the online refinement (``_note_step_s``)
        # would otherwise start from compile-poisoned values.  A new
        # staged variant added here is automatically both compiled
        # AND seeded — the two passes can never drift apart.  In
        # tiered mode the ladder covers the serving tier only;
        # background-filled rungs follow the documented unseeded-rung
        # rule (assumed free, first dispatch seeds).
        for timed in (False, True) if self._slo_budget_s else (False,):
            if timed:
                self._rung_ewma_s.clear()
            t0 = time.perf_counter()
            self._dispatch(warm, _inline_stamps(t0))
            self._reap(0)
            if timed:
                self._rung_ewma_s[1] = time.perf_counter() - t0
            for g in serving_sizes:
                t0 = time.perf_counter()
                self._dispatch_mega([(warm, _inline_stamps(t0))] * g)
                self._reap(0)
                if timed:
                    self._rung_ewma_s[g] = time.perf_counter() - t0
        # publish the ready set LAST: every executable above is
        # installed and compile-free before a drain may pick its rung
        self._ready_sizes = serving_sizes
        # warm dispatches are compile triggers, not traffic — keep them
        # out of the dispatch-block accounting
        self._reset_dispatch_counters()
        boot["serving_ready_s"] = round(
            time.perf_counter() - self._boot_t0, 4)
        if self._cache is not None:
            boot["cache"] = self._cache.report()
        self._boot = boot
        if fill_plan:
            self._warm_plan = tuple(fill_plan)
            self._warm_thread_obj = threading.Thread(
                target=self._warm_worker, name="fsx-warm", daemon=True)
            self._warm_thread_obj.start()

    # -- AOT executable staging (ISSUE 20) ----------------------------------

    @staticmethod
    def _variant_label(name: tuple) -> str:
        return name[0] if len(name) == 1 else f"{name[0]}{name[1]}"

    def _aot_build(self, name: tuple) -> tuple[Any | None, dict]:
        """Load-or-compile ONE staged variant ahead of time.

        Worker-safe by construction: touches only the pristine jit
        wrappers and abstract arg specs captured at __init__
        (``_aot_specs``) and the compile cache — never the live device
        state, never a dispatch.  Returns ``(executable, entry)``
        where entry is the per-variant boot record (source:
        cache | compile | error, seconds); executable is None on
        failure (fail-open: the jit wrapper keeps serving)."""
        label = self._variant_label(name)
        fn, args = self._aot_specs[name]
        t0 = time.perf_counter()
        if self._cache is not None:
            exe = self._cache.load(label)
            if exe is not None:
                return exe, {
                    "source": "cache",
                    "seconds": round(time.perf_counter() - t0, 4)}
        try:
            exe = fn.lower(*args).compile()
        except Exception as e:  # noqa: BLE001 — fail-open by contract
            import sys

            print(f"fsx warm: AOT staging of {label} failed ({e!r}); "
                  "the jit path serves this variant (fail-open)",
                  file=sys.stderr)
            return None, {
                "source": "error", "error": repr(e),
                "seconds": round(time.perf_counter() - t0, 4)}
        if self._cache is not None:
            self._cache.store(label, exe)
        return exe, {"source": "compile",
                     "seconds": round(time.perf_counter() - t0, 4)}

    def _aot_install(self, name: tuple, exe: Any) -> None:
        """Publish one AOT executable over its jit wrapper — plain
        whole-object rebinds only (the atomic-ref discipline: launch
        sites read each reference once per dispatch, so an install
        from the warm fill thread is safe mid-serve; either the jit
        wrapper or the executable runs, byte-identical results)."""
        if name[0] == "single":
            self.step = exe
        else:
            self.megasteps = {**self.megasteps, name[1]: exe}

    def _warm_worker(self) -> None:
        """Background warm fill (warm(tiered=True)): AOT-stage the
        remaining ladder rungs, largest first, and grow the ready
        set as each lands.  NEVER dispatches — the launch
        and sink sections keep their single owners; everything this
        thread publishes (executables, ready set, boot block) is one
        reference rebind.  Fail-open: an error leaves the jit
        fallback serving that variant and is recorded in the boot
        block, never raised into serving."""
        try:
            for name in self._warm_plan:
                exe, entry = self._aot_build(name)
                label = self._variant_label(name)
                if exe is not None:
                    self._aot_install(name, exe)
                    self._ready_sizes = tuple(sorted(
                        set(self._ready_sizes) | {name[1]},
                        reverse=True))
                boot = dict(self._boot or {})
                boot["variants"] = {**boot.get("variants", {}),
                                    label: entry}
                boot["fill_pending"] = [
                    v for v in boot.get("fill_pending", ())
                    if v != label]
                self._boot = boot
            boot = dict(self._boot or {})
            boot["fill_done_s"] = round(
                time.perf_counter() - self._boot_t0, 4)
            if self._cache is not None:
                boot["cache"] = self._cache.report()
            self._boot = boot
        except BaseException as e:  # noqa: BLE001 — fail-open, counted
            self._boot = {**(self._boot or {}), "fill_error": repr(e)}

    def warm_fill_active(self) -> bool:
        """Whether a tiered warm's background fill is still running."""
        t = self._warm_thread_obj
        return t is not None and t.is_alive()

    def warm_fill_join(self, timeout: float | None = None) -> bool:
        """Wait for the background warm fill; True when it is done
        (including when none was started)."""
        t = self._warm_thread_obj
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()

    def _reset_dispatch_counters(self) -> None:
        self._group_hist = {}
        self._dispatch_calls = 0
        self._dispatched_chunks = 0
        self._staged_batches = 0
        self._staged_bytes = 0

    # -- stream rebinding ---------------------------------------------------

    def reset_stream(
        self,
        source: RecordSource,
        sink: VerdictSink | None = None,
        readback_depth: int | None = None,
        t0_ns: int | None = None,
    ) -> None:
        """Rebind the engine to a new record stream WITHOUT recompiling.

        The jitted step is the expensive part of an Engine (~seconds of
        XLA compile per batch shape); the stream plumbing around it is
        cheap.  Benchmarks and restarted feeds reuse one engine across
        many runs by swapping the source/sink and resetting the
        batcher, metrics, and in-flight queue.  Device state (table,
        stats) deliberately persists — it is the engine's long-lived
        flow memory, surviving stream restarts just like the kernel
        maps survive a daemon reconnect; use :meth:`restore` to reset
        it.  Because that memory holds t0-relative stream-seconds
        (last-seen, blacklist expiry), the clock EPOCH persists with
        it: ``t0_ns=None`` keeps the current anchor (re-anchoring to a
        new stream's first record would time-shift every persisted
        expiry — the same invariant :meth:`restore` protects).
        Per-stream report counters (metrics, blocked set, route drops)
        reset; ``_device_now`` survives, being a high-water mark on the
        persisting clock.  Must not be called with batches in flight."""
        if self._inflight or self._pending:
            raise RuntimeError("reset_stream with batches in flight")
        self.source = source
        self.sealed = bool(getattr(source, "provides_sealed", False))
        if self.sealed and not getattr(source, "started", False):
            source.start(self.cfg.batch, self.wire,
                         self.batcher.quant or None)
        if sink is not None:
            self.sink = sink
        if readback_depth is not None:
            self.readback_depth = readback_depth
        if self.sealed and self._arena is None:
            # an engine built on a record source without grouping
            # never staged; the sealed loop stages every batch
            self._arena = self._make_arena()
        quant = self.batcher.quant or None
        keep_t0 = self.batcher.t0_ns if t0_ns is None else t0_ns
        self.batcher = MicroBatcher(
            self.cfg.batch,
            t0_ns=keep_t0,
            n_buffers=self.readback_depth + 2 + self.mega_n,
            wire=self.wire,
            quant=quant,
        )
        if t0_ns is not None:
            self._t0_auto = False
            if hasattr(self.sink, "t0_ns"):
                self.sink.t0_ns = t0_ns
        elif not self._t0_auto and hasattr(self.sink, "t0_ns"):
            self.sink.t0_ns = keep_t0  # a swapped-in sink needs the anchor
        self.metrics = PipelineMetrics()
        # per-stream latency plane restarts with the metrics; the
        # per-rung EWMA table deliberately SURVIVES a rebind — it is a
        # property of the compiled step graphs, not of the stream
        # (paying a re-warm per paced trial would poison short runs)
        self._lat = LatencyRecorder()
        self._blocked = set()
        self._route_drop = 0
        # per-stream readback accounting restarts with the metrics
        self._d2h_bytes = 0
        self._sink_compact = 0
        self._sink_fallback = 0
        self._sunk_batches = 0
        self._reset_dispatch_counters()
        if self._gov is not None:
            # per-stream governor counters restart with the metrics;
            # the predictor's arrival window and any live forecast
            # deliberately survive — like the EWMA table, they are
            # properties of the traffic process, not of one stream
            self._gov.reset_counters()
        # A reap hook is per-stream plumbing: every current caller binds
        # it as a closure over the previous stream's source, so keeping
        # it across a rebind would yield silently wrong latencies (or a
        # mid-run pop_scheduled ValueError).  Callers re-attach.
        self.on_reap = None

    # -- checkpoint/resume (SURVEY.md §5.4: the map-pinning analog) ---------

    def _n_shards(self) -> int:
        return int(self.mesh.devices.size) if self.mesh is not None else 1

    def checkpoint(self, path) -> str:
        """Snapshot table+stats+clock so a restarted engine resumes with
        every tracked flow and blacklist expiry intact.  The write is
        atomic and the header records the table GEOMETRY (salt, shard
        count, capacity) so a restore under a different mesh reshards
        instead of mislocating keys (engine/checkpoint.py docstring)."""
        from flowsentryx_tpu.engine import checkpoint as ckpt

        return str(ckpt.save_state(path, self.table, self.stats,
                                   self.batcher.t0_ns,
                                   hash_salt=self.cfg.table.salt,
                                   n_shards=self._n_shards()))

    def restore(self, path) -> dict:
        """Resume from a snapshot.  Same geometry → bit-identical
        placement; a different mesh size or capacity re-places every
        occupied row for THIS engine's geometry
        (:func:`flowsentryx_tpu.engine.table.reshard_rows` — announced,
        with unplaceable rows counted, never silent).  A salt mismatch
        is refused outright: proceeding under either salt would break
        one side's slot layout.  Returns a summary dict
        (``resharded``/``dropped_rows``/``from``/``to``).

        A CORRUPT snapshot (failed CRC, torn/truncated file —
        :class:`~flowsentryx_tpu.engine.checkpoint.CheckpointCorrupt`)
        is never loaded: restore falls back to the retained previous
        generation (``checkpoint.prev_path``; the periodic-snapshot
        loop rotates it on every save), announced loudly and counted
        in ``EngineReport.health`` as a DEGRADED reason — flow memory
        resumes one generation stale, which fail-open serving absorbs
        the same way it absorbs a restart.  No ``.prev`` (or a
        ``.prev`` that is itself corrupt) re-raises: there is nothing
        safe to resume from, and inventing an empty table silently
        would unblock every previously-blocked source."""
        import sys

        from flowsentryx_tpu.engine import checkpoint as ckpt
        from flowsentryx_tpu.engine import table as tbl

        fallback_from = None
        try:
            ck = ckpt.load_checkpoint(path)
        except ckpt.CheckpointCorrupt as e:
            prev = ckpt.prev_path(path)
            if not prev.exists():
                raise
            print(
                f"fsx engine: checkpoint {path} REFUSED ({e}); "
                f"falling back to the retained previous generation "
                f"{prev}", file=sys.stderr)
            ck = ckpt.load_checkpoint(prev)  # corrupt too -> raises
            fallback_from = str(path)
            self._restore_fallbacks += 1
        if ck.hash_salt != self.cfg.table.salt:
            # A different salt relocates every slot: lookups would miss
            # all persisted flows and silently rebuild the table from
            # scratch while the stale rows rot.  Refuse; the caller
            # adopts the checkpoint's salt (checkpoint.peek_salt) before
            # building the engine, as `fsx serve --restore` does.
            raise ValueError(
                f"checkpoint hash salt {ck.hash_salt} != configured "
                f"{self.cfg.table.salt}; rebuild the engine with "
                "TableConfig(salt=<checkpoint salt>)"
            )
        key = np.asarray(ck.table.key)
        state = np.asarray(ck.table.state)
        if ("tok_bytes" in ck.missing_columns
                and self.cfg.limiter.bucket_burst_bytes > 0):
            # Pre-byte-bucket snapshot under a byte-limited config:
            # zero credit would spuriously rate-block every restored
            # flow's first batch (refill is elapsed-based, not full).
            # Occupied slots start with the full burst, matching the
            # is_new semantics their flows got on first sight.
            state = state.copy()
            state[:, int(schema.TableCol.TOK_BYTES)] = np.where(
                key != 0,
                np.float32(self.cfg.limiter.bucket_burst_bytes),
                np.float32(0.0))
        n_shards = self._n_shards()
        info = {
            "resharded": False, "dropped_rows": 0,
            "from": {"capacity": ck.capacity, "n_shards": ck.n_shards},
            "to": {"capacity": self.cfg.table.capacity,
                   "n_shards": n_shards},
            "crc_checked": ck.crc_checked,
            "fallback_from": fallback_from,
        }
        if (ck.capacity != self.cfg.table.capacity
                or ck.n_shards != n_shards):
            plan = tbl.TablePlan(capacity=self.cfg.table.capacity,
                                 n_shards=n_shards,
                                 salt=self.cfg.table.salt,
                                 probes=self.cfg.table.probes)
            key, state, dropped = tbl.reshard_rows(key, state, plan)
            info["resharded"] = True
            info["dropped_rows"] = dropped
            import sys

            print(
                f"fsx engine: resharding checkpoint "
                f"{ck.capacity} rows x {ck.n_shards} shard(s) -> "
                f"{plan.capacity} rows x {plan.n_shards} shard(s)"
                + (f"; {dropped} row(s) dropped (probe sequences "
                   "exhausted - table too full for the new geometry)"
                   if dropped else ""),
                file=sys.stderr,
            )
        table = schema.IpTableState(key=key, state=state)
        if self.mesh is not None:
            from flowsentryx_tpu import parallel as par

            table = par.shard_table(table, self.mesh)
        else:
            table = schema.IpTableState(key=jax.device_put(key),
                                        state=jax.device_put(state))
        # restored stats re-enter through _put for the same replication
        # reason as the boot-time make_stats()
        stats = schema.GlobalStats(*(np.asarray(v) for v in ck.stats))
        self.table, self.stats = table, self._put(stats)
        self.batcher.t0_ns = ck.t0_ns
        self._t0_auto = False
        if hasattr(self.sink, "t0_ns"):
            self.sink.t0_ns = ck.t0_ns
        return info

    # -- live shard handoff (cluster/rebalance.py; ISSUE 16) ----------------
    #
    # All three methods are QUIESCENT: the rebalancer calls them
    # between run() chunks, where no dispatch is in flight, so the
    # host fetch / re-place round-trip sees (and publishes) a stable
    # table — the same contract as checkpoint()/restore().

    def count_rebalance(self, name: str, n: int = 1) -> None:
        self._rebalance[name] = self._rebalance.get(name, 0) + int(n)

    def _host_table(self) -> tuple[np.ndarray, np.ndarray]:
        return (np.asarray(self.table.key),
                np.asarray(self.table.state))

    def _replace_table(self, key: np.ndarray, state: np.ndarray) -> None:
        """Re-place host arrays on device — the restore() placement
        idiom (sharded over the mesh, or plain device_put)."""
        table = schema.IpTableState(key=key, state=state)
        if self.mesh is not None:
            from flowsentryx_tpu import parallel as par

            table = par.shard_table(table, self.mesh)
        else:
            table = schema.IpTableState(key=jax.device_put(key),
                                        state=jax.device_put(state))
        self.table = table

    def extract_span_rows(
        self, shards, total_shards: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Occupied ``(keys, states)`` of the given RING shards (the
        ingest-affinity hash ``schema.shard_of`` over the table keys —
        the table key IS the folded saddr, so the donor's wire rows
        are selected by exactly the rule producers route by).  Pure
        read: the table is untouched (the donor keeps serving the
        span until the flip commits)."""
        key, state = self._host_table()
        occ = key != 0
        sel = occ & np.isin(schema.shard_of(key, total_shards),
                            np.asarray(list(shards), np.uint32))
        return key[sel].copy(), state[sel].copy()

    def drop_span_rows(self, shards, total_shards: int) -> int:
        """Zero every row of the given ring shards (donor post-flip,
        or boot-time foreign-row reconcile).  Returns the count."""
        key, state = self._host_table()
        key, state = key.copy(), state.copy()
        sel = (key != 0) & np.isin(schema.shard_of(key, total_shards),
                                   np.asarray(list(shards), np.uint32))
        n = int(np.sum(sel))
        if n:
            key[sel] = 0
            state[sel] = 0.0
            self._replace_table(key, state)
        return n

    def adopt_rows(self, keys, states) -> tuple[int, int]:
        """Probe-insert handed-off rows into the live table
        (:func:`flowsentryx_tpu.engine.table.insert_rows`).  Returns
        ``(inserted, dropped)`` — dropped rows (key collision or probe
        exhaustion) are the caller's to count as a DEGRADED reason,
        never silent."""
        from flowsentryx_tpu.engine import table as tbl

        keys = np.asarray(keys, np.uint32).reshape(-1)
        if not len(keys):
            return 0, 0
        key, state = self._host_table()
        plan = tbl.TablePlan(capacity=self.cfg.table.capacity,
                             n_shards=self._n_shards(),
                             salt=self.cfg.table.salt,
                             probes=self.cfg.table.probes)
        key, state, dropped = tbl.insert_rows(key, state, keys, states,
                                              plan)
        self._replace_table(key, state)
        return len(keys) - dropped, dropped

    # -- live model hot-swap ------------------------------------------------

    def hot_swap(self, params) -> None:
        """Replace the served artifact WITHOUT draining the pipeline or
        recompiling (the TPU-tier analog of ``fsx distill --pin``'s
        live map push).  The jitted step takes params as an ARGUMENT,
        so the swap is one atomic reference assignment: dispatches
        launched after it score with the new artifact, in-flight
        rounds finish with the old one — no serving gap, no verdict
        lost.  Safe from any thread (``on_reap`` hooks, the artifact
        watcher, an operator REPL): launch sites read ``self.params``
        exactly once per dispatch.

        Refused (ValueError) when the swap would invalidate compiled
        state rather than just re-parameterize it: a different leaf
        structure/shape/dtype would silently retrace mid-serve, and a
        compact16 ``model``-mode wire quantizes with the BOOT
        artifact's observer constants (baked into the traced decode
        and the sealed-ingest workers), so a new artifact must carry
        the same ``in_scale``/``in_zp``/``log1p`` — or be served over
        raw48."""
        old_leaves = jax.tree_util.tree_leaves(self.params)
        new_leaves = jax.tree_util.tree_leaves(params)
        if (jax.tree_util.tree_structure(self.params)
                != jax.tree_util.tree_structure(params)):
            raise ValueError(
                "hot_swap: artifact tree structure differs from the "
                "served model (different family?); boot a fresh engine")
        for i, (a, b) in enumerate(zip(old_leaves, new_leaves)):
            sa, sb = np.shape(a), np.shape(b)
            da = np.dtype(getattr(a, "dtype", type(a)))
            db = np.dtype(getattr(b, "dtype", type(b)))
            if sa != sb or da != db:
                raise ValueError(
                    f"hot_swap: params leaf {i} is {db}{list(sb)}, "
                    f"served model has {da}{list(sa)} — a shape/dtype "
                    "change would retrace the step mid-serve")
        q = self.batcher.quant or None
        if q and q.get("feat_mode") == "model":
            nq = schema.model_quant_args(params)
            drift = {k: (q.get(k), nq[k])
                     for k in ("in_scale", "in_zp", "log1p")
                     if nq[k] != q.get(k)}
            if drift:
                raise ValueError(
                    "hot_swap: the compact16 wire quantizes with the "
                    "boot artifact's input observer, but the new "
                    f"artifact's differs: {drift}; serve raw48 or "
                    "reboot with the new artifact")
        self.params = jax.tree.map(self._put, params)
        self._hot_swaps += 1

    def watch_artifact(self, path: str) -> None:
        """Live artifact reload (``fsx serve --artifact-reload``): the
        serving loops re-stat ``path`` at most twice a second and
        :meth:`hot_swap` when its mtime changes.  A failed reload
        (half-written file, wrong family) is announced on stderr and
        serving continues on the incumbent model — fail-open, the data
        plane never dies for a bad artifact push."""
        import os

        self._watch_path = str(path)
        try:
            self._watch_mtime = os.stat(self._watch_path).st_mtime_ns
        except OSError:
            self._watch_mtime = 0
        self._watch_next = 0.0

    def _maybe_reload_artifact(self) -> None:
        if self._watch_path is None:
            return
        t = time.monotonic()
        if t < self._watch_next:
            return
        self._watch_next = t + 0.5
        import os

        try:
            m = os.stat(self._watch_path).st_mtime_ns
        except OSError:
            return  # mid-replace or gone; try again next tick
        if m == self._watch_mtime:
            return
        self._watch_mtime = m
        import sys
        import zipfile

        try:
            from flowsentryx_tpu.models.registry import load_artifact

            self.hot_swap(load_artifact(self.cfg.model.name,
                                        self._watch_path))
            print(f"fsx engine: hot-swapped artifact "
                  f"{self._watch_path} (swap #{self._hot_swaps})",
                  file=sys.stderr)
        # BadZipFile: a non-atomic deploy caught mid-write hands
        # np.load a partial zip — the headline case the fail-open
        # contract exists for (a later poll picks up the finished file)
        except (ValueError, KeyError, OSError,
                zipfile.BadZipFile) as e:
            print("fsx engine: artifact reload failed (serving "
                  f"continues on the incumbent model): {e}",
                  file=sys.stderr)

    # -- main loop ----------------------------------------------------------

    def run(
        self,
        max_batches: int | None = None,
        max_seconds: float | None = None,
    ) -> EngineReport:
        """Run until the source is exhausted (or a bound trips).

        With ``sink_thread`` (auto-on where the host has ≥3 cores) the
        verdict sink runs on a dedicated thread for the duration of
        this call: started here, drained and joined before the report
        is built, crash surfaced as a RuntimeError (module
        docstring)."""
        self._start_sink_thread()
        try:
            rep = (self._run_sealed(max_batches, max_seconds)
                   if self.sealed
                   else self._run_inline(max_batches, max_seconds))
        finally:
            self._stop_sink_thread()
            # Serving is over; do not hand a daemon fill thread to
            # interpreter teardown mid-XLA-compile (measured segfault
            # in short-lived `fsx serve --batches N --tiered-warm`
            # runs whose drain outpaces the ladder fill).  Bounded: a
            # compile always terminates, and a long-lived server's
            # fill finished long before its drain did.
            if not self.warm_fill_join(300.0):
                import sys

                print("fsx engine: warm fill still compiling 300 s "
                      "after the drain finished — abandoning it "
                      "(report's fill_done_s will be missing)",
                      file=sys.stderr)
        self._check_sink()  # a crash in the very last drain group
        return rep

    def _run_inline(
        self,
        max_batches: int | None = None,
        max_seconds: float | None = None,
    ) -> EngineReport:
        """The record-source serving loop (the batcher lives here; the
        sealed-batch twin is :meth:`_run_sealed`)."""
        t_start = time.perf_counter()
        cfg_b = self.cfg.batch

        def bounded() -> bool:
            if max_batches is not None and self.batcher.batches_emitted >= max_batches:
                return True
            if max_seconds is not None and time.perf_counter() - t_start >= max_seconds:
                return True
            return False

        while not bounded():
            with self.metrics.poll:
                # Mega mode polls up to the remaining GROUP capacity so
                # a deep source backlog can seal several batches in
                # one drain; otherwise exactly one batch's worth.
                group_room = max(self.mega_n - len(self._pending), 1)
                requested = group_room * cfg_b.max_batch - self.batcher.fill
                records = self.source.poll(requested)
                if self._t0_auto and len(records):
                    if self.precompact:
                        t0 = int(schema.unwrap_kernel_ts16(
                            records["w3"][:1],
                            time.clock_gettime_ns(time.CLOCK_MONOTONIC),
                        )[0])
                    else:
                        t0 = int(records["ts_ns"][0])
                    self.batcher.t0_ns = t0
                    if hasattr(self.sink, "t0_ns"):
                        self.sink.t0_ns = t0  # sinks translate s -> abs ns
                    self._t0_auto = False
                # the (simulated) kernel tier splits records exactly
                # where XDP would: after the drain, before the batcher.
                # n_polled drives the idle backoff below — a hot source
                # whose records all drop in-kernel is not an idle link.
                n_polled = len(records)
                if self._gov is not None and n_polled:
                    # the governor observes the PRE-filter arrival
                    # process (like the idle backoff): the burst shape
                    # it forecasts is the link's, not the survivors'
                    self._gov.note_arrivals(time.perf_counter(),
                                            n_polled)
                if self.kernel_tier is not None and n_polled:
                    records = self.kernel_tier.filter(records)
                if not len(records):
                    sealed = []
                    if self.precompact:
                        # A drain opportunity with no records: note it so
                        # the wrap-risk heuristic keys on drain cadence,
                        # not traffic cadence (a lull is not a stall).
                        self.batcher.note_poll()
                elif self.precompact:
                    sealed = self.batcher.add_precompact(records)
                else:
                    sealed = self.batcher.add(records)
                # The idle-pipe deadline-flush rule lives in
                # _deadline_flush_due (flush ONLY when the pipe is
                # fully drained, never mid-flight; SLO mode adds the
                # budget bound on batcher residency) — extracted so
                # the rule is tested directly, not just documented.
                if not sealed and self._deadline_flush_due():
                    took = self.batcher.take()
                    sealed = [took] if took is not None else []
            if self.mega_n > 0:
                # Backlog-triggered grouping: full top-rung groups go
                # as one dispatch; the moment the source comes back
                # short (no deep backlog) the stragglers flush through
                # the largest staged rung they still fill (adaptive),
                # then singly — so grouping only ever ADDS latency to
                # batches that were queueing behind a backlog anyway.
                # Shortness is judged PRE-filter (n_polled): a flood
                # the kernel tier mostly drops still means a deep
                # source backlog, exactly when coalescing pays most.
                for raw in sealed:
                    self._pending.append((raw, self._pop_stamps()))
                self._drain_pending(short=n_polled < requested)
            else:
                for raw in sealed:
                    self._dispatch(raw, self._pop_stamps())
                    self._reap(self.readback_depth)
            # Latency path: sink whatever the device has finished, every
            # iteration — including iterations that sealed nothing (the
            # depth cap above only bounds the pipe; waiting for it to
            # fill would defer verdicts by depth × batch-fill time).
            self._reap_ready()
            if not sealed and self.source.exhausted():
                if self.batcher.fill:
                    self._dispatch(self.batcher.take(), self._pop_stamps())
                break
            if not sealed and not n_polled:
                if self._busy_depth() == 0:
                    # Proactive rung pre-sizing (engine/predict.py):
                    # inside the pre-warm lead window before a
                    # forecast burst onset, spend this otherwise-idle
                    # iteration re-dispatching the predicted rung with
                    # a masked zero-valid batch — results untouched
                    # (warm()'s masking argument), but the rung's
                    # step-time EWMA refreshes launch-absorbed, so
                    # _slo_cap prices the incoming burst with a HOT
                    # measurement instead of a stale one and the XLA
                    # executable/arena path is warm when the burst
                    # lands.  Idle iterations only: a pre-warm must
                    # never queue ahead of real traffic.
                    if self._gov is not None:
                        rung = self._gov.prewarm_rung(
                            time.perf_counter(),
                            self._rung_ewma_s.get(1, 0.0))
                        if rung:
                            # clamp the forecast rung to the READY set
                            # (a tiered warm may still be filling):
                            # pre-warming an uninstalled rung would
                            # spend the idle window on an inline
                            # compile instead of a hot re-dispatch
                            self._prewarm_dispatch(
                                self._rung_for(rung) if rung > 1
                                else rung)
                            continue
                    # Idle link: back off instead of spinning poll() at
                    # 100% CPU (sync/tuning.py IDLE_SLEEP_S, the
                    # daemon-matched cadence).  A fraction of the batch
                    # deadline keeps added latency under the flush
                    # budget.
                    with self.metrics.idle:
                        time.sleep(tuning.idle_sleep_s(cfg_b.deadline_us))
                elif self._sink_active:
                    # Pipe busy, nothing new to dispatch: YIELD the GIL
                    # (sync/tuning.py GIL_YIELD_S — a spinning dispatch
                    # loop starved the sink thread's pure-Python
                    # decode/writeback, measured 10-25 ms sinks).
                    with self.metrics.idle:
                        time.sleep(tuning.GIL_YIELD_S)

        # A bounded exit (max_batches/max_seconds) can in principle trip
        # with sealed group candidates still pending (span-boundary
        # partial seals make the per-iteration invariants fragile):
        # dispatch them singly — their records are already counted in
        # records_emitted, and leaving them would also wedge a later
        # reset_stream on a genuinely idle engine.
        for raw, stamps in self._pending:
            self._dispatch(raw, stamps)
        self._pending.clear()
        self._reap(0)
        with self.metrics.report:
            return self._build_report(time.perf_counter() - t_start)

    def _run_sealed(
        self,
        max_batches: int | None = None,
        max_seconds: float | None = None,
    ) -> EngineReport:
        """The sharded-ingest serving loop: stage → dispatch → reap.

        Everything per-record — ring drain, decode, quantization, batch
        assembly — already happened in the drain workers; what is left
        on this thread is ONE shm-slot-view → dispatch-arena memcpy per
        batch (``poll_batches_into`` staging; the queue slot is
        released the moment the bytes land in the arena, before the
        batch is even dispatched) and the async dispatch, so the loop's
        cost scales with BATCHES, not records.  Groups dispatch as
        contiguous arena slices — no ``np.stack``, no consume copy.
        Semantics otherwise mirror :meth:`run`: depth-capped pipe,
        readiness reaping, ladder grouping on backlog
        (:meth:`_drain_pending`'s policy), deadline behavior delegated
        to the workers (they own the micro-batchers now)."""
        t_start = time.perf_counter()
        src = self.source
        if not self._t0_auto and hasattr(src, "set_t0"):
            # A fixed epoch (explicit t0_ns, or a restored checkpoint's
            # via restore()) must reach the worker fleet before its
            # min-first_ts handshake resolves: the workers seal device
            # times against THEIR t0, the sink translates until-ns with
            # OURS, and nothing downstream can reconcile the two.
            src.set_t0(self.batcher.t0_ns)

        def bounded() -> bool:
            if (max_batches is not None
                    and self.batcher.batches_emitted >= max_batches):
                return True
            if (max_seconds is not None
                    and time.perf_counter() - t_start >= max_seconds):
                return True
            return False

        self._sealed_loop_arena(src, bounded)
        self._reap(0)
        with self.metrics.report:
            return self._build_report(time.perf_counter() - t_start)

    def _adopt_fleet_t0(self, src) -> None:
        """The fleet's epoch handshake picked t0; adopt it for the
        device clock and the sink's ns translation."""
        self.batcher.t0_ns = src.t0_ns
        if hasattr(self.sink, "t0_ns"):
            self.sink.t0_ns = src.t0_ns
        self._t0_auto = False

    def _sealed_idle(self, src) -> bool:
        """Empty-poll tail of the sealed loop: True = source
        exhausted, stop serving."""
        if src.exhausted():
            return True
        if self._busy_depth() == 0:
            with self.metrics.idle:
                time.sleep(tuning.idle_sleep_s(self.cfg.batch.deadline_us))
        elif self._sink_active:
            # yield the GIL to the sink thread (sync/tuning.py)
            with self.metrics.idle:
                time.sleep(tuning.GIL_YIELD_S)
        return False

    def _sealed_loop_arena(self, src, bounded) -> None:
        """The zero-copy sealed loop (single-copy staging tentpole).

        One arena SLOT is live at a time: ``poll_batches_into`` stages
        sealed payloads into its rows at ``fill`` (releasing the shm
        slots immediately), the ladder dispatches contiguous
        ``rows[done:done+g]`` slices, and a fresh slot is claimed only
        after a USED slot fully dispatches — never on an empty poll, so
        the arena's reuse-safety rule (a slot recycles only after its
        batches are sunk; engine/arena.py) holds by construction."""
        top = self._mega_sizes[0] if self._mega_sizes else 0
        slo = self._slo_budget_s
        rows: np.ndarray | None = None
        fill = done = 0
        metas: list[tuple[_Stamps, int]] = []  # (stamps, n_records)/row
        while not bounded():
            if rows is None or (fill and fill == done):
                rows = self._arena.rows(self._arena.claim())
                fill = done = 0
                metas = []
            want = len(rows) - fill
            if top:
                want = min(want, max(top - (fill - done), 0))
            batches = []
            if want > 0:
                with self.metrics.poll:
                    batches = src.poll_batches_into(
                        rows[fill:], want,
                        pop_timer=self.metrics.pop,
                        stage_timer=self.metrics.stage)
            if self._t0_auto and batches and src.t0_ns:
                self._adopt_fleet_t0(src)
            for sb in batches:
                # workers sealed these; mirror into the engine-side
                # counters the report and bounds are built on
                self.batcher.batches_emitted += 1
                self.batcher.records_emitted += sb.n_records
                self._staged_batches += 1
                self._staged_bytes += int(sb.raw.nbytes)
                metas.append((_sealed_stamps(sb), sb.n_records))
                fill += 1
            if self._gov is not None and batches:
                self._gov.note_arrivals(
                    time.perf_counter(),
                    sum(sb.n_records for sb in batches))
            # ``want == 0`` (slot rows exhausted under a pending carry)
            # must flush, not poll: treat it as a short poll.
            short = len(batches) < want or want == 0

            def flush(g: int) -> None:
                nonlocal done
                if g > 1:
                    oldest = min(m[0] for m in metas[done:done + g])
                    n = sum(m[1] for m in metas[done:done + g])
                    self._dispatch_group(rows[done:done + g], oldest, n)
                else:
                    self._dispatch(rows[done], metas[done][0])
                done += g
                self._reap(self.readback_depth)

            while top and fill - done >= top:
                # an existing top-rung backlog stays un-capped in SLO
                # mode (the sub-linear-step argument; _drain_pending)
                flush(top)
            # no ladder staged → singles dispatch as they arrive;
            # with a ladder, the remainder flushes only on a short
            # poll (a full poll means a backlog is still building) —
            # or, under --slo-us, the moment holding would cost the
            # oldest staged record its budget
            if short or not top or (
                    slo and fill - done
                    and self._slo_pressed(metas[done][0].t_enqueue)):
                while fill - done:
                    g = self._rung_for(fill - done)
                    if slo:
                        g = min(g, self._slo_cap(
                                metas[done][0].t_enqueue))
                    flush(g)
            self._reap_ready()
            if not batches and self._sealed_idle(src):
                break
        # bounded exit with staged-but-undispatched rows: flush singly
        # (their records are already counted in records_emitted, and a
        # wedged slot would also poison the next claim's safety rule)
        while fill - done:
            self._dispatch(rows[done], metas[done][0])
            done += 1

    def _build_report(self, wall: float) -> EngineReport:
        # "now" on the device clock (t0-anchored stream seconds, not wall
        # time) comes from the reaped step outputs — no extra reduction.
        table_sum = pallas_kernels.table_summary(
            self.table, now=self._device_now, stale_s=self.cfg.table.stale_s
        )

        readback = {
            "mode": "compact" if self.verdict_k else "full",
            "k_max": self.verdict_k,
            "wire_bytes": (fused.verdict_wire_words(self.verdict_k) * 4
                           if self.verdict_k else None),
            "compact_sinks": self._sink_compact,
            "fallback_sinks": self._sink_fallback,
            "d2h_bytes": self._d2h_bytes,
            "bytes_per_batch": round(
                self._d2h_bytes / max(self._sunk_batches, 1), 1),
            "sink_thread": self.sink_thread,
            "sink_occupancy": (round(
                self._chan.busy_s / max(wall, 1e-9), 4)
                if self.sink_thread else None),
            # blocks given up on a verdict ring whose reader stood
            # still (ShmVerdictSink.dropped): each leaves its source
            # unsuppressed in the kernel until it offends again.  None
            # for a sink that cannot drop.
            "verdict_ring_dropped": getattr(self.sink, "dropped", None),
        }
        if hasattr(self.sink, "ring_accounting"):
            # a ring sink's own accounting: dropped, applies that waited
            # for room (span fsx.sink.vring_wait), peak fill
            readback.update(self.sink.ring_accounting())

        # Dispatch-pipeline accounting.  host_copies_per_batch counts
        # ENGINE-side host memcpys per dispatched batch: arena staging
        # is the zero-copy pipeline's one copy (sealed path == 1.0);
        # the subsequent device_put of the page-aligned slice is the
        # host↔device boundary itself, not a host copy.  Inline singles
        # dispatch the batcher's own buffer (no staging), so a pure
        # inline single-dispatch run reads 0.0.
        dispatch = {
            "mode": ("adaptive" if self.mega_auto
                     else "fixed" if self.mega_n else "single"),
            "mega_n": self.mega_n,
            # latency-budget serving (--slo-us): the budget and the
            # warm-measured per-rung step-time EWMA the deadline-aware
            # policy bounded coalescing with.  None = throughput mode.
            "slo": ({
                "slo_us": self.slo_us,
                "rung_ewma_ms": {
                    str(k): round(v * 1e3, 4)
                    for k, v in sorted(self._rung_ewma_s.items())},
            } if self.slo_us else None),
            "group_sizes": list(self._mega_sizes),
            "group_hist": {str(k): v for k, v in
                           sorted(self._group_hist.items())},
            "dispatches": self._dispatch_calls,
            "dispatch_hz": round(
                self._dispatch_calls / max(wall, 1e-9), 1),
            "staged_batches": self._staged_batches,
            "staged_bytes": self._staged_bytes,
            "host_copies_per_batch": round(
                self._staged_batches / max(self._dispatched_chunks, 1),
                3),
            "arena": (self._arena.info()
                      if self._arena is not None else None),
        }

        escalation = None
        if self.kernel_tier is not None:
            escalation = self.kernel_tier.report()
            escalation["kernel_drop_hz"] = round(
                (escalation.get("kernel_drops", 0)
                 + escalation.get("blacklist_hits", 0)) / max(wall, 1e-9),
                1)

        # explicit D2H for the report counters (transfer-guard contract)
        st = schema.GlobalStats(*jax.device_get(tuple(self.stats)))
        ingest_stats = (self.source.ingest_stats()
                        if self.sealed and hasattr(self.source,
                                                   "ingest_stats")
                        else None)
        cluster_rep = (self.gossip.report()
                       if self.gossip is not None else None)
        # Boot-latency block (ISSUE 20): one consistent snapshot of the
        # warm/fill story (the fill thread publishes whole-dict
        # rebinds, so a single read is coherent even mid-fill) plus
        # the sink-stamped time-to-first-verdict and the caller-
        # stamped import wall.
        boot_rep = None
        boot_snap = self._boot
        if boot_snap is not None:
            boot_rep = dict(boot_snap)
            boot_rep["import_s"] = round(self.boot_import_s, 4)
            boot_rep["time_to_first_verdict_s"] = (
                round(self._first_verdict_s, 4)
                if self._first_verdict_s is not None else None)
            boot_rep["fill_active"] = self.warm_fill_active()
            if self.boot_jax_compiles is not None:
                # read AFTER table_summary above: its compile is part
                # of what a boot of this process costs
                boot_rep["jax_cache"] = self.boot_jax_compiles.report()
        hists = {sp.name: sp.hist for sp in self.metrics.spans()}
        hists.update(self._lat.hists())
        if hasattr(self.sink, "spans"):
            hists.update((sp.name, sp.hist) for sp in self.sink.spans())
        if self.sealed and hasattr(self.source, "ingest_spans"):
            hists.update((sp.name, sp.hist)
                         for sp in self.source.ingest_spans())
        predict_rep = None
        if self._gov is not None:
            predict_rep = self._gov.report()
            if cluster_rep is not None:
                # fold the shed counters in next to the actuation
                # counters they motivate — one block to alert on
                predict_rep["gossip_ticks_deferred"] = cluster_rep.get(
                    "ticks_deferred", 0)
                predict_rep["net_resync_deferred"] = (
                    cluster_rep.get("net") or {}).get(
                        "resync_deferred", 0)
        return EngineReport(
            batches=self.batcher.batches_emitted,
            records=self.batcher.records_emitted,
            wall_s=round(wall, 4),
            records_per_s=round(self.batcher.records_emitted / max(wall, 1e-9), 1),
            stats=st.to_dict(),
            stages_ms=self.metrics.to_dict(),
            blocked_sources=len(self._blocked),
            table=table_sum,
            ts_wrap_risk_polls=self.batcher.ts_wrap_risk_polls,
            route_drop=self._route_drop,
            ingest=ingest_stats,
            readback=readback,
            dispatch=dispatch,
            escalation=escalation,
            cluster=cluster_rep,
            # compute_is_wall: on backends that execute the step graph
            # synchronously at dispatch (XLA:CPU scatter custom-calls)
            # the launch wall IS the compute; a CPU backend is the
            # honest proxy for that here
            latency=self._lat.to_dict(
                self.slo_us,
                compute_is_wall=self._device["platform"] == "cpu"),
            # the health ladder is a pure function of the blocks above
            # (engine/health.py): impossible to drift from the counters
            health=health.engine_health(
                ingest=ingest_stats,
                gossip=cluster_rep,
                watchdog=self._watchdog.to_dict(),
                restore_fallbacks=self._restore_fallbacks,
                rebalance=self._rebalance or None,
                readback=readback),
            rebalance=dict(self._rebalance) or None,
            predict=predict_rep,
            boot=boot_rep,
            device=dict(self._device),
            spans=span_store(hists),
        )
