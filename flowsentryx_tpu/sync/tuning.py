"""THE idle/backoff timing table of the host pipeline.

Every sleep, spin budget and wait quantum the concurrent host code
uses lives here, with the measurement that justifies it — previously
these were magic literals scattered through ``engine/engine.py`` and
``ingest/worker.py``, which meant a retune in one loop silently
diverged from its twin in the other.  The contract checker
(``fsx sync``) treats this module as part of the documented thread
model: docs/CONCURRENCY.md §tuning mirrors this table.

All values are seconds unless the name says otherwise.  Nothing here
imports jax (the ingest workers read this on their sub-second boot
path).
"""

from __future__ import annotations

#: Dispatch-thread GIL yield while the pipe is busy but nothing new is
#: sealable.  A spinning dispatch loop holds the interpreter for the
#: full 5 ms switch interval per slice, starving the sink
#: thread's pure-Python decode/writeback — measured (PR 3) stretching
#: sub-millisecond sinks to 10-25 ms.  20 µs is long enough to force a
#: drop of the GIL and short enough to be invisible against the
#: ~100 µs+ batch cadence.
GIL_YIELD_S = 20e-6

#: Idle sleep between empty polls, engine loops and drain workers
#: alike.  Matches the daemon's 200 µs idle sleep so an end-to-end
#: idle link wakes at one cadence; the engine additionally caps it at
#: a quarter of the batch deadline so the added latency stays well
#: under the flush budget for small ``deadline_us`` configs.
IDLE_SLEEP_S = 200e-6

def idle_sleep_s(deadline_us: float) -> float:
    """The engine's idle back-off: IDLE_SLEEP_S capped at a quarter of
    the batch deadline (both dispatch loops share this — the cap must
    not be retuned in one and not the other)."""
    return min(deadline_us / 4, IDLE_SLEEP_S * 1e6) / 1e6


#: Drain-worker bounded spin before falling back to IDLE_SLEEP_S
#: (``ingest/worker.py::_Backoff``).  150 µs covers the common
#: inter-burst gap at Mpps rates without paying a scheduler wakeup
#: (≥ the 200 µs sleep, often a multi-ms quantum on a loaded host) on
#: the next record's path.  AUTO policy: only spent when the host has
#: cores ≥ workers + 2 — on the 2-vCPU CI container a spinning worker
#: steals the very XLA cycles it is trying to feed (measured ~15 %
#: sealed-drain loss, PR 5).
SPIN_US_DEFAULT = 150

#: Backpressure wait quantum: how long the dispatch thread's
#: ``SinkChannel.wait_below`` sleeps per check while the pipe is over
#: depth.  Pure liveness bound — every state change notifies the cv,
#: so this only limits how stale a MISSED wakeup can get (it cannot
#: happen under the channel's notify-on-complete discipline, but a
#: bound beats an unbounded wait if that discipline ever regressed).
BACKPRESSURE_WAIT_S = 0.05

#: Worker-side pop wait quantum (``SinkChannel.pop``): same liveness
#: rationale as BACKPRESSURE_WAIT_S; 2x longer because an idle worker
#: waking is cheaper than a dispatch thread stalling.
POP_WAIT_S = 0.1

#: Single-thread-mode ready-reap coalescing: minimum gap between sink
#: groups when the pipe is shallow, capped at half the flush deadline
#: so a small ``deadline_us`` keeps its latency budget (engine
#: ``_min_sink_gap_s``).  Each sink has a fixed host cost; reaping
#: every iteration at trivial loads burned more host time than the
#: verdicts were worth (the r4 open-loop collapse's little sibling).
MIN_SINK_GAP_S = 0.3e-3

#: Cluster gossip merge/heartbeat cadence (``cluster/gossip.py``:
#: ``GossipPlane.tick``, called from the engine loop every iteration
#: and throttled here).  Each tick stats N-1 peer mailboxes — pure
#: python, ~µs — so 5 ms costs nothing measurable on the dispatch
#: thread while keeping blacklist convergence three orders of
#: magnitude under the default 10 s block TTL (a peer's block is
#: enforced cluster-wide within one interval plus one loop iteration;
#: test-pinned).
GOSSIP_MERGE_INTERVAL_S = 5e-3

#: Per-rung step-time EWMA smoothing for the latency-budget serving
#: mode (``fsx serve --slo-us``; engine ``_note_step_s``).  The
#: estimate gates COALESCING only (never correctness), so it wants to
#: track regime shifts — table growth, host throttling — within a few
#: dozen dispatches without chasing single-step noise: 0.2 reaches
#: ~90 % of a step-time shift in ~10 dispatches.  Applied only to
#: launches whose call absorbed the compute (synchronous backends);
#: elsewhere the warm-pass seed stands.
SLO_EWMA_ALPHA = 0.2

#: Bounded wait on a full sealed-batch queue once stop was requested —
#: the consumer may already be gone and worker shutdown must not hang.
#: A give-up is NOT silent: the seq is un-burned and the loss lands in
#: the queue's ``emit_drop`` counter (``ingest/worker.py::_Emitter``).
EMIT_STOP_TIMEOUT_S = 2.0

#: How long the verdict ring's writer (``engine/shm.py::ShmVerdictSink``)
#: waits on a full ring whose reader's cursor does not move before it
#: gives the reader up and counts what is left in ``dropped``
#: (``verdict_ring_dropped``, ``health`` DEGRADED).  A live ``fsxd``
#: takes 4,096 verdicts every loop iteration and sleeps at most 200 µs
#: between two, so its cursor moves within a millisecond; 2 s is three
#: orders above that and the bound EMIT_STOP_TIMEOUT_S already puts on
#: the same question one stage upstream ("is the consumer gone?").
#: Progress restarts the clock, and between looks the writer sleeps
#: IDLE_SLEEP_S, the daemon's own idle cadence.
VRING_WAIT_TIMEOUT_S = 2.0

# -- robustness plane (PR 13: fsx chaos + the hardening it forced) ----------

#: Dispatch-watchdog stall bound (``engine/watchdog.py``): batches in
#: flight with zero completions for this long soft-trips (per-thread
#: stack dump, DEGRADED reason), for 2x this long hard-trips (the
#: drain fails loudly instead of hanging forever).  10 s is ~3 orders
#: of magnitude above the worst healthy gap (a cold top-rung launch
#: on a throttled host measures tens of ms; this container's cgroup
#: throttle windows stretch seconds — PR 3/PR 11 measurements), so a
#: trip means wedged, not slow.  The two-stage form exists precisely
#: because of those throttle windows: one full bound of grace after
#: the stack dump lets a starved-but-live pipe recover.
WATCHDOG_STALL_S = 10.0

#: Supervisor liveness-poll cadence (``ClusterSupervisor.run``):
#: previously a hard-coded 0.05 in the run signature.  50 ms bounds
#: corpse-detection latency at one order of magnitude under the stub
#: serve times tier-1 pins, while keeping the supervisor's idle CPU
#: (a handful of ctl-block u64 loads per rank per poll) unmeasurable.
SUPERVISOR_POLL_S = 0.05

#: Supervisor heartbeat staleness bound (``ClusterSupervisor`` —
#: previously a hard-coded ``heartbeat_timeout_s=5.0`` default).  The
#: engine heartbeat rides the gossip tick (5 ms cadence,
#: GOSSIP_MERGE_INTERVAL_S), so 5 s of silence is ~1000 missed beats:
#: far past any measured GC/throttle pause, short enough that a
#: wedged-but-alive rank surfaces in ``stalled_ranks`` within one
#: operator glance.  The boot-over-live-plane refusal uses 2x this.
SUPERVISOR_HEARTBEAT_TIMEOUT_S = 5.0

#: Crash-loop respawn backoff (``ClusterSupervisor``): the k-th
#: respawn inside the sliding window waits ``BASE * 2**(k-1)`` capped
#: at MAX before the rank is re-spawned.  Before PR 13 respawn was
#: immediate, so a rank dying at boot (bad artifact push, torn
#: checkpoint) burned its whole restart budget in milliseconds and
#: parked before an operator could even read the first traceback.
#: BASE at 100 ms is >= the stub boot and ~the real engine's fork
#: cost, so a single transient death restarts essentially instantly;
#: MAX at 5 s keeps a flapping rank from hammering the host while
#: staying well inside the heartbeat/liveness cadence above.
RESPAWN_BACKOFF_BASE_S = 0.1
RESPAWN_BACKOFF_MAX_S = 5.0

# -- multi-host gossip transport (ISSUE 15: cluster/transport.py) -----------

#: Reorder-buffer depth per remote peer (``NetMailbox``): out-of-order
#: datagrams park here until the sequence hole fills; a buffer past
#: this depth EVICTS its oldest wire (delivered out of order, counted
#: ``reorder_evict``) instead of growing — bounded memory, never a
#: stall.  16 wires ≈ 9 KB/peer covers every reorder depth a same-rack
#: ECMP/offload path produces (single-digit packets); a hole deeper
#: than 16 is loss, and waiting on loss is exactly the coordinator
#: coupling the plane exists to avoid.
NET_REORDER_WINDOW = 16

#: How long a sequence HOLE may park later wires in the reorder buffer
#: before the hole is conceded as loss (``rx_gap`` counted, buffered
#: wires delivered in order).  Genuine in-flight reorder resolves in
#: sub-ms on a rack; 200 ms is 2-3 orders above that and well under
#: the 10 s default block TTL, so a lost wire delays its successors'
#: verdicts imperceptibly instead of parking them until the window
#: fills.  Waiting longer would be the retransmit coupling a
#: last-wins, resync-repaired stream does not need.
NET_REORDER_TIMEOUT_S = 0.2

#: A backward sequence jump deeper than this (in wires) from a peer is
#: a peer RESTART (its seq space restarted from 1), not a stale
#: duplicate: the rx state resets and is counted, instead of dropping
#: every wire of the peer's new life as a "duplicate".  4x the reorder
#: window keeps genuine late stragglers (bounded by the window by
#: construction) strictly inside the dup-suppression regime.
NET_RESTART_JUMP = 4 * NET_REORDER_WINDOW

#: TX handoff queue bound (``NetMailbox.queue_tx``): the engine's sink
#: section hands wires to the merge-side pump through a deque; past
#: this depth the PUBLISHER drops-and-counts (``txq_dropped``) rather
#: than grow without bound — a blocked (or bloating) publisher is the
#: coordinator coupling the gossip plane exists to avoid, the same
#: posture as the full shm mailbox.  256 wires ≈ 144 KB and ~1.3 s of
#: headroom at the 5 ms gossip-tick drain cadence.
NET_OUTQ_MAX = 256

#: Peer-discovery handshake (``NetMailbox.handshake``): HELLO is
#: re-sent per silent peer with exponential backoff from BASE doubling
#: to CAP, bounded by TIMEOUT overall.  BASE at 50 ms is ~100x a
#: loopback/rack RTT so one lost HELLO costs little; CAP at 1 s keeps
#: a long wait from hammering a dead address; TIMEOUT at 10 s is the
#: supervisor heartbeat bound — past it the peer is somebody else's
#: incident and the caller fails OPEN (serve now, converge when the
#: peer appears: its first HELLO triggers a full-map resync).
NET_HANDSHAKE_BACKOFF_BASE_S = 0.05
NET_HANDSHAKE_BACKOFF_MAX_S = 1.0
NET_HANDSHAKE_TIMEOUT_S = 10.0

#: Anti-entropy resync cadence (``NetMailbox.pump``): every interval,
#: each endpoint re-publishes its own full blocked map to every peer —
#: UDP loss (and a healed partition, where neither side ever died, so
#: no HELLO fires) is repaired within ONE interval plus delivery.
#: 0.5 s is two orders of magnitude under the 10 s default block TTL
#: (a healed partition re-converges while the verdicts still matter)
#: and the map is TTL-bounded, so the re-publish is a handful of
#: wires, not a flood.
NET_RESYNC_INTERVAL_S = 0.5

#: Supervisor federation beacon cadence + death bound
#: (``cluster/transport.py::HostBeacon``): each host's supervisor
#: beacons its liveness every interval; a peer host silent past the
#: timeout is DEAD — its IP span is announced and fleet health folds
#: FAILED.  The 1 s / 5 s pair mirrors the intra-host heartbeat
#: discipline (SUPERVISOR_HEARTBEAT_TIMEOUT_S): 5 missed beacons is
#: far past any GC/throttle pause yet inside one operator glance.
NET_BEACON_INTERVAL_S = 1.0
NET_HOST_TIMEOUT_S = 5.0

#: Crash-loop sliding window (``ClusterSupervisor``): only deaths
#: within this window count against ``max_restarts`` — a rank that
#: served cleanly for an hour and then crashed is a fresh incident,
#: not the tail of last hour's crash loop.  60 s is >> the backoff
#: ladder's total span (0.1+0.2+...+5 s), so a genuine crash loop
#: cannot out-wait the window between respawns.
RESTART_WINDOW_S = 60.0

# -- predictive dispatch governor (ISSUE 18: engine/predict.py) -------------

#: Arrival-histogram bin width for the burst period estimator.  The
#: pulse regimes the SLO engine exists for (traffic.py pulse-wave
#: specs, the PR 11 A/B corpus) have periods of a few batcher
#: deadlines — single-digit ms — so 0.25 ms gives ~15-30 bins/period:
#: enough autocorrelation resolution to place the period within ~2 %
#: while keeping a full estimator pass (one FFT-free O(bins·lags)
#: numpy correlation over the window) in the tens of µs, invisible at
#: the PREDICT_REESTIMATE_S cadence.
PREDICT_BIN_S = 0.25e-3

#: Estimator observation window.  At the shortest supported period
#: (2x the bin, Nyquist) this holds hundreds of cycles; at the pulse
#: corpus's 7.5 ms it holds ~40 — both sides of PREDICT_MIN_PERIODS
#: with margin — while bounding predictor memory and keeping the
#: estimate tracking regime shifts within a window, not a serve.
PREDICT_WINDOW_S = 0.3

#: Confidence gate floor: the normalized autocorrelation peak
#: (ac[lag]/ac[0]) a forecast must reach before ANY actuation.  Noise
#: over a steady process autocorrelates near 0; a clean pulse wave
#: scores > 0.7 within a handful of periods.  0.5 splits those modes
#: with margin on both sides; below it the governor is quiescent and
#: the engine is bit-identical to the reactive PR 11 policy.
PREDICT_CONF_MIN = 0.5

#: Confidence exit fraction (Schmitt-trigger hysteresis): once a
#: forecast is LOCKED (an estimate reached PREDICT_CONF_MIN), tracking
#: estimates keep it alive down to ``conf_min * this``.  The engine's
#: own observation jitter — burst arrivals coalesce into whatever poll
#: the dispatch loop was free to make — leaves a real pulse wave's
#: measured confidence hovering AROUND the entry gate (measured
#: 0.35-0.70 on the r22 pulse corpus), so a single threshold flaps the
#: forecast at the re-estimate cadence and most bursts ride the
#: reactive point anyway.  0.6 puts the exit at 0.30: above a full
#: window of Poisson noise (measured ~0.06-0.10, so a regime change
#: still drops the lock within one re-estimate) and below the pulse
#: wave's worst tracking estimate.  Entry — and therefore EVERY
#: quiescent guarantee — still requires the full PREDICT_CONF_MIN.
PREDICT_CONF_EXIT_FRAC = 0.6

#: Histogram box-smooth width (bins) applied before the period
#: search.  The dispatch loop observes arrivals at POLL times, so a
#: burst lands as 1-3 clumps jittered by up to a dispatch+reap pass
#: (~1-1.5 ms on the pulse corpus — about this many bins); raw per-bin
#: autocorrelation decorrelates under that jitter while the smoothed
#: series keeps the period peak.  Costs period resolution at the
#: short end: the estimator's lag floor is 2x this (1.5 ms minimum
#: detectable period), far under any burst process the batcher's
#: own deadline wouldn't already absorb.  1 disables.
PREDICT_SMOOTH_BINS = 6

#: Minimum whole periods the window must span at the estimated period
#: before the estimate is eligible at all — an autocorr peak measured
#: over fewer cycles is curve-fitting, not evidence.
PREDICT_MIN_PERIODS = 4

#: Re-estimation cadence: the estimator pass runs on the dispatch
#: thread (engine ``_reap_ready``), so it is throttled like the gossip
#: tick.  50 ms re-locks phase within ~7 periods of the fastest pulse
#: the bin width resolves while costing < 0.1 % of the thread.
PREDICT_REESTIMATE_S = 0.05

#: Onset tolerance: arrivals within this of a predicted burst onset
#: count the pre-warm as a HIT; an onset passing by more than this
#: with no arrivals is a MISS (forecast expired, governor falls back
#: to reactive until re-confirmed).  2 bins — the phase quantization
#: of the estimator itself.
PREDICT_ONSET_TOL_S = 2 * PREDICT_BIN_S

#: Pre-warm lead margin added to the predicted rung's step-time EWMA:
#: the pre-warm dispatch must RETIRE (and refresh the rung's EWMA)
#: before the burst lands, so it is issued ewma+margin ahead of the
#: predicted onset.  One bin absorbs the estimator's phase error.
PREDICT_PREWARM_MARGIN_S = PREDICT_BIN_S

#: Budget-pressure shedding threshold: when the oldest staged work's
#: remaining SLO headroom fraction drops under this, the engine defers
#: gossip anti-entropy/report ticks (never verdict publish).  0.25
#: means shedding starts while there is still time to matter — a
#: threshold at 0 would shed only after the budget is already lost.
PREDICT_SHED_HEADROOM = 0.25

#: Under pressure the gossip merge tick and the net resync cadence
#: stretch by this factor — anti-entropy work drops to 1/4 rate, it
#: does not stop (convergence bounds scale by the same factor,
#: staying far inside the 10 s block TTL).
SHED_TICK_STRETCH = 4

#: Consecutive-deferral cap: after this many back-to-back deferred
#: resyncs the next one runs regardless of pressure — a persistently
#: squeezed engine must still heal partitions; shedding bounds the
#: RATE of anti-entropy work, never its eventual occurrence.
SHED_MAX_DEFER = 8

# -- elastic fleet (ISSUE 16) ----------------------------------------------

#: Autoscaler decision cadence (``ClusterSupervisor.run --elastic``):
#: the supervisor samples the signal vector (ring backlog, record-rate
#: skew, last aggregate's p99 / tx_drop / watchdog trips) once per
#: tick.  2 s sits between the 0.2 s poll (too noisy — one dispatch
#: burst would read as load) and the report cadence (too slow — a
#: backlog grows by millions of records per minute at line rate).
ELASTIC_TICK_S = 2.0

#: Hysteresis: a grow/shrink/rebalance signal must hold for this many
#: CONSECUTIVE ticks before the policy emits a plan.  3 ticks x 2 s
#: rides out a single checkpoint stall or jit recompile (both < 5 s
#: here) without deferring a genuine ramp for more than ~6 s.
ELASTIC_HYSTERESIS_TICKS = 3

#: Cooldown after any EXECUTED plan: the fleet needs one full
#: handoff + report cycle to show the plan's effect; re-deciding
#: before that double-provisions on the same backlog spike (the
#: classic autoscaler oscillation).  Decisions suppressed by the
#: cooldown are counted and logged, not silently dropped.
ELASTIC_COOLDOWN_S = 10.0

#: Grow when the mean per-live-engine ring backlog exceeds this many
#: records (sustained, see hysteresis).  One dispatch batch is 256-2k
#: records; 8k backlog is several seconds of drain at smoke-scale
#: rates — real pressure, not jitter.
ELASTIC_GROW_BACKLOG = 8192

#: Shrink when every live engine's backlog stays under this (and
#: n_live > min).  64 records is sub-batch — effectively idle.
ELASTIC_SHRINK_BACKLOG = 64

#: Rebalance (move half the hottest rank's span to the coldest) when
#: the max/mean record-rate skew across live ranks exceeds this.
#: 2.0 means one rank does double the fleet average — past hash
#: jitter, into hot-span territory.
ELASTIC_SKEW_RATIO = 2.0

#: Donor-side handoff ship timeout (``rebalance.ship_rows``): a full
#: mailbox means the recipient stopped draining; past this the
#: handoff aborts (fence clears, donor keeps the span) rather than
#: wedging the fleet behind one dead recipient.
HANDOFF_SHIP_TIMEOUT_S = 30.0

#: Supervisor-side bound on a whole handoff (fence stamp -> all acks).
#: Past this the supervisor aborts and clears the fence: the span was
#: never unserved (donor kept it), so the safe exit is always "undo".
HANDOFF_TIMEOUT_S = 60.0

# -- liveness bounds (ISSUE 19: fsx live) -----------------------------------
#
# Every bound below is REFERENCED from the PROGRESS registry
# (``flowsentryx_tpu/live/registry.py``): the liveness checker proves
# the obligation within the bound and the runtime enforces the same
# number, so a retune here re-proves (or breaks) the model in the same
# verify run.  Previously these were call-site literals the checker
# could not see.

#: Engine-exit gossip quiesce bound (``cluster/runner.py::_serve`` —
#: previously a hard-coded ``spec.get("gossip_quiesce_s", 2.0)``
#: default).  Quiesce returns early after 3 consecutive idle ticks
#: (idle plane measures < 50 ms total at the 5 ms merge cadence);
#: 2 s is therefore pure deadline headroom: ~400 merge intervals for a
#: backlogged plane to drain its rx mailboxes and still two orders of
#: magnitude under the supervisor's drain budget below.
GOSSIP_QUIESCE_S = 2.0

#: Cross-host handoff stream bound (``rebalance.NetHandoff`` — was a
#: hard-coded 10.0 on both ``send_stream`` and ``recv_stream``).  A
#: healthy same-rack stream moves a full span in tens of ms (slot
#: ship + ack RTT per window); 10 s is the handshake/beacon discipline
#: (NET_HANDSHAKE_TIMEOUT_S) — past it the peer host is somebody
#: else's incident and the donor keeps the span, mirroring the shm
#: path's HANDOFF_SHIP_TIMEOUT_S abort posture.
NET_HANDOFF_TIMEOUT_S = 10.0

#: Supervisor stop-drain budget (``ClusterSupervisor.run`` — was a
#: hard-coded ``drain_timeout_s=60.0`` default): after a stop request
#: every rank gets this long to finish its chunk, quiesce gossip
#: (GOSSIP_QUIESCE_S) and checkpoint before being declared wedged.
#: Matches HANDOFF_TIMEOUT_S — the slowest legitimate thing a rank
#: can be mid-flight on at stop time is a handoff.
SUPERVISOR_DRAIN_TIMEOUT_S = 60.0

#: Supervisor close/join bound per child (``ClusterSupervisor.close``
#: — was a hard-coded ``timeout_s=10.0``): SIGTERM -> join this long
#: -> SIGKILL.  10 s covers a worst-case checkpoint flush (tier-1
#: measures < 1 s at smoke scale) without letting a wedged child
#: stall operator shutdown past one glance.
SUPERVISOR_CLOSE_TIMEOUT_S = 10.0
