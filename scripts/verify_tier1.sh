#!/usr/bin/env bash
# Tier-1 verification gate — the EXACT invocation from ROADMAP.md, so
# the builder, CI, and any reviewer run the same thing.  Keep this in
# lockstep with the "Tier-1 verify" line in ROADMAP.md; if they ever
# disagree, ROADMAP.md wins and this file is the bug.
#
# Usage: scripts/verify_tier1.sh                (from anywhere)
#        scripts/verify_tier1.sh --sanitizers   (ALSO run the opt-in
#            C-plane sanitizer stage first: the daemon's TSAN shm-ring
#            torture plus ASan/UBSan builds+runs of kern/host_test,
#            kern/prop_driver and an fsxd --sim smoke)
# Always-on pre-stages (each failure exits early, before pytest):
#   * scripts/lint.py — syntax, unused-import, local-import,
#     traced-region-purity and sync_contracts gates
#   * fsx sync        — host thread contracts + bounded-interleaving
#     model checks (arena bound tightness re-proved per run); writes
#     artifacts/SYNC_r13.json
#   * fsx crash       — exhaustive crash-consistency model check of
#     the durable-state protocols (planted regressions must be
#     caught); writes artifacts/CRASH_r21.json
#   * fsx audit       — static dtype/donation/transfer/retrace/
#     collective/in-place contracts over every staged step variant (8
#     virtual CPU devices so the sharded variant stages too); writes
#     the machine-readable artifacts/AUDIT_r08.json byte-budget
#     artifact
#   * fsx ranges      — whole-pipeline integer value-range proof over
#     the same staged variants (+ the WRAP_OK staleness audit, the
#     planted negative controls and the BPF<->jaxpr containment
#     bridge); writes artifacts/RANGES_r16.json
# Exit code: pytest's (a pre-stage failure exits early).  Prints
# DOTS_PASSED=<n> as a tamper-evident passed-test count derived from
# the progress dots, not the summary.
set -u
cd "$(dirname "$0")/.."

if [ "${1:-}" = "--sanitizers" ]; then
    shift
    echo "== sanitizers: daemon TSAN torture (shm-ring protocol) =="
    make -C daemon tsan || exit 1

    SAN="-fsanitize=address,undefined -fno-sanitize-recover=all -O1 -g"
    export ASAN_OPTIONS=detect_leaks=1

    echo "== sanitizers: kern/host_test under ASan+UBSan =="
    mkdir -p kern/build
    gcc $SAN -Wall -Wextra -Werror -DFSX_HOST_BUILD -Ikern \
        kern/host_test.c -o kern/build/host_test_asan -lm || exit 1
    kern/build/host_test_asan || exit 1

    echo "== sanitizers: kern/prop_driver under ASan+UBSan =="
    gcc $SAN -Wall -Wextra -Werror -DFSX_HOST_BUILD -Ikern \
        kern/prop_driver.c -o kern/build/prop_driver_asan || exit 1
    # tiny smoke trace: fixed-window limiter, 3 aggregated ticks
    printf '0 100 1000000 1000000000 200 200 0 0\n3\n1 100 0\n200 20000 500000000\n1 100 2000000000\n' \
        | kern/build/prop_driver_asan > /dev/null || exit 1

    echo "== sanitizers: fsxd --sim smoke under ASan+UBSan =="
    mkdir -p daemon/build
    g++ $SAN -std=c++17 -Wall -Wextra -Werror -Ikern \
        daemon/fsxd.cpp -o daemon/build/fsxd_asan -lpthread || exit 1
    daemon/build/fsxd_asan --sim --duration 2 --rate 2e5 \
        --feature-ring /tmp/fsx_t1_asan_ring \
        --verdict-ring /tmp/fsx_t1_asan_verdicts > /dev/null || exit 1
    rm -f /tmp/fsx_t1_asan_ring /tmp/fsx_t1_asan_verdicts
    echo "== sanitizers: all clean =="
fi

echo "== lint gate (scripts/lint.py) =="
python scripts/lint.py || exit 1

echo "== fsx sync: host thread contracts + interleaving model checks =="
# The host-plane leg of the static suite (docs/CONCURRENCY.md):
# re-proves every registered thread contract over the real source,
# runs the bounded-interleaving model checker on the real protocol
# objects (SinkChannel crash atomicity, SealedBatchQueue wraparound),
# and re-proves the arena reuse bound TIGHT — all interleavings pass
# at depth+ring+1 slots, a staged-copy-overwrite counterexample is
# emitted one below.  Jax-free; writes the machine-readable artifact.
python -m flowsentryx_tpu.cli sync --out artifacts/SYNC_r13.json \
    || exit 1

echo "== fsx crash: crash-consistency model check of the durable protocols =="
# The fifth static leg (docs/CRASH.md): drives the REAL checkpoint-
# rotate, layout-flip, fenced-handoff and dead-span-adoption code over
# a simulated POSIX fs, crashing at every atomic step (power loss +
# each party's death), reconstructing every legal post-crash durable
# state, running real recovery, and asserting the ten-invariant
# catalog (row conservation, single ownership, generation
# monotonicity, checkpoint fallback, ...).  Four planted regressions
# must each be CAUGHT with a printed crash schedule and their
# unplanted controls must be clean.  Jax-free; --quick trims tear
# variants per un-synced file (full fan-out stays on `fsx crash`).
python -m flowsentryx_tpu.cli crash --quick --quiet-plants \
    --out artifacts/CRASH_r21.json || exit 1

echo "== fsx live: liveness + progress model check of the blocking protocols =="
# The sixth static leg (docs/LIVENESS.md): state-graph search over the
# REAL protocol objects proving deadlock-freedom (every park names its
# wake edge), livelock-freedom under weak fairness and bounded
# starvation — the SinkChannel drain, the fenced handoff with a stamp
# dropped at every edge (a lost fence-lift must recover, not wedge),
# autoscale flap-freedom, shed deferral bounds, quiesce termination —
# plus the PROGRESS registry audit closing every blocking loop over
# its declared wake source.  Four planted regressions (deleted notify,
# dropped fence-lift, removed streak cap, zeroed cooldown) must each
# be CAUGHT with a printed schedule from clean controls.  Jax-free;
# --quick trims the handoff drop-edge fan-out (full set on `fsx live`).
python -m flowsentryx_tpu.cli live --quick --quiet-plants \
    --out artifacts/LIVE_r23.json || exit 1

echo "== fsx live: jax-free import path =="
# The liveness leg rides the supervisor's sub-second import path: the
# whole flowsentryx_tpu.live package plus the cluster plane it drives
# must import without pulling jax (the same contract the
# cluster_jax_free lint stage proves for cluster/ module levels).
python - <<'PY' || exit 1
import sys, time
t0 = time.perf_counter()
import flowsentryx_tpu.live.checker  # noqa: F401
import flowsentryx_tpu.cluster.supervisor  # noqa: F401
dt = time.perf_counter() - t0
assert "jax" not in sys.modules, "fsx live import path pulled jax"
assert dt < 1.0, f"cluster+live import took {dt:.2f}s (budget 1.0s)"
print(f"live+cluster import: {dt*1000:.0f} ms, jax-free")
PY

echo "== fsx audit: static step-graph contracts (docs/AUDIT.md) =="
env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m flowsentryx_tpu.cli audit --mesh 8 --mega 2 \
    --out artifacts/AUDIT_r08.json || exit 1

echo "== fsx audit: eviction-epoch step variants (quick shapes) =="
# The in-step aging sweep changes every staged graph (a rolling
# gather + victim-only-scatter window at step start), so the
# eviction-enabled family is audited as its own artifact set: donation
# through the sweep, the 528 B wire pin, and the unchanged collective
# census (the eviction count rides the existing stats psum) are
# re-proved each run.
env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m flowsentryx_tpu.cli audit --mesh 8 --mega 2 \
    --evict-ttl 30 --quick \
    --out artifacts/AUDIT_evict_r12.json || exit 1

echo "== fsx ranges: whole-pipeline integer value-range proof =="
# The fourth static leg (docs/RANGES.md): interval abstract
# interpretation over every staged variant — singles, sharded, every
# rung of the adaptive mega ladder, the eviction-epoch family (--evict-ttl stages the rolling-window
# batches-counter arithmetic) — proving no equation can silently wrap
# modulo the audited WRAP_OK registry (staleness-checked per run).
# Also re-proves the planted negative controls fire and the BPF<->jaxpr
# interval-containment bridge on the shipped distill artifact.
env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m flowsentryx_tpu.cli ranges --mesh 8 --mega auto \
    --evict-ttl 30 --quick \
    --out artifacts/RANGES_r16.json || exit 1

echo "== table-scale smoke: eviction + occupancy bound + shard-local rows =="
# Bounded CPU smoke of the production flow table: re-proves that the
# eviction epoch fires under churn, occupancy stays bounded at the
# live-flow count, every occupied key is resident on its owner shard,
# and a mesh=4 checkpoint reshards losslessly into mesh=8 — rewriting
# the "smoke" section of artifacts/TABLESCALE_r12.json (the paced
# 4M-row drain/ladder evidence in the same file is preserved).
env JAX_PLATFORMS=cpu python scripts/table_scale_smoke.py || exit 1

echo "== fsx distill: kernel-tier compile + static check + JAX<->BPF parity =="
# Compiles the shipped artifact into the kernel tier, statically
# verifies both --ml program variants, and proves bit-exact band
# parity by EXECUTING the emitted scorer bytecode over a 10k-vector
# corpus (docs/DISTILL.md); rewrites artifacts/DISTILL_r10.json.
env JAX_PLATFORMS=cpu python -m flowsentryx_tpu.cli distill \
    artifacts/logreg_int8.npz --check --emulate \
    --report artifacts/DISTILL_r10.json || exit 1

echo "== dispatch smoke: single-copy staging + adaptive coalescing =="
# Bounded CPU smoke of the zero-copy dispatch pipeline: proves
# host copies/batch == 1.0 (shm slot view -> arena -> device) and that
# adaptive grouping fires, re-writing the "smoke" section of
# artifacts/DISPATCH_r09.json (the paced PR-4 comparison evidence in
# the same file is preserved).
env JAX_PLATFORMS=cpu python scripts/dispatch_smoke.py || exit 1

echo "== cluster smoke: 2-engine drain + gossip + kill/restart =="
# Bounded CPU smoke of the coordinator-less scale-out (docs/
# CLUSTER.md): two supervised engine processes each drain their own
# prefilled ring shard losslessly (per-rank counts), their blacklists
# gossip-converge to byte-identical digests under the shared t0
# epoch, and one SIGKILL'd engine is restarted from its checkpoint
# while the survivor keeps serving — re-writing the "smoke" section
# of artifacts/CLUSTER_r14.json (the paced 2-engine-vs-single
# scaling evidence in the same file is preserved).
env JAX_PLATFORMS=cpu python scripts/cluster_smoke.py || exit 1

echo "== rebalance smoke: live shard handoff + autoscale grow + mid-ship kill =="
# The elastic-fleet gate (docs/CLUSTER.md §elastic): a 3-rank-
# provisioned fleet (2 live) moves shard 2 between engines UNDER LIVE
# LOAD through the full fence->ship->stage->flip protocol with exact
# row conservation (donor rows_shipped == recipient rows_adopted,
# CRC-sealed byte identity) and nonzero survivor throughput; an
# ElasticPolicy grows the fleet 2->3 off the real ring-cursor backlog
# signal (hysteresis-confirmed, decision logged with its signal
# vector) and the new rank serves its moved span; a donor SIGKILLed
# mid-ship aborts cleanly (nothing moves), respawns gen-1 from its
# checkpoint, and the RETRY conserves exactly — rewriting
# artifacts/REBALANCE_r20.json each run.
env JAX_PLATFORMS=cpu python scripts/rebalance_smoke.py || exit 1

echo "== net smoke: multi-host gossip transport on loopback =="
# The network leg of the gossip plane (docs/CLUSTER.md §multi-host):
# two simulated hosts with epochs 250 s apart drain verdict streams
# losslessly over real UDP (digests converge byte-identically on the
# canonical rebased form; a sampled absolute expiry survives the
# rebase within f32 quantization), a partition is injected and healed
# (anti-entropy re-converges within a bounded tick count, pinned),
# a dead peer host is detected by the federation beacons, and the
# u64 sequence split crosses the 2^32 word boundary intact on BOTH
# transports.  ~2 s; rewrites artifacts/NET_r19.json.  (The transport
# itself is jax-free; the GossipPlane merge path pulls the writeback
# decoder's jax import chain, hence the cpu pin.)
env JAX_PLATFORMS=cpu python scripts/net_smoke.py || exit 1

echo "== chaos smoke: seeded fault-injection campaign + planted regressions =="
# The robustness gate (docs/CHAOS.md): the seeded quick campaign over
# the REAL stack — supervised rank kill/respawn, crash-loop park with
# backoff, corrupt/truncated checkpoint refusal + loud .prev fallback
# on a live engine, shm slot corruption (bad magic/seq gap) skipped
# and counted, poisoned-batch quarantine (counted + spooled), gossip
# stall/flood drop accounting, clock jumps, the wedged-sink watchdog
# trip, and the six network faults over real loopback UDP (partition,
# heal, reorder, duplication, loss burst, lying epoch) — every
# invariant green AND all five planted regressions (split-atomicity,
# CRC skipped, backoff removed, dup-suppression removed, epoch-rebase
# skipped) caught by their named invariants.  Rewrites
# artifacts/CHAOS_r17.json each run.
env JAX_PLATFORMS=cpu python scripts/chaos_smoke.py || exit 1

echo "== latency smoke: seal->verdict plane + SLO degradation =="
# Bounded CPU smoke of the per-record latency plane (docs/ENGINE.md
# §latency): re-proves the seal/launch/sink stamps are monotone
# (negatives == 0), the HDR percentile chain is finite and ordered
# with every record accounted, --slo-us keeps stats/blacklist
# byte-identical while provably degrading the ladder under a breached
# budget, and warm() seeds the per-rung EWMA table — re-writing the
# "smoke" section of artifacts/LATENCY_r15.json (the paced pulse-wave
# A/B evidence in the same file is preserved).
env JAX_PLATFORMS=cpu python scripts/latency_smoke.py || exit 1

echo "== predict smoke: burst forecast + pre-warm + pressure shedding =="
# Bounded CPU smoke of the predictive dispatch governor (docs/ENGINE.md
# §prediction): re-proves the forecaster goes confident on the pulse
# schedule, a pre-warm was issued AND hit, the forecast-end early
# flush fired, gossip anti-entropy was deferred under measured budget
# pressure (and ONLY then — the quiescent high-budget control leg
# actuates nothing and defers nothing), the latency plane stays sound
# (negatives == 0), and the fsx sync registry is clean — re-writing
# the "smoke" section of artifacts/PREDICT_r22.json (the paced A/B
# evidence in the same file is preserved).
env JAX_PLATFORMS=cpu python scripts/predict_smoke.py || exit 1

echo "== boot smoke: persistent compile cache + tiered warm + GROW spare =="
# Bounded CPU smoke of boot-to-serving (docs/ENGINE.md §boot), each leg
# a FRESH subprocess: re-proves a cold boot stores the full ladder, a
# cached boot is all-cache-hit and reaches SERVING >= 3x faster, the
# tiered background fill completes with nothing pending, a GROW spare
# booting from a prewarm_main-filled cache recompiles NOTHING, and all
# legs serve byte-identical verdicts (stats + blacklist digests equal)
# — re-writing the "smoke" section of artifacts/BOOT_r24.json (the
# cold-vs-cached A/B evidence in the same file is preserved).
env JAX_PLATFORMS=cpu python scripts/boot_smoke.py || exit 1

set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
