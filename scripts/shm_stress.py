"""Shm-transport stress: push daemon → shm ring → batcher → engine to
the Mpps regime.

A mitigation scenario's records/s is SCENARIO-bound — once a source
is blacklisted the kernel stops emitting records for it, so the stream
converges to a trickle by design — and says nothing about the
transport's ceiling.  This harness measures that ceiling, in two
phases against a free-running `fsxd --sim` producer (no pacing beyond
ring backpressure; the C++ generator is the same record statistics the
daemon integration tests use):

* **drain** — ShmRingSource.poll in a bare loop, no engine: the shm
  ring + numpy-copy ceiling of the Python consumer side.
* **engine** — the real Engine (micro-batcher → fused step → verdict
  writeback to the verdict ring) consuming the same stream.  Runs on
  the CPU (JAX_PLATFORMS=cpu) by design: the artifact measures the
  HOST pipeline, and never contends with a chip-using command.

Traffic is benign-only by default (attack_fraction 0) so blacklist
suppression cannot throttle the stream mid-measurement; a mixed run
exercises the verdict path too and reports suppression separately.

Writes artifacts/SHMSTRESS_inline.json.
Reference seam: the rebuilt analog of AmruthSD/FlowSentryX's intended
ringbuf → userspace ML hand-off (src/fsx_load.py:5-12), which the
reference never drove at rate.

**Sharded mode** (``--shards N``): measures the sharded parallel
host-ingest subsystem (flowsentryx_tpu/ingest/) instead — ``fsxd
--shards N`` fans records out over N ring shards by IP hash, N drain
workers decode + quantize + seal in parallel, and this process plays
the engine's host side (``ShardedIngest.poll_batches``: one queue-slot
copy per sealed batch).  Alongside it, the matching INLINE rows — the
full single-threaded engine and the bare drain+seal stage — on the same
host, so the artifact records the host-ingest ceiling shift the
subsystem buys.  Writes ``artifacts/SHMSTRESS_sharded_r06.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

# CPU-only by design (module docstring).  jax itself is imported only
# by the phases that run it: the sharded phases spawn drain workers
# whose spawn-context boot re-imports THIS module, and a module-level
# jax import would tax every worker with the multi-second jax boot for
# code only the parent runs.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from flowsentryx_tpu.core import schema  # noqa: E402
from flowsentryx_tpu.core.config import (  # noqa: E402
    BatchConfig, FsxConfig, ModelConfig, TableConfig,
)

FSXD = REPO / "daemon" / "build" / "fsxd"
DUR = float(os.environ.get("FSX_STRESS_DUR", "20"))


def start_daemon(fring: str, vring: str, duration: float,
                 attack_fraction: float, rate_pps: float,
                 ring_capacity: int = 1 << 17,
                 pace: bool = False, shards: int = 1,
                 boost: bool = False) -> subprocess.Popen:
    # Benign pool scales with the SIM clock rate so per-source pps stays
    # ~250 (benign-plausible): at a fixed 1024-source pool a 1e6-pps sim
    # clock makes every benign source timestamp out to ~1 kpps, which
    # the model/limiters rightly treat as attack traffic — a generator
    # artifact, not a benign-FPR signal.
    n_benign = max(1024, int(rate_pps * (1.0 - attack_fraction) / 250))
    cmd = [str(FSXD), "--sim",
           "--duration", str(duration),
           "--rate", str(rate_pps),
           "--attack-fraction", str(attack_fraction),
           "--attack-ips", "64",
           "--benign-ips", str(n_benign),
           "--feature-ring", fring, "--verdict-ring", vring,
           "--ring-capacity", str(ring_capacity),
           "--seed", "7"]
    if shards > 1:
        cmd += ["--shards", str(shards)]
    if pace:
        cmd.append("--pace")
    # boost: a paced producer stands in for line-rate hardware — a NIC
    # does not slow down because the host is busy.  On an oversubscribed
    # box the fair scheduler starves it below its configured rate, which
    # understates the offered load; raising its priority (root only)
    # keeps the offer honest and pushes ALL backpressure onto the
    # consumers under measurement, the conservative direction.
    pre = None
    if boost and hasattr(os, "nice") and os.geteuid() == 0:
        def pre():
            os.nice(-10)
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True,
                            preexec_fn=pre)


def daemon_result(proc: subprocess.Popen) -> dict:
    out, _ = proc.communicate(timeout=30)
    for line in out.splitlines()[::-1]:
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    return {}


def phase_drain(duration: float) -> dict:
    """Bare ring-drain ceiling: no batcher, no step."""
    from flowsentryx_tpu.engine.shm import ShmRingSource

    with tempfile.TemporaryDirectory() as td:
        fring, vring = f"{td}/fring", f"{td}/vring"
        proc = start_daemon(fring, vring, duration + 1.0,
                            attack_fraction=0.0, rate_pps=1e7)
        try:
            src = ShmRingSource(fring)
            n = 0
            polls = 0
            t0 = time.perf_counter()
            deadline = t0 + duration
            while time.perf_counter() < deadline:
                chunk = src.poll(8192)
                polls += 1
                if len(chunk):
                    n += len(chunk)
                else:
                    time.sleep(0.0002)
            wall = time.perf_counter() - t0
        finally:
            proc.terminate()
        d = daemon_result(proc)
        return {
            "records_drained": n,
            "wall_s": round(wall, 3),
            "drain_mpps": round(n / wall / 1e6, 4),
            "polls": polls,
            "daemon": d,
        }


class _IdleSource:
    """Placeholder source so engines can be built (and their step
    compiled) before the daemon's rings exist."""

    def poll(self, max_records: int):
        import numpy as np

        return np.zeros(0, schema.FLOW_RECORD_DTYPE)

    def exhausted(self) -> bool:
        return True


def get_engine(max_batch: int, mega_n: int = 0, _cache: dict = {}):
    """Build + WARM a cached engine for ``max_batch``.

    The pristine table/stats checkpoint is taken first; ``Engine.warm``
    then triggers the step's XLA compile OUTSIDE any measured window
    (the first sweep row would otherwise eat multi-second compile while
    the daemon floods the ring), and the checkpoint is restored so
    every row starts from identical state."""
    got = _cache.get((max_batch, mega_n))
    if got is not None:
        return got
    from flowsentryx_tpu.engine.engine import Engine
    from flowsentryx_tpu.engine.writeback import NullSink

    cfg = FsxConfig(
        table=TableConfig(capacity=1 << 20),
        batch=BatchConfig(max_batch=max_batch, deadline_us=10_000),
        model=ModelConfig(vote_k=4, vote_m=2),
    )
    # readback_depth counts BATCHES: a mega engine needs 2 groups'
    # worth so one group can fill/dispatch while the previous runs.
    eng = Engine(cfg, _IdleSource(), NullSink(),
                 readback_depth=max(8, 2 * mega_n), mega_n=mega_n)
    ckpt = eng.checkpoint(
        tempfile.mktemp(prefix=f"fsx_stress_ckpt_{max_batch}_"))
    eng.warm()
    eng.restore(ckpt)
    _cache[(max_batch, mega_n)] = (eng, ckpt)
    return eng, ckpt


def phase_engine(duration: float, attack_fraction: float,
                 max_batch: int, label: str,
                 rate_pps: float = 1e7, pace: bool = False,
                 mega_n: int = 0) -> dict:
    """Real pipeline: ring → MicroBatcher → fused step → verdict ring.

    ``pace=True`` offers records at ``rate_pps`` in real time (the
    achieved/offered view — a real data plane delivers at line rate);
    ``pace=False`` free-runs against ring backpressure (the ceiling
    view, generator and engine contending for the same host).  Engines
    are cached per batch size (reset_stream between runs) so each
    compile is paid once, as a long-lived server would — and each row
    RESTORES the pristine table/clock checkpoint taken at construction:
    every fsxd restart rewinds simulated time to ~1 s, so carrying the
    previous row's table (last-seen stamps ahead of the new stream)
    would feed the IAT/vote logic negative time deltas.  A 10 ms flush
    deadline keeps batches full at low offered loads (this harness
    measures throughput; latency artifacts are DISPATCH/BENCH's job).
    """
    from flowsentryx_tpu.engine.shm import ShmRingSource, ShmVerdictSink

    from flowsentryx_tpu.engine.writeback import NullSink

    eng, ckpt = get_engine(max_batch, mega_n)
    # Reset + restore BEFORE the daemon exists: restoring the 1M-row
    # table costs seconds on this host, and a daemon already producing
    # into a 131072-slot ring would overflow it during that window —
    # startup loss masquerading as steady-state loss.  The live
    # source/sink swap in afterwards without touching engine state.
    eng.reset_stream(_IdleSource(), NullSink())
    eng.restore(ckpt)
    with tempfile.TemporaryDirectory() as td:
        fring, vring = f"{td}/fring", f"{td}/vring"
        proc = start_daemon(fring, vring, duration + 2.0,
                            attack_fraction=attack_fraction,
                            rate_pps=rate_pps, pace=pace)
        try:
            src = ShmRingSource(fring)
            sink = ShmVerdictSink(vring)
            eng.source = src
            eng.sink = sink
            t0 = time.perf_counter()
            rep = eng.run(max_seconds=duration)
            wall = time.perf_counter() - t0
            ring_left = src.ring.readable()
        finally:
            proc.terminate()
        d = daemon_result(proc)
        offered = d.get("produced", 0) - d.get("suppressed", 0)
        # NOTE on daemon counters: the daemon outlives the engine's
        # measurement window (duration+2 plus terminate latency), so its
        # dropped_ring_full is dominated by the post-run tail when the
        # engine keeps up — achieved/offered over the ENGINE's window is
        # the loss signal, not ring_drop.
        return {
            "label": label,
            "attack_fraction": attack_fraction,
            "max_batch": max_batch,
            "mega_n": mega_n,
            "paced": pace,
            "offered_mpps": (round(rate_pps / 1e6, 3) if pace
                             else round(offered / max(wall, 1e-9) / 1e6, 4)),
            "wire": eng.wire,
            "engine_records": rep.records,
            # rep.wall_s covers the serving loop + final reap and
            # EXCLUDES the end-of-report 1M-row table summary (~3 s on
            # this host), which the outer wall would misattribute as
            # serving time.
            "engine_wall_s": rep.wall_s,
            "outer_wall_s": round(wall, 3),
            "ring_readable_at_stop": int(ring_left),
            "engine_mpps": round(rep.records_per_s / 1e6, 4),
            "records_per_s": rep.records_per_s,
            "stages_ms": {k: {"p50": v["p50"], "p99": v["p99"]}
                          for k, v in rep.stages_ms.items()},
            "blocked_sources": rep.blocked_sources,
            "stats": rep.stats,
            "daemon": d,
        }


#: Seal size for the sharded rows (and their inline-host reference).
#: Two opposing terms pick it: per-batch overhead (queue-slot copy,
#: seal bookkeeping, dequeue wakeups) is the cost sharding cannot
#: parallelize away, and it amortizes out by ~4k records — so the 2048
#: the legacy engine rows use understates the subsystem — while LARGER
#: seals stretch the worker's drain cadence (a 16384-seal touches its
#: ring every ~19 ms at 0.85 Mpps/shard), so one scheduler desched on
#: an oversubscribed host eats the ring-depth headroom and shows up as
#: ring-full drops that are cadence artifacts, not subsystem capacity.
INGEST_BATCH = int(os.environ.get("FSX_STRESS_INGEST_BATCH", "4096"))


def phase_inline_host(duration: float, max_batch: int = INGEST_BATCH) -> dict:
    """The inline host-ingest stage in isolation: one thread draining
    the ring and sealing compact16 batches (drain → decode → quantize →
    seal), no device step.  This is exactly the per-record work the
    sharded subsystem moves into the drain workers, so sharded vs THIS
    row is the stage-level speedup and sharded vs the full inline
    engine is the system-level one."""
    from flowsentryx_tpu.core.config import BatchConfig as BC
    from flowsentryx_tpu.engine.batcher import MicroBatcher
    from flowsentryx_tpu.engine.shm import ShmRingSource

    import numpy as np

    schema.quantize_feat_minifloat(np.zeros(8, np.uint32))  # LUT build
    with tempfile.TemporaryDirectory() as td:
        fring, vring = f"{td}/fring", f"{td}/vring"
        proc = start_daemon(fring, vring, duration + 1.0,
                            attack_fraction=0.0, rate_pps=1e6)
        try:
            src = ShmRingSource(fring)
            b = None
            n = 0
            batches = 0
            t0 = time.perf_counter()
            deadline = t0 + duration
            while time.perf_counter() < deadline:
                chunk = src.poll(2 * max_batch)
                if not len(chunk):
                    time.sleep(0.0002)
                    continue
                if b is None:  # anchor t0 on the first record, as Engine does
                    b = MicroBatcher(
                        BC(max_batch=max_batch, deadline_us=10_000),
                        t0_ns=int(chunk["ts_ns"][0]), n_buffers=2,
                        wire=schema.WIRE_COMPACT16,
                        quant=dict(feat_mode="minifloat"))
                for _ in b.add(chunk):
                    b.pop_seal_time()
                    batches += 1
                n += len(chunk)
            wall = time.perf_counter() - t0
        finally:
            proc.terminate()
        daemon_result(proc)
        return {
            "label": f"inline_host_b{max_batch}",
            "records": n,
            "batches_sealed": batches,
            "wall_s": round(wall, 3),
            "mpps": round(n / wall / 1e6, 4),
        }


def phase_sharded(duration: float, n_workers: int, rate_pps: float,
                  pace: bool, max_batch: int = INGEST_BATCH,
                  label: str | None = None) -> dict:
    """Sharded host ingest, end to end minus the device: ``fsxd --shards
    N`` → N drain workers (decode + minifloat quantize + seal in
    parallel processes) → sealed-batch SPSC queues → this process
    dequeuing via ``ShardedIngest.poll_batches`` — the engine's actual
    host-side cost per batch (one queue-slot copy + seq/metrics
    bookkeeping).  The daemon waits (bounded) for its rings to drain
    before exiting, and the fleet drains queues on stop, so LOSSLESS is
    checkable: consumed == produced and no ring-full drops and no
    sequence gaps."""
    from flowsentryx_tpu.core.config import BatchConfig as BC
    from flowsentryx_tpu.ingest import ShardedIngest

    with tempfile.TemporaryDirectory() as td:
        fring, vring = f"{td}/fring", f"{td}/vring"
        # Fleet first, producer second: worker boot (spawn + numpy
        # import) must not overlap the measurement window, or startup
        # ring overflow masquerades as steady-state loss.  precompact
        # is passed explicitly because no ring exists to probe yet
        # (the sim daemon emits raw 48 B records).
        ing = ShardedIngest(fring, n_workers, queue_slots=32,
                            precompact=False)
        ing.start(BC(max_batch=max_batch, deadline_us=10_000),
                  schema.WIRE_COMPACT16, dict(feat_mode="minifloat"))
        ing.wait_ready()
        # 2^18-slot shards: a worker descheduled for ~100 ms on this
        # oversubscribed host must be absorbed by ring depth, not read
        # as steady-state loss.
        proc = start_daemon(fring, vring, duration,
                            attack_fraction=0.0, rate_pps=rate_pps,
                            pace=pace, shards=n_workers,
                            ring_capacity=1 << 18, boost=pace)
        records = 0
        batches = 0
        stopped = False
        try:
            t0 = time.perf_counter()
            while True:
                got = ing.poll_batches(16)
                for sb in got:
                    records += sb.n_records
                    batches += 1
                if not stopped and proc.poll() is not None:
                    ing.request_stop()  # daemon exited: drain the tail
                    stopped = True
                if stopped and ing.exhausted():
                    break
                if not got:
                    time.sleep(0.0002)
            wall = time.perf_counter() - t0
        finally:
            ing.close()
            if proc.poll() is None:
                proc.terminate()
        d = daemon_result(proc)
        stats = ing.ingest_stats()
        produced = d.get("produced", 0)
        ring_drops = d.get("dropped_ring_full", 0)
        seq_gaps = sum(w["seq_gaps"] for w in stats["workers"].values())
        return {
            "label": label or f"sharded_w{n_workers}"
                              f"{'_paced' if pace else '_freerun'}",
            "n_workers": n_workers,
            "max_batch": max_batch,
            "paced": pace,
            "offered_mpps": (round(rate_pps / 1e6, 3) if pace
                             else round(produced / max(wall, 1e-9) / 1e6, 4)),
            "records": records,
            "batches": batches,
            "wall_s": round(wall, 3),
            "mpps": round(records / wall / 1e6, 4),
            "lossless": bool(records == produced and ring_drops == 0
                             and seq_gaps == 0
                             and stats["dropped_emit_batches"] == 0
                             and not stats["dead_workers"]),
            "produced": produced,
            "dropped_ring_full": ring_drops,
            "seq_gaps": seq_gaps,
            "dropped_tail_batches": stats["dropped_tail_batches"],
            "dropped_emit_batches": stats["dropped_emit_batches"],
            "workers": stats["workers"],
            "daemon": d,
        }


def run_sharded_suite(n_workers: int, dur: float) -> dict:
    """The sharded-vs-inline evidence run (``--shards N``)."""
    out = {
        "round": 6,
        "purpose": ("sharded parallel host ingest (flowsentryx_tpu/"
                    "ingest/) vs the inline single-threaded path: N "
                    "drain workers seal in parallel and the engine "
                    "dequeues finished batches"),
        "host_cores": os.cpu_count(),
        "n_workers": n_workers,
        "ingest_batch": INGEST_BATCH,
        "duration_s_per_phase": dur,
        "wire": "compact16 (minifloat quantize in the seal stage — the "
                "default engine wire, and the stage the r5 bottleneck "
                "lived in)",
    }
    # Inline references first (engine row compiles jax; do it before
    # worker processes exist so nothing contends with the measurement).
    out["inline_engine"] = phase_engine(
        dur, 0.0, 2048, "inline_paced_1.0mpps", 1.0e6, pace=True)
    out["inline_host"] = phase_inline_host(dur)
    # The acceptance rows: paced ≥3 Mpps offered, lossless required.
    # A rate LADDER, not fixed-rate retries: the boosted producer does
    # not slow down for a busy host (that is the point — a NIC would
    # not either), so offering 3.4 to a box whose consumer ceiling sits
    # at 3.1 guarantees ring-full drops even though the box sustains
    # the 3.0 target fine; step the offer down toward the target and
    # keep the first lossless ≥3.0 row.  The container's CPU allocation
    # also swings with HOST load (cgroup cpu-shares) — same idiom as
    # bench.py's link-window retry — so the artifact carries every
    # attempt; a bad-window run measures the neighborhood, not the
    # subsystem.
    rows = []
    for attempt, rate in enumerate((3.4e6, 3.4e6, 3.2e6, 3.1e6, 3.05e6)):
        row = phase_sharded(dur, n_workers, rate, pace=True,
                            label=f"sharded_w{n_workers}_paced_"
                                  f"{rate / 1e6:g}mpps_try{attempt}")
        rows.append(row)
        if row["lossless"] and row["mpps"] >= 3.0:
            break
    rows.append(phase_sharded(dur, n_workers, 1e6, pace=False))
    # Cores-matched context row: on a box with fewer cores than the
    # requested shard count the w=N row measures oversubscription tax
    # on top of the subsystem; w=min(N, cores) shows the scaling shape
    # the same code gives when the fleet fits the host.
    cores = os.cpu_count() or 1
    if 1 < cores < n_workers:
        rows.append(phase_sharded(
            dur, cores, 3.4e6, pace=True,
            label=f"sharded_w{cores}_coresmatched_paced_3.4mpps"))
    out["sharded_rows"] = rows
    # Headline from the requested-shard-count rows only; the
    # cores-matched row is context, not the acceptance measurement.
    wn = [r for r in rows if r["n_workers"] == n_workers]
    best = max(wn, key=lambda r: r["mpps"])
    lossless = [r for r in wn if r["lossless"]]
    best_lossless = max(lossless, key=lambda r: r["mpps"]) if lossless else None
    out["headline"] = {
        "inline_engine_mpps": out["inline_engine"]["engine_mpps"],
        "inline_host_mpps": out["inline_host"]["mpps"],
        "sharded_mpps": best["mpps"],
        "sharded_lossless_mpps": (best_lossless["mpps"]
                                  if best_lossless else 0.0),
        "sharded_config": best["label"],
        "meets_3mpps_lossless": bool(best_lossless
                                     and best_lossless["mpps"] >= 3.0),
    }
    cm = [r for r in rows if r["n_workers"] != n_workers]
    if cm:
        out["headline"]["coresmatched_lossless_mpps"] = max(
            (r["mpps"] for r in cm if r["lossless"]), default=0.0)
    return out


def main() -> None:
    r = subprocess.run(["make", "-C", str(REPO / "daemon")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    shards = 0
    for a in sys.argv[1:]:
        if a.startswith("--shards"):
            shards = int(a.split("=", 1)[1] if "=" in a else
                         sys.argv[sys.argv.index(a) + 1])
    if shards:
        out = run_sharded_suite(shards, DUR)
        path = REPO / "artifacts" / "SHMSTRESS_sharded_r06.json"
        path.write_text(json.dumps(out, indent=1))
        print(json.dumps(out["headline"]))
        return

    out = {
        "round": 5,
        "purpose": ("shm ring -> batcher -> engine throughput ceiling of "
                    "the HOST pipeline (a mitigation scenario's "
                    "records/s is scenario-bound, not a transport limit)"),
        "engine_backend": "cpu (by design: a host-pipeline measurement)",
        "duration_s_per_phase": DUR,
        "drain_only": phase_drain(DUR),
    }
    rows = [
        phase_engine(DUR, 0.0, 2048, "paced_0.25mpps", 0.25e6, pace=True),
        phase_engine(DUR, 0.0, 2048, "paced_0.5mpps", 0.5e6, pace=True),
        phase_engine(DUR, 0.0, 2048, "paced_1.0mpps", 1.0e6, pace=True),
        # overload pair: offered above the single-dispatch ceiling, with
        # and without mega grouping — backlog forms, groups fire, and
        # the dispatch amortization shows up as achieved throughput
        # (at the documented group-latency trade)
        phase_engine(DUR, 0.0, 2048, "paced_1.5mpps", 1.5e6, pace=True),
        phase_engine(DUR, 0.0, 2048, "paced_1.5mpps_mega8", 1.5e6,
                     pace=True, mega_n=8),
        # Freerun rows pin the SIM clock to 1e6 pps: the generator runs
        # at memcpy speed regardless, but record timestamps must keep
        # per-source rates benign-plausible (at --rate 1e7 every benign
        # source timestamps out to ~10 k pps and the model correctly
        # blocks it — a sim-clock artifact, not a benign-FPR signal).
        phase_engine(DUR, 0.0, 2048, "freerun_b2048", 1e6),
        # mega-dispatch engine on the same freerun stream: the
        # backlog-grouped lax.scan path (Engine mega_n) amortizing
        # per-dispatch overhead
        phase_engine(DUR, 0.0, 2048, "freerun_b2048_mega8", 1e6,
                     mega_n=8),
        phase_engine(DUR, 0.0, 1024, "freerun_b1024", 1e6),
        phase_engine(DUR, 0.2, 2048, "freerun_mixed_attack20", 1e6),
    ]
    out["engine_rows"] = rows
    best = max(rows, key=lambda r: r["engine_mpps"])
    out["headline"] = {
        "drain_mpps": out["drain_only"]["drain_mpps"],
        "engine_mpps": best["engine_mpps"],
        "engine_config": best["label"],
        "host_cores": os.cpu_count(),
    }
    Path(REPO / "artifacts" / "SHMSTRESS_inline.json").write_text(
        json.dumps(out, indent=1))
    print(json.dumps(out["headline"]))


if __name__ == "__main__":
    main()
