"""Paced table-scale evidence — the ``"paced"`` half of
``artifacts/TABLESCALE_r12.json``.

Two claims, measured per the repo's established drain methodology
(interleaved trials on persistent warmed engines, raw data + host-noise
disclosure; see DISPATCH_r09):

1. **Drain stays flat at production scale** — sealed-drain Mpps of a
   4M-row (2^22) table with the in-step eviction sweep ACTIVE, versus
   the PR 7 bench-shape table (2^20 rows, ``bench.py TABLE_CAP``, no
   eviction), at the same serving configuration (B=512, ``--mega
   8``).  Measured sharded (mesh=2 — the 2-vCPU container's honest
   mesh) and single-device; trials interleave A/B/A/B so host drift
   hits both configs alike, and the per-pair ratio is the robust
   statistic on this noise-swinging host.

2. **Occupancy stays bounded under churn** — a capacity ladder
   (2^16 → 2^22) serving sustained fresh-key churn with eviction on:
   final occupancy holds near the live-flow count at every rung while
   a no-eviction control fills monotonically.

Traffic: a wide rotating flow pool with the synthetic clock advancing
10 µs/record, so within one multi-second trial early flows really go
idle past the 2 s ttl and the sweep does live work (eviction "active"
means firing, not just compiled in).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
           python scripts/table_scale_bench.py [out.json]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla:
    os.environ["XLA_FLAGS"] = (
        xla + " --xla_force_host_platform_device_count=8").strip()

B = 512
TRIAL_BATCHES = 768           # >= 2.5 s on this host (methodology floor)
TRIALS = int(os.environ.get("FSX_TBENCH_TRIALS", "5"))
#                               interleaved rounds; round 0 is the
#                               page-in warmup (disclosed, excluded
#                               from the headline median)
PR7_CAP = 1 << 20             # bench.py TABLE_CAP — the PR 7 bench shape
PROD_CAP = 1 << 22            # the production-scale contender
EVICT_TTL = 2.0
EVICT_EVERY = 32768           # 128-row window/batch at 4M: sized by
#                               cycle time (~7 s at the 10 Mpps design
#                               rate), per-batch sweep cost ~zero
TS_STEP_NS = 10_000           # 10 µs/record → ~4 s clock span per trial
FLOW_POOL = 1 << 18


def _cfg(cap: int, ttl: float, every: int = EVICT_EVERY):
    from flowsentryx_tpu.core.config import (
        BatchConfig, FsxConfig, LimiterConfig, TableConfig,
    )

    return FsxConfig(
        table=TableConfig(capacity=cap, stale_s=1e6, salt=1,
                          evict_ttl_s=ttl, evict_every=every),
        batch=BatchConfig(max_batch=B),
        limiter=LimiterConfig(pps_threshold=1e9, bps_threshold=1e18),
    )


def _recs(n: int, seed: int = 0):
    import numpy as np

    from flowsentryx_tpu.core import schema

    r = np.random.default_rng(seed)
    buf = np.zeros(n, schema.FLOW_RECORD_DTYPE)
    buf["saddr"] = r.integers(1, FLOW_POOL, n).astype(np.uint32)
    buf["pkt_len"] = 100
    buf["ts_ns"] = (np.arange(n, dtype=np.uint64)
                    * np.uint64(TS_STEP_NS)) + np.uint64(1)
    buf["feat"][:, 0] = 80.0
    return buf


def _noise() -> dict:
    la = os.getloadavg()
    return {"loadavg_1m": round(la[0], 2), "ts": round(time.time(), 2)}


def _drain_pair(mesh_n: int, recs) -> dict:
    """Interleaved sealed-drain trials: A = PR 7 bench shape (2^20, no
    eviction), Bc = 4M + eviction, one warmed persistent engine each."""
    from flowsentryx_tpu.engine import CollectSink, Engine
    from flowsentryx_tpu.engine.sources import ArraySource
    from flowsentryx_tpu.parallel import make_mesh

    mesh = make_mesh(mesh_n) if mesh_n else None
    engines = {}
    # prod4M_noevict is the decomposition control: its ratio vs
    # pr7_shape is the pure table-scale cost, and prod4M_evict vs it
    # is the eviction sweep's own cost
    for name, cap, ttl in (("pr7_shape", PR7_CAP, 0.0),
                           ("prod4M_noevict", PROD_CAP, 0.0),
                           ("prod4M_evict", PROD_CAP, EVICT_TTL)):
        eng = Engine(_cfg(cap, ttl), ArraySource(recs[:B].copy()),
                     CollectSink(), sink_thread=False, mesh=mesh,
                     mega_n=8)  # fixed top rung: the prefilled backlog
        #            dispatches top-rung groups either way, and the
        #            ladder's extra per-rung compiles (~45 s each at
        #            mesh2 x 4M) would dominate the bench wall
        t_w = time.perf_counter()
        eng.warm()
        eng.run()  # flush the seed source so reset_stream is legal
        print(f"  {name}: warmed in "
              f"{time.perf_counter() - t_w:.1f}s", flush=True)
        engines[name] = eng

    trials: list[dict] = []
    prev_evicted = {n: 0 for n in engines}
    order = ("pr7_shape", "prod4M_noevict", "prod4M_evict")
    for t in range(TRIALS):
        for name in (order if t % 2 == 0 else order[::-1]):
            eng = engines[name]
            eng.reset_stream(ArraySource(recs.copy()))
            rep = eng.run()
            # stats are cumulative across the persistent engine's
            # trials; report the per-trial eviction delta
            ev = rep.stats["evicted"]
            trials.append({
                "config": name, "trial": t,
                "records": rep.records, "wall_s": rep.wall_s,
                "mpps": round(rep.records_per_s / 1e6, 4),
                "evicted_this_trial": ev - prev_evicted[name],
                "tracked": rep.table["tracked"],
                "noise": _noise(),
            })
            prev_evicted[name] = ev
            print(f"  round {t} {name}: {trials[-1]['mpps']} Mpps "
                  f"(wall {rep.wall_s}s)", flush=True)
    out: dict = {"trials": trials}
    for name in ("pr7_shape", "prod4M_noevict", "prod4M_evict"):
        vals = sorted(x["mpps"] for x in trials if x["config"] == name)
        out[name] = {"mpps_trials": vals,
                     "median_mpps": vals[len(vals) // 2]}
    ratios = []
    by_round: dict[int, dict] = {}
    for x in trials:
        by_round.setdefault(x["trial"], {})[x["config"]] = x["mpps"]
    scale_r, evict_r = [], []
    for t, pair in sorted(by_round.items()):
        ratios.append(round(pair["prod4M_evict"] / pair["pr7_shape"], 4))
        scale_r.append(round(pair["prod4M_noevict"] / pair["pr7_shape"],
                             4))
        evict_r.append(round(pair["prod4M_evict"]
                             / pair["prod4M_noevict"], 4))
    out["per_round_ratio_4M_over_pr7"] = ratios
    out["per_round_ratio_scale_only"] = scale_r
    out["per_round_ratio_evict_only"] = evict_r
    st_scale = sorted(scale_r[1:])
    st_evict = sorted(evict_r[1:])
    out["median_steady_scale_only"] = st_scale[len(st_scale) // 2]
    out["median_steady_evict_only"] = st_evict[len(st_evict) // 2]
    # round 0 pages the 4M table's ~216 MB in (first touch of much of
    # the donated buffer chain) — a boot cost, not a steady-state one;
    # it is disclosed above and excluded from the headline
    steady = sorted(ratios[1:])
    out["warmup_round_ratio"] = ratios[0]
    out["median_steady_ratio"] = steady[len(steady) // 2]
    del engines
    return out


def _ladder() -> list[dict]:
    import numpy as np

    from flowsentryx_tpu.core import schema
    from flowsentryx_tpu.engine import ArraySource, CollectSink, Engine

    rungs = []
    for cap_bits in (16, 18, 20, 22):
        cap = 1 << cap_bits
        phases, per = 48, 2048
        bufs = []
        for i in range(phases):
            buf = np.zeros(per, schema.FLOW_RECORD_DTYPE)
            buf["saddr"] = 100_000 * (i + 1) + np.arange(per)
            buf["pkt_len"] = 100
            buf["ts_ns"] = int(i * 1e9) + np.arange(per) * 1000
            buf["feat"][:, 0] = 80.0
            bufs.append(buf)
        recs = np.concatenate(bufs)
        # the ladder probes OCCUPANCY, not drain rate: a short 32-batch
        # cycle gives six full sweep passes inside the 192-batch run at
        # every rung (the drain pair uses the production-tuned long
        # cycle instead, where the trial proves the cost side)
        every = 32
        res = {}
        for ttl in (EVICT_TTL, 0.0):
            eng = Engine(_cfg(cap, ttl, every), ArraySource(recs.copy()),
                         CollectSink(), sink_thread=False)
            rep = eng.run()
            res[ttl] = rep
        rungs.append({
            "capacity": cap,
            "evict_every": every,
            "distinct_flows_offered": phases * per,
            "tracked_evict": res[EVICT_TTL].table["tracked"],
            "evicted": res[EVICT_TTL].stats["evicted"],
            "tracked_no_evict_control": res[0.0].table["tracked"],
            # bounded = held near the live-flow count (<= ~3 phases of
            # ttl+cycle slack), far under the control's cumulative fill
            "live_flow_bound": 6 * per,
            "bounded": res[EVICT_TTL].table["tracked"] <= 6 * per,
        })
        print(f"ladder 2^{cap_bits}: tracked {rungs[-1]['tracked_evict']}"
              f" vs control {rungs[-1]['tracked_no_evict_control']} "
              f"(evicted {rungs[-1]['evicted']})", flush=True)
    return rungs


def main() -> int:
    # stages let a wall-clock-budgeted runner split the work
    # (FSX_TBENCH_STAGE=pairs|ladder|all); results merge into the one
    # artifact either way
    stage = os.environ.get("FSX_TBENCH_STAGE", "all")
    t0 = time.perf_counter()
    n = B * TRIAL_BATCHES
    recs = _recs(n)

    mesh_pair = single_pair = None
    ladder = None
    if stage in ("pairs", "mesh2", "all"):
        print("== drain pair, mesh=2 (sharded) ==", flush=True)
        mesh_pair = _drain_pair(2, recs)
        print(json.dumps({k: v for k, v in mesh_pair.items()
                          if k != "trials"}), flush=True)
    if stage in ("pairs", "single", "all"):
        print("== drain pair, single-device ==", flush=True)
        single_pair = _drain_pair(0, recs)
        print(json.dumps({k: v for k, v in single_pair.items()
                          if k != "trials"}), flush=True)
    if stage in ("ladder", "all"):
        print("== capacity ladder ==", flush=True)
        ladder = _ladder()

    paced = {
        "ts": time.time(),
        "wall_s": round(time.perf_counter() - t0, 1),
        "method": (
            "Interleaved inline-sealed drain trials (ArraySource -> "
            "MicroBatcher compact16 seal -> mega-auto dispatch; the "
            "worker-fleet seal path is benched by DISPATCH_r09 "
            "and orthogonal to table scale) on two "
            "persistent warmed engines per pair (ABAB order per "
            "round): A = the "
            "PR 7 bench-shape table (2^20 rows = bench.py TABLE_CAP, "
            "no eviction), B = the production 4M-row (2^22) table "
            "with the rolling eviction sweep ACTIVE (ttl 2 s, "
            "128-row window/batch) and FIRING (the 10 us/record "
            "synthetic clock idles early flows past the ttl inside "
            "each ~4 s trial). Same serving config otherwise: B=512, "
            "--mega auto, CollectSink, "
            f"{TRIAL_BATCHES} batches/trial ({B * TRIAL_BATCHES} "
            "records, >= 2.5 s -- the methodology floor on this "
            "2-vCPU container whose capacity swings 2-3x; the "
            "per-round B/A ratio cancels the shared host factor and "
            "is the robust statistic; round 0 additionally pages the "
            "4M table in and is disclosed as warmup, excluded from "
            "the headline median). Measured sharded over a "
            "mesh=2 virtual-CPU mesh (the tentpole configuration; 2 "
            "virtual devices share the container's 2 cores, so "
            "cross-mesh comparisons are meaningless here, "
            "within-mesh ratios are not) AND single-device. The "
            "capacity ladder serves 48 phases x 2048 fresh keys of "
            "churn (98k distinct flows) per rung with "
            "evict_every=capacity/4096, against a no-eviction "
            "control."),
        "config": {
            "pr7_shape_capacity": PR7_CAP,
            "prod_capacity": PROD_CAP,
            "evict_ttl_s": EVICT_TTL,
            "evict_every": EVICT_EVERY,
            "batch": B,
            "trial_batches": TRIAL_BATCHES,
            "ts_step_ns": TS_STEP_NS,
            "flow_pool": FLOW_POOL,
        },
        "sharded_mesh2": mesh_pair,
        "single_device": single_pair,
        "capacity_ladder": ladder,
    }

    out_path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "artifacts", "TABLESCALE_r12.json")
    try:
        artifact = json.loads(open(out_path).read())
    except (OSError, ValueError):
        artifact = {}
    prev = artifact.get("paced", {})
    # stage runs merge over the previous artifact's sections
    for key, val in (("sharded_mesh2", mesh_pair),
                     ("single_device", single_pair),
                     ("capacity_ladder", ladder)):
        if val is None and key in prev:
            paced[key] = prev[key]
    artifact["paced"] = paced
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"table-scale bench: wrote {out_path}")
    for label, pair in (("mesh2", paced.get("sharded_mesh2")),
                        ("single", paced.get("single_device"))):
        if pair:
            print(f"  {label} steady median ratio 4M-evict/pr7-shape: "
                  f"{pair['median_steady_ratio']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
