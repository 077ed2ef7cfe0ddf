"""Predictive-governor pulse-wave A/B — the paced half of
``artifacts/PREDICT_r22.json``.

Same-build A/B (the ``--predict`` engine with no confident forecast
IS the reactive SLO engine, test-pinned byte-identical): two
persistent warmed mega-auto engines at the SAME ``--slo-us`` budget —
reactive (PR 11 deadline-flush point) vs governed (``--predict``
forecast-end flush + rung pre-warm) — serve the SAME pulse-wave
offered process in INTERLEAVED trials (PR 7 discipline:
alternate arms within one process, trials >= 2.5 s so cgroup throttle
bursts don't dominate, order swapped every pair, raw trials + loadavg
disclosed; on this 2-3x-swinging host the per-trial ratios are the
statistic, never a single window).

Two tiers:

* ``pulse`` — open-loop pulse-wave PacedSource (the PR 11 corpus:
  96-record bursts every 7.5 ms, smaller than one batch, so every
  record rides the deadline-flush point — the point the governor
  moves from the reactive ~budget/2 floor to the forecast burst end).
  PASS = median per-trial ratio (reactive p99 / governed p99)
  >= 1.20 — the governor must beat the reactive arm by >= 20 %.
* ``steady`` — saturating sealed-backlog drain (ArraySource replay,
  aperiodic: the forecaster must stay quiescent) per arm,
  interleaved: records/wall.  PASS = governed throughput within 5 %
  of reactive (prediction must not tax the regime it can't read).

Per-trial governor counters (forecasts / onset hits / pre-warm hits /
early flushes / pressure ticks) are disclosed in every row; the
shed-only-under-pressure proof lives in the ``"smoke"`` section of
the same artifact (scripts/predict_smoke.py, run by every
verify_tier1 pass).

Usage: JAX_PLATFORMS=cpu python scripts/predict_latency_bench.py \
           [--trials N] [--seconds S] [out.json]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

BATCH = 256
DEADLINE_US = 5000
TABLE_CAP = 1 << 14
#: Budget == batcher deadline: the regime where the PR 11 reactive
#: flush point parks at the budget/2 floor (~2.5 ms) because the rung
#: EWMA is small — and the governor's forecast-end flush (~period x
#: duty = 1.5 ms) is the whole p99 lever.
SLO_US = 5000
RATE_PPS = 0.0128e6        # mean offered: ~3x headroom inside this
#                            host's worst measured throttle window
BURST_PERIOD_S = 0.0075    # 96 records/burst — SMALLER than one
DUTY = 0.20                # batch, so every burst rides the flush
PULSE_SECONDS = 3.0        # >= 2.5 s trial floor (PR 7 discipline)
STEADY_BATCHES = 192       # saturating drain trial size


def _cfg():
    import dataclasses

    from flowsentryx_tpu.core.config import FsxConfig

    cfg = FsxConfig()
    return dataclasses.replace(
        cfg,
        batch=dataclasses.replace(cfg.batch, max_batch=BATCH,
                                  deadline_us=DEADLINE_US),
        table=dataclasses.replace(cfg.table, capacity=TABLE_CAP),
        limiter=dataclasses.replace(
            cfg.limiter, pps_threshold=200.0, bps_threshold=1e9),
    )


def _predict_row(rep) -> dict:
    p = rep.predict or {}
    return {k: p.get(k, 0) for k in (
        "forecasts", "onset_hits", "onset_misses", "prewarm_issued",
        "prewarm_hits", "early_flushes", "holds", "pressure_ticks")}


def main() -> int:
    args = list(sys.argv[1:])
    trials = 8
    seconds = PULSE_SECONDS
    argv: list[str] = []
    i = 0
    while i < len(args):
        a = args[i]
        if a.startswith("--trials"):
            trials = int(a.split("=", 1)[1] if "=" in a else args[i + 1])
            i += 1 if "=" in a else 2
        elif a.startswith("--seconds"):
            seconds = float(a.split("=", 1)[1] if "=" in a
                            else args[i + 1])
            i += 1 if "=" in a else 2
        else:
            argv.append(a)
            i += 1

    from flowsentryx_tpu.benchmarks import (
        paced_latency_run, summarize_latencies,
    )
    from flowsentryx_tpu.engine import ArraySource, Engine, NullSink, PacedSource
    from flowsentryx_tpu.engine.traffic import (
        Scenario, TrafficGen, TrafficSpec,
    )

    t_start = time.perf_counter()
    pool = TrafficGen(TrafficSpec(
        scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
        n_attack_ips=64, n_benign_ips=192, attack_fraction=0.8, seed=41,
    )).next_records(1 << 14)

    engines = {}
    for name, pred in (("slo", False), ("gov", True)):
        eng = Engine(_cfg(), ArraySource(pool[:0].copy()), NullSink(),
                     sink_thread=False, readback_depth=2,
                     mega_n="auto", slo_us=SLO_US, predict=pred)
        eng.warm()
        engines[name] = eng
    print(f"predict bench: engines warm; gov ewma = "
          f"{engines['gov']._rung_ewma_s}", flush=True)

    total = int(RATE_PPS * seconds)
    pulse_rows: list[dict] = []
    for t in range(trials):
        # order swapped every trial: slow host drift cancels pairwise
        order = ("slo", "gov") if t % 2 == 0 else ("gov", "slo")
        for arm in order:
            src = PacedSource(pool.copy(), rate_pps=RATE_PPS,
                              total=total,
                              burst_period_s=BURST_PERIOD_S,
                              duty_cycle=DUTY)
            lats, wall, rep = paced_latency_run(
                engines[arm], src, readback_depth=2,
                max_seconds=seconds + 4)
            row = {
                "trial": t, "arm": arm,
                **summarize_latencies(lats),
                "achieved_mpps": round(
                    len(lats) / max(wall, 1e-9) / 1e6, 4),
                "offered_all_consumed": bool(len(lats) >= total),
                "engine_p99_us": rep.latency["seal_to_verdict"]["p99"],
                "negatives": rep.latency["negatives"],
                "predict": _predict_row(rep),
                "loadavg": list(os.getloadavg()),
            }
            pulse_rows.append(row)
            pr = row["predict"]
            print(f"pulse t{t} {arm}: p50={row.get('p50_ms')} "
                  f"p99={row.get('p99_ms')} n={row.get('n')} "
                  f"prewarm_hits={pr['prewarm_hits']} "
                  f"early={pr['early_flushes']} "
                  f"load={row['loadavg'][0]:.2f}", flush=True)

    steady_rows: list[dict] = []
    recs = TrafficGen(TrafficSpec(
        scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
        n_attack_ips=64, n_benign_ips=192, attack_fraction=0.8, seed=43,
    )).next_records(BATCH * STEADY_BATCHES)
    for t in range(max(trials // 2, 3)):
        order = ("slo", "gov") if t % 2 == 0 else ("gov", "slo")
        for arm in order:
            eng = engines[arm]
            eng.reset_stream(ArraySource(recs.copy()))
            t0 = time.perf_counter()
            rep = eng.run()
            wall = time.perf_counter() - t0
            row = {
                "trial": t, "arm": arm,
                "records": rep.records,
                "wall_s": round(wall, 4),
                "mpps": round(rep.records / max(wall, 1e-9) / 1e6, 4),
                "predict": _predict_row(rep),
                "loadavg": list(os.getloadavg()),
            }
            steady_rows.append(row)
            print(f"steady t{t} {arm}: {row['mpps']} Mpps "
                  f"load={row['loadavg'][0]:.2f}", flush=True)

    def med(rows, arm, key):
        v = [r[key] for r in rows if r["arm"] == arm and key in r]
        return round(float(np.median(v)), 4) if v else None

    p99_r = med(pulse_rows, "slo", "p99_ms")
    p99_g = med(pulse_rows, "gov", "p99_ms")
    # per-trial pairwise ratios: the robust statistic on a host whose
    # capacity swings 2-3x between windows (PR 7 discipline)
    ratios = []
    for t in range(trials):
        a = [r for r in pulse_rows
             if r["trial"] == t and r["arm"] == "slo" and "p99_ms" in r]
        b = [r for r in pulse_rows
             if r["trial"] == t and r["arm"] == "gov" and "p99_ms" in r]
        if a and b and b[0]["p99_ms"]:
            ratios.append(round(a[0]["p99_ms"] / b[0]["p99_ms"], 3))
    ratio_med = round(float(np.median(ratios)), 3) if ratios else None
    st_r = med(steady_rows, "slo", "mpps")
    st_g = med(steady_rows, "gov", "mpps")
    steady_ratio = round(st_g / st_r, 4) if st_r else None
    wins = sum(1 for r in ratios if r > 1.0)
    # the steady legs must ALSO show the forecaster stayed quiescent:
    # aperiodic drain -> no early flushes, no pre-warms (degrade to
    # reactive, never worse)
    gov_steady_actuations = sum(
        r["predict"]["early_flushes"] + r["predict"]["prewarm_issued"]
        for r in steady_rows if r["arm"] == "gov")

    verdict = {
        "pulse_p50_ms": {"slo": med(pulse_rows, "slo", "p50_ms"),
                         "gov": med(pulse_rows, "gov", "p50_ms")},
        "pulse_p99_ms": {"slo": p99_r, "gov": p99_g},
        "pulse_p99_ratio_slo_over_gov": {
            "per_trial": ratios,
            "median": ratio_med,
            "gov_wins": f"{wins}/{len(ratios)}",
        },
        "steady_mpps": {"slo": st_r, "gov": st_g},
        "steady_ratio_gov_over_slo": steady_ratio,
        "gov_steady_actuations": gov_steady_actuations,
        "pass_latency": bool(ratio_med and ratio_med >= 1.20),
        "pass_throughput": bool(steady_ratio and steady_ratio >= 0.95),
        "pass_quiescent": gov_steady_actuations == 0,
    }
    paced = {
        "ts": time.time(),
        "wall_s": round(time.perf_counter() - t_start, 1),
        "discipline": (
            "PR 7: same-build A/B in one process, persistent "
            "warmed engines, SAME slo budget both arms, interleaved "
            "trials with order swapped every pair, >= 2.5 s per "
            "trial, raw trials + loadavg + per-trial governor "
            "counters disclosed; medians + per-trial ratios are the "
            "statistic (single windows on this host swing 2-3x)"),
        "config": {
            "batch": BATCH, "deadline_us": DEADLINE_US,
            "mega": "auto", "slo_us": SLO_US, "predict_arm": "gov",
            "rate_mpps": RATE_PPS / 1e6,
            "burst_period_s": BURST_PERIOD_S, "duty_cycle": DUTY,
            "trials": trials, "seconds": seconds,
        },
        "pulse_trials": pulse_rows,
        "steady_trials": steady_rows,
        "verdict": verdict,
    }

    out_path = argv[0] if argv else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "artifacts", "PREDICT_r22.json")
    try:
        artifact = json.loads(open(out_path).read())
    except (OSError, ValueError):
        artifact = {}
    artifact["paced"] = paced
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"predict bench: wrote {out_path}")
    print(json.dumps(verdict, indent=2))
    return 0 if (verdict["pass_latency"] and verdict["pass_throughput"]
                 and verdict["pass_quiescent"]) else 1


if __name__ == "__main__":
    raise SystemExit(main())
