"""Bounded CPU latency-plane smoke — the seal→verdict CI gate.

Two legs, both over the same UDP-flood record set (ISSUE 11):

* **parity** — singles vs mega-auto vs two budgeted runs on one
  deterministic ArraySource backlog: byte-identical stats and
  blacklist every time (the SLO policy bounds WAITING, never
  results), with a 1 µs budget — every record already late — keeping
  full amortization (the greedy-flush recovery rule).  Then the
  deterministic degradation proof, driven through the real
  ``_drain_pending`` greedy flush: a sub-top pending backlog with
  planted-unaffordable rung EWMAs must dispatch as singles (skip
  climbing) where the control flushes rung 4 — re-proving the
  budget-exceeded path actually rewires dispatch, each run.
* **pulse** — a pulse-wave ``PacedSource`` through a WARMED
  ``--slo-us`` engine: the report's latency block must exist with a
  FINITE ordered percentile chain (0 < p50 ≤ p99 ≤ max), every record
  accounted (n == records served), all four stages populated, and —
  the stamp-monotonicity proof — ``negatives == 0``: no seal→launch→
  sink interval ever came out negative, so the seal stamps, launch
  stamps and sink stamps are mutually ordered on every path the run
  exercised.  The warm pass must also have seeded the per-rung EWMA
  table the deadline-aware policy reads.

Results merge into ``artifacts/LATENCY_r15.json`` under ``"smoke"``
(the ``"paced"`` pulse-wave A/B evidence in the same artifact is
preserved), so the measurement plane is re-proved by every
``scripts/verify_tier1.sh`` run, not benched once and trusted forever.

Usage: JAX_PLATFORMS=cpu python scripts/latency_smoke.py [out.json]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_BATCHES = 24
BATCH = 256
SLO_US = 5000           # the pulse leg's budget (ms-scale CPU steps)
PULSE_RATE = 0.02e6     # 20 kpps mean offered
PULSE_SECONDS = 2.0


def _records(n: int):
    from flowsentryx_tpu.engine.traffic import Scenario, TrafficGen, TrafficSpec

    return TrafficGen(TrafficSpec(
        scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
        n_attack_ips=8, n_benign_ips=24, attack_fraction=0.8, seed=31,
    )).next_records(n)


def _cfg(deadline_us: int = 200):
    import dataclasses

    from flowsentryx_tpu.core.config import FsxConfig

    cfg = FsxConfig()
    return dataclasses.replace(
        cfg,
        batch=dataclasses.replace(cfg.batch, max_batch=BATCH,
                                  deadline_us=deadline_us),
        table=dataclasses.replace(cfg.table, capacity=1 << 14),
        limiter=dataclasses.replace(
            cfg.limiter, pps_threshold=200.0, bps_threshold=1e9),
    )


def main() -> int:
    from flowsentryx_tpu.engine import (
        ArraySource, CollectSink, Engine, NullSink, PacedSource,
    )

    t_start = time.perf_counter()
    recs = _records(BATCH * N_BATCHES)
    failures: list[str] = []

    # -- leg 1: parity + provable policy behavior (deterministic) ----------
    def run(**kw):
        sink = CollectSink()
        eng = Engine(_cfg(), ArraySource(recs.copy()), sink,
                     readback_depth=4, sink_thread=False, **kw)
        rep = eng.run()
        return rep, sink

    rep0, sink0 = run()
    repa, sinka = run(mega_n="auto")
    reps, sinks = run(mega_n="auto", slo_us=2000)
    repl, sinkl = run(mega_n="auto", slo_us=1)
    if not (rep0.stats == repa.stats == reps.stats == repl.stats):
        failures.append("stats parity broken across slo/mega/singles")
    if not (sink0.blocked == sinka.blocked == sinks.blocked
            == sinkl.blocked):
        failures.append("blacklist parity broken across slo/mega/singles")
    if not any(int(g) > 1 for g in repl.dispatch["group_hist"]):
        failures.append(
            f"already-late stream served as singles: "
            f"{repl.dispatch['group_hist']} (the greedy-flush recovery "
            "rule must keep full amortization once headroom is gone)")
    if not any(int(g) > 1 for g in repa.dispatch["group_hist"]):
        failures.append("control mega-auto never coalesced — the "
                        "degradation comparison is vacuous")

    # the deterministic skip-climbing proof through the REAL greedy
    # flush: 5 pending sealed batches, every coalesced rung's EWMA
    # planted unaffordable under ample headroom -> singles; control
    # flushes the same backlog through rung 4
    import time as _t

    import numpy as np

    def seed_pending(eng, n):
        from flowsentryx_tpu.core import schema as _schema
        from flowsentryx_tpu.engine.engine import _Stamps

        warm = np.zeros((eng.cfg.batch.max_batch + 1,
                         _schema.COMPACT_RECORD_WORDS), np.uint32)
        now = _Stamps(*[_t.perf_counter()] * 3)
        eng._pending = [(warm.copy(), now) for _ in range(n)]

    ctl = Engine(_cfg(), ArraySource(recs[:0].copy()), NullSink(),
                 sink_thread=False, mega_n="auto")
    seed_pending(ctl, 5)
    ctl._drain_pending(short=True)
    ctl_hist = {int(g): n for g, n in ctl._group_hist.items()}
    cap = Engine(_cfg(), ArraySource(recs[:0].copy()), NullSink(),
                 sink_thread=False, mega_n="auto", slo_us=10_000_000)
    cap._rung_ewma_s.update({2: 9e9, 4: 9e9, 8: 9e9})
    seed_pending(cap, 5)
    cap._drain_pending(short=True)
    cap_hist = {int(g): n for g, n in cap._group_hist.items()}
    if ctl_hist != {4: 1, 1: 1}:
        failures.append(f"control greedy flush dispatched {ctl_hist}, "
                        "expected {4: 1, 1: 1}")
    if cap_hist != {1: 5}:
        failures.append(
            f"unaffordable rungs still climbed: {cap_hist} (the "
            "budget-bounded greedy flush must dispatch singles)")

    # -- leg 2: pulse-wave latency plane through a warmed SLO engine -------
    eng = Engine(_cfg(), ArraySource(recs[:0].copy()), NullSink(),
                 readback_depth=2, sink_thread=False, mega_n="auto",
                 slo_us=SLO_US)
    eng.warm()
    ewma = dict(eng._rung_ewma_s)
    if set(ewma) < {1, 2, 4, 8} or any(v <= 0 for v in ewma.values()):
        failures.append(f"warm() did not seed the rung EWMA table: {ewma}")
    total = int(PULSE_RATE * PULSE_SECONDS)
    src = PacedSource(recs.copy(), rate_pps=PULSE_RATE, total=total,
                      burst_period_s=0.008, duty_cycle=0.25)
    eng.reset_stream(src)
    rep = eng.run(max_seconds=PULSE_SECONDS + 4)
    lat = rep.latency
    sv = lat["seal_to_verdict"]
    if lat["negatives"] != 0:
        failures.append(
            f"{lat['negatives']} negative stage interval(s): the seal/"
            "launch/sink stamps are NOT monotone on some path")
    if sv.get("n", 0) != rep.records or rep.records == 0:
        failures.append(
            f"latency plane covered {sv.get('n')} of {rep.records} records")
    chain = [sv.get(k, 0) for k in ("p50", "p90", "p99", "p999", "max")]
    import math

    if not all(math.isfinite(v) for v in chain):
        failures.append(f"non-finite percentile in {chain}")
    if not (0 < chain[0] and all(a <= b for a, b in zip(chain, chain[1:]))):
        failures.append(f"percentile chain not ordered/positive: {chain}")
    for s, d in lat["stages"].items():
        if d.get("n", 0) != rep.records:
            failures.append(f"stage {s} covered {d.get('n')} of "
                            f"{rep.records} records")
    if "slo" not in lat or rep.dispatch["slo"] is None:
        failures.append("slo accounting missing from a --slo-us run")

    smoke = {
        "ts": time.time(),
        "wall_s": round(time.perf_counter() - t_start, 2),
        "parity": {
            "records": rep0.records,
            "late_recovery_group_hist": repl.dispatch["group_hist"],
            "control_group_hist": repa.dispatch["group_hist"],
            "greedy_flush_control_hist": ctl_hist,
            "greedy_flush_capped_hist": cap_hist,
        },
        "pulse": {
            "slo_us": SLO_US,
            "records": rep.records,
            "seal_to_verdict_us": sv,
            "stages_p50_us": {s: d.get("p50")
                              for s, d in lat["stages"].items()},
            "negatives": lat["negatives"],
            "slo": lat.get("slo"),
            "rung_ewma_ms": rep.dispatch["slo"]["rung_ewma_ms"]
            if rep.dispatch["slo"] else None,
            "group_hist": rep.dispatch["group_hist"],
        },
        "ok": not failures,
        "failures": failures,
    }

    out_path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "artifacts", "LATENCY_r15.json")
    try:
        artifact = json.loads(open(out_path).read())
    except (OSError, ValueError):
        artifact = {}
    artifact["smoke"] = smoke
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"latency smoke: wrote {out_path}")
    print(f"latency smoke: p99={sv.get('p99')}us negatives="
          f"{lat['negatives']} capped_flush={cap_hist} "
          f"late_hist={repl.dispatch['group_hist']}")
    for msg in failures:
        print(f"latency smoke: FAIL {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
