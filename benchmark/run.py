#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the engine as ``fsx serve`` assembles it, warms it,
serves the configuration's resident population into the table, starts
the cell's traffic driver, serves a warm-up, then measures one window
with ``Engine.run(max_seconds=...)``.  ``setup_s`` runs from the moment
JAX has the chip (what comes before is the machine's, not the
program's: it is printed as ``chip_start_s``) to the window's start.
Everything reported is the snapshot after the window less the snapshot
before it, over the window's own wall clock.  After the window the plain
reference replays what was dispatched and decides ``correct``.  The last
line of stdout is the result.  README.md says how cells, configurations,
metrics and drivers are added as files; this file names none of them.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

T_PROCESS = T_SETUP = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

from benchmark import harness, judge, peaks as peaks_mod, trace_reduce  # noqa: E402

TRACE_SLICE_S = 3.0  # of the window, traced when --trace 1


def note(obj: dict) -> None:
    """An earlier line: anything worth a number that is not the result."""
    print(json.dumps(obj), flush=True)


def find_cell(name: str) -> tuple[dict, dict, dict]:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return bench, cell, conf


def metric_wanted(m: dict, cell_name: str) -> bool:
    return "workloads" not in m or cell_name in m["workloads"]


def trace_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # every Python call traced slows the host
    return opts


def snapshot(rep, driver, reaps, compiles) -> dict:
    return {"rep": rep._asdict(), "gen": driver.counters(),
            "reaped": len(reaps.lat_s), "sunk": reaps.sunk,
            "compiles": compiles.report(), "t": time.perf_counter()}


def ring_wait_ms(snap0: dict, snap1: dict, window_s: float):
    """Time a record waits in the feature rings before its batch starts
    to fill: backlog at the window's end over the rate forwarded in the
    window.  No end-to-end metric sees it (the latency clock starts at a
    batch's first record), so it is an earlier line, not a metric."""
    fwd = snap1["gen"]["forwarded"] - snap0["gen"]["forwarded"]
    if fwd <= 0:
        return None
    return round(1e3 * snap1["gen"]["backlog"] / (fwd / window_s), 2)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: proves control flow only")
    ap.add_argument("--control", default="",
                    help="run the reference at this precision in the "
                         "program's place (tests only)")
    args = ap.parse_args()

    bench, cell_entry, conf_entry = find_cell(args.workload)
    config = harness.load_json(ROOT / conf_entry["file"])
    cell = harness.load_json(HERE / "workloads" / f"{args.workload}.json")
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        config = harness.merged(config, config.get("rehearse", {}))
        cell = harness.merged(cell, cell.get("rehearse", {}))

    import jax

    devs = jax.devices()
    global T_SETUP
    T_SETUP = time.perf_counter()  # the chip is ours: set-up starts
    from flowsentryx_tpu.core import runtime
    import flowsentryx_tpu.engine  # noqa: F401  (the import wall, timed)
    import_s = time.perf_counter() - T_SETUP
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) < cell_entry["chips"]):
        print(f"benchmark: needs {cell_entry['chips']} TPU chip(s), JAX "
              f"found {len(devs)} x {devs[0].platform}", file=sys.stderr)
        return 3
    compiles = runtime.CompileCounters(runtime.place_compile_cache())

    workdir = Path(tempfile.mkdtemp(prefix="fsxbench-"))
    ctx = SimpleNamespace(cell=cell, config=config, seed=args.seed,
                          workdir=workdir, rehearse=args.rehearse)
    driver = harness.load_module("drivers", cell["driver"]).Driver(ctx)
    try:
        return run(args, bench, config, cell, driver, compiles, import_s)
    finally:
        if hasattr(driver, "close"):
            driver.close()
        shutil.rmtree(workdir, ignore_errors=True)


def run(args, bench, config, cell, driver, compiles, import_s) -> int:
    import jax
    import numpy as np

    # -- set-up ----------------------------------------------------------
    phases = {"chip_start": T_SETUP - T_PROCESS,
              "imports": time.perf_counter() - T_SETUP}
    clock = harness.Stopwatch()
    source, sink = driver.build()
    phases["driver_build"] = clock.lap()
    eng = harness.build_engine(config, source, sink, import_s, compiles)
    phases["engine"] = clock.lap()
    reaps = harness.ReapLog(driver.source_tap())
    eng.on_reap = reaps
    eng.warm()
    phases["warm"] = clock.lap()
    if args.trace:  # the profiler's first start is its slow one
        warm_dir = driver.ctx.workdir / "trace-warm"
        jax.profiler.start_trace(str(warm_dir),
                                 profiler_options=trace_options(jax))
        jax.profiler.stop_trace()
        shutil.rmtree(warm_dir, ignore_errors=True)
        phases["profiler_warm"] = clock.lap()
    prefilled = driver.prefill(eng)
    phases["prefill"] = clock.lap()
    driver.start()
    phases["driver_start"] = clock.lap()
    driver.warm_up(eng)
    rep = eng.run(max_seconds=0.0)
    phases["warm_up"] = clock.lap()
    snap0 = snapshot(rep, driver, reaps, compiles)
    setup_s = snap0["t"] - T_SETUP
    note({"setup_phases_s": {k: round(v, 2) for k, v in phases.items()},
          "chip_start_s": round(T_SETUP - T_PROCESS, 2),
          "prefilled_records": prefilled})

    # -- window ----------------------------------------------------------
    # A traced run traces the window's last TRACE_SLICE_S alone (a trace
    # of all of it is too large to reduce and slows the host); the
    # profiler is started once in set-up so that its start costs little
    # here, and stopped after the window's end so that its stop costs
    # nothing: after the generator too, or an open-loop generator fills
    # the rings and sheds while the profiler stops.  The report-derived
    # metrics cover the whole window.
    traced = None
    if args.trace:
        slice_s = min(TRACE_SLICE_S, args.seconds)
        trace_dir = driver.ctx.workdir / "trace"
        t0 = snapshot(eng.run(max_seconds=args.seconds - slice_s), driver,
                      reaps, compiles)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=trace_options(jax))
        t_started = time.perf_counter()
        rep = eng.run(max_seconds=slice_s)
        snap1 = snapshot(rep, driver, reaps, compiles)
        gen_final = driver.stop()
        jax.profiler.stop_trace()
        traced = {"dir": trace_dir, "snap0": t0, "snap1": snap1}
        note({"trace_start_s": round(t_started - t0["t"], 3),
              "trace_stop_s": round(time.perf_counter() - snap1["t"], 3)})
    else:
        rep = eng.run(max_seconds=args.seconds)
        snap1 = snapshot(rep, driver, reaps, compiles)
        gen_final = driver.stop()
    window_s = snap1["t"] - snap0["t"]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.local_devices())

    # -- after the window --------------------------------------------------
    driver.drain(eng)
    rep_end = eng.run(max_seconds=0.0)._asdict()
    if hasattr(source, "ingest_stats") and hasattr(source, "close"):
        source.close()
        rep_end["ingest"] = source.ingest_stats()
    end = {"rep": rep_end, "gen": driver.counters()}
    device = dict(rep_end["device"])
    peaks = peaks_mod.peaks_for(device["kind"]) \
        if device["platform"] == "tpu" else None

    trace = None
    if traced is not None:
        t_red = time.perf_counter()
        trace = trace_reduce.reduce_dir(traced["dir"],
                                        config["step_programs"])
        trace["snap0"], trace["snap1"] = traced["snap0"], traced["snap1"]
        note({"trace_reduce_s": round(time.perf_counter() - t_red, 2),
              "trace_lines": trace.get("lines"),
              "trace_modules": trace.get("modules")})

    a, b = snap0["reaped"], snap1["reaped"]
    if b - a > 1:
        note({"verdict_ms_percentiles": {
            str(q): round(1e3 * harness.weighted_percentile(
                reaps.lat_s[a:b], reaps.weight[a:b], q), 2)
            for q in (50, 75, 90, 94, 95, 96, 97, 98, 99, 99.9)},
            "longest_gap_between_sinks_ms": round(
                1e3 * float(np.max(np.diff(reaps.t_done[a:b]))), 1)})
    t_ref = time.perf_counter()
    verdict = judge.judge(config, driver, sink, end,
                          precision=args.control or "int8")
    note({"reference_s": round(time.perf_counter() - t_ref, 2),
          "window_s": round(window_s, 3),
          "compiles_in_window": snap1["compiles"]["requests"]
          - snap0["compiles"]["requests"],
          "host_cores": len(os.sched_getaffinity(0)),
          "batches_in_window": snap1["rep"]["batches"]
          - snap0["rep"]["batches"],
          "generator": gen_final,
          "backlog": [snap0["gen"]["backlog"], snap1["gen"]["backlog"]],
          "ring_fill": [snap0["gen"].get("ring_fill"),
                        snap1["gen"].get("ring_fill")],
          "sunk_in_window": snap1["sunk"] - snap0["sunk"],
          "ring_wait_ms": ring_wait_ms(snap0, snap1, window_s),
          "dispatch_groups": rep_end["dispatch"]["group_hist"],
          "stats": rep_end["stats"]})

    # -- metrics -----------------------------------------------------------
    mctx = SimpleNamespace(
        snap0=snap0, snap1=snap1, end=end, window_s=window_s,
        setup_s=setup_s, gen_final=gen_final, reaps=reaps, trace=trace,
        config=config, cell=cell, device=device, peaks=peaks,
        harness=harness)
    wanted = bench["per_layer" if args.trace else "end_to_end"]
    metrics = {}
    for m in wanted:
        if not metric_wanted(m, args.workload):
            continue
        value = harness.load_module("metrics", m["name"]).read(mctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    attempted = snap1["gen"]["forwarded"] - snap0["gen"]["forwarded"]
    failed = judge.failed_records(config, snap0, snap1, end)
    device["memory_peak_bytes"] = int(mem)
    result = {"correct": bool(verdict["correct"]), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    if args.rehearse:
        result["rehearse"] = True
    result["compared_detail"] = verdict["detail"]
    result["compared"] = verdict["compared"]
    for name, c in verdict["compared"].items():
        print(f"compared {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
