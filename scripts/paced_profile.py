"""Open-loop paced latency/throughput curve.

Drives the real Engine with PacedSource at a grid of offered loads and
prints ONE JSON line per config with achieved rate and per-record
arrival→verdict-sunk latency percentiles, a ``readback`` block (D2H
bytes per sunk batch, compact vs fallback sink counts, sink-thread
occupancy), plus a final summary line.

``--baseline`` serves through the PRE-compaction engine configuration —
single-thread sink, full [B] verdict fetch (verdict_k=0) — so the same
build measures both sides of the threaded-sink/compact-wire change.
``--loads`` extends/overrides the B=2048 load column (Mpps, comma
separated) to find where achieved≈offered stops holding.

The engine compiles OUTSIDE the paced clock (reset_stream reuse).
Runs on the TPU; ``JAX_PLATFORMS=cpu`` runs it on the CPU on purpose
(every row names its backend).

Usage: [JAX_PLATFORMS=cpu] python scripts/paced_profile.py
           [--baseline] [--loads=0.8,1.0,1.5] [out.json]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

GRID = (
    # (batch, depth, load_mpps, deadline_us)
    (256, 2, 0.01, 200),
    (1024, 2, 0.2, 1000),
    (1024, 4, 0.5, 1000),
    (2048, 4, 0.8, 2000),
    (2048, 4, 1.0, 2000),
)


def main() -> int:
    import jax

    from flowsentryx_tpu.core import runtime

    runtime.require_platform("paced_profile")
    runtime.place_compile_cache()

    from flowsentryx_tpu.core import schema
    from flowsentryx_tpu.core.config import BatchConfig, FsxConfig, TableConfig

    from flowsentryx_tpu.engine import Engine, NullSink, PacedSource

    argv = [a for a in sys.argv[1:]]
    baseline = "--baseline" in argv
    if baseline:
        argv.remove("--baseline")
    loads_override = None
    for a in list(argv):
        if a.startswith("--loads="):
            loads_override = [float(x) for x in a.split("=", 1)[1].split(",")]
            argv.remove(a)

    grid = list(GRID)
    if loads_override:
        # replace the B=2048 rows with the requested load column
        grid = [g for g in grid if g[0] != 2048]
        grid += [(2048, 4, ld, 2000) for ld in loads_override]

    dev = jax.devices()[0]
    out = {"ts": time.time(), "backend": dev.platform,
           "device_kind": dev.device_kind, "baseline": baseline,
           "rows": []}

    rng = np.random.default_rng(0)
    pool = np.zeros(1 << 14, dtype=schema.FLOW_RECORD_DTYPE)
    pool["saddr"] = rng.integers(1, 1 << 13, len(pool)).astype(np.uint32)
    pool["pkt_len"] = rng.integers(64, 1500, len(pool))
    pool["feat"] = rng.integers(0, 1 << 20, (len(pool), 8))

    engines: dict = {}
    for bsz, depth, load, dl in grid:
        batch_cfg = (BatchConfig(max_batch=bsz, deadline_us=dl, verdict_k=0)
                     if baseline
                     else BatchConfig(max_batch=bsz, deadline_us=dl))
        cfg = FsxConfig(table=TableConfig(capacity=1 << 16), batch=batch_cfg)
        rate = load * 1e6
        total = int(max(rate * 3, 1))
        src = PacedSource(pool, rate_pps=rate, total=total)
        key = (bsz, dl)
        eng = engines.get(key)
        if eng is None:
            eng = Engine(cfg, src, NullSink(),
                         readback_depth=depth, wire=schema.WIRE_COMPACT16,
                         sink_thread=False if baseline else None)
            quant = schema.wire_quant_for(eng.params)
            warm = schema.encode_compact(pool[:bsz], bsz, t0_ns=0, **quant)
            eng.table, eng.stats, o = eng.step(
                eng.table, eng.stats, eng.params, warm)
            jax.block_until_ready(o.verdict)
            engines[key] = eng
        from flowsentryx_tpu.benchmarks import (
            paced_latency_run, summarize_latencies,
        )

        lats, wall, erep = paced_latency_run(eng, src, readback_depth=depth)
        row = {
            "batch": bsz, "depth": depth, "load_mpps": load,
            "deadline_us": dl,
            **summarize_latencies(lats),
            "achieved_mpps": round(len(lats) / wall / 1e6, 4),
            "offered_all_consumed": bool(len(lats) >= total),
            "readback": erep.readback,
            # the engine's in-band seal->verdict HDR block (ISSUE 11)
            "engine_latency": erep.latency,
        }
        out["rows"].append(row)
        print(json.dumps(row), flush=True)

    print(json.dumps({"summary": True, **{k: out[k] for k in
                                          ("backend", "device_kind",
                                           "baseline")},
                      "n_rows": len(out["rows"])}))
    if argv:
        with open(argv[0], "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
