"""Bounded CPU boot-to-serving smoke — the compile-cache CI gate.

Boot latency can only be measured in FRESH processes (a second boot in
the same process rides jax's in-memory caches and proves nothing), so
every leg below is a subprocess of this script, each reporting its
import wall, its engine boot block, and a digest of what it served:

* **cold** — empty cache dir, full ``warm()``: every staged variant
  (singles and each ladder rung) compiles and is stored.
* **cached** — same staged shape, ``warm(tiered=True)``: every variant
  must load from the cache (zero misses/compiles), serving must open
  >= MIN_SPEEDUP x faster than the cold leg (engine boot-to-serving,
  the wall the cache governs; import is reported alongside), and the
  background fill must complete with nothing pending and no error.
* **spare** — the elastic GROW path end-to-end: a FRESH cache dir is
  populated by :func:`cluster.runner.prewarm_main` (the exact child
  the supervisor spawns at elastic-fleet boot), then a "spare" engine
  of the fleet's geometry boots against it — all-cache-hit is the
  gate, because a real GROW spawn happens while the burst it answers
  is already landing.

Zero parity drift is gated across all three legs: identical stats and
identical blacklist (keys AND untils) — the cache accelerates boots,
it must never change a verdict.

Results merge into ``artifacts/BOOT_r24.json`` under ``"smoke"`` (the
paced/fleet A/B evidence in the same artifact is preserved).

Usage: JAX_PLATFORMS=cpu python scripts/boot_smoke.py [out.json]
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BATCH = 256
N_BATCHES = 24
MIN_SPEEDUP = 3.0       # the acceptance floor; measured is ~10x+
CHILD_TIMEOUT_S = 420


def _cfg_json() -> str:
    import dataclasses

    from flowsentryx_tpu.core.config import FsxConfig

    cfg = FsxConfig()
    cfg = dataclasses.replace(
        cfg,
        batch=dataclasses.replace(cfg.batch, max_batch=BATCH),
        table=dataclasses.replace(cfg.table, capacity=1 << 14),
        limiter=dataclasses.replace(
            cfg.limiter, pps_threshold=200.0, bps_threshold=1e9),
    )
    return cfg.to_json()


def _child(mode: str, cache_dir: str, out_path: str) -> int:
    """One fresh-process boot: import (timed) -> engine(compile_cache)
    -> warm -> sealed drain -> JSON report for the parent to gate."""
    t_imp = time.perf_counter()
    from flowsentryx_tpu.core.config import FsxConfig
    from flowsentryx_tpu.engine import ArraySource, CollectSink, Engine
    from flowsentryx_tpu.engine.traffic import (
        Scenario, TrafficGen, TrafficSpec,
    )

    import_s = time.perf_counter() - t_imp
    cfg = FsxConfig.from_json(_cfg_json())
    recs = TrafficGen(TrafficSpec(
        scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
        n_attack_ips=8, n_benign_ips=24, attack_fraction=0.8, seed=3,
    )).next_records(N_BATCHES * BATCH)
    sink = CollectSink()
    eng = Engine(cfg, ArraySource(recs), sink, mega_n="auto",
                 sink_thread=False, compile_cache=cache_dir)
    eng.boot_import_s = round(import_s, 4)
    eng.warm(tiered=(mode != "cold"))
    fill_ok = eng.warm_fill_join(CHILD_TIMEOUT_S / 2)
    rep = eng.run()
    blocked_sha = hashlib.sha256(json.dumps(
        sorted((int(k), round(float(v), 6))
               for k, v in sink.blocked.items())).encode()).hexdigest()
    with open(out_path, "w") as f:
        json.dump({
            "mode": mode,
            "import_s": round(import_s, 4),
            "boot": rep.boot,
            "fill_joined": fill_ok,
            "records": rep.records,
            "stats": rep.stats,
            "blocked_sha": blocked_sha,
        }, f, indent=2)
    return 0


def _prewarm(cache_dir: str) -> int:
    """The supervisor's elastic pre-warm child, verbatim."""
    from flowsentryx_tpu.cluster.runner import prewarm_main

    return prewarm_main({
        "cfg_json": _cfg_json(),
        "mega": "auto",
        "compile_cache": cache_dir,
    })


def _spawn(args: list[str]) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        env=env, timeout=CHILD_TIMEOUT_S, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"boot child {args} rc={proc.returncode}:\n{proc.stderr[-2000:]}")


def main() -> int:
    t_start = time.perf_counter()
    failures: list[str] = []
    work = tempfile.mkdtemp(prefix="fsx_boot_smoke_")
    cache = os.path.join(work, "cache")
    legs: dict[str, dict] = {}

    for mode in ("cold", "cached"):
        out = os.path.join(work, f"{mode}.json")
        _spawn(["--child", mode, cache, out])
        legs[mode] = json.loads(open(out).read())

    # -- the GROW-spare path: prewarm_main fills a FRESH cache, the
    # spare boots against it all-cache-hit (the supervisor spawns this
    # exact child at elastic fleet boot; geometry matches by spec)
    cache2 = os.path.join(work, "cache_fleet")
    _spawn(["--prewarm", cache2])
    out = os.path.join(work, "spare.json")
    _spawn(["--child", "spare", cache2, out])
    legs["spare"] = json.loads(open(out).read())

    cold, cached, spare = legs["cold"], legs["cached"], legs["spare"]
    n_variants = len(cold["boot"]["variants"])

    # -- gates: the cold leg stored the whole ladder ------------------------
    c = cold["boot"]["cache"]
    if not (n_variants >= 4 and c["stores"] == n_variants):
        failures.append(
            f"cold leg stored {c['stores']} of {n_variants} variants "
            f"(expected singles + the full ladder): {c}")

    # -- gates: the cached leg is all hits, >= MIN_SPEEDUP x faster --------
    c = cached["boot"]["cache"]
    srcs = {k: v["source"] for k, v in cached["boot"]["variants"].items()}
    if c["hits"] != n_variants or c["misses"] or any(
            s != "cache" for s in srcs.values()):
        failures.append(
            f"cached leg was not all-cache-hit: {c} variants={srcs}")
    cold_s = cold["boot"]["serving_ready_s"]
    cached_s = cached["boot"]["serving_ready_s"]
    speedup = cold_s / max(cached_s, 1e-9)
    if speedup < MIN_SPEEDUP:
        failures.append(
            f"cached boot-to-serving {cached_s:.3f}s is only "
            f"{speedup:.1f}x faster than cold {cold_s:.3f}s "
            f"(floor {MIN_SPEEDUP}x)")
    if not cached["fill_joined"]:
        failures.append("cached leg's background fill never finished")
    if cached["boot"].get("fill_pending") or "fill_error" in cached["boot"]:
        failures.append(
            f"cached leg fill did not complete cleanly: "
            f"pending={cached['boot'].get('fill_pending')} "
            f"error={cached['boot'].get('fill_error')}")

    # -- gates: the GROW spare is pure cache hits ---------------------------
    c = spare["boot"]["cache"]
    if c["hits"] != n_variants or c["misses"] or c["stores"]:
        failures.append(
            f"GROW spare recompiled: the pre-warm child did not cover "
            f"the fleet geometry: {c}")

    # -- gates: zero parity drift across every leg --------------------------
    for mode in ("cached", "spare"):
        leg = legs[mode]
        if leg["records"] != cold["records"]:
            failures.append(f"{mode} leg served {leg['records']} records "
                            f"vs cold {cold['records']}")
        if leg["stats"] != cold["stats"]:
            failures.append(f"{mode} leg stats drifted from cold: "
                            f"{leg['stats']} != {cold['stats']}")
        if leg["blocked_sha"] != cold["blocked_sha"]:
            failures.append(
                f"{mode} leg blacklist (keys/untils) drifted from cold")

    smoke = {
        "ts": time.time(),
        "wall_s": round(time.perf_counter() - t_start, 2),
        "config": {"batch": BATCH, "n_batches": N_BATCHES,
                   "mega": "auto",
                   "min_speedup": MIN_SPEEDUP},
        "cold": {"import_s": cold["import_s"],
                 "boot": cold["boot"]},
        "cached": {"import_s": cached["import_s"],
                   "boot": cached["boot"]},
        "grow_spare": {"import_s": spare["import_s"],
                       "boot": spare["boot"]},
        "serving_ready_speedup": round(speedup, 2),
        "ok": not failures,
        "failures": failures,
    }

    out_path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "artifacts", "BOOT_r24.json")
    try:
        artifact = json.loads(open(out_path).read())
    except (OSError, ValueError):
        artifact = {}
    artifact["smoke"] = smoke
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"boot smoke: wrote {out_path}")
    print(f"boot smoke: cold serving_ready={cold_s:.3f}s cached="
          f"{cached_s:.3f}s ({speedup:.1f}x, floor {MIN_SPEEDUP}x); "
          f"spare hits={spare['boot']['cache']['hits']}/{n_variants} "
          f"misses={spare['boot']['cache']['misses']}")
    for msg in failures:
        print(f"boot smoke: FAIL {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        sys.exit(_child(sys.argv[2], sys.argv[3], sys.argv[4]))
    if len(sys.argv) >= 2 and sys.argv[1] == "--prewarm":
        sys.exit(_prewarm(sys.argv[2]))
    sys.exit(main())
