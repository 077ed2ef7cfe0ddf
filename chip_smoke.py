#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the normal serving path once on one TPU chip, through the entry
points a user types, at BASELINE config 5 (``benchmarks.py``: table
2^20 rows, batch 16,384, limiter 1000 pps / 125 MB/s, artifact
``artifacts/logreg_int8.npz``, ``--mega auto``):

    fsxd --sim --shards 2 --pace  ->  two shm feature rings
      ->  fsx serve --ingest-workers 2 (ShardedIngest, sealed loop,
          fused step, sink)  ->  shm verdict ring  ->  fsxd

Phases, each printing one JSON line, any failure a non-zero exit:

1. ``build``  — ``fsxd`` built from the committed sources into a
   directory this run makes; a binary found on disk is never used.
2. ``serve``  — the path above, >= 256 batches' worth of records, ended
   by ``fsx serve --seconds`` with the producer done and the rings idle.
   The daemon starts first, as in production, so the engine boots into
   the backlog the daemon buffered (drained through the mega rungs) and
   then serves the paced stream live.  The rings are sized to hold the
   whole stream, so no boot time can cause a ring-full drop.
3. ``parity`` — one record file made from ``--seed`` by ``TrafficGen``
   served twice by ``fsx serve --records``: on the chip, and in a child
   with ``JAX_PLATFORMS=cpu``.  That child is a comparison, named as
   such, not a fallback.
4. ``cache``  — the chip side of ``parity`` booted once more in a fresh
   child: what JAX's persistent cache stored before must load now.

``--chips 4`` runs one thing and what it is compared with and no other
phase: the parity record file through ``fsx serve --mesh 4`` on the four
chips, through ``--mesh 1`` on one chip, and through ``--mesh 4`` on four
virtual CPU devices (the same slot geometry, so equal in everything).

The last line of stdout is the result,
``{"ok": true, "device": {"platform", "kind", "count"}}``, with the
device taken from the serve child's own report.  This process never
imports JAX: it owns no chip, so its children can — one at a time.
``--rehearse`` is the same run at a tiny size for a machine without a
chip (``JAX_PLATFORMS=cpu``); it proves the control flow, nothing more.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARTIFACT = ROOT / "artifacts" / "logreg_int8.npz"

#: BASELINE config 5 geometry and the traffic that drives it.
FULL = dict(
    capacity=1 << 20, max_batch=16384,
    attack_ips=1 << 19, benign_ips=1 << 19,   # config 5: 1M sources
    min_batches=256,                          # records the serve must reach
    # 280k records/s a shard: a 16,384-record batch spans 59 ms of record
    # time, inside the compact16 wire's 65 ms — slower and batches seal
    # at the span, never full.  57 s paced: a cold boot (~33 s) plus the
    # ~6 records a source must show before it can be blocked still
    # leaves the daemon alive to honour the first blocks
    sim_packets=32_000_000, sim_rate=560_000,
    ring_capacity=1 << 24,                     # per shard: holds the stream
    # serving goes on this long after the paced stream has ended, to
    # drain what the daemon buffered while the engine booted
    drain_margin_s=50,
    parity_batches=256,
    child_timeout_s=600,
)
#: The same run cut to seconds, for --rehearse on the CPU.
TINY = dict(
    capacity=1 << 14, max_batch=1024,
    attack_ips=1 << 9, benign_ips=1 << 9,
    min_batches=64,
    sim_packets=200_000, sim_rate=10_000,
    ring_capacity=1 << 17,
    drain_margin_s=10,
    parity_batches=64,
    child_timeout_s=300,
)
ATTACK_FRACTION = 0.8
SHARDS = 2


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Children:
    """Every process this run starts, each in its own session so the
    whole tree (ingest workers included) can be stopped on the way
    out, whatever happened."""

    def __init__(self) -> None:
        self.procs: list[subprocess.Popen] = []

    def start(self, cmd: list[str], **kw) -> subprocess.Popen:
        p = subprocess.Popen(cmd, start_new_session=True, **kw)
        self.procs.append(p)
        return p

    def stop_all(self) -> None:
        for p in self.procs:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


def fsx(*args: str) -> list[str]:
    return [sys.executable, "-m", "flowsentryx_tpu.cli", *args]


def last_json(text: str, what: str) -> dict:
    """The JSON object a child printed last (``fsx serve`` pretty-prints
    its report; ``fsxd`` prints one line)."""
    start = text.rfind("\n{")
    start = 0 if text.startswith("{") and start < 0 else start + 1
    try:
        return json.loads(text[start:])
    except ValueError:
        raise SmokeFailure(f"{what} printed no JSON report; its output "
                           f"ends: {text[-400:]!r}") from None


def run_serve(kids: Children, work: Path, label: str, args: list[str],
              env: dict, timeout_s: float) -> dict:
    """One ``fsx serve`` child to completion; returns its report."""
    out, err = work / f"{label}.out", work / f"{label}.err"
    with open(out, "w") as fo, open(err, "w") as fe:
        p = kids.start(fsx("serve", *args), cwd=ROOT, env=env,
                       stdout=fo, stderr=fe)
        try:
            rc = p.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{label}: fsx serve still running after {timeout_s:.0f}s; "
                f"stderr ends: {err.read_text()[-800:]!r}") from None
    check(rc == 0, f"{label}: fsx serve exited {rc}; stderr ends: "
                   f"{err.read_text()[-1200:]!r}")
    return last_json(out.read_text(), f"{label}: fsx serve")


def write_config(work: Path, size: dict, seed: int) -> Path:
    """BASELINE config 5 as a config file: the defaults ARE its limiter
    and model; the file fixes table, batch and — from the seed — the
    table salt, so two boots stage the same programs."""
    salt = random.Random(seed).getrandbits(32) | 1
    path = work / "config5.json"
    path.write_text(json.dumps({
        "limiter": {"kind": "fixed_window", "pps_threshold": 1000.0,
                    "bps_threshold": 125e6},
        "table": {"capacity": size["capacity"], "salt": salt},
        "batch": {"max_batch": size["max_batch"]},
    }))
    return path


def serve_args(cfg: Path) -> list[str]:
    return ["--config", str(cfg), "--artifact", str(ARTIFACT),
            "--mega", "auto"]


def device_of(rep: dict, label: str, want_platform: str) -> dict:
    dev = rep.get("device") or {}
    check(dev.get("platform") == want_platform,
          f"{label}: the report's device block says {dev!r}, "
          f"wanted platform {want_platform!r}")
    return dev


# -- phases -----------------------------------------------------------------

def phase_build(work: Path) -> Path:
    """fsxd from the committed sources, into this run's directory."""
    t0 = time.perf_counter()
    build = work / "fsxd_build"
    r = subprocess.run(
        ["make", "-B", "-C", str(ROOT / "daemon"), f"BUILD={build}"],
        capture_output=True, text=True)
    check(r.returncode == 0, f"build: make failed: {r.stderr[-800:]!r}")
    binary = build / "fsxd"
    check(binary.is_file(), f"build: {binary} was not produced")
    emit({"phase": "build", "ok": True, "binary": str(binary),
          "seconds": round(time.perf_counter() - t0, 2)})
    return binary


def phase_serve(kids: Children, work: Path, fsxd: Path, cfg: Path,
                size: dict, seed: int, env: dict,
                want_platform: str) -> dict:
    fring, vring = work / "fring", work / "vring"
    paced_s = size["sim_packets"] / size["sim_rate"]
    t0 = time.perf_counter()
    with open(work / "fsxd.err", "w") as fe:
        daemon = kids.start(
            [str(fsxd), "--sim", "--shards", str(SHARDS), "--pace",
             "--rate", str(size["sim_rate"]),
             "--packets", str(size["sim_packets"]),
             "--attack-fraction", str(ATTACK_FRACTION),
             "--attack-ips", str(size["attack_ips"]),
             "--benign-ips", str(size["benign_ips"]),
             "--ring-capacity", str(size["ring_capacity"]),
             "--feature-ring", str(fring), "--verdict-ring", str(vring),
             "--seed", str(seed)],
            stdout=subprocess.PIPE, stderr=fe, text=True)
        # --seconds counts from the start of serving, which is after the
        # daemon started: the whole paced stream plus a drain margin
        # always ends after the producer did
        rep = run_serve(
            kids, work, "serve",
            serve_args(cfg) + [
                "--feature-ring", str(fring), "--verdict-ring", str(vring),
                "--ingest-workers", str(SHARDS),
                "--seconds", str(paced_s + size["drain_margin_s"])],
            env, size["child_timeout_s"])
        try:
            dout, _ = daemon.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("serve: fsxd outlived fsx serve") from None
    check(daemon.returncode == 0, f"serve: fsxd exited {daemon.returncode}")
    sim = last_json(dout, "fsxd")
    wall = time.perf_counter() - t0
    (work / "serve_report.json").write_text(json.dumps(rep))

    # the transport as `fsx status` sees it, and the report through the
    # same merge an operator would query
    rings = []
    for k in range(SHARDS):
        r = subprocess.run(
            fsx("status", "--feature-ring", f"{fring}.{k}",
                "--verdict-ring", str(vring),
                "--engine-report", str(work / "serve_report.json")),
            cwd=ROOT, env=env, capture_output=True, text=True)
        check(r.returncode == 0, f"serve: fsx status failed: {r.stderr!r}")
        rings.append(json.loads(r.stdout))
    status = rings[0]

    dev = device_of(rep, "serve", want_platform)
    forwarded = (sim["produced"] - sim["suppressed"]
                 - sim["dropped_ring_full"])
    ingest = rep["ingest"]
    line = {
        "phase": "serve", "device": dev,
        "table_rows": size["capacity"], "max_batch": size["max_batch"],
        "sim": {**sim, "rate_pps": size["sim_rate"],
                "attack_ips": size["attack_ips"],
                "benign_ips": size["benign_ips"]},
        "forwarded": forwarded, "records": rep["records"],
        "batches": rep["batches"],
        "records_per_batch": round(rep["records"] / max(rep["batches"], 1)),
        "route_drop": rep["route_drop"],
        "serve_wall_s": rep["wall_s"],
        "records_per_s_over_wall": rep["records_per_s"],
        "phase_wall_s": round(wall, 2),
        "boot": rep["boot"], "dispatch_groups": rep["dispatch"]["group_hist"],
        "stats": rep["stats"], "blocked_sources": rep["blocked_sources"],
        "verdict_ring_written": status["verdict_ring"]["produced"],
        "verdict_ring": {k: rep["readback"].get(k) for k in (
            "verdict_ring_dropped", "verdict_ring_waits",
            "verdict_ring_fill_peak")},
        "feature_backlog": [r["feature_ring"]["backlog"] for r in rings],
        "ingest_drops": {k: ingest[k] for k in (
            "dropped_tail_batches", "dropped_emit_batches",
            "bad_wire_slots", "quarantined_batches")},
        "health": rep["health"], "table": rep["table"],
        "latency_us": (rep["latency"] or {}).get("seal_to_verdict"),
    }
    emit(line)
    check(status["device"]["platforms"] == [want_platform],
          f"serve: fsx status --engine-report lost the device block: "
          f"{status.get('device')!r}")
    check(sim["dropped_ring_full"] == 0,
          f"serve: {sim['dropped_ring_full']} records dropped ring-full")
    check(rep["records"] == forwarded,
          f"serve: engine served {rep['records']} records, fsxd "
          f"forwarded {forwarded}")
    check(all(b == 0 for b in line["feature_backlog"]),
          f"serve: feature rings not idle at the end: "
          f"{line['feature_backlog']}")
    check(rep["records"] >= size["min_batches"] * size["max_batch"],
          f"serve: {rep['records']} records is under "
          f"{size['min_batches']} batches of {size['max_batch']}")
    check(rep["route_drop"] == 0, f"serve: route_drop {rep['route_drop']}")
    check(not any(line["ingest_drops"].values()),
          f"serve: ingest fail-opens counted: {line['ingest_drops']}")
    check(line["verdict_ring_written"] > 0 and rep["blocked_sources"] > 0,
          "serve: no block reached the verdict ring")
    check(rep["readback"].get("verdict_ring_dropped") == 0,
          f"serve: blocks decided and not written to the verdict ring: "
          f"{line['verdict_ring']}")
    check(rep["health"]["state"] == "healthy",
          f"serve: health {rep['health']}")
    check(rep["table"]["tracked"] > 0,
          "serve: table_summary tracked no flow")
    return rep


def make_records(work: Path, size: dict, seed: int) -> Path:
    """The parity stream: config 5's TrafficSpec from the seed.  Numpy
    only — importing the generator must not pull JAX into this
    process."""
    from flowsentryx_tpu.engine.traffic import (
        Scenario, TrafficGen, TrafficSpec,
    )

    spec = TrafficSpec(scenario=Scenario.MIXED_L34_1M, rate_pps=1e7,
                       attack_fraction=ATTACK_FRACTION, seed=seed)
    if size is TINY:
        spec = spec.with_(n_attack_ips=size["attack_ips"],
                          n_benign_ips=size["benign_ips"])
    path = work / "parity_records.bin"
    n = size["parity_batches"] * size["max_batch"]
    path.write_bytes(TrafficGen(spec).next_records(n).tobytes())
    return path


def table_of(ckpt: Path) -> dict:
    """{source key: table row} out of an ``fsx serve --checkpoint``."""
    import numpy as np

    from flowsentryx_tpu.core.schema import TABLE_COLUMN_NAMES

    with np.load(ckpt) as z:
        key = z["table_key"]
        live = key != 0
        cols = np.stack([z[f"table_{c}"][live] for c in TABLE_COLUMN_NAMES],
                        axis=1)
    blocked_col = TABLE_COLUMN_NAMES.index("blocked_until")
    keys = key[live]
    order = np.argsort(keys, kind="stable")
    return {"keys": keys[order], "rows": cols[order],
            "blocked": set(keys[cols[:, blocked_col] > 0].tolist())}


def serve_records(kids: Children, work: Path, label: str, cfg: Path,
                  records: Path, env: dict, size: dict,
                  extra: tuple[str, ...] = ()) -> tuple[dict, dict]:
    ckpt = work / f"{label}.ckpt.npz"
    rep = run_serve(
        kids, work, label,
        serve_args(cfg) + ["--records", str(records),
                           "--checkpoint", str(ckpt), *extra],
        env, size["child_timeout_s"])
    return rep, table_of(ckpt)


def compare(a: tuple[dict, dict], b: tuple[dict, dict],
            names: tuple[str, str]) -> dict:
    """Integer stats counters and blocked-source sets of two runs over
    the same records; rows that differ are listed, not hidden."""
    import numpy as np

    (rep_a, tab_a), (rep_b, tab_b) = a, b
    stat_diff = {k: [rep_a["stats"][k], rep_b["stats"][k]]
                 for k in rep_a["stats"]
                 if rep_a["stats"][k] != rep_b["stats"][k]}
    same_keys = np.array_equal(tab_a["keys"], tab_b["keys"])
    row_diffs: list = []
    n_row_diff = None
    if same_keys:
        bad = np.flatnonzero(
            ~np.all(tab_a["rows"] == tab_b["rows"], axis=1))
        n_row_diff = int(bad.size)
        for i in bad[:5]:
            row_diffs.append({
                "source": int(tab_a["keys"][i]),
                names[0]: tab_a["rows"][i].tolist(),
                names[1]: tab_b["rows"][i].tolist()})
    only_a = sorted(tab_a["blocked"] - tab_b["blocked"])
    only_b = sorted(tab_b["blocked"] - tab_a["blocked"])
    return {
        "records": [rep_a["records"], rep_b["records"]],
        "blocked_sources": [len(tab_a["blocked"]), len(tab_b["blocked"])],
        "blocked_set_equal": not only_a and not only_b,
        "blocked_only_counts": [len(only_a), len(only_b)],
        f"blocked_only_{names[0]}": only_a[:5],
        f"blocked_only_{names[1]}": only_b[:5],
        "stats_equal": not stat_diff, "stats_diff": stat_diff,
        "tracked_sources": [len(tab_a["keys"]), len(tab_b["keys"])],
        "tracked_keys_equal": same_keys,
        "table_rows_differing": n_row_diff, "first_row_diffs": row_diffs,
    }


def check_same(cmp: dict, label: str) -> None:
    check(cmp["records"][0] == cmp["records"][1],
          f"{label}: the two runs served different record counts "
          f"{cmp['records']}")
    check(cmp["blocked_set_equal"],
          f"{label}: blocked-source sets differ: {cmp}")
    check(cmp["blocked_sources"][0] > 0,
          f"{label}: nothing was blocked, the comparison is empty")
    if not cmp["stats_equal"]:
        # single records at a quantisation boundary that leave the set
        # equal: printed above with their first cases (ROADMAP speed
        # item 4(e)), bounded here so a real divergence still fails
        off = sum(abs(x - y) for x, y in cmp["stats_diff"].values())
        check(off <= 64, f"{label}: stats counters differ by {off} "
                         f"records in total: {cmp['stats_diff']}")


def phase_parity(kids: Children, work: Path, cfg: Path, records: Path,
                 size: dict, env: dict, want_platform: str):
    t0 = time.perf_counter()
    chip = serve_records(kids, work, "parity_chip", cfg, records, env, size)
    device_of(chip[0], "parity", want_platform)
    cpu_env = dict(env, JAX_PLATFORMS="cpu")
    cpu = serve_records(kids, work, "parity_cpu_reference", cfg, records,
                        cpu_env, size)
    device_of(cpu[0], "parity (CPU comparison child)", "cpu")
    cmp = compare(chip, cpu, ("chip", "cpu"))
    emit({"phase": "parity",
          "compared": [f"fsx serve --records on {want_platform}",
                       "fsx serve --records in a JAX_PLATFORMS=cpu child "
                       "(the comparison, not a fallback)"],
          "batches": size["parity_batches"], **cmp,
          "chip_records_per_s": chip[0]["records_per_s"],
          "chip_dispatch_groups": chip[0]["dispatch"]["group_hist"],
          "seconds": round(time.perf_counter() - t0, 2)})
    check_same(cmp, "parity")
    return chip[0]


def phase_cache(kids: Children, work: Path, cfg: Path, records: Path,
                size: dict, env: dict, cold: dict, before: dict) -> None:
    """``cold`` is the serve phase's boot (nothing cached), ``before``
    the parity chip child's — the same command as this boot, so what it
    loaded or stored is exactly what this one must load."""
    t0 = time.perf_counter()
    rep, _ = serve_records(kids, work, "cache_boot", cfg, records, env,
                           size)
    now = rep["boot"]["jax_cache"]
    was = before["boot"]["jax_cache"]
    first = cold["boot"]["jax_cache"]
    emit({"phase": "cache", "counters": "JAX's own persistent-cache "
          "events (core/runtime.py CompileCounters)",
          "cache_dir": now["dir"],
          "first_boot": {"backend_compile_s": first["backend_compile_s"],
                         "stores": first["stores"], "hits": first["hits"],
                         "serving_ready_s": cold["boot"]["serving_ready_s"]},
          "same_command_before": {k: was[k] for k in (
              "backend_compile_s", "stores", "hits")},
          "this_boot": {"backend_compile_s": now["backend_compile_s"],
                        "stores": now["stores"], "hits": now["hits"],
                        "serving_ready_s": rep["boot"]["serving_ready_s"]},
          "seconds": round(time.perf_counter() - t0, 2)})
    check(now["hits"] > 0,
          "cache: this boot loaded nothing from JAX's cache")
    check(now["hits"] >= was["hits"] + was["stores"],
          f"cache: this boot loaded {now['hits']} programs, the same "
          f"command before it loaded {was['hits']} and stored "
          f"{was['stores']}: something stored was compiled again")


FOUR_CPU_DEVICES = "--xla_force_host_platform_device_count=4"


def phase_mesh4(kids: Children, work: Path, cfg: Path, records: Path,
                size: dict, env: dict, want_platform: str) -> dict:
    """``--mesh 4`` on the four chips against two references.

    The SAME program on four virtual CPU devices has the same slot
    geometry, so everything must be equal, as in ``parity``.
    ``--mesh 1`` on one chip has another geometry: the table's rows
    shard by owner bits, so WHICH flows lose slot arbitration (or find
    their probe window full — config 5 fills the table past 90 %)
    differs, and an untracked flow is dropped record by record but not
    blacklisted.  So across geometries the blocked sets differ in some
    of the flows that went untracked for a batch or more, and with them
    the counters (a source blocked in one run has its later records
    dropped there and judged one by one in the other).  No report
    counts those flows, so the bound here is on gross divergence only:
    2 % of the sources, set before the first four-chip run from CPU
    runs of both geometries (0.02 % at a quarter-full table, 0.27 % at
    86 % full)."""
    import numpy as np

    from flowsentryx_tpu.core.schema import FLOW_RECORD_DTYPE

    t0 = time.perf_counter()
    n = 4
    sources = len(np.unique(np.fromfile(records, FLOW_RECORD_DTYPE)["saddr"]))
    m4 = serve_records(kids, work, "mesh4", cfg, records, env, size,
                       ("--mesh", str(n)))
    dev = device_of(m4[0], "mesh4", want_platform)
    check(dev["count"] == n,
          f"mesh4: the table landed on {dev['count']} device(s), not {n}")
    m1 = serve_records(kids, work, "mesh1", cfg, records, env, size,
                       ("--mesh", "1"))
    check(m1[0]["device"]["count"] == 1,
          f"mesh1: device block {m1[0]['device']}")
    cpu_env = dict(env, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        env.get("XLA_FLAGS", "").replace(FOUR_CPU_DEVICES, "")
        + " " + FOUR_CPU_DEVICES).strip())
    m4cpu = serve_records(kids, work, "mesh4_cpu_reference", cfg, records,
                          cpu_env, size, ("--mesh", str(n)))
    check(m4cpu[0]["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": n},
          f"mesh4 CPU comparison child: device {m4cpu[0]['device']}")
    same_geometry = compare(m4, m4cpu, ("mesh4", "mesh4_cpu"))
    cmp = compare(m4, m1, ("mesh4", "mesh1"))
    untracked = [sources - t for t in cmp["tracked_sources"]]
    emit({"phase": "mesh4", "device": dev,
          "batches": size["parity_batches"], "sources": sources,
          "route_drop": {"mesh4": m4[0]["route_drop"],
                         "mesh1": m1[0]["route_drop"]},
          "records_per_s": {"mesh4": m4[0]["records_per_s"],
                            "mesh1": m1[0]["records_per_s"]},
          "boot_serving_ready_s": {
              "mesh4": m4[0]["boot"]["serving_ready_s"],
              "mesh1": m1[0]["boot"]["serving_ready_s"]},
          "against_mesh4_on_4_virtual_cpu_devices (same geometry; the "
          "comparison, not a fallback)": same_geometry,
          "against_mesh1_on_one_chip (other geometry)": {
              **{k: v for k, v in cmp.items()
                 # slot layout differs by design across mesh sizes
                 if k not in ("tracked_keys_equal", "table_rows_differing",
                              "first_row_diffs")},
              "untracked_sources": untracked},
          "seconds": round(time.perf_counter() - t0, 2)})
    check(m4[0]["route_drop"] == 0, "mesh4: route_drop counted")
    check_same(same_geometry, "mesh4 against the same program on the CPU")
    check(cmp["records"][0] == cmp["records"][1]
          and m4[0]["batches"] == m1[0]["batches"],
          f"mesh4 against mesh1: served {cmp['records']} records")
    off = (sum(cmp["blocked_only_counts"])
           + sum(abs(x - y) for x, y in cmp["stats_diff"].values()))
    check(off <= sources // 50,
          f"mesh4 against mesh1: blocked sets and counters differ by "
          f"{off}, more than 2 % of the {sources} sources")
    return dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only --mesh 4 and what it is compared "
                         "with, on the four-chip host")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on JAX_PLATFORMS=cpu: control flow "
                         "only, no chip, no claim")
    args = ap.parse_args()

    for need in ("flowsentryx_tpu/cli.py", "daemon/fsxd.cpp",
                 "kern/fsx_schema.h", "artifacts/logreg_int8.npz"):
        if not (ROOT / need).is_file():
            print(f"chip_smoke: {need} is missing next to this script; "
                  "run it from a checkout of the repository",
                  file=sys.stderr)
            return 2
    env = dict(os.environ)
    if args.rehearse:
        size, want = TINY, "cpu"
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                                + FOUR_CPU_DEVICES).strip()
    else:
        size, want = FULL, "tpu"
        asked = env.get("JAX_PLATFORMS")
        if asked and "tpu" not in asked.split(","):
            print(f"chip_smoke: JAX_PLATFORMS={asked} keeps JAX off the "
                  "TPU; this smoke proves the chip path and has no "
                  "other (--rehearse is the tiny CPU rehearsal)",
                  file=sys.stderr)
            return 2

    kids = Children()
    work = Path(tempfile.mkdtemp(prefix="fsx_chip_smoke_"))
    try:
        cfg = write_config(work, size, args.seed)
        records = make_records(work, size, args.seed)
        if args.chips == 4:
            dev = phase_mesh4(kids, work, cfg, records, size, env, want)
        else:
            fsxd = phase_build(work)
            cold = phase_serve(kids, work, fsxd, cfg, size, args.seed,
                               env, want)
            before = phase_parity(kids, work, cfg, records, size, env,
                                  want)
            phase_cache(kids, work, cfg, records, size, env, cold, before)
            dev = cold["device"]
        check("jax" not in sys.modules,
              "this process imported jax: it could have held the chip")
    finally:
        kids.stop_all()
        shutil.rmtree(work, ignore_errors=True)
    result = {"ok": True, "device": dev}
    if args.rehearse:
        result["rehearsal"] = True
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
