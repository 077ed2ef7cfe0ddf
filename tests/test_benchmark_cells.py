"""The benchmark's configuration files, the readers ISSUE 32 added, and
the cell it added rehearsed end to end on the CPU against the plain
reference, with a verdict ring smaller than the blocks of one sunk
group: the sealed path (``fsxd --sim --pace`` -> shm rings -> ingest
workers -> ``Engine.run`` -> verdict ring) as ``benchmark/run.py
--rehearse`` drives it, through ``tests/vring_rehearsal.py``."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
CONFIGS = sorted((ROOT / "benchmark" / "configs").glob("*.json"))
NEW_CELL = "c5-l34-1m.saturate"
NEW_METRICS = ("sink.vring_wait.tput", "sink.vring_fill_peak.tput",
               "sink.fallback_share.tput")
EXACT = ("records_unaccounted", "batches_gap", "ingest_words_differ",
         "verdict_ring_differ", "verdict_ring_dropped")
SMALL_RING = 2


def harness():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from benchmark import harness as h

    return h


def artifact_params(model: dict) -> SimpleNamespace:
    with np.load(ROOT / model["artifact"]) as z:
        return SimpleNamespace(**{k: z[k] for k in z.files})


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
class TestConfigFiles:
    def test_states_its_source_cuts_sizes_and_guarantees(self, path):
        config = json.loads(path.read_text())
        assert config["name"] == path.stem
        assert config["source"].strip()
        assert len(config["guarantees"]) >= 3
        assert all(g.strip() for g in config["guarantees"])
        # every cut names a group of the file, and says what was assumed
        assert isinstance(config["reduced"], list)
        for key in config["reduced"]:
            assert key in config, key
            assert any(a.split(".")[0] == key for a in config["assumed"]), key
        assert all(v.strip() for v in config["assumed"].values())
        entry = next(c for c in bench()["configs"]
                     if c["name"] == config["name"])
        assert entry["reduced"] == config["reduced"]
        assert entry["source"] == config["source"]

    def test_model_numbers_are_the_artifacts(self, path):
        h = harness()
        config = json.loads(path.read_text())
        m = config["model"]
        h.check_artifact(config, artifact_params(m))  # SystemExit if not
        assert set(h.load_module("models", m["name"]).FIELDS) <= set(m)


def test_a_changed_model_number_is_refused():
    h = harness()
    config = json.loads(CONFIGS[0].read_text())
    m = config["model"]
    config["model"] = dict(m, w_scale=m["w_scale"] * 1.01)
    with pytest.raises(SystemExit):
        h.check_artifact(config, artifact_params(m))


def test_the_deployment_keeps_the_sources_shapes_and_c4s_guarantees():
    """``c5-l34-1m`` is BASELINE config 5 as the program states it
    (``benchmarks.py`` ``config5_mixed_l34_1m_ips``), under the three
    guarantees of ``c4-syn-mix``, word for word."""
    from flowsentryx_tpu.benchmarks import scenario_suite

    src = next(s for s in scenario_suite()
               if s.name == "config5_mixed_l34_1m_ips")
    c5 = json.loads((ROOT / "benchmark/configs/c5-l34-1m.json").read_text())
    c4 = json.loads((ROOT / "benchmark/configs/c4-syn-mix.json").read_text())
    lim = src.cfg.limiter
    assert c5["limiter"] == {
        "kind": "fixed_window", "pps_threshold": lim.pps_threshold,
        "bps_threshold": lim.bps_threshold, "window_s": lim.window_s,
        "block_s": lim.block_s}
    assert c5["batch"]["max_batch"] == src.cfg.batch.max_batch == 16384
    t = c5["traffic"]
    assert t["attack_fraction"] == src.traffic.attack_fraction == 0.8
    assert t["attack_ips"] + t["benign_ips"] == 1 << 20
    assert c5["guarantees"] == c4["guarantees"]
    for group in ("vote", "model", "wire", "mega", "ingest_workers",
                  "verdict_ring", "step_programs", "correct_limits"):
        assert c5[group] == c4[group], group
    cell = json.loads((ROOT / "benchmark/workloads" / f"{NEW_CELL}.json"
                       ).read_text())
    tr = cell["traffic"]
    assert tr["rate"] == round(tr["knee_multiple"] * tr["knee_offered"])
    assert 1.0 < tr["knee_multiple"] <= 1.10    # above the knee
    assert tr["ring_capacity"] == 1 << 23 and tr["warmup_s"] == 6.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_read_a_number_or_nothing(name):
    h = harness()
    mod = h.load_module("metrics", name)
    entry = next(m for m in bench()["per_layer"] if m["name"] == name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        name, entry["unit"], entry["layer"], entry["moves"])
    # (ISSUE 39's cell shares the driver's accounting and joins the list)
    assert entry["workloads"] == [NEW_CELL, "c6-spoof-churn.saturate"]
    span = {"n": 2, "sum_us": 5e4, "max_us": 4e4,
            "hist": {"scheme": "log2x16us", "buckets": {"240": 2}}}
    # the engine's report, and the ring writer's accounting as the
    # cell's driver hands it over (drivers/sim_paced_vring.py)
    full = {"rep": {"readback": {"fallback_sinks": 30, "compact_sinks": 10}},
            "gen": {"vring": {"verdict_ring_fill_peak": 0.03125,
                              "spans": {"fsx.sink.vring_wait": span}}}}
    before = {"rep": {"readback": {"fallback_sinks": 10,
                                   "compact_sinks": 10}},
              "gen": {"vring": {"verdict_ring_fill_peak": 0.0,
                                "spans": {}}}}
    ctx = SimpleNamespace(snap0=before, snap1=full, window_s=10.0)
    want = {"sink.vring_wait.tput": 0.5, "sink.vring_fill_peak.tput": 3.125,
            "sink.fallback_share.tput": 100.0}
    assert mod.read(ctx) == pytest.approx(want[name])
    # a program without the span or the keys (the parent commit)
    bare = {"rep": {"readback": {"verdict_ring_dropped": None}, "spans": {}},
            "gen": {"forwarded": 0}}
    old = SimpleNamespace(snap0=bare, snap1=bare, window_s=10.0)
    assert mod.read(old) is None


@pytest.mark.parametrize("program", ["since_issue_32", "before"])
def test_the_cells_driver_adds_the_ring_writers_accounting(program):
    """``sim_paced_vring`` is ``sim_paced`` plus the accounting of the
    sink behind the harness's tap, where that sink has one."""
    from flowsentryx_tpu.engine.metrics import Span

    h = harness()
    cell = json.loads((ROOT / "benchmark/workloads" / f"{NEW_CELL}.json"
                       ).read_text())
    assert cell["driver"] == "sim_paced_vring"
    mod = h.load_module("drivers", cell["driver"])
    assert issubclass(mod.Driver, mod.sim_paced.Driver)

    class Writer:
        dropped = 0

    if program == "since_issue_32":
        wait = Span("fsx.sink.vring_wait")
        wait.add(0.25)
        Writer.ring_accounting = lambda self: {
            "verdict_ring_dropped": 0, "verdict_ring_waits": 1,
            "verdict_ring_fill_peak": 0.5}
        Writer.spans = lambda self: (wait,)
    d = object.__new__(mod.Driver)
    d.prefilled, d.cursors, d.final, d.backlog = 7, [], {}, lambda: 3
    d.sink = h.SinkTap(Writer())
    got = d.counters()
    assert got["forwarded"] == 7 and got["backlog"] == 3
    if program == "before":
        assert "vring" not in got
        return
    assert got["vring"]["verdict_ring_fill_peak"] == 0.5
    assert got["vring"]["verdict_ring_waits"] == 1
    span = got["vring"]["spans"]["fsx.sink.vring_wait"]
    assert span["n"] == 1 and span["sum_us"] == pytest.approx(0.25e6)
    json.dumps(got)                      # a snapshot is plain data


def rehearse(tmp_path_factory, writer: str) -> tuple[dict, dict]:
    """One run of the new cell at rehearse size against a verdict ring
    of ``SMALL_RING`` slots: its result line and the sink's accounting.
    One CPU device and a compile cache of its own: conftest's eight
    virtual devices and the checkout's ``.jax_cache`` are the test
    workers'."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("jax_cache")))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "tests/vring_rehearsal.py", str(SMALL_RING), writer,
         "--workload", NEW_CELL, "--seed", "1", "--seconds", "3",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    acc = [ln.split(" ", 1)[1] for ln in p.stderr.splitlines()
           if ln.startswith("vring_accounting ")]
    assert len(acc) == 1
    return json.loads(p.stdout.strip().splitlines()[-1]), json.loads(acc[0])


@pytest.fixture(scope="module")
def waiting(tmp_path_factory):
    return rehearse(tmp_path_factory, "waiting")


@pytest.fixture(scope="module")
def discarding(tmp_path_factory):
    return rehearse(tmp_path_factory, "discarding")


class TestNewCellRehearsedOnASmallRing:
    def test_correct_against_the_plain_reference(self, waiting):
        r, ring = waiting
        assert r["correct"] is True, r["compared"]
        assert r["rehearse"] is True and r["failed"] == 0
        limits = json.loads((ROOT / "benchmark/configs/c5-l34-1m.json"
                             ).read_text())["correct_limits"]
        c = r["compared"]
        for name in EXACT:
            assert c[name] == {"value": 0, "limit": 0}, name
        for name in ("blocks_gap", "counters_gap"):
            assert c[name]["limit"] == limits[name]
            assert c[name]["value"] <= limits[name]
        assert set(r["metrics"]) == {"records_per_s", "setup_s"}

    def test_the_ring_was_smaller_than_a_groups_blocks_and_lost_none(
            self, waiting):
        r, ring = waiting
        assert ring["slots"] == SMALL_RING
        assert ring["verdict_ring_waits"] == ring["wait_samples"] > 0
        assert ring["verdict_ring_fill_peak"] == 1.0
        assert ring["verdict_ring_dropped"] == 0
        back = r["compared_detail"]["verdict_ring_differ"]
        assert back["sink_blocks"] == back["ring_head"] \
            == back["fsxd_verdicts"] > 8 * SMALL_RING

    def test_the_parents_discarding_writer_is_not_correct(self, discarding):
        r, ring = discarding
        assert r["correct"] is False
        c = r["compared"]
        assert c["verdict_ring_dropped"]["value"] \
            == ring["verdict_ring_dropped"] > 0
        assert c["verdict_ring_differ"]["value"] > 0
        assert ring["verdict_ring_waits"] == 0
        # what the engine decided is all there: the loss is the ring's
        for name in ("records_unaccounted", "batches_gap",
                     "ingest_words_differ"):
            assert c[name]["value"] == 0, name


# -- ISSUE 39: c6-spoof-churn ----------------------------------------------

CHURN = "c6-spoof-churn"
CHURN_CELL = "c6-spoof-churn.saturate"
CHURN_METRICS = ("step.stage_evict_ms.tput", "evict.hbm_roofline.tput",
                 "probe.stale_read_share.tput",
                 "table.untracked_share.tput", "table.load.tput")
STEADY = ("occupancy_drift", "evicted_gap", "untracked_share")


def load(kind: str, name: str) -> dict:
    return json.loads((ROOT / "benchmark" / kind / f"{name}.json"
                       ).read_text())


def test_the_churn_deployment_differs_from_c5_only_where_it_says():
    c5, c6 = load("configs", "c5-l34-1m"), load("configs", CHURN)
    same = set(c5) - {"name", "source", "table", "traffic", "guarantees",
                      "assumed", "rehearse"}
    assert set(c6) == set(c5)
    for group in same:
        assert c6[group] == c5[group], group
    assert c6["table"] == dict(c5["table"], evict_ttl_s=12.0,
                               evict_every=512)
    assert c6["traffic"] == {"attack_ips": 1 << 17, "benign_ips": 1 << 17,
                             "attack_fraction": 0.8, "spoof_fraction": 0.75}
    assert c6["guarantees"][:3] == c5["guarantees"]
    assert len(c6["guarantees"]) == 4 and "untracked" in c6["guarantees"][3]
    # a blocked source finds its row when it returns: TTL above the block
    assert c6["table"]["evict_ttl_s"] > max(c6["limiter"]["block_s"],
                                            c6["vote"]["ml_block_s"])
    r = harness().merged(c6, c6["rehearse"])
    assert r["table"]["evict_ttl_s"] > max(r["limiter"]["block_s"],
                                           r["vote"]["ml_block_s"])
    cell, c5cell = load("workloads", CHURN_CELL), load("workloads", NEW_CELL)
    assert cell["driver"] == "sim_churn" and cell["config"] == CHURN
    keep = ("pace", "rate", "high_water", "low_water", "ring_capacity",
            "drain_limit_s")
    assert {k: cell["traffic"][k] for k in keep} \
        == {k: c5cell["traffic"][k] for k in keep}
    assert set(cell["traffic"]) == set(keep) | {"warmup_s"}
    assert cell["traffic"]["warmup_s"] > c5cell["traffic"]["warmup_s"]


def test_benchmark_json_gained_the_cell_and_lost_nothing():
    b = bench()
    assert [c["name"] for c in b["configs"]][-1] == CHURN
    assert b["workloads"][-1] == dict(
        b["workloads"][-1], name=CHURN_CELL, config=CHURN,
        traffic="saturate", chips=1)
    for m in b["end_to_end"] + b["per_layer"]:
        lst = m.get("workloads")
        if lst and NEW_CELL in lst:
            assert CHURN_CELL in lst, m["name"]
    assert [m["name"] for m in b["per_layer"]][-5:] == list(CHURN_METRICS)


def churn_ctx(stats0, stats1, **more):
    snap = lambda st, b, n: {  # noqa: E731
        "rep": {"stats": st, "batches": b,
                "table": {"tracked": 30 << 20}},
        "gen": {"tap_batches": n}}
    words = [np.array([[1 << 31 | 7, 0, 0, 0], [5, 0, 0, 0], [5, 0, 0, 0]],
                      np.uint32)] * 4
    return SimpleNamespace(
        snap0=snap(stats0, 10, 1), snap1=snap(stats1, 14, 3),
        config={"table": {"capacity": 1 << 26, "evict_every": 512},
                "step_programs": ["jit_step"]},
        reaps=SimpleNamespace(tap=SimpleNamespace(words=words)),
        peaks={"hbm_bytes_per_s": 819e9}, trace=None, **more)


@pytest.mark.parametrize("name", CHURN_METRICS)
def test_churn_readers_read_a_number_or_nothing(name, monkeypatch):
    h = harness()
    mod = h.load_module("metrics", name)
    entry = next(m for m in bench()["per_layer"] if m["name"] == name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        name, entry["unit"], entry["layer"], entry["moves"])
    assert entry["workloads"] == [CHURN_CELL]
    s0 = {"batches": 10, "evicted": 100, "stale_reads": 9, "untracked": 1}
    s1 = {"batches": 14, "evicted": 500, "stale_reads": 12, "untracked": 2}
    ctx = churn_ctx(s0, s1)
    # a device trace that holds the scope: 4 batches, 2 ms under fsx.evict
    from benchmark import trace_scopes

    stage_s = {"evict": 2e-3, "probe": 1e-3}
    monkeypatch.setattr(trace_scopes, "stages",
                        lambda c: {"stage_s": stage_s, "scoped": True})
    ctx.trace = {"snap0": ctx.snap0, "snap1": ctx.snap1}
    window_rows = (1 << 26) // 512
    want = {
        "step.stage_evict_ms.tput": 0.5,
        "evict.hbm_roofline.tput":
            100 * (4 * window_rows + 400) * 52 / 819e9 / 2e-3,
        "probe.stale_read_share.tput": 75.0,
        # two sealed batches of the window, two keys each
        "table.untracked_share.tput": 25.0,
        "table.load.tput": 100 * 30 / 64,
    }
    assert mod.read(ctx) == pytest.approx(want[name])
    # the parent's program: no counter, no scope, no batch count
    old = {"batches": 14}
    bare = churn_ctx({"batches": 10}, old)
    bare.snap0["gen"], bare.snap1["gen"] = {}, {}
    bare.snap1["rep"]["table"] = None
    stage_s = {"probe": 1e-3}
    monkeypatch.setattr(trace_scopes, "stages",
                        lambda c: {"stage_s": stage_s, "scoped": True})
    bare.trace = {"snap0": bare.snap0, "snap1": bare.snap1}
    assert mod.read(bare) is None


def churn_driver(tmp_path, fsxd):
    h = harness()
    mod = h.load_module("drivers", "sim_churn")
    assert issubclass(mod.Driver, mod.vring.Driver)
    ctx = SimpleNamespace(
        cell=load("workloads", CHURN_CELL), seed=1, workdir=tmp_path,
        config=h.merged(load("configs", CHURN),
                        load("configs", CHURN)["rehearse"]), rehearse=True)
    d = mod.Driver(ctx)
    d.fsxd, d.shards = fsxd, 2
    d.fring, d.vring = tmp_path / "fring", tmp_path / "vring"
    return mod, d


def test_a_daemon_without_the_option_fails_the_run_at_once(tmp_path,
                                                           monkeypatch):
    """The parent commit's `fsxd` does not name `--spoof-fraction` in
    its usage: the driver says so in `build`, before the engine is built
    and compiled, and starts nothing."""
    old = tmp_path / "fsxd"
    old.write_text("#!/bin/sh\necho 'usage: fsxd [--sim]' >&2\nexit 2\n")
    old.chmod(0o755)
    _, d = churn_driver(tmp_path, old)
    monkeypatch.setattr(harness(), "build_fsxd", lambda: old)
    with pytest.raises(SystemExit, match="no --spoof-fraction"):
        d.build()
    assert d.proc is None and not hasattr(d, "tap")


@pytest.mark.parametrize("case,fails", [
    ("steady", ()), ("still_filling", ("occupancy_drift", "evicted_gap")),
    ("aging_off", ("evicted_gap",)),
    ("no_inserts", ("untracked_share",)),
])
def test_the_steady_state_gate(tmp_path, case, fails):
    """Six sealed batches of 100 flows, 60 spoofed, in the window."""
    mod, d = churn_driver(tmp_path, tmp_path / "fsxd")
    key = np.arange(1, 101, dtype=np.uint32)
    key[:60] |= np.uint32(1 << 31)
    d.tap = SimpleNamespace(words=[np.stack([key] * 4, axis=1)] * 8)
    cap = d.ctx.config["table"]["capacity"]
    rows, evicted, untracked = {
        "steady": (cap // 2 + 40, 355, 3),
        "still_filling": (cap // 2 + cap // 10, 0, 3),
        "aging_off": (cap // 2 + 40, 0, 3),
        "no_inserts": (cap // 2, 0, 360),
    }[case]
    rep = lambda r, e, u: {"table": {"tracked": r, "newest_seen_s": 1.0},  # noqa: E731
                           "stats": {"evicted": e, "untracked": u}}
    d.reports = [(rep(cap // 2, 1000, 10), 1),
                 (rep(rows, 1000 + evicted, 10 + untracked), 7)]
    got = d.steady_state(d.ctx.config)
    assert set(got) == set(STEADY)
    assert got["occupancy_drift"]["detail"]["spoofed_flows"] == 360
    assert got["occupancy_drift"]["detail"]["flows"] == 600
    bad = {k for k, c in got.items() if c["value"] > c["limit"]}
    assert bad == set(fails)


@pytest.fixture(scope="module")
def churn_rehearsed(tmp_path_factory):
    """One paced run of the churn cell at its rehearse size, as
    `c5-l34-1m`'s rehearsal: its result line and its `stats` line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("jax_cache")))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CHURN_CELL,
         "--seed", "1", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    return lines[-1], next(ln["stats"] for ln in lines if "stats" in ln)


class TestChurnCellRehearsed:
    def test_correct_against_the_plain_reference(self, churn_rehearsed):
        r, _ = churn_rehearsed
        assert r["correct"] is True, r["compared"]
        assert r["rehearse"] is True and r["failed"] == 0
        c = r["compared"]
        for name in EXACT:
            assert c[name] == {"value": 0, "limit": 0}, name
        limits = load("configs", CHURN)["correct_limits"]
        for name in ("blocks_gap", "counters_gap"):
            assert c[name]["limit"] == limits[name] == 0.001
            assert c[name]["value"] <= limits[name]
        assert r["compared_detail"]["blocks"]["ref_blocks"] > 500
        assert set(r["metrics"]) == {"records_per_s", "setup_s"}

    def test_the_table_aged_rows_out_and_held_steady(self, churn_rehearsed):
        r, stats = churn_rehearsed
        c = r["compared"]
        assert [c[k]["limit"] for k in STEADY] == [0.05, 0.05, 0.03]
        for k in STEADY:
            assert c[k]["value"] <= c[k]["limit"], k
        d = r["compared_detail"]["occupancy_drift"]
        assert d["evicted"] > 0.9 * d["spoofed_flows"] > 10_000
        lo, hi = sorted(d["tracked"])
        assert d["capacity"] / 3 < lo and hi < 0.6 * d["capacity"]
        assert stats["evicted"] > d["evicted"] and stats["stale_reads"] > 0
        assert 0 < stats["untracked"] < 0.03 * d["flows"]
