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
    assert entry["workloads"] == [NEW_CELL]
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
