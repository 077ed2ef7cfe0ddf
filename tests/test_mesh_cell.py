"""ISSUE 42: BASELINE config 5's edge on a four-chip host
(``c5-l34-mesh4``): a table whose rows are split by the owner hash over
four devices (``TableConfig.shards``), served by the sharded step of
``parallel/step.py`` through the normal path, held to the plain
reference, with the exchange's scopes and the cell's readers.  On 4 of
conftest's 8 virtual CPU devices."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import (
    BatchConfig, FsxConfig, TableConfig,
)
from flowsentryx_tpu.engine import ArraySource, Engine, NullSink
from flowsentryx_tpu.models.registry import load_artifact
from flowsentryx_tpu.ops import fused, hashtable

ROOT = Path(__file__).resolve().parents[1]
MESH = "c5-l34-mesh4"
CELL = "c5-l34-mesh4.saturate"
READERS = ("step.stage_route_ms.tput", "step.stage_merge_ms.tput",
           "route.ici_roofline.tput", "route.drop_share.tput",
           "route.owner_rows_share.tput")
EXACT = ("records_unaccounted", "batches_gap")
#: the parent's TableConfig fields (before ISSUE 42)
PARENT_TABLE_KEYS = {"capacity", "probes", "stale_s", "salt",
                     "evict_ttl_s", "evict_every"}


def bench_mod(kind, name=None):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from benchmark import harness

    if kind == "harness":
        return harness
    return harness.load_module(kind, name)


def load(kind: str, name: str) -> dict:
    return json.loads((ROOT / "benchmark" / kind / f"{name}.json"
                       ).read_text())


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


# -- the configuration reaches the mesh ----------------------------------------

class TestTableShards:
    def test_an_engine_built_from_table_shards_serves_sharded(self):
        cfg = FsxConfig(table=TableConfig(capacity=1 << 12, shards=4),
                        batch=BatchConfig(max_batch=256))
        eng = Engine(cfg, ArraySource(np.zeros(0, schema.FLOW_RECORD_DTYPE)),
                     NullSink(), sink_thread=False, wire="compact16")
        assert eng.mesh is not None and eng.mesh.devices.size == 4
        assert eng._n_shards() == 4
        shards = eng.table.key.addressable_shards
        assert len({s.device for s in shards}) == 4
        assert {s.data.shape for s in shards} == {(1 << 10,)}
        raw = np.zeros((257, schema.COMPACT_RECORD_WORDS), np.uint32)
        text = eng.step.lower(eng.table, eng.stats, eng.params,
                              raw).as_text()
        assert text.count("all_to_all") == 2

    def test_without_the_key_the_table_is_one_devices(self):
        eng = Engine(FsxConfig(table=TableConfig(capacity=1 << 12)),
                     ArraySource(np.zeros(0, schema.FLOW_RECORD_DTYPE)),
                     NullSink(), sink_thread=False)
        assert eng.mesh is None and eng._n_shards() == 1
        assert len(eng.table.key.addressable_shards) == 1

    @pytest.mark.parametrize("kw", [dict(shards=3), dict(shards=0),
                                    dict(capacity=2, shards=4)])
    def test_a_table_that_cannot_split_is_refused(self, kw):
        with pytest.raises(ValueError, match="shards"):
            TableConfig(**{"capacity": 1 << 12, **kw})

    def test_a_mesh_passed_in_must_agree(self):
        from flowsentryx_tpu.parallel import make_mesh

        cfg = FsxConfig(table=TableConfig(capacity=1 << 12, shards=4))
        with pytest.raises(ValueError, match="table.shards=4"):
            Engine(cfg, ArraySource(np.zeros(0, schema.FLOW_RECORD_DTYPE)),
                   NullSink(), sink_thread=False, mesh=make_mesh(2))

    def test_the_unread_mesh_group_is_gone(self):
        assert "mesh" not in FsxConfig().to_dict()
        assert FsxConfig().to_dict()["table"]["shards"] == 1
        with pytest.raises(KeyError, match="mesh"):
            FsxConfig.from_dict({"mesh": {"ip_axis": 4}})

    def test_serve_mesh_sets_table_shards(self, tmp_path, capsys):
        """`fsx serve --mesh N` is a spelling of the key: the engine it
        boots holds an N-way table and its checkpoint says so."""
        from flowsentryx_tpu.cli import main
        from flowsentryx_tpu.engine.checkpoint import peek_header

        path = str(tmp_path / "ck.npz")
        rc = main(["serve", "--scenario", "benign", "--packets", "512",
                   "--mesh", "4", "--table-capacity", "4096",
                   "--checkpoint", path])
        assert rc == 0, capsys.readouterr().err
        assert peek_header(path)["n_shards"] == 4
        rc = main(["serve", "--scenario", "benign", "--packets", "64",
                   "--mesh", "3"])
        assert rc == 1 and "--mesh" in capsys.readouterr().err


# -- the sharded step's scopes -------------------------------------------------

def _sharded_engine(mega=False):
    cfg = FsxConfig(table=TableConfig(capacity=1 << 12, shards=4),
                    batch=BatchConfig(max_batch=256))
    eng = Engine(cfg, ArraySource(np.zeros(0, schema.FLOW_RECORD_DTYPE)),
                 NullSink(), sink_thread=False, wire="compact16",
                 mega_n="auto" if mega else 0)
    raw = np.zeros((257, schema.COMPACT_RECORD_WORDS), np.uint32)
    if not mega:
        return eng, eng.step, raw
    g = max(eng.megasteps)
    return eng, eng.megasteps[g], np.stack([raw] * g)


#: the owner side's three rung loops (``parallel/step.py::
#: owner_flow_step``), each with its trip test
RUNG_LOOPS = ["while", "lt"] * 3


@pytest.mark.parametrize("mega,outside", [
    (False, ["jit", "jit", "shard_map"] + RUNG_LOOPS),
    (True, ["jit", "scan", "jit", "jit", "shard_map"] + RUNG_LOOPS)],
    ids=["step", "mega_rung"])
def test_the_sharded_step_is_named_by_stage(mega, outside):
    """The one-device stage names on the sharded step's own work, and
    the exchange's two: ``fsx.route`` (packing, both ``all_to_all``,
    unpacking) and ``fsx.merge`` (the owner's sort and reductions, and
    its choice of rung).  No operation lies outside them but the
    programs' own wrappers and the rung loops, which hold ``fsx.probe``
    and ``fsx.update`` and so lie under no scope, and no ``fsx.*``
    scope holds another (a device trace files an operation under its
    first)."""
    import re

    from flowsentryx_tpu.audit.graph import iter_staged_eqns

    eng, fn, arg = _sharded_engine(mega)
    args = (eng.table, eng.stats, eng.params, arg)
    staged = list(iter_staged_eqns(jax.make_jaxpr(fn)(*args)))
    assert [eqn.primitive.name for stage, eqn in staged
            if stage is None] == outside
    assert {stage for stage, _ in staged} - {None} \
        == set(fused.STEP_SCOPES) | {"route", "merge"}
    text = fn.lower(*args).as_text(debug_info=True)
    locs = set(re.findall(r'loc\("([^"]*)"', text))
    assert not [x for x in locs if len(re.findall(r"fsx\.", x)) > 1]
    a2a = [x for x in locs if "all_to_all" in x]
    assert a2a and all(x.startswith("fsx.route/") for x in a2a)


# -- held to the reference ------------------------------------------------------

CAP = 1 << 17
BATCH = 1024
STREAM_BATCHES = 24


def scenario(shards: int, skew: bool = False):
    """``c5-l34-mesh4``'s groups at a small size: a 2^17-row table an
    eighth full (2^14 resident sources), then a seeded two-pool flood
    of 64 attack and 256 benign sources; one engine on ``shards``
    devices, served through the inline path, judged as the benchmark
    judges a run.  ``skew`` plants every live key on one owner."""
    h = bench_mod("harness")
    from benchmark import judge as judge_mod
    from benchmark import trafficgen

    config = load("configs", MESH)
    config["table"] = dict(config["table"], capacity=CAP, shards=shards)
    config["batch"] = dict(config["batch"], max_batch=BATCH)
    config["traffic"] = dict(config["traffic"],
                             background={"sources": CAP // 8,
                                         "block": 1024, "ports": [443]})
    p = {"array_records": BATCH * STREAM_BATCHES, "n_attack_ips": 64,
         "n_benign_ips": 256, "attack_fraction": 0.8, "rate_pps": 1e6,
         "protos": [17], "attack_ports": [53], "benign_ports": [443]}
    recs = trafficgen.flow_records(p, 7)
    if skew:
        salt = config["table"]["salt"]
        keys = np.arange(1, 1 << 16, dtype=np.uint32)
        owner = np.asarray(hashtable.hash_u32(keys, salt)) >> 30
        mine = keys[owner == 0][:512]
        recs["saddr"] = mine[np.arange(len(recs)) % len(mine)]
    loop = bench_mod("drivers", "loop_file")
    bg = trafficgen.Background(config["traffic"]["background"], 7)
    source = loop.LoopSource(recs, int(1e9 / p["rate_pps"]), bg)
    sink = h.SinkTap(NullSink())
    eng = Engine(h.engine_config(config), source, sink,
                 params=load_artifact(config["model"]["name"],
                                      str(ROOT / config["model"]["artifact"])),
                 sink_thread=False)
    rep = eng.run(max_batches=bg.n // BATCH + STREAM_BATCHES)._asdict()
    driver = SimpleNamespace(
        dispatched=lambda c: loop.Driver.dispatched(
            SimpleNamespace(source=source), c),
        transport_compared=lambda c, s: {})
    end = {"rep": rep, "gen": {"forwarded": source.handed, "backlog": 0,
                               "dropped_ring_full": 0}}
    verdict = judge_mod.judge(config, driver, sink, end)
    return SimpleNamespace(eng=eng, rep=rep, sink=sink, verdict=verdict,
                           config=config, end=end, judge=judge_mod,
                           live=np.unique(recs["saddr"]))


@pytest.fixture(scope="module")
def one():
    return scenario(1)


@pytest.fixture(scope="module")
def four():
    return scenario(4)


def _blocks(s):
    key, until = s.sink.blocks()
    order = np.lexsort((until, key))
    return key[order], until[order]


class TestShardedAgainstTheReference:
    def test_both_engines_are_correct_and_agree(self, one, four):
        for s in (one, four):
            c = s.verdict["compared"]
            assert s.verdict["correct"], c
            assert c["blocks_gap"]["value"] == 0.0
            assert c["counters_gap"]["value"] == 0.0
            for name in EXACT:
                assert c[name]["value"] == 0, name
            assert s.rep["route_drop"] == 0
        assert four.eng.mesh.devices.size == 4 and one.eng.mesh is None
        assert one.verdict["detail"]["blocks"]["ref_blocks"] >= 64
        for a, b in zip(_blocks(one), _blocks(four)):
            np.testing.assert_array_equal(a, b)
        names = ("allowed", "dropped_blacklist", "dropped_rate",
                 "dropped_ml")
        assert [one.rep["stats"][k] for k in names] \
            == [four.rep["stats"][k] for k in names]

    def test_the_shards_add_up_to_the_whole(self, one, four):
        """Every occupied key sits on its owner's shard and on no other,
        and the four shards' live keys together are the one-device
        table's (the resident population is contested in set-up, 1,024
        new keys a batch, so which of those two engines keep differs)."""
        salt = four.config["table"]["salt"]
        per_shard = []
        for s in sorted(four.eng.table.key.addressable_shards,
                        key=lambda s: s.index[0].start):
            k = np.asarray(s.data)
            per_shard.append(k[k != 0])
        for i, k in enumerate(per_shard):
            assert (np.asarray(hashtable.hash_u32(k, salt)) >> 30
                    == i).all(), i
        every = np.concatenate(per_shard)
        assert len(np.unique(every)) == len(every)
        k1 = np.asarray(one.eng.table.key)
        k1 = k1[k1 != 0]
        live4 = np.intersect1d(every, four.live)
        live1 = np.intersect1d(k1, one.live)
        np.testing.assert_array_equal(live4, live1)
        assert len(live1) == len(four.live)
        resident = CAP // 8
        assert 0.99 * resident < len(every) - len(live4) <= resident

    def test_a_skew_onto_one_owner_is_counted_and_not_correct(self):
        s = scenario(4, skew=True)
        assert s.rep["route_drop"] > 0
        c = s.verdict["compared"]
        assert not s.verdict["correct"]
        assert c["records_unaccounted"]["value"] == s.rep["route_drop"]
        snap = {"rep": {"route_drop": 0, "ingest": None}}
        assert s.judge.failed_records(s.config, snap, s.end, s.end) \
            == s.rep["route_drop"]


# -- the benchmark's cell ----------------------------------------------------------

def test_the_deployment_is_c5_on_four_chips():
    c5, m4 = load("configs", "c5-l34-1m"), load("configs", MESH)
    assert set(m4) == set(c5)
    for group in set(c5) - {"name", "source", "chips", "table", "batch",
                            "ingest_workers", "traffic", "assumed",
                            "rehearse"}:
        assert m4[group] == c5[group], group
    assert m4["table"] == dict(c5["table"], capacity=1 << 28, shards=4)
    assert m4["batch"] == dict(c5["batch"], max_batch=1 << 16)
    assert m4["ingest_workers"] == 4 and m4["chips"] == 4
    bg = dict(c5["traffic"]["background"], sources=1 << 25)
    assert m4["traffic"] == dict(c5["traffic"], background=bg)
    # each chip holds what c5-l34-1m's one chip holds
    n = m4["table"]["shards"]
    assert m4["table"]["capacity"] // n == c5["table"]["capacity"]
    assert bg["sources"] // n == c5["traffic"]["background"]["sources"]
    assert m4["batch"]["max_batch"] // n == c5["batch"]["max_batch"]
    # the parent's TableConfig knows every other key of the group
    assert set(m4["table"]) - PARENT_TABLE_KEYS == {"shards"}
    cell, c5cell = load("workloads", CELL), load("workloads",
                                                 "c5-l34-1m.saturate")
    t, t5 = cell["traffic"], c5cell["traffic"]
    assert cell["driver"] == c5cell["driver"] == "sim_paced_vring"
    assert t["high_water"] * n == t5["high_water"] * 2
    assert t["low_water"] == t["high_water"] - t["high_water"] // 64
    for k in ("pace", "rate", "ring_capacity", "warmup_s", "drain_limit_s"):
        assert t[k] == t5[k], k


def test_the_parents_program_refuses_the_key_at_once():
    """Before ISSUE 42 ``TableConfig`` has no ``shards``, and
    ``FsxConfig.from_dict`` refuses a key it does not know: the run
    stops in ``harness.engine_config``, before an engine or a compile
    (4 s on this sandbox, with the `fsxd` build)."""
    config = load("configs", MESH)
    parent = dict(config["table"])
    parent["unknown_to_this_program"] = parent.pop("shards")
    with pytest.raises(KeyError, match="unknown config key"):
        bench_mod("harness").engine_config(dict(config, table=parent))


def test_benchmark_json_gained_one_configuration_one_cell_four_readers():
    b = bench()
    assert b["configs"][-1]["name"] == MESH
    assert b["configs"][-1]["file"] == f"benchmark/configs/{MESH}.json"
    assert b["workloads"][-1] == dict(b["workloads"][-1], name=CELL,
                                      config=MESH, chips=4)
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    new = [m for m in b["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in new] == list(READERS)
    assert all(m["workloads"] == [CELL] for m in new)
    joined = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", ())} - set(READERS)
    assert joined == {
        "records_per_s", "gen.blocked.tput", "dispatch.occupancy.tput",
        "dispatch.host_busy.tput", "device.idle.tput",
        "device.idle_serving.tput",
        "step.device_ms_per_batch.tput", "sink.host_busy.tput",
        "sink.fallback_share.tput"} | {
        f"step.stage_{s}_ms.tput" for s in fused.STEP_SCOPES + ("unscoped",)}
    # four chips' records over one chip's peak: not these
    for name in ("step.hbm_roofline.tput", "step.mfu.tput"):
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL not in m["workloads"]


def test_the_route_bytes_are_the_programs_shapes():
    from flowsentryx_tpu.parallel import step as par_step

    bench_mod("harness")  # puts the checkout's root on sys.path
    from benchmark import mesh as bmesh

    for batch, n in ((65536, 4), (1024, 4), (256, 4), (16384, 2)):
        assert bmesh.route_capacity(batch, n) \
            == par_step.route_capacity(batch, n)
    assert bmesh.ROUTE_ROW_BYTES == par_step.ROUTE_WORDS * 4
    config = load("configs", MESH)
    # 3 other chips x C = 8,192 rows x (20 B out + 4 B back)
    assert bmesh.route_bytes(config) == 3 * 8192 * 24
    assert bmesh.route_bytes(load("configs", "c5-l34-1m")) == 0


def _trace_ctx(stage_s, monkeypatch, route_drop=(0, 0), spent=2e-3,
               owner_rows=(0, 0)):
    from benchmark import mesh as bmesh
    from benchmark import trace_scopes

    monkeypatch.setattr(trace_scopes, "stages",
                        lambda c: {"stage_s": stage_s, "scoped": True})
    monkeypatch.setattr(bmesh, "collective_s", lambda c: spent)
    snap = lambda b, r, rd, o: {  # noqa: E731
        "rep": {"batches": b, "records": r, "route_drop": rd,
                "stats": {"batches": b, "owner_rows": o}}}
    s0 = snap(10, 1000, route_drop[0], owner_rows[0])
    s1 = snap(14, 5000, route_drop[1], owner_rows[1])
    return SimpleNamespace(snap0=s0, snap1=s1,
                           trace={"snap0": s0, "snap1": s1},
                           config=load("configs", MESH),
                           peaks={"hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("name", READERS)
def test_the_readers_read_a_number_or_nothing(name, monkeypatch):
    mod = bench_mod("metrics", name)
    entry = next(m for m in bench()["per_layer"] if m["name"] == name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        name, entry["unit"], entry["layer"], entry["moves"])
    # 4 batches on four chips: three owners on the quarter rung (8,192
    # rows), one on the middle one (18,432), of n·C = 32,768 each
    ctx = _trace_ctx({"route": 4e-3, "merge": 2e-3, "probe": 1e-3},
                     monkeypatch, route_drop=(5, 45),
                     owner_rows=(7, 7 + 4 * (3 * 8192 + 18432)))
    from benchmark import mesh as bmesh

    want = {
        "step.stage_route_ms.tput": 1.0,
        "step.stage_merge_ms.tput": 0.5,
        "route.ici_roofline.tput":
            100 * 4 * bmesh.route_bytes(ctx.config) / 200e9 / 2e-3,
        "route.drop_share.tput": 1.0,
        "route.owner_rows_share.tput": 32.8125,
    }
    assert mod.read(ctx) == pytest.approx(want[name])
    # the parent's program: no exchange scope, no counter in the report
    bare = _trace_ctx({"probe": 1e-3}, monkeypatch)
    for snap in (bare.snap0, bare.snap1):
        del snap["rep"]["route_drop"], snap["rep"]["stats"]["owner_rows"]
    assert mod.read(bare) is None


def test_collective_time_is_the_all_to_alls_inside_step_programs(
        monkeypatch, tmp_path):
    from benchmark import mesh as bmesh
    from benchmark import trace_reduce, trace_scopes

    def plane(name, a2a_ns):
        return {"name": name, "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_mega(123)", 0.0, 1000.0],
                ["jit_table_summary", 2000.0, 500.0]]},
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = f32[4] fusion()", 0.0, 300.0],
                ["%all-to-all.2 = u32[4,5] all-to-all()", 300.0, a2a_ns],
                ["%all-to-all.3 = u32[4] all-to-all()", 2000.0, 400.0]]}]}

    trace = {"planes": [plane("/device:TPU:0", 100.0),
                        plane("/device:TPU:1", 300.0),
                        {"name": "/host:CPU", "lines": []}]}
    monkeypatch.setattr(trace_scopes, "newest_trace",
                        lambda: tmp_path / "a.xplane.pb")
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda p: trace)
    ctx = SimpleNamespace(trace={"x": 1},
                          config={"step_programs": ["jit_step", "jit_mega"]})
    assert bmesh.collective_s(ctx) == pytest.approx(200e-9)
    assert bmesh.collective_s(SimpleNamespace(trace=None)) is None


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One traced run of the cell at its rehearse size: paced, on 4 of
    the 8 virtual CPU devices the suite runs on."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("jax_cache")))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


class TestCellRehearsed:
    def test_correct_against_the_plain_reference(self, rehearsed):
        r = rehearsed
        assert r["correct"] is True, r["compared"]
        assert r["rehearse"] is True and r["failed"] == 0
        assert r["device"]["count"] == 4
        for name in EXACT + ("ingest_words_differ", "verdict_ring_differ",
                             "verdict_ring_dropped"):
            assert r["compared"][name] == {"value": 0, "limit": 0}, name
        assert r["compared_detail"]["blocks"]["ref_blocks"] > 100

    def test_the_new_readers_read_a_number_or_nothing(self, rehearsed):
        m = rehearsed["metrics"]
        assert m["route.drop_share.tput"]["value"] == 0.0
        # the owners ran the core on a rung of their n·C rows
        assert 25.0 <= m["route.owner_rows_share.tput"]["value"] <= 100.0
        # no TPU plane in a CPU trace: the device readers read nothing
        for name in READERS[:3]:
            assert name not in m

    def test_the_int4_control_is_not_correct(self, tmp_path):
        """The reference a precision step below the configuration's
        int8, in the program's place: the limits, `c5-l34-1m`'s, tell
        it from the program on four devices too."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
        p = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL,
             "--seed", "11", "--seconds", "3", "--trace", "0",
             "--rehearse", "--control", "int4"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        r = json.loads(p.stdout.strip().splitlines()[-1])
        assert r["correct"] is False
        c = r["compared"]
        assert (c["blocks_gap"]["value"] > c["blocks_gap"]["limit"]
                or c["counters_gap"]["value"] > c["counters_gap"]["limit"])
