"""A table keyed by the full 128-bit source (``TableConfig.key_bits`` 128).

The engine is served 7-word sealed batches (the four key words, then the
compact16 words 1-3) of seeded traffic in which IPv6 attackers share the
32-bit XOR fold of benign IPv4 sources, and is judged exactly against
the benchmark's plain reference through ``benchmark/key128.py``'s
rename.  The control feeds the same traffic with every key folded to 32
bits, as the plane keyed it before: its blocks name the innocent IPv4
sources, and it is not correct.  A 32-bit step stages the gathers,
scatters and sorts it always staged, and every path not built for the
wide key refuses it at load.
"""

import collections
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, key128, reference, trafficgen
from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import (
    BatchConfig, FsxConfig, LimiterConfig, ModelConfig, TableConfig,
)
from flowsentryx_tpu.engine import Engine, NullSink
from flowsentryx_tpu.models import get_model
from flowsentryx_tpu.models.registry import load_artifact
from flowsentryx_tpu.ops import fused

REPO = Path(__file__).resolve().parents[1]
CAPACITY = 1 << 16
BATCH = 256
T0_NS = 1_000_000_000
N_ATTACK, N_BENIGN, PLANTED = 64, 256, 16


def _config() -> dict:
    """``c8-dual6-mix``'s configuration at 2^16 rows and 256-record
    batches."""
    c = json.loads((REPO / "benchmark/configs/c8-dual6-mix.json")
                   .read_text())
    return harness.merged(c, {"table": {"capacity": CAPACITY},
                              "batch": {"max_batch": BATCH}})


def _traffic(seed: int, n: int = 60_000):
    """48 B records of a two-pool mix and each record's full source:
    half of each pool IPv6, the first ``PLANTED`` IPv6 attackers with
    the XOR fold of a distinct benign IPv4 source.  Returns the records,
    their ``[n, 4]`` keys and the planted pairs."""
    rec = trafficgen.flow_records(dict(
        array_records=n, n_attack_ips=N_ATTACK, n_benign_ips=N_BENIGN,
        attack_fraction=0.5, rate_pps=100_000, protos=[6, 17],
        attack_ports=[80], benign_ports=[443]), seed)
    uniq, inv = np.unique(rec["saddr"], return_inverse=True)
    keys = np.zeros((len(uniq), 4), np.uint32)
    keys[:, :3] = key128.V4_MAPPED
    keys[:, 3] = uniq
    rng = np.random.default_rng([seed, 6])
    attack = np.flatnonzero(uniq < (1 << 24))
    benign = np.flatnonzero(uniq >= (1 << 24))
    v6 = np.concatenate([attack[:len(attack) // 2],
                         benign[:len(benign) // 2]])
    keys[v6] = rng.integers(0, 1 << 32, (len(v6), 4), dtype=np.uint32)
    keys[v6, 0] = (keys[v6, 0] & ~np.uint32(0xE0)) | np.uint32(0x20)
    victims = benign[len(benign) // 2:][:PLANTED]
    pairs = []
    for a, v in zip(attack[:PLANTED], victims):
        keys[a, 3] = uniq[v] ^ keys[a, 0] ^ keys[a, 1] ^ keys[a, 2]
        pairs.append((keys[a].copy(), keys[v].copy()))
    for a, v in pairs:
        assert key128.fold(a[None])[0] == key128.fold(v[None])[0]
    return rec, keys[inv.reshape(-1)], pairs


def _sealed(rec, keys, model, fold: bool = False):
    """``(words, base_rel_us)`` sealed batches of ``BATCH`` records:
    7-word rows, or (``fold``) compact16 rows keyed by the XOR fold."""
    for s in range(0, len(rec), BATCH):
        chunk, k = rec[s:s + BATCH], keys[s:s + BATCH]
        base = int(chunk["ts_ns"][0])
        if fold:
            narrow = chunk.copy()
            narrow["saddr"] = key128.fold(k)
            words = reference.quantise_records(narrow, model, base)
        else:
            words = key128.quantise_records(key128.widen(chunk, k), model,
                                            base)
        yield words, (base - T0_NS) // 1000


def _serve(config: dict, batches, key_bits: int):
    """The engine, as ``fsx serve`` builds it, fed ``batches`` through
    the source tap's staging; returns (report, sink tap, engine)."""
    c = harness.merged(config, {"table": {"key_bits": key_bits}})
    cfg = harness.engine_config(c)
    params = load_artifact(cfg.model.name,
                           str(REPO / config["model"]["artifact"]))
    tap = harness.SourceTap(SimpleNamespace(
        start=lambda *a: None, t0_ns=0, precompact=False), BATCH)
    tap.begin_prefill(batches)
    sink = harness.SinkTap()
    eng = Engine(cfg, tap, sink, params=params, mega_n=0)
    rep = eng.run()
    return rep, sink, eng


def _judge(config: dict, batches, got_key, got_until, rename):
    """``judge.judge``'s comparison of blocks and counters, the batches
    handed to the reference as the ``sim_dual6`` driver hands them (the
    source renamed into column 0); returns (blocks_gap, its detail, the
    reference)."""
    renamed = []
    for words, base in batches:
        w = np.empty((len(words), 4), np.uint32)
        w[:, 0] = rename(words[:, :4])
        w[:, 1:] = words[:, 4:]
        renamed.append((w, base))
    ids, n_src = reference.dense_ids(
        np.concatenate([w[:, 0] for w, _ in renamed]))
    ref = reference.Reference(config, n_src)
    pos = 0
    for words, base in renamed:
        ref.step(words, base, ids[pos:pos + len(words)])
        pos += len(words)
    ref_key, ref_until = ref.blocks()
    gap, detail = reference.blocks_gap(ref_key, ref_until, got_key,
                                       got_until)
    return gap, detail, ref


@pytest.fixture(scope="module")
def run128():
    config = _config()
    rec, keys, pairs = _traffic(seed=2_147_483_999)
    batches = list(_sealed(rec, keys, config["model"]))
    rep, sink, eng = _serve(config, batches, 128)
    return SimpleNamespace(config=config, rec=rec, keys=keys, pairs=pairs,
                           batches=batches, rep=rep, sink=sink, eng=eng)


class TestAgainstTheReference:
    def test_blocks_and_counters_are_exact(self, run128):
        r = run128
        rename = key128.Rename(r.keys)
        got_key, got_until = r.sink.blocks()
        assert got_key.shape[1:] == (4,)
        gap, detail, ref = _judge(r.config, r.batches, rename(got_key),
                                  got_until, rename)
        assert detail["ref_blocks"] > 0
        assert gap == 0.0, detail
        cgap, cdetail = reference.counters_gap(ref.counts, r.rep.stats)
        assert cgap == 0.0, cdetail
        assert ref.records == r.rep.records == len(r.rec)
        # planted attackers were blocked by their full address, and no
        # block names the benign IPv4 source that shares its fold
        blocked = {tuple(k) for k in got_key.tolist()}
        assert any(tuple(a) in blocked for a, _ in r.pairs)
        assert not any(tuple(v) in blocked for _, v in r.pairs)

    def test_the_v6_counters(self, run128):
        r = run128
        v6 = ~key128.is_v4_mapped(r.keys)
        assert r.rep.stats["v6_records"] == int(v6.sum())
        got_key, _ = r.sink.blocks()
        assert (r.rep.stats["v6_blocks"]
                == int((~key128.is_v4_mapped(got_key)).sum()) > 0)

    def test_sources_sharing_a_fold_take_rows_of_their_own(self, run128):
        key = np.asarray(jax.device_get(run128.eng.table.key))
        assert key.shape == (CAPACITY, 4)
        for a, v in run128.pairs:
            rows_a = np.flatnonzero((key == a).all(axis=1))
            rows_v = np.flatnonzero((key == v).all(axis=1))
            assert len(rows_a) == len(rows_v) == 1
            assert rows_a[0] != rows_v[0]

    def test_the_fold_keyed_control_is_not_correct(self, run128):
        """The same traffic keyed by the 32-bit fold, as the plane was:
        each block of a fold is read as what the kernel's
        ``blacklist_map`` makes of it, a block of the IPv4 source with
        that address."""
        r = run128
        folded = list(_sealed(r.rec, r.keys, r.config["model"], fold=True))
        rep, sink, _ = _serve(r.config, folded, 32)
        fk, fu = sink.blocks()
        rename = key128.Rename(r.keys)
        gap, detail, _ = _judge(r.config, r.batches,
                                rename(_v4_mapped(fk)), fu, rename)
        assert gap > 0.1, detail
        # and the innocent IPv4 sources of the plant are among them
        blocked = {tuple(k) for k in _v4_mapped(fk).tolist()}
        assert any(tuple(v) in blocked for _, v in r.pairs)


def _v4_mapped(saddr: np.ndarray) -> np.ndarray:
    """``[n, 4]`` keys of IPv4 sources: ``::ffff:a.b.c.d``."""
    out = np.zeros((len(saddr), 4), np.uint32)
    out[:, :3] = key128.V4_MAPPED
    out[:, 3] = saddr
    return out


def _one_batch_step(key_bits: int, keys: np.ndarray):
    """One jitted step over one batch of ``keys`` (benign records, the
    classifier's threshold out of reach, 10 records a window allowed);
    returns (blocked key rows, occupied table rows)."""
    cfg = FsxConfig(
        limiter=LimiterConfig(pps_threshold=10.0),
        model=ModelConfig(threshold=1.0),
        table=TableConfig(capacity=1 << 10, key_bits=key_bits),
        batch=BatchConfig(max_batch=32, verdict_k=8))
    spec = get_model(cfg.model.name)
    params = spec.init()
    quant = schema.wire_quant_for(params)
    rec = np.zeros(len(keys), trafficgen.FLOW_RECORD)
    rec["ts_ns"] = T0_NS + np.arange(len(keys)) * 1000
    rec["pkt_len"] = 100
    wide = key128.widen(rec, keys)
    if key_bits == 128:
        words = schema.compact_pack(wide, T0_NS, **quant)
    else:
        rec["saddr"] = key128.fold(keys)
        words = schema.compact_pack(rec, T0_NS, **quant)
    raw = np.zeros((33, words.shape[1]), np.uint32)
    raw[:len(words)] = words
    raw[32, 0] = len(words)
    step = fused.make_jitted_compact_step(cfg, spec.classify_batch,
                                          **quant)
    table, _, out = step(schema.make_table(1 << 10, key_bits),
                         schema.make_stats(), params, jnp.asarray(raw))
    bk = np.asarray(out.block_key)
    blocked = (bk != 0xFFFFFFFF) if bk.ndim == 1 else \
        (bk != 0xFFFFFFFF).any(axis=1)
    tk = np.asarray(table.key)
    occupied = (tk != 0) if tk.ndim == 1 else (tk != 0).any(axis=1)
    return int(blocked.sum()), int(occupied.sum())


def test_sources_sharing_a_fold_never_share_a_run():
    """Six records of an IPv6 source and six of the IPv4 source its
    fold names, in one batch, under a limit of ten: two flows of six at
    128 bits, one of twelve (and a block) at the fold."""
    a = np.array([0x20010DB8, 0x12345678, 0x9ABCDEF0, 0], np.uint32)
    v = np.array([0, 0, 0xFFFF0000, 0x0A000001], np.uint32)
    a[3] = v[3] ^ a[0] ^ a[1] ^ a[2]
    keys = np.array([a, v] * 6, np.uint32)
    assert _one_batch_step(128, keys) == (0, 2)
    assert _one_batch_step(32, keys) == (1, 1)


# -- the 32-bit step is the one it was ------------------------------------

def _count(jaxpr, c):
    for e in jaxpr.eqns:
        c[e.primitive.name] += 1
        for p in e.params.values():
            for q in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(q, jax.extend.core.ClosedJaxpr):
                    _count(q.jaxpr, c)
                elif isinstance(q, jax.extend.core.Jaxpr):
                    _count(q, c)
    return c


@pytest.mark.parametrize("aging,want", [
    (0.0, {"gather": 3, "scatter": 4, "sort": 2}),
    (5.0, {"gather": 5, "scatter": 6, "sort": 2}),
], ids=["no-aging", "aging"])
def test_a_32_bit_step_stages_what_it_staged(aging, want):
    """The gathers, scatters and sorts of the parent's 32-bit compact
    step, counted on its jaxpr before the 128-bit key was added."""
    cfg = FsxConfig(table=TableConfig(capacity=1 << 12, evict_ttl_s=aging),
                    batch=BatchConfig(max_batch=256, verdict_k=16))
    spec = get_model(cfg.model.name)
    params = spec.init()
    step = fused.make_compact_step(cfg, spec.classify_batch,
                                   **schema.wire_quant_for(params))
    j = jax.make_jaxpr(step)(schema.make_table(cfg.table.capacity),
                             schema.make_stats(), params,
                             jnp.zeros((257, 4), jnp.uint32))
    c = _count(j.jaxpr, collections.Counter())
    assert {k: c[k] for k in want} == want


# -- what is not built for the wide key refuses it -------------------------

def _engine128(tmp_path, **kw):
    cfg = FsxConfig(table=TableConfig(capacity=1 << 12, key_bits=128),
                    batch=BatchConfig(max_batch=256, verdict_k=16))
    tap = harness.SourceTap(SimpleNamespace(
        start=lambda *a: None, t0_ns=0, precompact=False), 256)
    return Engine(cfg, tap, NullSink(), **kw)


def _cli_config(tmp_path) -> str:
    p = tmp_path / "c128.json"
    p.write_text(FsxConfig(table=TableConfig(capacity=1 << 12,
                                             key_bits=128)).to_json())
    return str(p)


@pytest.mark.parametrize("case", [
    "width", "sharded", "aging", "inline", "audit", "save", "policy",
    "restore", "serve_checkpoint", "serve_inline", "serve_mesh", "cluster"])
def test_what_is_not_built_for_128_bits_refuses_it(case, tmp_path, capsys):
    from flowsentryx_tpu import cli
    from flowsentryx_tpu.engine import ArraySource
    from flowsentryx_tpu.engine.checkpoint import CheckpointPolicy

    if case == "width":
        with pytest.raises(ValueError, match="32 or 128"):
            TableConfig(key_bits=64)
    elif case == "sharded":
        with pytest.raises(ValueError, match="sharded step"):
            TableConfig(capacity=1 << 12, shards=4, key_bits=128)
    elif case == "aging":
        with pytest.raises(ValueError, match="aging sweep"):
            TableConfig(capacity=1 << 12, evict_ttl_s=5.0, key_bits=128)
    elif case == "inline":
        cfg = FsxConfig(table=TableConfig(capacity=1 << 12, key_bits=128))
        rec = np.zeros(4, schema.FLOW_RECORD_DTYPE)
        with pytest.raises(ValueError, match="sealed ring path"):
            Engine(cfg, ArraySource(rec), NullSink())
    elif case == "audit":
        with pytest.raises(ValueError, match="boot audit"):
            _engine128(tmp_path, audit=True)
    elif case in ("save", "policy", "restore"):
        eng = _engine128(tmp_path)
        call = {"save": lambda: eng.checkpoint(tmp_path / "ck"),
                "policy": lambda: eng.set_checkpoint_policy(
                    CheckpointPolicy(str(tmp_path / "ck"), 30.0)),
                "restore": lambda: eng.restore(tmp_path / "ck")}[case]
        with pytest.raises(ValueError, match="checkpoint save or restore"):
            call()
        assert not (tmp_path / "ck").exists()
    else:
        path = _cli_config(tmp_path)
        argv = {"serve_checkpoint": ["serve", "--config", path,
                                     "--checkpoint", str(tmp_path / "ck")],
                "serve_inline": ["serve", "--config", path],
                "serve_mesh": ["serve", "--config", path, "--feature-ring",
                               str(tmp_path / "fr"), "--ingest-workers", "2",
                               "--mesh", "4"],
                "cluster": ["cluster", "--config", path, "--engines", "2"],
                }[case]
        t0 = time.perf_counter()
        assert cli.main(argv) == 1
        assert time.perf_counter() - t0 < 5.0  # at load, before any boot
        assert "key_bits 128" in capsys.readouterr().err


# -- the benchmark's cell ---------------------------------------------------

CELL = "c8-dual6-mix.saturate"
READERS = ("key.v6_share.tput", "sink.v6_block_share.tput",
           "step.key128_hbm_roofline.tput")


def test_benchmark_json_names_the_dual6_cell():
    """The configuration, the cell and its three readers, the cell
    appended to the accepted metrics it reports and to nothing else."""
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert b["configs"][-1]["name"] == "c8-dual6-mix"
    assert b["workloads"][-1] == dict(b["workloads"][-1], name=CELL,
                                      config="c8-dual6-mix",
                                      traffic="saturate", chips=1)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["records_per_s"]["workloads"][-1] == CELL
    mine = [m["name"] for m in b["per_layer"][-3:]]
    assert mine == list(READERS)
    for m in b["per_layer"][:-3]:
        assert m["workloads"][-1] == CELL or CELL not in m["workloads"]
        if CELL in m["workloads"]:
            assert m["moves"] in ("records_per_s", "setup_s"), m["name"]


def _snaps(stats0, stats1, records, blocks):
    return SimpleNamespace(
        snap0={"rep": {"records": 0, "stats": stats0},
               "gen": {"sink_blocks": 0}},
        snap1={"rep": {"records": records, "stats": stats1},
               "gen": {"sink_blocks": blocks}},
        trace=None, peaks=None, config={"table": {"key_bits": 128}})


@pytest.mark.parametrize("name", READERS)
def test_dual6_readers_read_a_number_or_nothing(name):
    """A program without the v6 counters (the parent) reads as nothing;
    with them, the window's share."""
    mod = harness.load_module("metrics", name)
    entry = next(m for m in json.loads(
        (REPO / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] == name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"])
    assert mod.read(_snaps({}, {}, 100, 10)) is None
    ctx = _snaps({"v6_records": 10, "v6_blocks": 1},
                 {"v6_records": 60, "v6_blocks": 6}, 100, 10)
    if name == "step.key128_hbm_roofline.tput":
        assert mod.read(ctx) is None  # no trace
        ctx.trace = {"step_s": 1e-3, "snap0": ctx.snap0,
                     "snap1": ctx.snap1}
        ctx.peaks = {"hbm_bytes_per_s": 819e9}
        ctx.harness = harness
        want = 100.0 * key128.step_bytes(100) / 819e9 / 1e-3
        assert mod.read(ctx) == pytest.approx(want)
    else:
        assert mod.read(ctx) == pytest.approx(50.0)


def test_the_cell_rehearses_correct():
    """The whole cell, `run.py --rehearse` on the CPU: `fsxd --sim
    --key-bits 128` through the rings, the ingest workers' 7-word seal,
    the engine, the 32 B verdict ring, judged `correct` by the
    reference through the rename."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", "3000000019", "--seconds", "3", "--trace", "0",
         "--rehearse"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True, r["compared"]
    assert r["compared_detail"]["ingest_words_differ"]["compared"] > 0
    assert r["compared_detail"]["verdict_ring_differ"]["v6_blocks"] > 0
