"""Persistent AOT compile cache + tiered warm (the boot-to-serving
tentpole).

Pins the cache contract end to end: the shared staging signature
(core/signature.py — audit, ranges and the compile cache key on ONE
rule), the entry format's refusal ladder (miss vs corrupt vs version
drift, each counted distinctly, every one fail-open into a recompile),
the engine-level hit/miss story across boots, and the tiered warm's
byte-identity promise — a partial ladder (top rung only, fill held)
must produce byte-identical verdicts/stats/table to the full ladder,
because grouping is dispatch-granularity only.

Runs on the virtual 8-device CPU mesh (conftest); the serving-loop
tests hold ``jax.transfer_guard("disallow")`` exactly like the mega
parity tests they extend.
"""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from flowsentryx_tpu.core.config import BatchConfig, FsxConfig, TableConfig
from flowsentryx_tpu.core.signature import (
    params_signature,
    signature_digest,
    staging_signature,
)
from flowsentryx_tpu.engine import ArraySource, CollectSink, Engine
from flowsentryx_tpu.engine import compile_cache as cc
from flowsentryx_tpu.engine.compile_cache import CompileCache
from flowsentryx_tpu.engine.traffic import Scenario, TrafficGen, TrafficSpec


def small_cfg(batch=256, cap=1 << 12, verdict_k=64, **lim) -> FsxConfig:
    from flowsentryx_tpu.core.config import LimiterConfig

    return FsxConfig(
        table=TableConfig(capacity=cap),
        batch=BatchConfig(max_batch=batch, verdict_k=verdict_k),
        limiter=LimiterConfig(**lim) if lim else LimiterConfig(),
    )


def flood_records(cfg, n_batches=24, seed=3):
    return TrafficGen(
        TrafficSpec(scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
                    n_attack_ips=8, n_benign_ips=24,
                    attack_fraction=0.8, seed=seed)
    ).next_records(n_batches * cfg.batch.max_batch)


@pytest.fixture
def no_jax_persistent_cache():
    """The AOT store serialises what ``compile()`` hands it, and on the
    CPU backend an executable that JAX's own persistent cache handed
    over does not load again from the store (counted ``corrupt``, so a
    boot recompiles: fail-open, but not the hit these tests count).
    That cache is on in a test process once any CLI verb has run in it
    (``runtime.place_compile_cache``) or where the environment names a
    directory, and holds whatever compiled slowly enough to be kept, so
    the boots counted here compile without it."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


class TestSignature:
    def test_params_signature_default_vs_leaves(self):
        assert params_signature(None, "logreg") == ["default", "logreg"]
        sig = params_signature(
            {"w": np.zeros((4, 2), np.float32),
             "b": np.zeros((2,), np.int8)}, "logreg")
        assert ["float32", [4, 2]] in sig and ["int8", [2]] in sig

    def test_digest_is_deterministic_and_shape_sensitive(self):
        cfg = small_cfg()
        kw = dict(wire="compact16", mesh_devices=1, mega_sizes=(8, 4, 2),
                  params=None, donate=True)
        a = staging_signature(cfg, **kw)
        b = staging_signature(cfg, **kw)
        assert signature_digest(a) == signature_digest(b)
        # every keyed axis moves the digest
        for change in (dict(wire="records"), dict(mesh_devices=8),
                       dict(mega_sizes=(8, 4)),
                       dict(donate=False), dict(donate=None)):
            c = staging_signature(cfg, **{**kw, **change})
            assert signature_digest(c) != signature_digest(a), change

    def test_config_knobs_key_the_signature(self):
        kw = dict(wire="compact16")
        a = staging_signature(small_cfg(batch=256), **kw)
        b = staging_signature(small_cfg(batch=128), **kw)
        assert signature_digest(a) != signature_digest(b)


def _tiny_compiled():
    fn = jax.jit(lambda x: x * 2)
    return fn.lower(jax.ShapeDtypeStruct((8,), jnp.int32)).compile()


@pytest.mark.usefixtures("no_jax_persistent_cache")
class TestCompileCacheUnit:
    """CompileCache against a tiny real executable: the refusal ladder
    (miss / corrupt / version drift / foreign digest), each counted
    distinctly and every one returning None (the caller recompiles)."""

    def test_roundtrip_hit(self, tmp_path):
        cache = CompileCache(tmp_path, {"k": 1})
        assert cache.load("single") is None and cache.misses == 1
        assert cache.store("single", _tiny_compiled())
        assert cache.stores == 1 and cache.path("single").exists()
        exe = cache.load("single")
        assert exe is not None and cache.hits == 1
        out = np.asarray(exe(np.arange(8, dtype=np.int32)))
        np.testing.assert_array_equal(out, np.arange(8) * 2)

    def test_corrupt_blob_refuses_and_counts(self, tmp_path, capsys):
        cache = CompileCache(tmp_path, {"k": 1})
        cache.store("single", _tiny_compiled())
        p = cache.path("single")
        raw = bytearray(p.read_bytes())
        raw[-1] ^= 0xFF  # flip one blob byte: CRC must refuse
        p.write_bytes(bytes(raw))
        assert cache.load("single") is None
        assert cache.corrupt == 1 and cache.hits == 0
        assert "corrupt" in capsys.readouterr().err
        # bad magic is the same refusal, counted the same way
        raw[0] ^= 0xFF
        p.write_bytes(bytes(raw))
        assert cache.load("single") is None and cache.corrupt == 2

    def test_version_drift_refuses_and_counts(self, tmp_path,
                                              monkeypatch, capsys):
        CompileCache(tmp_path, {"k": 1}).store("single", _tiny_compiled())
        monkeypatch.setattr(
            cc, "toolchain_versions",
            lambda: {"jax": "99.0", "jaxlib": "99.0",
                     "backend": "cpu", "platform_version": "x"})
        cache2 = CompileCache(tmp_path, {"k": 1})
        assert cache2.load("single") is None
        assert cache2.version_drift == 1
        assert cache2.corrupt == 0 and cache2.misses == 0
        assert "drift" in capsys.readouterr().err

    def test_foreign_digest_is_a_plain_miss(self, tmp_path):
        a = CompileCache(tmp_path, {"k": 1})
        a.store("single", _tiny_compiled())
        b = CompileCache(tmp_path, {"k": 2})
        # plant a's entry where b expects its own (filename-prefix
        # collision): the header digest check must call it a miss
        b.path("single").write_bytes(a.path("single").read_bytes())
        assert b.load("single") is None
        assert b.misses == 1 and b.corrupt == 0

    def test_store_failure_is_counted_not_raised(self, tmp_path, capsys):
        cache = CompileCache(tmp_path, {"k": 1})
        assert cache.store("single", object()) is False  # unserializable
        assert cache.store_errors == 1 and cache.stores == 0
        assert "failed to store" in capsys.readouterr().err


@pytest.mark.usefixtures("no_jax_persistent_cache")
class TestEngineCacheBoots:
    def _boot(self, cfg, recs, cache_dir, **kw):
        sink = CollectSink()
        eng = Engine(cfg, ArraySource(recs.copy()), sink, mega_n="auto",
                     readback_depth=4, sink_thread=False,
                     compile_cache=cache_dir, **kw)
        eng.warm()
        with jax.transfer_guard("disallow"):
            rep = eng.run()
        return rep, sink, eng

    def test_cold_then_cached_boot_parity(self, tmp_path):
        """Boot 1 (cold): every variant misses and is stored.  Boot 2
        (same staged shape): every variant loads from the cache, no
        recompiles — and the served results are byte-identical, plus
        identical to a cache-less engine on the same stream."""
        cfg = small_cfg(batch=256, pps_threshold=200.0,
                        bps_threshold=1e9)
        recs = flood_records(cfg)
        rep_cold, sink_cold, eng_cold = self._boot(
            cfg, recs, tmp_path / "cache")
        c = rep_cold.boot["cache"]
        n_variants = len(rep_cold.boot["variants"])
        assert n_variants >= 3  # single + >= 2 ladder rungs
        assert c["misses"] == n_variants and c["stores"] == n_variants
        assert c["hits"] == 0
        assert all(v["source"] == "compile"
                   for v in rep_cold.boot["variants"].values())
        assert rep_cold.boot["serving_ready_s"] > 0

        rep_hit, sink_hit, eng_hit = self._boot(
            cfg, recs, tmp_path / "cache")
        c = rep_hit.boot["cache"]
        assert c["hits"] == n_variants and c["misses"] == 0
        assert c["corrupt"] == 0 and c["version_drift"] == 0
        assert all(v["source"] == "cache"
                   for v in rep_hit.boot["variants"].values())

        # a cache-less engine on the same stream: the baseline
        sink_ref = CollectSink()
        eng_ref = Engine(cfg, ArraySource(recs.copy()), sink_ref,
                         mega_n="auto", readback_depth=4,
                         sink_thread=False)
        rep_ref = eng_ref.run()
        assert (rep_cold.stats == rep_hit.stats == rep_ref.stats)
        assert (sink_cold.blocked == sink_hit.blocked
                == sink_ref.blocked)
        for a, b in zip(jax.tree_util.tree_leaves(eng_cold.table),
                        jax.tree_util.tree_leaves(eng_hit.table)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_corrupt_entry_recompiles_fail_open(self, tmp_path, capsys):
        cfg = small_cfg(batch=128)
        recs = flood_records(cfg, n_batches=8)
        rep1, _, _ = self._boot(cfg, recs, tmp_path / "cache")
        # corrupt EVERY stored entry: the next boot must count the
        # refusals, recompile, re-store, and serve identically
        for p in (tmp_path / "cache").glob("*.aot"):
            raw = bytearray(p.read_bytes())
            raw[-1] ^= 0xFF
            p.write_bytes(bytes(raw))
        rep2, _, _ = self._boot(cfg, recs, tmp_path / "cache")
        c = rep2.boot["cache"]
        n_variants = len(rep2.boot["variants"])
        assert c["corrupt"] == n_variants and c["hits"] == 0
        assert c["stores"] == n_variants  # re-published for boot 3
        assert rep2.stats == rep1.stats
        rep3, _, _ = self._boot(cfg, recs, tmp_path / "cache")
        assert rep3.boot["cache"]["hits"] == n_variants

    def test_version_bump_recompiles(self, tmp_path, monkeypatch):
        cfg = small_cfg(batch=128)
        recs = flood_records(cfg, n_batches=8)
        rep1, _, _ = self._boot(cfg, recs, tmp_path / "cache")
        monkeypatch.setattr(
            cc, "toolchain_versions",
            lambda: {"jax": "99.0", "jaxlib": "99.0",
                     "backend": "cpu", "platform_version": "x"})
        rep2, _, _ = self._boot(cfg, recs, tmp_path / "cache")
        c = rep2.boot["cache"]
        assert c["version_drift"] == len(rep2.boot["variants"])
        assert c["hits"] == 0 and c["corrupt"] == 0
        assert rep2.stats == rep1.stats

    def test_cached_boot_on_mesh(self, tmp_path):
        """The sharded engine (mesh=8, sharded mega ladder) caches and
        reloads the same way — shardings ride the serialized
        executable, and the cache key carries mesh_devices."""
        from flowsentryx_tpu.parallel import make_mesh

        cfg = small_cfg(batch=256, cap=1 << 12, pps_threshold=200.0,
                        bps_threshold=1e9)
        recs = flood_records(cfg, n_batches=16)
        rep1, sink1, _ = self._boot(cfg, recs, tmp_path / "cache",
                                    mesh=make_mesh(8))
        n = len(rep1.boot["variants"])
        assert rep1.boot["cache"]["stores"] == n
        rep2, sink2, _ = self._boot(cfg, recs, tmp_path / "cache",
                                    mesh=make_mesh(8))
        assert rep2.boot["cache"]["hits"] == n
        assert rep2.boot["cache"]["misses"] == 0
        assert rep1.stats == rep2.stats
        assert sink1.blocked == sink2.blocked


@pytest.mark.usefixtures("no_jax_persistent_cache")
class TestTieredWarm:
    def test_partial_ladder_is_byte_identical(self, tmp_path):
        """The tiered warm's core promise: serving with ONLY the top
        rung ready (background fill held) produces byte-identical
        stats/verdicts/table to the full ladder — unready rungs
        degrade to top-rung flushes, a dispatch-granularity change
        only."""
        cfg = small_cfg(batch=256, pps_threshold=200.0,
                        bps_threshold=1e9)
        recs = flood_records(cfg)

        def run(tiered, hold_fill):
            sink = CollectSink()
            eng = Engine(cfg, ArraySource(recs.copy()), sink,
                         mega_n="auto", readback_depth=4,
                         sink_thread=False,
                         compile_cache=tmp_path / "cache")
            if hold_fill:
                # deterministic partial ladder: the fill never runs,
                # so the ready set stays at the serving tier for the
                # WHOLE drain (not a race on fill speed)
                eng._warm_worker = lambda: None
            eng.warm(tiered=tiered)
            if hold_fill:
                assert eng.warm_fill_join(10.0)
                assert eng._ready_sizes == eng._mega_sizes[:1]
            with jax.transfer_guard("disallow"):
                rep = eng.run()
            return rep, sink, eng

        rep_full, sink_full, eng_full = run(tiered=False, hold_fill=False)
        rep_part, sink_part, eng_part = run(tiered=True, hold_fill=True)
        assert rep_part.records == rep_full.records
        assert rep_part.stats == rep_full.stats
        assert sink_part.blocked == sink_full.blocked
        for a, b in zip(jax.tree_util.tree_leaves(eng_full.table),
                        jax.tree_util.tree_leaves(eng_part.table)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the partial ladder really did serve tiered: only the top
        # rung (and singles) dispatched
        hist = {int(g): n
                for g, n in rep_part.dispatch["group_hist"].items()}
        assert set(hist) <= {1, eng_part._mega_sizes[0]}
        assert rep_part.boot["tiered"] is True

    def test_background_fill_completes_the_ladder(self, tmp_path):
        """Unheld tiered warm: serving opens on the top rung, the
        fsx-warm thread installs every remaining rung, the
        ready set converges to the full ladder, and the boot block
        records the whole story (every variant sourced, fill_done_s
        stamped, nothing left pending)."""
        cfg = small_cfg(batch=128)
        sink = CollectSink()
        eng = Engine(cfg, ArraySource(flood_records(cfg, 4).copy()),
                     sink, mega_n="auto", sink_thread=False,
                     compile_cache=tmp_path / "cache")
        eng.warm(tiered=True)
        assert eng._ready_sizes == eng._mega_sizes[:1]
        assert eng.warm_fill_join(120.0)
        assert eng._ready_sizes == eng._mega_sizes
        with jax.transfer_guard("disallow"):
            rep = eng.run()
        boot = rep.boot
        assert boot["fill_pending"] == [] and "fill_error" not in boot
        assert boot["fill_done_s"] >= boot["serving_ready_s"]
        assert boot["fill_active"] is False
        labels = {"single"} | {
            f"mega{g}" for g in eng._mega_sizes}
        assert set(boot["variants"]) == labels
        assert boot["cache"]["stores"] == len(labels)

    def test_warm_refuses_reentry_while_filling(self, tmp_path):
        cfg = small_cfg(batch=128)
        eng = Engine(cfg, ArraySource(flood_records(cfg, 2).copy()),
                     CollectSink(), mega_n="auto", sink_thread=False,
                     compile_cache=tmp_path / "cache")
        gate = threading.Event()
        eng._warm_worker = gate.wait  # a fill that never finishes
        eng.warm(tiered=True)
        try:
            with pytest.raises(RuntimeError, match="warm fill"):
                eng.warm()
        finally:
            gate.set()
            assert eng.warm_fill_join(10.0)


class TestOperatorSurface:
    def _write_report(self, path, boot):
        path.write_text(json.dumps(
            {"rank": 0, "report": {"records": 1, "boot": boot}}))

    def test_merged_boot_folds_reports(self, tmp_path):
        from flowsentryx_tpu.cli import _iter_engine_reports, _merged_boot

        self._write_report(tmp_path / "r0.json", {
            "serving_ready_s": 0.5,
            "cache": {"hits": 5, "misses": 0, "stores": 0}})
        self._write_report(tmp_path / "r1.json", {
            "serving_ready_s": 8.0,
            "cache": {"hits": 0, "misses": 5, "stores": 5}})
        reports = list(_iter_engine_reports(
            [str(tmp_path / "r*.json")]))
        out = _merged_boot(reports)
        assert out["cache_hits"] == 5 and out["cache_misses"] == 5
        assert out["max_serving_ready_s"] == 8.0
        assert len(out["per_report"]) == 2
        # no boot blocks anywhere -> no stanza at all
        self._write_report(tmp_path / "r0.json", None)
        self._write_report(tmp_path / "r1.json", None)
        assert _merged_boot(list(_iter_engine_reports(
            [str(tmp_path / "r*.json")]))) is None

    def test_monitor_alert_cold_boot_requires_reports(self, capsys):
        from flowsentryx_tpu.cli import main

        assert main(["monitor", "--alert-cold-boot"]) == 1
        assert "--engine-report" in capsys.readouterr().err

    def test_serve_tiered_warm_requires_mega(self, capsys):
        from flowsentryx_tpu.cli import main

        assert main(["serve", "--tiered-warm"]) == 1
        assert "--mega" in capsys.readouterr().err

    def test_boot_salt_pinned_in_cache_dir(self, tmp_path, capsys):
        """The auto hash salt is a jit closure constant, so a fresh
        random draw per boot would miss the persistent cache on every
        variant forever (found live: two boots of the same `fsx serve
        --compile-cache` line produced two digests).  With a cache dir
        the salt pins in `boot_salt`; without one, fresh per boot."""
        from flowsentryx_tpu.cli import _boot_salt

        cache = tmp_path / "cache"
        s1 = _boot_salt(str(cache), "serve")
        assert "pinned" in capsys.readouterr().err
        s2 = _boot_salt(str(cache), "serve")
        assert s1 == s2 and s1 & 1 and 0 < s1 < 1 << 32
        assert capsys.readouterr().err == ""  # reuse is silent
        assert (cache / "boot_salt").exists()

        # malformed pin: announced, redrawn, re-pinned valid
        (cache / "boot_salt").write_text("0x0\n")
        s3 = _boot_salt(str(cache), "serve")
        assert s3 & 1 and "malformed" in capsys.readouterr().err
        assert _boot_salt(str(cache), "serve") == s3

        # no cache dir: the historical fresh-per-boot draw (valid odd
        # u32, nothing written anywhere)
        for s in (_boot_salt(None, "serve"), _boot_salt("", "serve")):
            assert s & 1 and 0 < s < 1 << 32

    def test_run_joins_background_fill(self, tmp_path):
        """run() must not return with the fsx-warm thread still
        compiling: a short-lived process would hand a live thread
        mid-XLA-compile to interpreter teardown (measured segfault in
        `fsx serve --batches N --tiered-warm`)."""
        cfg = small_cfg(batch=128)
        eng = Engine(cfg, ArraySource(flood_records(cfg, 2).copy()),
                     CollectSink(), mega_n="auto", sink_thread=False,
                     compile_cache=tmp_path / "cache")
        eng.warm(tiered=True)
        eng.run()
        assert not eng.warm_fill_active()
        assert eng._ready_sizes == eng._mega_sizes

    def test_supervisor_prewarm_gating(self, tmp_path):
        """Stub fleets (entry override) and cache-less fleets never
        spawn the pre-warm child; the elastic + cache + real-engine
        combination is what arms it."""
        from flowsentryx_tpu.cluster.supervisor import ClusterSupervisor

        sup = ClusterSupervisor(
            tmp_path / "c1", [{"a": 1}, {"a": 1}],
            entry=lambda spec: 0)
        assert sup._entry_is_real is False
        sup._elastic = object()
        sup._maybe_prewarm()
        assert sup._prewarm_proc is None and sup.prewarm_spawned == 0

        sup2 = ClusterSupervisor(tmp_path / "c2", [{"a": 1}, {"a": 1}])
        assert sup2._entry_is_real is True
        sup2._elastic = object()
        sup2._maybe_prewarm()  # no compile_cache in any spec: skip
        assert sup2._prewarm_proc is None

        sup3 = ClusterSupervisor(tmp_path / "c3", [{"a": 1}, {"a": 1}])
        sup3._maybe_prewarm()  # not elastic: skip
        assert sup3._prewarm_proc is None


class TestRuntime:
    """core/runtime.py: no silent CPU, the JAX cache placed from
    outside, and JAX's own cache events counted per boot."""

    @pytest.mark.parametrize("asked,ok", [
        ("cpu", True), ("tpu,cpu", True), ("", False), ("tpu", False)])
    def test_no_silent_cpu(self, monkeypatch, asked, ok):
        from flowsentryx_tpu.core import runtime

        # the backend here IS the cpu (conftest); what varies is
        # whether the environment asked for it
        monkeypatch.setenv("JAX_PLATFORMS", asked)
        if ok:
            assert runtime.require_platform("fsx serve") == "cpu"
        else:
            with pytest.raises(SystemExit, match="fsx serve: JAX found "
                                                 "no TPU"):
                runtime.require_platform("fsx serve")

    def test_cache_dir_comes_from_the_environment_first(self, monkeypatch,
                                                        tmp_path):
        from flowsentryx_tpu.core import runtime

        set_in_code = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: set_in_code.append((k, v)))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        # either way the key covers each program's metadata (a cached
        # executable must not carry another build's scope names)
        meta = ("jax_compilation_cache_include_metadata_in_key", True)
        assert runtime.place_compile_cache() == str(tmp_path)
        assert set_in_code == [meta]  # JAX reads the variable itself
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(runtime.CHECKOUT / ".jax_cache")
        assert runtime.place_compile_cache() == want
        assert set_in_code == [meta, meta,
                               ("jax_compilation_cache_dir", want)]
        assert (runtime.CHECKOUT / "flowsentryx_tpu").is_dir()

    def test_compile_counters_count_from_their_own_start(self):
        from flowsentryx_tpu.core import runtime

        first = runtime.CompileCounters("somewhere")
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
        second = runtime.CompileCounters("somewhere")
        a, b = first.report(), second.report()
        assert a["dir"] == "somewhere"
        assert a["backend_compile_s"] > 0 and b["backend_compile_s"] == 0
        assert set(a) == {"dir", "requests", "hits", "stores",
                          "backend_compile_s"}

    def test_serve_report_carries_the_counters(self):
        from flowsentryx_tpu.core import runtime

        cfg = small_cfg()
        eng = Engine(cfg, ArraySource(flood_records(cfg, 2)), CollectSink(),
                     mega_n=2, sink_thread=False)
        eng.boot_jax_compiles = runtime.CompileCounters("d")
        eng.warm()
        boot = eng.run().boot
        assert boot["jax_cache"]["dir"] == "d"
        assert boot["jax_cache"]["backend_compile_s"] > 0
