"""Static auditor (`fsx audit`) tests.

Acceptance: every step variant the engine can serve — raw48, compact16,
sharded, megastep — stages clean under the five graph contracts, and
the compact step's steady-state D2H is *statically* reported as exactly
``(2*verdict_k + 4) * 4`` bytes.

Negatives mirror tests/test_verifier.py's table-driven planted-defect
style: a planted f64 leak, a dropped donation, a hidden io_callback,
and a forced retrace must each be rejected with a diagnostic naming the
offending equation / output / input.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import io_callback
from jax.sharding import PartitionSpec as P

from flowsentryx_tpu.audit import graph, runner
from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import BatchConfig, FsxConfig, TableConfig
from flowsentryx_tpu.models import get_model
from flowsentryx_tpu.ops import fused
from flowsentryx_tpu.parallel import make_mesh

CFG = FsxConfig(
    table=TableConfig(capacity=1 << 12),
    batch=BatchConfig(max_batch=256, verdict_k=16),
)


@pytest.fixture(scope="module")
def report():
    """One full audit over all four variants (module-cached: staging
    is the expensive part, the assertions below are reads)."""
    return runner.run_audit(CFG, mesh=make_mesh(8), mega_n=2)


class TestAcceptance:
    def test_all_variants_pass(self, report):
        assert report.ok, [str(f) for v in report.variants
                           for f in v.findings]
        assert [v.name for v in report.variants] == [
            "raw", "compact", "sharded", "megastep",
            "sharded_megastep"]

    def test_steady_state_d2h_is_exactly_the_wire(self, report):
        want = (2 * CFG.batch.verdict_k + 4) * 4
        for v in report.variants:
            assert v.wire_words == 2 * CFG.batch.verdict_k + 4, v.name
            assert v.steady_state_d2h_bytes == want, v.name
            wire = [o for o in v.outputs if o["name"] == "out.wire"]
            assert wire and wire[0]["dtype"] == "uint32"

    def test_default_k_reports_528_bytes(self):
        """The PR 3 headline number, pinned statically: K=64 → 528 B."""
        cfg = FsxConfig(table=TableConfig(capacity=1 << 12),
                        batch=BatchConfig(max_batch=256, verdict_k=64))
        rep = runner.run_audit(cfg, variants=("compact",))
        assert rep.ok
        assert rep.variants[0].steady_state_d2h_bytes == 528

    def test_donation_proved_on_every_variant(self, report):
        for v in report.variants:
            assert v.donation["checked"], v.name
            # sharded variants donate the table only (stats replicate)
            need = (2 if v.name.startswith("sharded")
                    else len(runner.CARRY_NAMES))
            assert v.donation["required"] == runner.CARRY_NAMES[:need]
            assert set(range(need)) <= set(v.donation["aliased_params"]), (
                v.name)

    def test_sharded_collectives_are_the_designed_set(self, report):
        coll = {v.name: v.collectives for v in report.variants}
        for name in ("raw", "compact", "megastep"):
            assert coll[name] == {}, name  # single-device: none at all
        for name in ("sharded", "sharded_megastep"):
            sh = coll[name]
            assert sh["all_to_all"] == 2   # partials out, verdicts back
            assert sh["all_gather"] == 2   # wire keys + untils, K each
            assert set(sh) <= graph.EXPECTED_COLLECTIVES, name

    def test_no_f64_and_quantized_lane_present(self, report):
        for v in report.variants:
            assert not any(d.startswith(("float64", "complex"))
                           for d in v.dtypes), v.name
            assert "uint8" in v.dtypes  # the packed verdict lane

    def test_boot_audit_caches_per_shape(self):
        runner._BOOT_CACHE.clear()
        rep = runner.boot_audit(CFG, wire=schema.WIRE_RAW48, mesh=None,
                                mega_n=0)
        assert rep is not None and rep.ok
        assert runner.boot_audit(CFG, wire=schema.WIRE_RAW48, mesh=None,
                                 mega_n=0) is None  # cache hit

    def test_mega_sizes_stage_one_report_per_rung(self):
        """Adaptive-coalescing ladder: every power-of-two group size is
        its own compiled scan artifact and gets its own audited report,
        each holding the merged-wire D2H pin."""
        rep = runner.run_audit(CFG, mega_n=4, mega_sizes=(2, 4),
                               variants=("megastep",))
        assert rep.ok, [str(f) for v in rep.variants for f in v.findings]
        assert [v.name for v in rep.variants] == ["megastep@4",
                                                  "megastep@2"]
        want = (2 * CFG.batch.verdict_k + 4) * 4
        for v in rep.variants:
            assert v.steady_state_d2h_bytes == want, v.name
        assert rep.config["mega_sizes"] == [4, 2]

    def test_boot_cache_keys_on_group_size_set(self):
        """An engine re-booting with a DIFFERENT ladder serves
        different compiled artifacts: the boot cache must miss (and
        re-prove) on a changed group-size set, and hit on the same."""
        runner._BOOT_CACHE.clear()
        rep = runner.boot_audit(CFG, wire=schema.WIRE_COMPACT16,
                                mesh=None, mega_n=2, mega_sizes=(2,))
        assert rep is not None and rep.ok
        assert runner.boot_audit(CFG, wire=schema.WIRE_COMPACT16,
                                 mesh=None, mega_n=2,
                                 mega_sizes=(2,)) is None  # cache hit
        rep2 = runner.boot_audit(CFG, wire=schema.WIRE_COMPACT16,
                                 mesh=None, mega_n=4,
                                 mega_sizes=(2, 4))
        assert rep2 is not None and rep2.ok  # different set: re-proved
        assert [v.name for v in rep2.variants] == [
            "compact", "megastep@4", "megastep@2"]

    def test_report_json_shape(self, report):
        d = report.to_json()
        assert d["ok"] is True
        assert d["config"]["verdict_k"] == CFG.batch.verdict_k
        v0 = d["variants"][0]
        assert {"name", "ok", "findings", "outputs",
                "steady_state_d2h_bytes", "donation",
                "collectives"} <= set(v0)


def bench_cell_cfg(name: str):
    """``benchmark/configs/<name>.json`` at its ``rehearse`` sizes, as
    the benchmark's own harness turns it into the program's config and
    artifact: ``(FsxConfig, params, mega)``."""
    import importlib.util
    from pathlib import Path

    from flowsentryx_tpu.models.registry import load_artifact

    root = Path(__file__).resolve().parents[1]
    spec = importlib.util.spec_from_file_location(
        "bench_harness", root / "benchmark" / "harness.py")
    harness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(harness)
    config = harness.load_json(
        root / "benchmark" / "configs" / f"{name}.json")
    config = harness.merged(config, config.get("rehearse", {}))
    cfg = harness.engine_config(config)
    params = load_artifact(cfg.model.name,
                           str(root / config["model"]["artifact"]))
    return cfg, params, config["mega"]


#: `c6-spoof-churn` ages its table: its step holds the sweep's switch on
#: the lowering platform (`TestInplaceCensus`'s aging cases)
BENCH_CONFIGS = ("c3-offline-ml", "c4-syn-mix", "c5-l34-1m",
                 "c6-spoof-churn")


class TestBenchmarkConfigs:
    @pytest.mark.parametrize("name", BENCH_CONFIGS)
    def test_the_programs_a_cell_stages_prove_clean(self, name):
        """The graphs the benchmark's cells run — ``jit_step`` and every
        rung of the ``--mega auto`` ladder, under the cell's own
        limiter, widths and artifact at its rehearsal sizes — hold every
        contract: what ``warm()`` stages there is what is audited
        here."""
        cfg, params, mega = bench_cell_cfg(name)
        assert mega == "auto"
        rep = runner.run_audit(
            cfg, params=params, variants=("compact", "megastep"),
            mega_n=8, mega_sizes=fused.pow2_group_sizes(8))
        assert rep.ok, [str(f) for v in rep.variants for f in v.findings]
        assert [v.name for v in rep.variants] == [
            "compact", "megastep@8", "megastep@4", "megastep@2"]


def _staged(fn, *example_args):
    return jax.jit(fn).trace(*example_args).jaxpr


class TestInplaceCensus:
    """The in-place/copy census: PR 8's measured XLA:CPU table cliffs
    (a lax.cond-carried table; a dynamic-offset DUS) pinned as graph
    facts, with a planted-violation negative per cliff."""

    def test_every_variant_censuses_zero_copies(self, report):
        for v in report.variants:
            assert v.inplace["checked"], v.name
            assert v.inplace["copies"] == 0, v.name
            assert v.inplace["converts"] == 0, v.name
            assert v.inplace["conditionals"] == 0, v.name
            assert len(v.inplace["table_types"]) == 2  # key + state

    def test_sharded_census_uses_local_shard_types(self, report):
        sh = next(v for v in report.variants if v.name == "sharded")
        single = next(v for v in report.variants if v.name == "compact")
        # shard-local shapes are capacity/mesh — NOT the global shapes
        assert sh.inplace["table_types"] != single.inplace["table_types"]

    @staticmethod
    def _plant(step):
        cap = 64
        j = jax.jit(step, donate_argnums=(0, 1))
        key = jnp.zeros(cap, jnp.uint32)
        st = jnp.zeros((cap, 4), jnp.float32)
        tr = j.trace(key, st, jnp.uint32(3))
        hlo = tr.lower().compile().as_text()
        return graph.check_inplace(
            tr.jaxpr, hlo, list(tr.jaxpr.in_avals)[:2],
            ["table.key", "table.state"])

    def test_planted_cond_carried_table(self):
        cap = 64

        def step(key, state, x):
            key, state = jax.lax.cond(
                x > jnp.uint32(0),
                lambda k, s: (k.at[x % cap].set(x), s),
                lambda k, s: (k, s), key, state)
            return key, state, jnp.sum(state[:4])

        finds, census = self._plant(step)
        assert finds
        cond = [f for f in finds if "lax.cond carries the donated "
                "table" in f.reason]
        assert cond and cond[0].contract == "inplace"
        assert "table.key" in cond[0].reason
        assert "eqns[" in cond[0].where  # names the source equation
        # the executable-level census sees it too
        assert census["conditionals"] >= 1
        assert any("conditional op(s) return a table-shaped buffer"
                   in f.reason for f in finds)

    def test_a_cond_that_only_reads_the_table_is_fine(self):
        """The probe's form (ISSUE 38): branches that gather from the
        table and return rows of the batch.  The table is an operand
        of the conditional and no result of it, and nothing
        table-shaped is copied."""
        def step(key, state, x):
            rows = jnp.arange(8) * 3
            seen = jax.lax.cond(
                x > jnp.uint32(0), lambda: state[rows, 0],
                lambda: jnp.zeros((8,), jnp.float32))
            state = state.at[x % 64, 0].add(jnp.sum(seen))
            return key, state, jnp.sum(state[:4])

        finds, census = self._plant(step)
        assert [f for f in finds if f.contract == "inplace"] == [], [
            str(f) for f in finds]
        assert census["copies"] == 0 and census["conditionals"] == 0

    def test_planted_dynamic_offset_dus(self):
        def step(key, state, x):
            state = jax.lax.dynamic_update_slice(
                state, jnp.ones((1, 4), jnp.float32),
                (x.astype(jnp.int32), jnp.int32(0)))
            return key, state, jnp.sum(state[:4])

        finds, _ = self._plant(step)
        dus = [f for f in finds
               if "dynamic-offset dynamic_update_slice" in f.reason]
        assert dus and dus[0].contract == "inplace"
        assert "table.state" in dus[0].reason
        assert "gather reads + victim-only scatter" in dus[0].reason

    def test_the_aging_step_is_clean_on_every_variant(self):
        """The aging sweep hands the donated table through a
        `platform_dependent` switch whose `tpu` branch slices it at a
        computed start (ISSUE 40).  Neither is a cliff: the switch is
        resolved at lowering, and the slices are lowered only for the
        TPU.  On XLA:CPU the compiled program is the `default`
        branch's: no copy, no conditional."""
        cfg = FsxConfig(
            table=TableConfig(capacity=1 << 12, evict_ttl_s=12.0,
                              evict_every=16),
            batch=CFG.batch)
        (compact,), _, _ = runner.stage_variants(cfg,
                                                 variants=("compact",))
        closed = compact.jitted.trace(*compact.make_args()).jaxpr
        switches = [graph.platform_branches(e)
                    for _, e in graph.iter_eqns(closed)
                    if graph.platform_branches(e)]
        assert switches == [(("tpu",), ("default",))]
        sliced = {p for _, e, p in graph.iter_platform_eqns(closed)
                  if e.primitive.name == "dynamic_update_slice"
                  and e.invars[0].aval.shape[0] == cfg.table.capacity}
        assert sliced == {("tpu",)}  # what the audit walks past

        rep = runner.run_audit(cfg, mesh=make_mesh(8), mega_n=2)
        assert rep.ok, [str(f) for v in rep.variants for f in v.findings]
        assert len(rep.variants) == 5
        for v in rep.variants:
            assert v.inplace["checked"], v.name
            assert (v.inplace["copies"], v.inplace["converts"],
                    v.inplace["conditionals"]) == (0, 0, 0), v.name

    @staticmethod
    def _sweepish(default):
        """A step that hands the table to a `platform_dependent` as
        the sweep does: the TPU's branch slices, `default` is given."""
        def on_tpu(key, state, x):
            start = (x.astype(jnp.int32), jnp.int32(0))
            rows = jax.lax.dynamic_slice(state, start, (8, 4))
            return key, jax.lax.dynamic_update_slice(state, rows * 0.0,
                                                     start)

        def step(key, state, x):
            key, state = jax.lax.platform_dependent(
                key, state, x, tpu=on_tpu, default=default)
            return key, state, jnp.sum(state[:4])
        return step

    def test_a_slice_in_the_default_branch_is_still_a_finding(self):
        """The `default` branch is what XLA:CPU lowers: held to its
        rules in full, a computed start there is PR 8's cliff.  The
        same two equations in the `tpu` branch are not reported."""
        def by_slice(key, state, x):
            return key, jax.lax.dynamic_update_slice(
                state, jnp.ones((8, 4), jnp.float32),
                (x.astype(jnp.int32), jnp.int32(0)))

        finds, _ = self._plant(self._sweepish(by_slice))
        jaxpr_finds = [f for f in finds if f.where]
        assert len(jaxpr_finds) == 1, [str(f) for f in finds]
        assert "dynamic-offset dynamic_update_slice" in jaxpr_finds[0].reason
        assert "tpu= branch" in jaxpr_finds[0].reason
        # ... and in the default branch it is: branches/ holds both
        assert "cond/branches/" in jaxpr_finds[0].where

        def by_scatter(key, state, x):
            return key, state.at[x % 64].set(0.0)

        finds, census = self._plant(self._sweepish(by_scatter))
        assert [f for f in finds if f.contract == "inplace"] == [], [
            str(f) for f in finds]
        assert census["copies"] == 0 and census["conditionals"] == 0

    def test_a_traced_cond_is_a_finding_inside_a_platform_branch_too(self):
        """Only the switch on `platform_index` is resolved at
        lowering.  A `lax.cond` on a traced predicate that returns the
        table is a conditional in every compiled program, whichever
        branch of the switch it sits in."""
        def default(key, state, x):
            return jax.lax.cond(
                x > jnp.uint32(0),
                lambda k, s: (k.at[x % 64].set(x), s),
                lambda k, s: (k, s), key, state)

        finds, census = self._plant(self._sweepish(default))
        cond = [f for f in finds
                if "lax.cond carries the donated table" in f.reason]
        assert len(cond) == 1 and cond[0].contract == "inplace"
        # the runtime one, inside the switch's second branch; the
        # switch itself (an outer `cond` that carries the table too)
        # is not reported
        assert cond[0].where.count(":cond") == 2
        assert census["conditionals"] >= 1

    def test_planted_shard_local_dus(self):
        # shard_map bodies stage SHARD-LOCAL avals — the census must
        # match the per-shard table shape too, or the production
        # scan-over-shard_map variants are blind to the DUS cliff
        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs a multi-device mesh")
        mesh = jax.sharding.Mesh(np.asarray(devs), ("ip",))

        def body(key, state, x):
            state = jax.lax.dynamic_update_slice(
                state, jnp.ones((1, 4), jnp.float32),
                (x[0].astype(jnp.int32), jnp.int32(0)))
            return key, state, jax.lax.psum(jnp.sum(state), "ip")

        sh = jax.shard_map(
            body, mesh=mesh, in_specs=(P("ip"), P("ip"), P("ip")),
            out_specs=(P("ip"), P("ip"), P()), check_vma=False)
        j = jax.jit(sh, donate_argnums=(0, 1))
        tr = j.trace(jnp.zeros(64, jnp.uint32),
                     jnp.zeros((64, 4), jnp.float32),
                     jnp.zeros(len(devs), jnp.uint32))
        finds, _ = graph.check_inplace(
            tr.jaxpr, tr.lower().compile().as_text(),
            list(tr.jaxpr.in_avals)[:2], ["table.key", "table.state"],
            n_shards=len(devs))
        dus = [f for f in finds
               if "dynamic-offset dynamic_update_slice" in f.reason]
        assert dus and dus[0].contract == "inplace"
        assert "table.state" in dus[0].reason

    def test_planted_table_copy_in_hlo(self):
        # positive for the executable-level copy census: returning the
        # donated table as TWO outputs is an aliasing conflict XLA can
        # only solve with a table-shaped materializing copy — if the
        # census regex ever stops matching the dump format, this trips
        def step(key, state, x):
            return key, state, state, jnp.sum(state[:1])

        j = jax.jit(step, donate_argnums=(0, 1))
        tr = j.trace(jnp.zeros(64, jnp.uint32),
                     jnp.zeros((64, 4), jnp.float32), jnp.uint32(3))
        finds, census = graph.check_inplace(
            tr.jaxpr, tr.lower().compile().as_text(),
            list(tr.jaxpr.in_avals)[:2], ["table.key", "table.state"])
        assert census["copies"] >= 1
        assert any("producing a table-shaped buffer" in f.reason
                   and f.contract == "inplace" for f in finds)

    def test_constant_offset_window_is_fine(self):
        # the legal form: a CONSTANT-offset window (and the scatters
        # XLA fuses to DUS) must NOT trip the census
        def step(key, state, x):
            # python-int starts stage as Literals — the static form
            state = jax.lax.dynamic_update_slice(
                state, jnp.ones((1, 4), jnp.float32), (0, 0))
            state = state.at[x % 64, 0].add(1.0)  # single-index scatter
            return key, state, jnp.sum(state[:4])

        finds, census = self._plant(step)
        assert [f for f in finds if f.contract == "inplace"] == [], [
            str(f) for f in finds]
        assert census["copies"] == 0 and census["conditionals"] == 0


class TestNegatives:
    """Planted defects, each caught with an instruction-level
    diagnostic (the `fsx check` rejection idiom on the TPU plane)."""

    def test_planted_f64_leak(self):
        def leaky(x):
            # the classic: a python float promotes the lane to f64
            return (x.astype(jnp.float64) * 2.0).sum().astype(jnp.float32)

        with jax.enable_x64():
            closed = _staged(leaky, np.ones((8,), np.float32))
        finds = graph.check_dtypes(closed)
        assert finds
        f = finds[0]
        assert f.contract == "dtype"
        assert "float64" in f.reason
        assert "eqns[" in f.where and f.eqn  # names the offending eqn

    def test_dropped_donation(self):
        spec = get_model(CFG.model.name)
        step = fused.make_jitted_raw_step(CFG, spec.classify_batch,
                                          donate=False)  # the defect
        traced = step.trace(
            schema.make_table(CFG.table.capacity), schema.make_stats(),
            spec.init(),
            np.zeros((CFG.batch.max_batch + 1, schema.RECORD_WORDS),
                     np.uint32))
        hlo = traced.lower().compile().as_text()
        finds, info = graph.check_donation(
            hlo, runner.CARRY_NAMES,
            list(traced.jaxpr.in_avals)[:len(runner.CARRY_NAMES)],
            n_inputs=len(traced.jaxpr.in_avals))
        assert finds
        assert finds[0].contract == "donation"
        # diagnostic names the buffer that would be silently copied
        assert any(f.where == "table.state" for f in finds)
        tbl = next(f for f in finds if f.where == "table.state")
        assert "input_output_alias" in tbl.reason

    def test_hidden_io_callback(self):
        def bad(x):
            y = io_callback(lambda v: np.float32(np.sum(v)),
                            jax.ShapeDtypeStruct((), jnp.float32), x)
            return x + y

        closed = _staged(bad, np.ones((8,), np.float32))
        finds = graph.check_callbacks(closed)
        assert finds
        assert finds[0].contract == "transfer"
        assert "io_callback" in finds[0].reason
        assert "eqns[" in finds[0].where and finds[0].eqn

    def test_hidden_debug_print(self):
        def bad(x):
            jax.debug.print("score {s}", s=x.sum())
            return x * 2

        finds = graph.check_callbacks(_staged(bad, np.ones((8,),
                                                           np.float32)))
        assert finds and "host round-trip" in finds[0].reason

    def test_forced_retrace(self):
        j = jax.jit(lambda x: x * 2)
        drift = iter([np.float32, np.int32])  # dtype wobble per batch

        def mk():
            return (np.zeros((8,), next(drift)),)

        finds, _ = graph.staging_cache_check(
            j, mk, arg_names=lambda i: f"batch[{i}]")
        assert finds
        f = finds[0]
        assert f.contract == "retrace"
        assert "recompile" in f.reason
        assert "batch[0]" in f.reason  # names the drifting input
        assert "float32[8]" in f.reason and "int32[8]" in f.reason

    def test_stable_staging_is_quiet(self):
        j = jax.jit(lambda x: x * 2)
        finds, traced = graph.staging_cache_check(
            j, lambda: (np.zeros((8,), np.float32),))
        assert finds == [] and traced is not None

    def test_carry_aval_drift(self):
        # weak-typed carry out vs strong carry in: retraces on batch 2
        closed = _staged(lambda s: jnp.asarray(1.0),
                         np.zeros((), np.float32))
        finds = graph.check_carry_avals(closed, 1, ["stats.allowed"])
        assert finds
        assert finds[0].contract == "retrace"
        assert finds[0].where == "stats.allowed"

    def test_unexpected_collective(self):
        # a [B]-sized all_gather is exactly the accidental-traffic case
        mesh = make_mesh(8)
        def body(x):
            return jax.lax.all_gather(x, "ip").sum(axis=0)

        f = jax.shard_map(body, mesh=mesh, in_specs=P("ip"),
                          out_specs=P("ip"), check_vma=False)
        closed = _staged(f, np.zeros((256,), np.float32))
        finds, _ = graph.check_collectives(closed, verdict_k=16,
                                           expect_sharded=True)
        assert finds
        assert finds[0].contract == "collectives"
        assert "all_gather" in finds[0].where or "all_gather" in finds[0].eqn

    def test_mega_zero_skips_megastep_cleanly(self):
        # operator typo (`fsx audit --mega 0`) must degrade to a noted
        # skip, never a zero-size-scan staging crash
        rep = runner.run_audit(CFG, mega_n=0, variants=None)
        assert {v.name for v in rep.variants} == {"raw", "compact"}
        assert any("mega" in n for n in rep.notes)
        with pytest.raises(ValueError, match="mega_n"):
            runner.run_audit(CFG, mega_n=0, variants=("megastep",))

    def test_boot_cache_keys_on_params_signature(self):
        """A different artifact (other leaf dtypes/shapes) is a
        DIFFERENT staged graph: the boot cache must not serve engine B
        a stale pass from engine A's params."""
        runner._BOOT_CACHE.clear()
        spec = get_model(CFG.model.name)
        assert runner.boot_audit(CFG, wire=schema.WIRE_RAW48, mesh=None,
                                 mega_n=0, params=spec.init()) is not None
        # same params signature → cache hit
        assert runner.boot_audit(CFG, wire=schema.WIRE_RAW48, mesh=None,
                                 mega_n=0, params=spec.init()) is None
        # params=None (model default marker) → distinct key, re-audits
        assert runner.boot_audit(CFG, wire=schema.WIRE_RAW48, mesh=None,
                                 mega_n=0) is not None

    def test_verdict_k_zero_fails_transfer_contract(self):
        cfg = FsxConfig(table=TableConfig(capacity=1 << 12),
                        batch=BatchConfig(max_batch=256, verdict_k=0))
        rep = runner.run_audit(cfg, variants=("raw",))
        assert not rep.ok
        assert any(f.contract == "transfer" and "verdict_k" in f.reason
                   for f in rep.variants[0].findings)


class TestEngineBoot:
    def test_engine_refuses_to_serve_on_violated_contract(self):
        """`Engine(audit=True)` is a boot-time gate, not a log line: a
        config whose steady-state D2H is NOT the compact wire
        (verdict_k=0, the full-[B]-fetch mode) fails the transfer
        contract before the first batch."""
        from flowsentryx_tpu.audit.graph import AuditError
        from flowsentryx_tpu.core.schema import FLOW_RECORD_DTYPE
        from flowsentryx_tpu.engine import ArraySource, Engine, NullSink

        cfg = FsxConfig(table=TableConfig(capacity=1 << 12),
                        batch=BatchConfig(max_batch=256, verdict_k=0))
        src = ArraySource(np.zeros(0, FLOW_RECORD_DTYPE))
        with pytest.raises(AuditError, match="verdict_k"):
            Engine(cfg, src, NullSink(), sink_thread=False, audit=True)

    def test_engine_boots_with_audit_on_clean_config(self):
        from flowsentryx_tpu.core.schema import FLOW_RECORD_DTYPE
        from flowsentryx_tpu.engine import ArraySource, Engine, NullSink

        eng = Engine(CFG, ArraySource(np.zeros(0, FLOW_RECORD_DTYPE)),
                     NullSink(), sink_thread=False, audit=True)
        # second engine on the same shape hits the boot-audit cache
        Engine(CFG, ArraySource(np.zeros(0, FLOW_RECORD_DTYPE)),
               NullSink(), sink_thread=False, audit=True)
        assert eng.verdict_k == CFG.batch.verdict_k


class TestCli:
    def test_fsx_audit_cli_json(self, capsys):
        import json

        from flowsentryx_tpu.cli import main

        rc = main(["audit", "--quick", "--mesh", "8", "--mega", "2",
                   "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["ok"] is True
        names = {v["name"] for v in out["variants"]}
        assert names == {"raw", "compact", "sharded", "megastep",
                         "sharded_megastep"}
        # --quick keeps the config's K, so the headline byte budget
        # still pins: (2*64+4)*4 = 528
        assert all(v["steady_state_d2h_bytes"] == 528
                   for v in out["variants"])
