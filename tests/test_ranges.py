"""``fsx ranges`` — the whole-pipeline integer value-range prover.

Acceptance: every step variant the engine can serve (singles, sharded,
mega rungs, eviction epochs) proves clean — no
equation's exact result interval escapes its dtype — modulo the four
audited WRAP_OK entries, each of which must both still match and still
name live code.  Negatives mirror the planted-defect style of
tests/test_audit.py: an unguarded u32 add, a narrowing convert, and a
stale registry entry must each produce an equation-level diagnostic.
The BPF↔jaxpr containment bridge is pinned on the shipped distill
artifact.
"""

from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import BatchConfig, FsxConfig, TableConfig
from flowsentryx_tpu.parallel import make_mesh
from test_audit import BENCH_CONFIGS, bench_cell_cfg
from flowsentryx_tpu.ranges import (
    interval as iv,
    prover,
    registry,
    runner as ranges_runner,
    seeds,
)

REPO = Path(__file__).resolve().parents[1]
ARTIFACT = REPO / "artifacts" / "logreg_int8.npz"

CFG = FsxConfig(
    table=TableConfig(capacity=1 << 12, evict_ttl_s=30.0),
    batch=BatchConfig(max_batch=256, verdict_k=16),
)


@pytest.fixture(scope="module")
def report():
    """One full range proof over every variant (module-cached; the
    staging is the expensive part, the assertions are reads)."""
    return ranges_runner.run_ranges(
        CFG, mesh=make_mesh(8), mega_n=2, artifact=str(ARTIFACT))


def _analyze(fn, *args, seeds_=None, **kw):
    closed = jax.jit(fn).trace(*args).jaxpr
    if seeds_ is None:
        seeds_ = [iv.top_for(a.dtype) for a in closed.in_avals]
    return prover.analyze(closed, seeds_, **kw)


class TestAcceptance:
    def test_every_variant_proves_clean(self, report):
        assert report.ok, [str(f) for v in report.variants
                           for f in v.findings] + [
            str(f) for f in report.registry_findings]
        names = [v.name for v in report.variants]
        assert names == ["raw", "compact", "sharded", "megastep",
                         "sharded_megastep"]
        for v in report.variants:
            assert v.ok, (v.name, [str(f) for f in v.findings])
            assert v.n_checked > 50, v.name  # the check actually ran
            assert not v.unmodeled, (v.name, v.unmodeled)

    @pytest.mark.parametrize("name", BENCH_CONFIGS)
    def test_the_programs_a_cell_stages_prove_clean(self, name):
        """No equation of the graphs the benchmark's cells run —
        ``jit_step`` and every rung of the ``--mega auto`` ladder at
        the cell's own limiter, widths and artifact — can wrap
        silently (tests/test_audit.py holds the same graphs to the
        audit's contracts)."""
        from flowsentryx_tpu.ops import fused

        cfg, params, _ = bench_cell_cfg(name)
        rep = ranges_runner.run_ranges(
            cfg, params=params, variants=("compact", "megastep"),
            mega_n=8, mega_sizes=fused.pow2_group_sizes(8),
            artifact=None, with_negatives=False)
        # the variants, not ``rep.ok``: the registry's staleness audit
        # wants every variant family staged (the module's ``report``)
        assert [v.name for v in rep.variants] == [
            "compact", "megastep@8", "megastep@4", "megastep@2"]
        for v in rep.variants:
            assert v.ok, (v.name, [str(f) for f in v.findings])
            assert v.n_checked > 50 and not v.unmodeled, v.name

    def test_every_wrap_ok_entry_matches(self, report):
        """The registry is exactly the live set: every entry fires in
        the full variant sweep (the staleness audit's other half)."""
        matched = set()
        for v in report.variants:
            matched |= set(v.wrap_ok_matches)
        assert matched == {e.name for e in registry.WRAP_OK}
        assert report.registry_findings == []

    def test_negative_controls_fire(self, report):
        neg = report.negatives
        assert neg["ok"]
        for key in ("unguarded_u32_add", "narrowing_convert",
                    "stale_wrap_ok"):
            assert neg[key]["fired"], key

    def test_artifact_roundtrip(self, report, tmp_path):
        p = ranges_runner.write_artifact(report,
                                         str(tmp_path / "r.json"))
        import json

        d = json.loads(Path(p).read_text())
        assert d["ok"] is True
        assert len(d["variants"]) == 5
        assert d["negative_controls"]["ok"] is True
        assert d["bridge"]["ok"] is True
        assert {e["name"] for e in d["wrap_ok_registry"]} == {
            e.name for e in registry.WRAP_OK}


class TestBridge:
    """The first STATIC parity bridge between the BPF and jaxpr lanes,
    pinned on the shipped distill artifact (ISSUE 12 acceptance)."""

    def test_containment_on_shipped_artifact(self, report):
        b = report.bridge
        assert b is not None and b["ok"], b
        assert b["mac_contained"] and b["band_contained"]
        assert len(b["mac_sites"]) == schema.NUM_FEATURES
        # the verifier derives the band range [0, 2] purely from the
        # branch-free select arithmetic — exactly the jax band set
        assert b["bpf_band"]["umin"] == int(schema.ML_BAND_PASS)
        assert b["bpf_band"]["umax"] == int(schema.ML_BAND_DROP)

    def test_probe_api_is_observational(self):
        """probes= must not change accept/reject or the explored
        state count."""
        from flowsentryx_tpu.bpf import progs, verifier

        prog = progs.build_ml_scorer()
        base = verifier.check_program(prog, entry_main=False)
        probed = verifier.check_program(prog, entry_main=False,
                                        probes={0: 1})
        assert probed.insns_visited == base.insns_visited
        assert probed.probes[0]["hits"] >= 1

    def test_drifted_scorer_shape_is_refused(self):
        """An emitted scorer without the expected MAC pattern must be
        refused, not silently 'contained'."""
        from flowsentryx_tpu.bpf import progs
        from flowsentryx_tpu.ranges import bridge

        prog = progs.build()  # the non-ML fast path: no fn_ml_score
        with pytest.raises(ValueError, match="shape drift"):
            bridge.locate_probe_sites(prog)


class TestPlantedNegatives:
    """Each finding class fires with an equation-level diagnostic."""

    def test_unguarded_u32_add(self):
        an = _analyze(lambda a, b: a + b,
                      np.zeros(4, np.uint32), np.zeros(4, np.uint32))
        assert not an.ok
        f = an.findings[0]
        assert f.contract == "range"
        assert "add result" in f.reason and "uint32" in f.reason
        assert f.where.startswith("eqns[") and f.eqn  # eqn-level

    def test_narrowing_convert(self):
        an = _analyze(lambda a: a.astype(jnp.uint8),
                      np.zeros(4, np.uint32))
        assert not an.ok
        f = an.findings[0]
        assert "narrowing convert" in f.reason
        assert "uint8" in f.reason and f.where and f.eqn

    def test_guarded_arithmetic_is_clean(self):
        # the same add, masked first: the refinement must prove it
        an = _analyze(lambda a, b: (a & np.uint32(0xFFFF))
                      + (b & np.uint32(0xFFFF)),
                      np.zeros(4, np.uint32), np.zeros(4, np.uint32))
        assert an.ok, [str(f) for f in an.findings]

    def test_stale_registry_entry_missing_function(self):
        stale = registry.WrapOk(
            "gone", "flowsentryx_tpu/ops/hashtable.py",
            "deleted_function_xyz", frozenset({"add"}), "r")
        out = registry.audit_registry((stale,), {"gone": 3})
        assert len(out) == 1 and "stale WRAP_OK" in out[0].reason

    def test_stale_registry_entry_never_matched(self):
        live = registry.WRAP_OK[0]
        out = registry.audit_registry((live,), {})
        assert len(out) == 1
        assert "matched no equation" in out[0].reason

    def test_shipped_registry_functions_exist(self):
        counts = {e.name: 1 for e in registry.WRAP_OK}
        assert registry.audit_registry(registry.WRAP_OK, counts) == []

    def test_wrap_ok_does_not_leak_across_functions(self):
        """An unguarded wrap OUTSIDE a registered function must not be
        absorbed by the registry."""

        def not_hash(a):
            return a * np.uint32(0x85EBCA6B)  # murmur-like, wrong site

        an = _analyze(not_hash, np.zeros(4, np.uint32))
        assert not an.ok


class TestIntervalDomain:
    def test_mask_then_shift_refines(self):
        an = _analyze(lambda w: ((w & np.uint32(0x7FF))
                                 << np.uint32(3)).astype(jnp.uint16),
                      np.zeros(4, np.uint32))
        assert an.ok  # 0x7FF << 3 = 0x3FF8 fits u16

    def test_shift_overflow_detected(self):
        an = _analyze(lambda w: (w & np.uint32(0x7FF))
                      << np.uint32(22),
                      np.zeros(4, np.uint32))
        assert not an.ok
        assert "shift_left" in an.findings[0].reason

    def test_sum_bound_scales_with_batch(self):
        # sum of 300 bytes each <= 255 does not fit u16, does fit u32
        def s16(a):
            return jnp.sum(a & np.uint16(0xFF), dtype=jnp.uint16)

        def s32(a):
            return jnp.sum((a & np.uint16(0xFF)).astype(jnp.uint32),
                           dtype=jnp.uint32)

        assert not _analyze(s16, np.zeros(300, np.uint16)).ok
        assert _analyze(s32, np.zeros(300, np.uint16)).ok

    def test_scan_carry_reaches_fixpoint(self):
        # a saturating carry (min with a cap) stays bounded through
        # the scan; an uncapped accumulating carry must be widened and
        # flagged at the add
        def capped(c, x):
            return jnp.minimum(c + (x & np.uint32(1)),
                               jnp.uint32(100)), x

        def run(c0, xs):
            return jax.lax.scan(capped, c0, xs)

        an = _analyze(run, np.uint32(0), np.zeros(8, np.uint32),
                      seeds_=[iv.scalar(0, 100),
                              iv.top_for(np.uint32)])
        assert an.ok, [str(f) for f in an.findings]

        def uncapped(c, x):
            return c + (x & np.uint32(0xFFFF)), x

        def run2(c0, xs):
            return jax.lax.scan(uncapped, c0, xs)

        an2 = _analyze(run2, np.uint32(0), np.zeros(8, np.uint32),
                       seeds_=[iv.scalar(0, 0),
                               iv.top_for(np.uint32)])
        assert not an2.ok

    def test_div_exact_past_2_53(self):
        # float division rounds past 2^53; the interval divide must
        # stay exact or a true wrap could pass the escape check
        big = (1 << 53) + 3
        d = iv.div(iv.scalar(big, big), iv.scalar(1, 1), np.int64)
        assert d.bounds() == (big, big)
        d2 = iv.div(iv.scalar((1 << 53) + 1, (1 << 53) + 1),
                    iv.scalar(1, 1), np.int64)
        assert d2.bounds() == ((1 << 53) + 1, (1 << 53) + 1)

    def test_reverse_cumsum_covers_suffix_sums(self):
        # reverse cumsum = SUFFIX sums: for lanes [10, -20] the last
        # suffix is -20, below every forward prefix sum
        closed = jax.jit(
            lambda x: jax.lax.cumsum(x, axis=0, reverse=True)).trace(
            np.zeros(2, np.int32)).jaxpr
        lo = np.empty((2,), object)
        lo[:] = [10, -20]
        an = prover.analyze(
            closed, [iv.IVal(lo, lo.copy())],
            collect=lambda w, e: ("c" if e.primitive.name == "cumsum"
                                  else None))
        assert an.collected["c"][0] <= -20

    def test_exact_literal_propagation(self):
        # 0xFFFF * 30000 = 1.97e9 fits int32; * 40000 = 2.6e9 does not
        # — only EXACT literal bounds can tell the two apart
        def f(a, k):
            return (a & np.uint32(0xFFFF)).astype(jnp.int32) * k

        assert _analyze(lambda a: f(a, np.int32(30000)),
                        np.zeros(4, np.uint32)).ok
        assert not _analyze(lambda a: f(a, np.int32(40000)),
                            np.zeros(4, np.uint32)).ok


class TestSeeds:
    def test_metadata_row_is_bounded(self):
        s = seeds.wire_seed((257, 4), schema.WIRE_COMPACT16, 256)
        assert s.hi[256, 0] == 256          # n_valid <= max_batch
        assert s.hi[255, 0] == (1 << 32) - 1  # record rows: full u32
        horizon_us = schema.RANGE_DEPLOY_HORIZON_S * 10 ** 9 // 1000
        assert s.hi[256, 2] == horizon_us >> 32

    def test_raw_ts_hi_words_bounded(self):
        s = seeds.wire_seed((257, 12), schema.WIRE_RAW48, 256)
        horizon_ns = schema.RANGE_DEPLOY_HORIZON_S * 10 ** 9
        assert s.hi[0, 1] == horizon_ns >> 32   # per-record ts HI
        assert s.hi[256, 2] == horizon_ns >> 32  # t0 HI
        assert s.hi[0, 0] == (1 << 32) - 1       # ts LO: full

    def test_param_contract_seeds(self):
        from flowsentryx_tpu.models import logreg

        p = logreg.golden_params()
        leaves = jax.tree_util.tree_flatten_with_path(p)[0]
        svals = seeds.param_seeds(p)
        by_name = {
            jax.tree_util.keystr(path).strip(".").split(".")[-1]: v
            for (path, _), v in zip(leaves, svals)}
        assert by_name["in_zp"].bounds() == (0, 255)
        assert by_name["log1p"].bounds() == (0, 1)

    def test_runtime_consumes_the_same_constants(self):
        """Satellite: the RANGE_* names are the runtime's actual
        clips/masks, not parallel declarations."""
        q = schema.quantize_feat_model(
            np.array([2 ** 32 - 1], np.uint32), 1.0, 0, False)
        assert int(q[0]) == schema.RANGE_FEAT_Q8_MAX
        # the minifloat 255 clamp only engages past the u32 range (the
        # u64 counter-mirror lanes)
        q2 = schema.quantize_feat_minifloat(
            np.array([1 << 63], np.uint64))
        assert int(q2[0]) == schema.RANGE_FEAT_Q8_MAX
        rec = np.zeros(1, schema.FLOW_RECORD_DTYPE)
        rec["pkt_len"] = 65535
        packed = schema.compact_pack(rec, 0)
        assert int(packed[0, 3] & schema.RANGE_LEN8_MAX) == \
            schema.RANGE_LEN8_MAX
