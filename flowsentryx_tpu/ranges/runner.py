"""Variant staging + report assembly for ``fsx ranges``.

Re-stages every serving-step variant through the audit runner's shared
staging surface (:func:`flowsentryx_tpu.audit.runner.stage_variants` —
singles, sharded, every mega rung, eviction epochs via the caller's
config), seeds each staged ``ClosedJaxpr``'s inputs
from the declared range registry, runs the interval prover, audits the
``WRAP_OK`` registry for staleness, proves the three planted negative
controls still fire, and (when a distill artifact is available) runs
the BPF↔jaxpr containment bridge.  One JSON-able report, the ``fsx
check``/``fsx audit`` idiom.

Nothing here executes a batch: ``jitted.trace`` stages the graph and
the prover walks it.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any

import jax
import numpy as np

from flowsentryx_tpu.audit.graph import Finding
from flowsentryx_tpu.audit.runner import stage_variants
from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import FsxConfig
from flowsentryx_tpu.ranges import interval as iv
from flowsentryx_tpu.ranges import prover, registry, seeds


@dataclasses.dataclass
class VariantRanges:
    """One staged variant's range-proof result."""

    name: str
    ok: bool
    findings: list[Finding]
    n_eqns: int
    n_checked: int
    wrap_ok_matches: dict
    unmodeled: dict

    def to_json(self) -> dict:
        return {
            "name": self.name, "ok": self.ok,
            "findings": [f.to_json() for f in self.findings],
            "n_eqns": self.n_eqns, "n_checked": self.n_checked,
            "wrap_ok_matches": self.wrap_ok_matches,
            "unmodeled": self.unmodeled,
        }


@dataclasses.dataclass
class RangesReport:
    """The full ``fsx ranges`` result."""

    ok: bool
    variants: list[VariantRanges]
    registry_findings: list[Finding]
    registry: list[dict]
    negatives: dict
    bridge: dict | None
    config: dict
    backend: str
    jax_version: str
    notes: list[str]

    def to_json(self) -> dict:
        return {
            "ok": self.ok,
            "jax_version": self.jax_version,
            "backend": self.backend,
            "config": self.config,
            "notes": self.notes,
            "variants": [v.to_json() for v in self.variants],
            "wrap_ok_registry": self.registry,
            "registry_findings": [f.to_json()
                                  for f in self.registry_findings],
            "negative_controls": self.negatives,
            "bridge": self.bridge,
        }


# -- planted negative controls ----------------------------------------------
#
# Three deliberately-broken inputs prove each finding class FIRES with
# an equation-level diagnostic — shipped in the report (and pinned in
# tier-1) so a prover regression that silently stops finding wraps
# cannot pass as "everything clean".

def negative_controls() -> dict:
    """Run the planted negatives; each entry records whether its
    finding class fired and the diagnostic it produced."""
    out: dict = {}

    # 1. an unguarded u32 add: two full-range u32 vectors summed with
    #    no carry guard — the canonical silent wrap
    def unguarded(a, b):
        return a + b
    closed = jax.jit(unguarded).trace(
        np.zeros(4, np.uint32), np.zeros(4, np.uint32)).jaxpr
    an = prover.analyze(
        closed, [iv.top_for(np.uint32), iv.top_for(np.uint32)])
    f = [x for x in an.findings if "add result" in x.reason]
    out["unguarded_u32_add"] = {
        "fired": bool(f and f[0].where and f[0].eqn),
        "finding": f[0].to_json() if f else None,
    }

    # 2. a narrowing convert: full-range u32 cast to u8
    def narrowing(a):
        return a.astype(np.uint8)
    closed = jax.jit(narrowing).trace(np.zeros(4, np.uint32)).jaxpr
    an = prover.analyze(closed, [iv.top_for(np.uint32)])
    f = [x for x in an.findings if "narrowing convert" in x.reason]
    out["narrowing_convert"] = {
        "fired": bool(f and f[0].where and f[0].eqn),
        "finding": f[0].to_json() if f else None,
    }

    # 3. a stale WRAP_OK entry: names a function that does not exist —
    #    the staleness audit must refuse the dangling exemption
    stale = registry.WrapOk(
        "planted-stale", "flowsentryx_tpu/ops/hashtable.py",
        "deleted_function_xyz", frozenset({"add"}), "planted control")
    f = registry.audit_registry((stale,), {"planted-stale": 1})
    out["stale_wrap_ok"] = {
        "fired": bool(f and "stale WRAP_OK" in f[0].reason),
        "finding": f[0].to_json() if f else None,
    }

    out["ok"] = all(v["fired"] for k, v in out.items() if k != "ok")
    return out


DEFAULT_ARTIFACT = "artifacts/logreg_int8.npz"


def run_ranges(
    cfg: FsxConfig,
    params: Any | None = None,
    mesh: Any | None = None,
    mega_n: int = 2,
    variants: tuple[str, ...] | None = None,
    mega_sizes: tuple[int, ...] | None = None,
    artifact: str | None = DEFAULT_ARTIFACT,
    with_negatives: bool = True,
) -> RangesReport:
    """Prove the no-silent-wrap property over every staged variant
    under ``cfg`` (staging semantics exactly as
    :func:`~flowsentryx_tpu.audit.runner.run_audit`), plus the
    registry staleness audit, the planted negative controls, and —
    when ``artifact`` names a loadable distill artifact — the BPF↔jaxpr
    containment bridge."""
    staged, notes, params = stage_variants(
        cfg, params=params, mesh=mesh, mega_n=mega_n,
        variants=variants, donate=False, mega_sizes=mega_sizes)

    reports: list[VariantRanges] = []
    match_totals: dict[str, int] = {}
    for sv in staged:
        closed = sv.jitted.trace(*sv.make_args()).jaxpr
        svseeds = seeds.variant_seeds(
            list(closed.in_avals), sv.wire, cfg.batch.max_batch, params)
        an = prover.analyze(closed, svseeds)
        for k, v in an.wrap_matches.items():
            match_totals[k] = match_totals.get(k, 0) + v
        reports.append(VariantRanges(
            name=sv.name, ok=an.ok, findings=an.findings,
            n_eqns=an.n_eqns, n_checked=an.n_checked,
            wrap_ok_matches=an.wrap_matches, unmodeled=an.unmodeled))

    reg_findings = registry.audit_registry(registry.WRAP_OK,
                                           match_totals)

    negatives = negative_controls() if with_negatives else {"ok": True}

    bridge_rep = None
    if artifact:
        apath = Path(artifact)
        if apath.is_file():
            from flowsentryx_tpu.models import logreg
            from flowsentryx_tpu.ranges import bridge

            try:
                art_params = logreg.load_params(str(apath))
                bridge_rep = bridge.containment_proof(art_params)
                bridge_rep["artifact"] = str(apath)
            except (ValueError, OSError) as e:
                bridge_rep = {"ok": False, "artifact": str(apath),
                              "error": str(e)}
        else:
            notes.append(f"containment bridge skipped: no distill "
                         f"artifact at {artifact}")

    ok = (all(v.ok for v in reports) and not reg_findings
          and negatives.get("ok", True)
          and (bridge_rep is None or bridge_rep.get("ok", False)))
    return RangesReport(
        ok=ok,
        variants=reports,
        registry_findings=reg_findings,
        registry=[e.to_json() for e in registry.WRAP_OK],
        negatives=negatives,
        bridge=bridge_rep,
        config={
            "max_batch": cfg.batch.max_batch,
            "verdict_k": cfg.batch.verdict_k,
            "capacity": cfg.table.capacity,
            "evict_ttl_s": cfg.table.evict_ttl_s,
            "evict_every": cfg.table.evict_every,
            "model": cfg.model.name,
            "mesh_devices": int(mesh.devices.size)
            if mesh is not None else 1,
            "mega_n": mega_n,
            "deploy_horizon_s": schema.RANGE_DEPLOY_HORIZON_S,
        },
        backend=jax.default_backend(),
        jax_version=jax.__version__,
        notes=notes,
    )


def write_artifact(report: RangesReport, path: str) -> str:
    """Write the machine-readable ranges artifact and return the
    path."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(report.to_json(), indent=2) + "\n")
    return str(p)
