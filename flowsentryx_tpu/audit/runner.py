"""Variant staging + report assembly for ``fsx audit``.

Stages every serving-step variant the engine can build — raw48 and
compact16 single-device (:mod:`flowsentryx_tpu.ops.fused`), the
IP-hash-sharded step (:mod:`flowsentryx_tpu.parallel.step`), and the
``lax.scan`` megastep — down to its ClosedJaxpr and compiled
executable, runs the :mod:`flowsentryx_tpu.audit.graph` contract checks
on each, and folds the results into one JSON-able
:class:`AuditReport` (the ``fsx check`` diagnostic idiom, aimed at the
TPU plane).

Nothing here executes a batch: ``jitted.trace`` stages the graph,
``.lower().compile()`` builds the executable whose alias map and
entry layout the donation/transfer contracts read.  The one
engine-visible entry point is :func:`boot_audit`, which caches by
(config, variant set) so a serving boot audits each compiled shape
exactly once per process.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable

import jax
import numpy as np

from flowsentryx_tpu.audit import graph
from flowsentryx_tpu.audit.graph import AuditError, Finding
from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import FsxConfig
from flowsentryx_tpu.models import get_model
from flowsentryx_tpu.ops import fused

#: Carried-state leaf names, in flattened (table, stats) order — the
#: donated buffers and the serving loop's feedback carry.
CARRY_NAMES = ["table.key", "table.state"] + [
    f"stats.{f}" for f in schema.GlobalStats._fields]

#: The auditable variants, in report order.  "sharded_megastep" is the
#: scan-over-shard_map graph a mesh+mega engine actually serves — its
#: contracts are NOT implied by sharded and megastep separately (the
#: scan could drop the table donation or add a collective of its own).
ALL_VARIANTS = ("raw", "compact", "sharded", "megastep",
                "sharded_megastep")


@dataclasses.dataclass
class VariantReport:
    """One staged step variant's audit result."""

    name: str
    ok: bool
    findings: list[Finding]
    outputs: list[dict]            # name/shape/dtype/bytes per output
    n_eqns: int
    steady_state_d2h_bytes: int | None  # the wire fetch; None if no wire
    wire_words: int | None
    donation: dict                 # aliased params / required leaves
    collectives: dict              # collective primitive -> count
    dtypes: dict                   # dtype -> eqn-output count
    inplace: dict                  # table copy/convert/conditional census

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["findings"] = [f.to_json() for f in self.findings]
        return d


@dataclasses.dataclass
class AuditReport:
    """The full ``fsx audit`` result (one entry per staged variant)."""

    ok: bool
    variants: list[VariantReport]
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
        }

    def raise_if_failed(self) -> None:
        for v in self.variants:
            if not v.ok:
                raise AuditError(v.name, v.findings)


def _out_names(out_info: Any) -> list[str]:
    """Semantic names for the flattened step outputs: the out tree is
    ``(IpTableState, GlobalStats, StepOutput)`` for every variant."""
    tops = {0: "table", 1: "stats", 2: "out"}
    names = []
    for path, _ in jax.tree_util.tree_flatten_with_path(out_info)[0]:
        key = jax.tree_util.keystr(path)  # e.g. "[2].wire"
        for idx, top in tops.items():
            prefix = f"[{idx}]"
            if key.startswith(prefix):
                key = top + key[len(prefix):]
                break
        names.append(key)
    return names


def _arg_name(i: int, n_params: int) -> str:
    if i < len(CARRY_NAMES):
        return CARRY_NAMES[i]
    if i < len(CARRY_NAMES) + n_params:
        return f"params[{i - len(CARRY_NAMES)}]"
    return "raw"


def _audit_one(
    name: str,
    jitted: Any,
    make_args: Callable[[], tuple],
    *,
    verdict_k: int,
    expect_sharded: bool,
    donate_leaves: int,
    quantized: bool,
    n_param_leaves: int,
    n_shards: int = 1,
) -> VariantReport:
    """Stage one variant and run every contract on it."""
    findings: list[Finding] = []

    # contract 4: retrace sentinel (also produces the staged trace)
    f, traced = graph.staging_cache_check(
        jitted, make_args, arg_names=lambda i: _arg_name(i, n_param_leaves))
    findings += f
    closed = traced.jaxpr
    findings += graph.check_carry_avals(closed, len(CARRY_NAMES),
                                        CARRY_NAMES)

    # contract 1: dtype / precision
    findings += graph.check_dtypes(closed)
    if quantized:
        findings += graph.check_quantized_lane(closed)
    dtypes = graph.dtype_histogram(closed)

    # contract 3: host round-trips + the steady-state D2H budget
    findings += graph.check_callbacks(closed)
    lowered = traced.lower()
    out_leaves = jax.tree_util.tree_leaves(lowered.out_info)
    names = _out_names(lowered.out_info)
    outputs = []
    wire_bytes = wire_words = None
    for n, leaf in zip(names, out_leaves):
        nbytes = int(np.prod(leaf.shape, dtype=np.int64)) * np.dtype(
            leaf.dtype).itemsize
        outputs.append({"name": n, "shape": list(leaf.shape),
                        "dtype": str(np.dtype(leaf.dtype)),
                        "bytes": int(nbytes)})
        if n.endswith(".wire"):
            wire_words = int(np.prod(leaf.shape, dtype=np.int64))
            wire_bytes = int(nbytes)
            if np.dtype(leaf.dtype) != np.uint32:
                findings.append(Finding(
                    contract="transfer", where=n,
                    reason=f"verdict wire dtype {leaf.dtype}, expected "
                           "uint32 (the host decoder bitcasts in place)"))
    expect_words = fused.verdict_wire_words(verdict_k) if verdict_k else 0
    if verdict_k <= 0:
        findings.append(Finding(
            contract="transfer",
            reason=("verdict_k == 0 disables the compact wire: "
                    "steady-state D2H is the full [B] block arrays — "
                    "the audited transfer budget requires verdict_k "
                    ">= 1")))
    elif wire_words is None:
        findings.append(Finding(
            contract="transfer", where="out.wire",
            reason="no compact verdict wire in the step outputs"))
    elif wire_words != expect_words:
        findings.append(Finding(
            contract="transfer", where="out.wire",
            reason=(f"wire is {wire_words} words, expected "
                    f"2*{verdict_k}+{fused.VERDICT_WIRE_SCALARS} = "
                    f"{expect_words}")))
    for n, leaf in zip(names, out_leaves):
        expected = {"out.verdict": np.uint8, "out.block_key": np.uint32,
                    "out.block_until": np.float32, "out.now": np.float32}
        want = expected.get(n)
        if want is not None and np.dtype(leaf.dtype) != want:
            findings.append(Finding(
                contract="dtype", where=n,
                reason=(f"step output {n} is {np.dtype(leaf.dtype)}, "
                        f"contract says {np.dtype(want).name}")))

    # contract 5: collectives
    f, coll = graph.check_collectives(closed, verdict_k, expect_sharded)
    findings += f

    # contract 2: donation (needs the compiled executable's alias map)
    donation: dict = {"checked": donate_leaves > 0,
                      "required": CARRY_NAMES[:donate_leaves]}
    hlo = None
    if donate_leaves:
        hlo = lowered.compile().as_text()
        f, info = graph.check_donation(
            hlo, CARRY_NAMES[:donate_leaves],
            list(closed.in_avals)[:donate_leaves],
            n_inputs=len(closed.in_avals))
        findings += f
        donation.update(info)

    # contract 6: in-place/copy census on the donated table (the two
    # table leaves are always the leading inputs); the jaxpr half
    # (cond / dynamic-offset DUS) runs even when donation is off and
    # matches shard-local avals inside shard_map bodies, the HLO half
    # censuses the same executable the donation check read
    f, inplace = graph.check_inplace(
        closed, hlo, list(closed.in_avals)[:2], CARRY_NAMES[:2],
        n_shards=n_shards)
    findings += f

    n_eqns = sum(1 for _ in graph.iter_eqns(closed))
    return VariantReport(
        name=name, ok=not findings, findings=findings, outputs=outputs,
        n_eqns=n_eqns, steady_state_d2h_bytes=wire_bytes,
        wire_words=wire_words, donation=donation, collectives=coll,
        dtypes=dtypes, inplace=inplace,
    )


def _normalize_mega_sizes(
    mega_sizes: tuple[int, ...] | None, mega_n: int
) -> tuple[int, ...]:
    """THE one (dedup, sort-descending, validate) rule for the
    megastep group-size ladder — shared by :func:`run_audit` (which
    stages the set) and :func:`boot_audit` (which keys the cache on
    it), so a cache hit can never vouch for a ladder that normalizes
    differently from what was actually staged."""
    if mega_sizes is not None:
        sizes = tuple(sorted({int(s) for s in mega_sizes}, reverse=True))
        if not sizes or min(sizes) < 1:
            raise ValueError(f"mega_sizes must be >= 1, got {mega_sizes}")
        return sizes
    return (mega_n,) if mega_n >= 1 else ()


def _zeros_raw(cfg: FsxConfig, compact: bool) -> np.ndarray:
    words = (schema.COMPACT_RECORD_WORDS if compact
             else schema.RECORD_WORDS)
    return np.zeros((cfg.batch.max_batch + 1, words), np.uint32)


@dataclasses.dataclass
class StagedVariant:
    """One stageable step variant plus the metadata every static pass
    over it needs — the shared staging surface of the device-plane
    static suite (``fsx audit`` consumes it here;
    :mod:`flowsentryx_tpu.ranges` re-stages the same set for the
    integer value-range proof, so the two legs can never audit
    different graphs for one config)."""

    name: str
    jitted: Any
    make_args: Callable[[], tuple]
    verdict_k: int
    expect_sharded: bool
    donate_leaves: int
    quantized: bool
    n_param_leaves: int
    n_shards: int = 1
    wire: str = schema.WIRE_COMPACT16  # which wire format `make_args`
    #                                    builds (the range seeder keys
    #                                    its per-word seeds on this)


def stage_variants(
    cfg: FsxConfig,
    params: Any | None = None,
    mesh: Any | None = None,
    mega_n: int = 2,
    variants: tuple[str, ...] | None = None,
    donate: bool = True,
    mega_sizes: tuple[int, ...] | None = None,
) -> tuple[list[StagedVariant], list[str], Any]:
    """Build (without tracing) every requested step variant under
    ``cfg``; returns ``(staged, notes, params)``.  Argument semantics
    are exactly :func:`run_audit`'s — this IS its staging loop,
    factored out so other static passes prove the same artifacts."""
    staged, notes, params, _donate, _sizes = _stage_variants(
        cfg, params, mesh, mega_n, variants, donate, mega_sizes)
    return staged, notes, params


def _stage_variants(
    cfg: FsxConfig,
    params: Any | None,
    mesh: Any | None,
    mega_n: int,
    variants: tuple[str, ...] | None,
    donate: bool,
    mega_sizes: tuple[int, ...] | None,
) -> tuple[list[StagedVariant], list[str], Any, bool, tuple[int, ...]]:
    notes: list[str] = []
    spec = get_model(cfg.model.name)
    if params is None:
        params = spec.init()
    quant = schema.wire_quant_for(params)
    n_param_leaves = len(jax.tree_util.tree_leaves(params))
    shardable = mesh is not None and int(mesh.devices.size) > 1
    sizes = _normalize_mega_sizes(mega_sizes, mega_n)
    mega_ok = bool(sizes)
    if variants is None:
        variants = tuple(
            v for v in ALL_VARIANTS
            if (shardable or not v.startswith("sharded"))
            and (mega_ok or "megastep" not in v))
        if not shardable:
            notes.append("sharded variants skipped: need a >1-device "
                         "mesh (run under "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N or on a real slice)")
        if not mega_ok:
            notes.append("megastep variants skipped: mega_n < 1")
    else:
        bad = [v for v in variants
               if ("megastep" in v and not mega_ok)
               or (v.startswith("sharded") and not shardable)]
        if bad:
            raise ValueError(
                f"variant(s) {bad} need "
                + ("mega_n >= 1" if "megastep" in bad[0]
                   else "a >1-device mesh"))

    def table_args(sharded: bool):
        table = schema.make_table(cfg.table.capacity)
        if sharded:
            from flowsentryx_tpu import parallel as par

            table = par.shard_table(table, mesh)
        return table, schema.make_stats()

    staged: list[StagedVariant] = []
    for name in variants:
        if name == "raw":
            jitted = fused.make_jitted_raw_step(
                cfg, spec.classify_batch, donate=donate)

            def mk():
                return (*table_args(False), params,
                        _zeros_raw(cfg, compact=False))
            staged.append(StagedVariant(
                name, jitted, mk, verdict_k=cfg.batch.verdict_k,
                expect_sharded=False,
                donate_leaves=len(CARRY_NAMES) if donate else 0,
                quantized=cfg.model.quantized,
                n_param_leaves=n_param_leaves,
                wire=schema.WIRE_RAW48))
        elif name == "compact":
            jitted = fused.make_jitted_compact_step(
                cfg, spec.classify_batch, donate=donate, **quant)

            def mk():
                return (*table_args(False), params,
                        _zeros_raw(cfg, compact=True))
            staged.append(StagedVariant(
                name, jitted, mk, verdict_k=cfg.batch.verdict_k,
                expect_sharded=False,
                donate_leaves=len(CARRY_NAMES) if donate else 0,
                quantized=cfg.model.quantized,
                n_param_leaves=n_param_leaves))
        elif name == "sharded":
            from flowsentryx_tpu import parallel as par

            jitted = par.make_sharded_compact_step(
                cfg, spec.classify_batch, mesh, donate=donate, **quant)

            def mk():
                return (*table_args(True), params,
                        _zeros_raw(cfg, compact=True))
            staged.append(StagedVariant(
                name, jitted, mk, verdict_k=cfg.batch.verdict_k,
                expect_sharded=True,
                # table only (stats replicate, cannot alias)
                donate_leaves=2 if donate else 0,
                quantized=cfg.model.quantized,
                n_param_leaves=n_param_leaves,
                n_shards=int(mesh.devices.size)))
        elif name in ("megastep", "sharded_megastep"):
            is_sh = name == "sharded_megastep"
            # one staged artifact PER group size: an adaptive engine
            # serves every rung of its ladder, so every rung's graph
            # must be proved, not just the largest
            for n_sz in sizes:
                if is_sh:
                    from flowsentryx_tpu import parallel as par

                    jitted = par.make_sharded_compact_megastep(
                        cfg, spec.classify_batch, mesh, n_sz,
                        donate=donate, **quant)
                else:
                    jitted = fused.make_jitted_compact_megastep(
                        cfg, spec.classify_batch, n_sz, donate=donate,
                        **quant)

                def mk(is_sh=is_sh, n_sz=n_sz):
                    raws = np.zeros(
                        (n_sz, cfg.batch.max_batch + 1,
                         schema.COMPACT_RECORD_WORDS), np.uint32)
                    return (*table_args(is_sh), params, raws)
                staged.append(StagedVariant(
                    name if len(sizes) == 1 else f"{name}@{n_sz}",
                    jitted, mk, verdict_k=cfg.batch.verdict_k,
                    expect_sharded=is_sh,
                    donate_leaves=((2 if is_sh else len(CARRY_NAMES))
                                   if donate else 0),
                    quantized=cfg.model.quantized,
                    n_param_leaves=n_param_leaves,
                    n_shards=(int(mesh.devices.size) if is_sh else 1)))
        else:
            raise ValueError(f"unknown audit variant {name!r}")
    return staged, notes, params, donate, sizes


def run_audit(
    cfg: FsxConfig,
    params: Any | None = None,
    mesh: Any | None = None,
    mega_n: int = 2,
    variants: tuple[str, ...] | None = None,
    donate: bool = True,
    mega_sizes: tuple[int, ...] | None = None,
) -> AuditReport:
    """Stage and audit the requested step variants under ``cfg``.

    ``variants`` defaults to everything stageable here: raw + compact +
    megastep always, sharded when ``mesh`` spans more than one device.
    ``donate`` is on as in the engine; ``False`` stages undonated
    graphs and skips the donation contract.

    ``mega_sizes`` audits the megastep variants once PER group size —
    the adaptive-coalescing engine's ladder
    (:func:`~flowsentryx_tpu.ops.fused.pow2_group_sizes`), where every
    rung is its own compiled scan artifact whose contracts (528 B wire
    after ``merge_verdict_wires``, donation through the scan carry,
    collective budget per chunk) must be proved individually.  With
    more than one size the per-size reports are named
    ``megastep@<n>``; ``None`` keeps the single-``mega_n`` staging and
    plain names.
    """
    staged, notes, params, donate, sizes = _stage_variants(
        cfg, params, mesh, mega_n, variants, donate, mega_sizes)
    reports = [
        _audit_one(
            sv.name, sv.jitted, sv.make_args, verdict_k=sv.verdict_k,
            expect_sharded=sv.expect_sharded,
            donate_leaves=sv.donate_leaves, quantized=sv.quantized,
            n_param_leaves=sv.n_param_leaves, n_shards=sv.n_shards)
        for sv in staged
    ]

    return AuditReport(
        ok=all(v.ok for v in reports),
        variants=reports,
        config={
            "max_batch": cfg.batch.max_batch,
            "verdict_k": cfg.batch.verdict_k,
            "capacity": cfg.table.capacity,
            # the eviction epoch changes every staged graph (the
            # in-step rolling sweep window), so the artifact records
            # which family this report proved; the boot cache keys on
            # cfg.to_json(), so eviction-enabled engines re-audit
            # automatically
            "evict_ttl_s": cfg.table.evict_ttl_s,
            "evict_every": cfg.table.evict_every,
            "model": cfg.model.name,
            "mesh_devices": int(mesh.devices.size) if mesh is not None
            else 1,
            "mega_n": mega_n,
            "mega_sizes": list(sizes),
            "donate": bool(donate),
        },
        backend=jax.default_backend(),
        jax_version=jax.__version__,
        notes=notes,
    )


# -- engine boot hook -------------------------------------------------------

#: Completed boot audits, keyed by the staged-shape signature — an
#: engine restart (or a test constructing many engines) re-proves each
#: compiled shape once per process, not once per construction.
_BOOT_CACHE: dict[tuple, bool] = {}


def boot_audit(
    cfg: FsxConfig,
    *,
    wire: str,
    mesh: Any | None,
    mega_n: int,
    params: Any | None = None,
    mega_sizes: tuple[int, ...] | None = None,
) -> AuditReport | None:
    """Audit exactly the variants a booting engine is about to serve
    and refuse the boot (raise :class:`AuditError`) on any violated
    contract.  Returns None on a cache hit.

    ``mega_sizes`` is the adaptive engine's group-size ladder: every
    size stages (and is cached) as its own variant, and the cache key
    includes the SET — an engine re-booting with a different ladder is
    serving different compiled artifacts and must re-prove them."""
    shardable = mesh is not None and int(mesh.devices.size) > 1
    variants: list[str] = []
    if shardable:
        variants.append("sharded")
    else:
        variants.append("compact" if wire == schema.WIRE_COMPACT16
                        else "raw")
    sizes = _normalize_mega_sizes(mega_sizes, mega_n)
    if sizes:
        # the scan-over-shard_map graph is its own compiled artifact —
        # auditing sharded + single-device megastep separately would
        # leave the variant that actually serves unproved
        variants.append("sharded_megastep" if shardable else "megastep")
    # The cache key must cover everything that changes the STAGED
    # graph: config, wire, mesh, the group-size set — and the params
    # leaves' shapes/dtypes (a later engine serving a different
    # artifact, e.g. an f64-poisoned .npz, is a different graph and
    # must re-audit).  The ONE definition of that rule is
    # core/signature.staging_signature — shared with the range
    # certifier (same staging surface) and the persistent AOT compile
    # cache (engine/compile_cache.py), so the three can never drift on
    # what keys a staged shape.
    from flowsentryx_tpu.core.signature import (
        signature_digest, staging_signature,
    )

    sig = staging_signature(
        cfg, wire=wire,
        mesh_devices=int(mesh.devices.size) if shardable else 1,
        mega_sizes=sizes, params=params)
    key = (signature_digest(sig), tuple(variants))
    if _BOOT_CACHE.get(key):
        return None
    rep = run_audit(cfg, params=params, mesh=mesh,
                    mega_n=mega_n or 2, variants=tuple(variants),
                    mega_sizes=sizes or None)
    rep.raise_if_failed()
    _BOOT_CACHE[key] = True
    return rep


def audit_serving(*args: Any, **kw: Any) -> AuditReport | None:
    """Alias of :func:`boot_audit` (the engine-facing name)."""
    return boot_audit(*args, **kw)


def write_artifact(report: AuditReport, path: str) -> str:
    """Write the machine-readable audit artifact (per-variant output
    byte budgets + findings) and return the path."""
    from pathlib import Path

    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(report.to_json(), indent=2) + "\n")
    return str(p)
