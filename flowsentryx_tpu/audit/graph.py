"""Jaxpr/HLO-level contract checks — the auditor's instruction layer.

Every check here takes a staged artifact (a ``ClosedJaxpr`` from
``jitted.trace(...)`` or the compiled module's HLO text) and returns a
list of :class:`Finding`\\s, each naming the offending equation (by its
path through the nested jaxpr) or executable parameter — the same
diagnostic shape :mod:`flowsentryx_tpu.bpf.verifier` gives for rejected
BPF instructions.  Nothing in this module executes device code: the
point is that the contracts are *properties of the compiled graph*,
provable before the first batch is dispatched.

Contract catalog (docs/AUDIT.md has the operator view):

* :func:`check_dtypes` — no f64/complex anywhere in the graph (the
  all-quantized-lanes claim; one stray ``float(...)`` promotion doubles
  every buffer it touches).
* :func:`check_quantized_lane` — the int8 classifier matmul really is
  integer-domain ``dot_general`` (a silent dequantize-then-float-dot
  keeps the numbers and loses the MXU int path).
* :func:`check_callbacks` — no ``pure_callback``/``io_callback``/
  ``debug_callback``/infeed/outfeed host round-trips hiding in the hot
  step.
* :func:`check_collectives` — the sharded step's cross-device traffic
  is exactly the designed set: two routing ``all_to_all``\\s, the
  O(verdict_k) ``all_gather`` on the compact wire, scalar reductions.
* :func:`check_donation` — ``donate_argnums`` buffers actually appear
  in the executable's ``input_output_alias`` map (a dropped donation is
  a silent HBM copy of the 1M-row table per batch).
* :func:`check_inplace` — the in-place/copy census: the donated table
  incurs zero ``copy``/``convert`` HLO ops and never rides a
  ``lax.cond`` or dynamic-offset ``dynamic_update_slice`` — the two
  measured XLA:CPU cliffs (PR 8) pinned as graph facts instead of
  bench-only findings.
* :func:`staging_cache_check` — staging twice under identical
  host-side construction hits the jit tracing cache (weak_type /
  dtype / static-arg drift means the serving loop recompiles forever).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Iterator

#: Primitives that round-trip through the host mid-graph.  Any of these
#: in a serving step turns the "one D2H wire per batch" budget into an
#: unbounded sync point.
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
})

#: Cross-device primitives the sharded step is *designed* to contain.
#: Anything else crossing devices is accidental traffic.
EXPECTED_COLLECTIVES = frozenset({
    "all_to_all",   # flow partials out + verdicts back (2 per step)
    "all_gather",   # the compact verdict wire only (K-sized operands)
    "psum", "pmax", "pmin",  # scalar stat/clock reductions
    "axis_index",   # device id, no traffic at all
})

#: All primitives we classify as collectives (superset of the expected
#: set — an unexpected member is a finding, not a crash).
COLLECTIVE_PRIMITIVES = EXPECTED_COLLECTIVES | frozenset({
    "ppermute", "pbroadcast", "all_gather_invariant", "reduce_scatter",
    "psum_scatter", "pgather", "pdot", "collective_permute",
})

#: Scalar-reduction operand ceiling (elements): psum/pmax carry the
#: [4+1] stat-count vector and the batch clock, never per-record data.
REDUCTION_MAX_ELEMS = 8

#: all_to_all count per staged step graph: partials out, verdicts back.
MAX_ALL_TO_ALL = 2


@dataclasses.dataclass
class Finding:
    """One violated contract, pinned to an equation or parameter."""

    contract: str   # dtype | quantized | transfer | donation | ...
    reason: str     # human-actionable sentence
    where: str = ""  # eqn path ("eqns[3]:convert_element_type/...") or
    #                  output/param name ("table.key", "out.wire")
    eqn: str = ""   # the offending equation's text (trimmed)

    def to_json(self) -> dict:
        return {k: v for k, v in dataclasses.asdict(self).items() if v}

    def __str__(self) -> str:
        loc = f" at {self.where}" if self.where else ""
        eqn = f"\n    {self.eqn}" if self.eqn else ""
        return f"[{self.contract}]{loc}: {self.reason}{eqn}"


class AuditError(RuntimeError):
    """Raised when an audited variant violates a contract (engine boot
    refuses to serve on it; ``fsx audit`` exits 1)."""

    def __init__(self, variant: str, findings: list[Finding]):
        self.variant = variant
        self.findings = findings
        lines = "\n  ".join(str(f) for f in findings)
        super().__init__(
            f"fsx audit: step variant {variant!r} violates "
            f"{len(findings)} contract(s):\n  {lines}")


# -- jaxpr traversal --------------------------------------------------------

def _sub_jaxprs(value: Any) -> Iterator[Any]:
    """Yield nested Jaxprs hiding inside one eqn param value (pjit /
    scan carry ClosedJaxpr, shard_map carries a bare Jaxpr, cond
    carries lists of branches)."""
    items = value if isinstance(value, (list, tuple)) else (value,)
    for v in items:
        if hasattr(v, "eqns"):           # bare Jaxpr
            yield v
        elif hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
            yield v.jaxpr                # ClosedJaxpr


def iter_eqns(jaxpr: Any, path: str = "") -> Iterator[tuple[str, Any]]:
    """Depth-first ``(path, eqn)`` walk over a (possibly closed) jaxpr,
    descending into every nested sub-jaxpr (pjit bodies, scan bodies,
    shard_map bodies, cond branches)."""
    for where, eqn, _ in iter_platform_eqns(jaxpr, path):
        yield where, eqn


#: what :func:`iter_platform_eqns` calls the ``default=`` branch of a
#: ``jax.lax.platform_dependent`` (JAX itself stores ``None``)
DEFAULT_PLATFORM = "default"


def platform_branches(eqn: Any) -> tuple[tuple[str, ...], ...] | None:
    """The platforms of each branch of a ``cond`` that
    ``jax.lax.platform_dependent`` staged, ``None`` for any other
    equation.  Such a cond's index is a ``platform_index`` and is
    resolved where the program is LOWERED: the compiler sees one
    branch inlined and never a conditional, so the rules that hold a
    runtime ``lax.cond`` do not apply to it, and each branch answers
    only to the rules of the platforms it is lowered for."""
    if eqn.primitive.name != "cond":
        return None
    platforms = eqn.params.get("branches_platforms")
    if platforms is None:
        return None
    return tuple((DEFAULT_PLATFORM,) if ps is None else tuple(ps)
                 for ps in platforms)


def iter_platform_eqns(jaxpr: Any, path: str = "",
                       platforms: tuple[str, ...] | None = None,
                       ) -> Iterator[tuple[str, Any, tuple[str, ...] | None]]:
    """:func:`iter_eqns` with, for each equation, the platforms of the
    innermost ``platform_dependent`` branch it lies in (``None``:
    outside any — lowered for every platform)."""
    if hasattr(jaxpr, "jaxpr"):          # ClosedJaxpr -> Jaxpr
        jaxpr = jaxpr.jaxpr
    for i, eqn in enumerate(jaxpr.eqns):
        where = f"{path}eqns[{i}]:{eqn.primitive.name}"
        yield where, eqn, platforms
        per_branch = platform_branches(eqn)
        for pname, pval in eqn.params.items():
            for k, sub in enumerate(_sub_jaxprs(pval)):
                inner = (per_branch[k] if per_branch is not None
                         and pname == "branches" else platforms)
                yield from iter_platform_eqns(sub, f"{where}/{pname}/",
                                              inner)


def iter_staged_eqns(jaxpr: Any, stage: str | None = None
                     ) -> Iterator[tuple[str | None, Any]]:
    """Depth-first ``(stage, eqn)`` walk like :func:`iter_eqns`, where
    ``stage`` is the first ``fsx.<stage>`` ``jax.named_scope`` an
    equation lies under (``None``: under none).  An equation's own
    name stack starts afresh inside a sub-jaxpr (a ``jnp.where`` is a
    ``jit`` of its own), so the enclosing equation's stage is handed
    down: what the lowering does with the stacks, and what a device
    trace then shows."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        own = next((c[4:] for c in str(eqn.source_info.name_stack).split("/")
                    if c.startswith("fsx.")), None)
        inner = stage or own
        yield inner, eqn
        for pval in eqn.params.values():
            for sub in _sub_jaxprs(pval):
                yield from iter_staged_eqns(sub, inner)


def _eqn_txt(eqn: Any, limit: int = 160) -> str:
    txt = " ".join(str(eqn).split())
    return txt if len(txt) <= limit else txt[: limit - 3] + "..."


def _avals(vars_: Any) -> Iterator[Any]:
    for v in vars_:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


# -- contract 1: dtype / precision ------------------------------------------

#: Width-doubling dtypes that must never appear in a serving graph.
BANNED_DTYPES = ("float64", "complex64", "complex128")


def dtype_histogram(closed_jaxpr: Any) -> dict[str, int]:
    """``dtype name -> eqn-output count`` over the whole graph (the
    report's precision inventory)."""
    hist: dict[str, int] = {}
    for _, eqn in iter_eqns(closed_jaxpr):
        for aval in _avals(eqn.outvars):
            name = str(aval.dtype)
            hist[name] = hist.get(name, 0) + 1
    return hist


def check_dtypes(closed_jaxpr: Any,
                 banned: tuple[str, ...] = BANNED_DTYPES) -> list[Finding]:
    """No banned dtype may appear on any equation input or output."""
    out: list[Finding] = []
    for where, eqn in iter_eqns(closed_jaxpr):
        for aval in _avals(list(eqn.outvars) + list(eqn.invars)):
            if str(aval.dtype) in banned:
                out.append(Finding(
                    contract="dtype", where=where, eqn=_eqn_txt(eqn),
                    reason=(f"{aval.dtype} value of shape "
                            f"{tuple(aval.shape)} in the step graph — "
                            "the serving plane is quantized/f32-only"),
                ))
                break  # one finding per eqn is enough to act on
    return out


def check_quantized_lane(closed_jaxpr: Any) -> list[Finding]:
    """A quantized model's classifier matmul must be an integer-domain
    ``dot_general`` — if every dot in the graph runs on floats, the int8
    weights were silently dequantized before the MXU."""
    saw_dot = False
    for _, eqn in iter_eqns(closed_jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        saw_dot = True
        if any(str(a.dtype).startswith(("int", "uint"))
               for a in _avals(eqn.invars)):
            return []
    if not saw_dot:
        return []  # no matmul at all (non-MXU model): nothing to pin
    return [Finding(
        contract="quantized",
        reason=("model is configured quantized but no integer-domain "
                "dot_general exists in the graph — the int8 lane was "
                "silently promoted to float before the matmul"),
    )]


# -- contract 3b: host round-trips ------------------------------------------

def check_callbacks(closed_jaxpr: Any) -> list[Finding]:
    """No host-callback / infeed / outfeed primitive may hide in the
    step: each one is an unbounded mid-graph host sync."""
    out = []
    for where, eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES or "callback" in name:
            out.append(Finding(
                contract="transfer", where=where, eqn=_eqn_txt(eqn),
                reason=(f"host round-trip primitive {name!r} in the "
                        "step graph — the serving step's only host "
                        "contact is the post-step wire fetch"),
            ))
    return out


# -- contract 5: collectives ------------------------------------------------

def check_collectives(closed_jaxpr: Any, verdict_k: int,
                      expect_sharded: bool) -> tuple[list[Finding], dict]:
    """Enumerate cross-device primitives and hold them to the design:

    * single-device variants contain none at all;
    * sharded variants contain at most :data:`MAX_ALL_TO_ALL`
      ``all_to_all``\\s (flow routing), ``all_gather`` only on
      verdict_k-sized operands (the compact wire fold), and scalar
      ``psum``/``pmax`` reductions — nothing may gather or reduce a
      ``[B]``-shaped per-record array across the mesh.

    The budget holds for EVERY staged variant: a megastep is a
    ``lax.scan``, and a scan stages its body jaxpr once — so the graph
    text carries the designed per-step collective set exactly once
    regardless of group size (test-pinned by the sharded_megastep
    audit acceptance)."""
    findings: list[Finding] = []
    counts: dict[str, int] = {}
    for where, eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMITIVES:
            continue
        counts[name] = counts.get(name, 0) + 1
        sizes = [int(a.size) for a in _avals(eqn.invars)]
        if not expect_sharded:
            findings.append(Finding(
                contract="collectives", where=where, eqn=_eqn_txt(eqn),
                reason=(f"collective {name!r} in a single-device step "
                        "variant"),
            ))
            continue
        if name not in EXPECTED_COLLECTIVES:
            findings.append(Finding(
                contract="collectives", where=where, eqn=_eqn_txt(eqn),
                reason=(f"unexpected collective {name!r} — the sharded "
                        "step's traffic is all_to_all routing, the "
                        "wire all_gather, and scalar reductions only"),
            ))
        elif name == "all_gather":
            bad = [s for s in sizes if s != verdict_k]
            if bad:
                findings.append(Finding(
                    contract="collectives", where=where,
                    eqn=_eqn_txt(eqn),
                    reason=(f"all_gather on a {bad[0]}-element operand; "
                            f"only the [{verdict_k}]-slot compact wire "
                            "may be gathered (per-record arrays stay "
                            "on their shard)"),
                ))
        elif name in ("psum", "pmax", "pmin"):
            bad = [s for s in sizes if s > REDUCTION_MAX_ELEMS]
            if bad:
                findings.append(Finding(
                    contract="collectives", where=where,
                    eqn=_eqn_txt(eqn),
                    reason=(f"{name} over a {bad[0]}-element operand "
                            f"(> {REDUCTION_MAX_ELEMS}): cross-device "
                            "reductions carry stat counts and clocks, "
                            "never batch data"),
                ))
    if counts.get("all_to_all", 0) > MAX_ALL_TO_ALL:
        findings.append(Finding(
            contract="collectives",
            reason=(f"{counts['all_to_all']} all_to_all ops in one step "
                    f"(design: {MAX_ALL_TO_ALL} — flow partials out, "
                    "verdicts back); extra ones double-route the batch"),
        ))
    return findings, counts


# -- contract 2: donation ---------------------------------------------------

_ALIAS_RE = re.compile(r"\(\s*(\d+)\s*,")
_SHAPE_TOKEN = re.compile(
    r"(?:pred|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|f8\w*|f16|bf16|f32|f64|"
    r"c64|c128)\[[^\]]*\]")


def _entry_param_tokens(hlo_text: str) -> list[str]:
    """Shape tokens of the entry parameters, in declaration order, off
    the ``entry_computation_layout`` header ([] when absent)."""
    m = re.search(r"entry_computation_layout=\{\((.*?)\)->", hlo_text,
                  re.DOTALL)
    return _SHAPE_TOKEN.findall(m.group(1)) if m else []


def parse_alias_map(hlo_text: str) -> tuple[set[int], int]:
    """Parse the compiled module header: returns (aliased parameter
    numbers from ``input_output_alias``, total entry parameter count
    from ``entry_computation_layout``)."""
    aliased: set[int] = set()
    i = hlo_text.find("input_output_alias={")
    if i >= 0:
        # entries look like "{out_idx}: (param, {param_idx}, kind)" —
        # scan forward to the balanced close of the outer map
        depth, k = 0, i + len("input_output_alias=")
        start = k
        while k < len(hlo_text):
            if hlo_text[k] == "{":
                depth += 1
            elif hlo_text[k] == "}":
                depth -= 1
                if depth == 0:
                    break
            k += 1
        body = hlo_text[start:k + 1]
        aliased = {int(m.group(1)) for m in _ALIAS_RE.finditer(body)}
    return aliased, len(_entry_param_tokens(hlo_text))


def check_donation(hlo_text: str, donated_names: list[str],
                   donated_avals: list[Any],
                   n_inputs: int) -> tuple[list[Finding], dict]:
    """Every donated input leaf must appear as an alias source in the
    executable's ``input_output_alias`` map.

    Donated leaves are the *first* ``len(donated_names)`` flattened
    parameters (``donate_argnums`` always covers the leading table/stats
    args here); ``n_inputs`` is the flattened input count, used to
    detect parameter dropping (``keep_unused=False`` elides unused
    params, which would shift numbering — that itself is a finding: a
    donated buffer the graph never reads means the state isn't
    threading through the step at all)."""
    findings: list[Finding] = []
    aliased, n_params = parse_alias_map(hlo_text)
    if n_params and n_params != n_inputs:
        findings.append(Finding(
            contract="donation",
            reason=(f"executable has {n_params} parameters for "
                    f"{n_inputs} traced inputs — unused (dropped) "
                    "arguments; donated state must be live in the "
                    "graph for in-place updates to mean anything"),
        ))
        return findings, {"aliased_params": sorted(aliased),
                          "n_params": n_params}
    for idx, (name, aval) in enumerate(zip(donated_names, donated_avals)):
        if idx not in aliased:
            nbytes = int(aval.size) * aval.dtype.itemsize
            findings.append(Finding(
                contract="donation", where=name,
                reason=(f"donated buffer {name} ({aval.dtype}"
                        f"{tuple(aval.shape)}, {nbytes} B) is NOT in "
                        "the executable's input_output_alias map — "
                        "every batch would allocate and copy it "
                        "instead of updating HBM in place"),
            ))
    return findings, {"aliased_params": sorted(aliased),
                      "n_params": n_params or n_inputs}


# -- contract 6: in-place / copy census -------------------------------------

#: numpy dtype name -> HLO shape-token prefix (the subset the serving
#: plane can produce; anything else simply won't match a table leaf).
_HLO_DTYPE = {
    "uint8": "u8", "uint16": "u16", "uint32": "u32", "uint64": "u64",
    "int8": "s8", "int16": "s16", "int32": "s32", "int64": "s64",
    "float16": "f16", "bfloat16": "bf16", "float32": "f32",
    "bool": "pred",
}


#: ``platform_dependent`` branches whose dynamic-offset slices of the
#: donated table are the in-place form: the TPU's compiler updates the
#: aliased table through a ``dynamic-update-slice`` and walks every
#: index of a scatter (tests/test_chip_compile.py holds the compiled
#: program to that).  A branch lowered for anything else as well — the
#: default's, or one shared with the CPU — answers to XLA:CPU's rules.
_SLICES_IN_PLACE = frozenset({("tpu",)})


def _is_literal(var: Any) -> bool:
    # test the POSITIVE property (Literal carries .val) so a jax
    # upgrade reshaping Var internals fails closed, not open
    return hasattr(var, "val")


def check_inplace(closed_jaxpr: Any, hlo_text: str | None,
                  table_avals: list[Any],
                  table_names: list[str],
                  n_shards: int = 1) -> tuple[list[Finding], dict]:
    """The donated table must stay on XLA's in-place path end to end.

    Two measured cliffs (PR 8) defeat it, each ~2 orders of magnitude
    at production capacity, and both are *graph facts* this contract
    pins statically instead of leaving to the bench:

    * a ``lax.cond`` the table rides THROUGH (a branch returns it)
      copies it through the ``conditional`` every batch, even when the
      branch never fires.  A cond that only READS the table — the
      probe's, whose branches gather from it and return ``[R, P]`` —
      is not that cliff: its operand is handed over by reference
      (XLA:CPU, 2^22 rows, donated: 1.94 ms a step with it, 1.99
      without; PR 38), and the executable-level census below would
      show a copy if one appeared;
    * a dynamic-offset ``dynamic_slice``/``dynamic_update_slice``
      touching the table defeats in-place buffer reuse for the whole
      donated chain (a CONSTANT-offset window is fine, and so are the
      single-index scatters XLA itself fuses into DUS — the checked
      property is table-shaped jaxpr-level DUS with computed starts,
      which the fast gather + victim-only-scatter form never emits).

    Both are XLA:CPU's rules, and ``jax.lax.platform_dependent`` is how
    a graph gives another backend its own form (the aging sweep's
    window, ``ops/fused.py::evict_idle_epoch``: a slice on the TPU,
    where a scatter is the slow form).  The ``cond`` it stages is
    resolved at lowering (:func:`platform_branches`) — no compiled
    program holds a conditional for it — so it is not the first cliff,
    and each of its branches is walked under its own platforms' rules:
    a branch lowered for the TPU alone may slice and update the donated
    table at a computed start; the ``default`` branch, and everything
    outside such a cond, is held to the rules above in full.  A
    ``lax.cond`` on a traced predicate stays a finding wherever it is.

    The jaxpr half catches both at their source equation (matching
    the global table shapes AND, given ``n_shards``, the per-shard
    shapes staged inside ``shard_map`` bodies); the HLO half is the
    executable-level census — zero ``copy``/``convert`` ops producing
    a table-shaped buffer, and no ``conditional`` whose result carries
    one (shapes are read per-executable, so sharded variants census
    their local shard shapes)."""
    findings: list[Finding] = []
    sigs: dict[tuple, str] = {}
    for a, n in zip(table_avals, table_names):
        shp = tuple(int(d) for d in a.shape)
        sigs[(shp, str(a.dtype))] = n
        # shard_map bodies stage SHARD-LOCAL avals (the layout shards
        # table.* along the leading ip axis), so the per-shard shape
        # must be a table signature too — otherwise the production
        # scan-over-shard_map variants are blind to both cliffs at
        # the jaxpr level
        if n_shards > 1 and shp and shp[0] % n_shards == 0:
            local = (shp[0] // n_shards,) + shp[1:]
            sigs.setdefault((local, str(a.dtype)), n)

    def sig_of(aval: Any) -> str | None:
        if aval is None or not hasattr(aval, "dtype"):
            return None
        return sigs.get((tuple(int(d) for d in getattr(aval, "shape",
                                                       ()) or ()),
                         str(aval.dtype)))

    for where, eqn, platforms in iter_platform_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name == "cond" and platform_branches(eqn) is None:
            carried = sorted({
                s for v in eqn.outvars
                if (s := sig_of(getattr(v, "aval", None))) is not None})
            if carried:
                findings.append(Finding(
                    contract="inplace", where=where, eqn=_eqn_txt(eqn),
                    reason=(f"lax.cond carries the donated table "
                            f"({', '.join(carried)}) — XLA:CPU copies "
                            "what a conditional returns every "
                            "batch even when the branch never fires "
                            "(the PR 8 in-place cliff); hoist the "
                            "table out of the cond or rewrite as a "
                            "lax.select/where on the rows"),
                ))
        elif (name in ("dynamic_slice", "dynamic_update_slice")
              and platforms not in _SLICES_IN_PLACE):
            operand = sig_of(getattr(eqn.invars[0], "aval", None))
            idx_start = 2 if name == "dynamic_update_slice" else 1
            dynamic = any(not _is_literal(v)
                          for v in eqn.invars[idx_start:])
            if operand is not None and dynamic:
                findings.append(Finding(
                    contract="inplace", where=where, eqn=_eqn_txt(eqn),
                    reason=(f"dynamic-offset {name} on the donated "
                            f"table ({operand}) — a computed start "
                            "index defeats XLA:CPU in-place reuse for "
                            "the whole donated chain (the PR 8 DUS "
                            "cliff); use gather reads + victim-only "
                            "scatter writes (the eviction sweep's "
                            "form off the TPU), or keep the slice to "
                            "the tpu= branch of a "
                            "lax.platform_dependent"),
                ))

    census = {"checked": hlo_text is not None,
              "copies": 0, "converts": 0, "conditionals": 0}
    if hlo_text is not None:
        # executable-local table types come off the entry layout — the
        # leading parameters are the donated leaves, so sharded
        # variants census their per-device shard shapes automatically;
        # the no-header fallback covers both signature sets (a global
        # token would never match a shard-local executable's text)
        tokens = _entry_param_tokens(hlo_text)[:len(table_avals)] or [
            f"{_HLO_DTYPE.get(dt, dt)}[{','.join(map(str, shp))}]"
            for (shp, dt) in sigs]
        toks = sorted({t.split("{")[0] for t in tokens})
        pat = "|".join(re.escape(t) for t in toks)
        census["table_types"] = toks
        for op, key in (("copy", "copies"), ("convert", "converts")):
            n = len(re.findall(
                rf"= ({pat})\{{[^}}]*\}} {op}\(", hlo_text))
            census[key] = n
            if n:
                findings.append(Finding(
                    contract="inplace",
                    reason=(f"{n} {op} op(s) producing a table-shaped "
                            f"buffer ({', '.join(toks)}) in the "
                            "compiled executable — the donated table "
                            "must flow copy-free through every step "
                            "variant (each one is a full-table "
                            "materialization per batch)"),
                ))
        # the result type sits before the op name on the instruction's
        # line (a tuple of them where the conditional returns several)
        pat_re = re.compile(pat)
        n_cond = 0
        for mc in re.finditer(r"conditional\(", hlo_text):
            line_start = hlo_text.rfind("\n", 0, mc.start()) + 1
            if pat_re.search(hlo_text, line_start, mc.start()):
                n_cond += 1
        census["conditionals"] = n_cond
        if n_cond:
            findings.append(Finding(
                contract="inplace",
                reason=(f"{n_cond} conditional op(s) return a "
                        f"table-shaped buffer ({', '.join(toks)}) in "
                        "the compiled executable — XLA:CPU copies "
                        "what a conditional returns every batch "
                        "(the PR 8 cond cliff)"),
            ))
    return findings, census


# -- contract 4: retrace sentinel -------------------------------------------

def staging_cache_check(jitted: Any, make_args: Callable[[], tuple],
                        arg_names: Callable[[int], str] = lambda i: f"arg[{i}]",
                        ) -> tuple[list[Finding], Any]:
    """Stage ``jitted`` twice with independently constructed inputs and
    require the second trace to hit the tracing cache.

    A miss means two host-side constructions of "the same" batch differ
    in aval (dtype / shape / weak_type) or static metadata — exactly
    the drift that makes a serving loop silently recompile per
    dispatch.  Returns ``(findings, traced)`` with the first trace for
    further graph checks.  The diagnostic names the first differing
    input."""
    t1 = jitted.trace(*make_args())
    t2 = jitted.trace(*make_args())
    if t2.jaxpr is t1.jaxpr:  # the tracing cache returns one object
        return [], t1
    diffs = []
    a1, a2 = list(t1.jaxpr.in_avals), list(t2.jaxpr.in_avals)
    for i, (x, y) in enumerate(zip(a1, a2)):
        if (x.shape, x.dtype, getattr(x, "weak_type", False)) != (
                y.shape, y.dtype, getattr(y, "weak_type", False)):
            diffs.append(f"{arg_names(i)}: {x.str_short()} vs "
                         f"{y.str_short()}")
    if len(a1) != len(a2):
        diffs.append(f"input leaf count {len(a1)} vs {len(a2)}")
    reason = ("staging twice under one BatchConfig re-traced (jit cache "
              "miss) — the serving loop would recompile every batch. ")
    reason += ("Differing inputs: " + "; ".join(diffs[:4])) if diffs else (
        "Avals identical: static-argument or donation metadata drift.")
    return [Finding(contract="retrace", reason=reason)], t1


def check_carry_avals(closed_jaxpr: Any, n_carry: int,
                      names: list[str]) -> list[Finding]:
    """The step's carried state (table, stats — outputs fed back as the
    next batch's inputs) must come out with avals identical to how it
    went in; any weak_type/dtype wobble retraces on the *second* batch
    and every batch after."""
    out = []
    ins = list(closed_jaxpr.in_avals)[:n_carry]
    outs = list(closed_jaxpr.out_avals)[:n_carry]
    for name, i, o in zip(names, ins, outs):
        if (i.shape, i.dtype, getattr(i, "weak_type", False)) != (
                o.shape, o.dtype, getattr(o, "weak_type", False)):
            out.append(Finding(
                contract="retrace", where=name,
                reason=(f"carried state {name} changes aval through the "
                        f"step ({i.str_short()} in, {o.str_short()} "
                        "out): feeding outputs back would retrace "
                        "every serving iteration"),
            ))
    return out
