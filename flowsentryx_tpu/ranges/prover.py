"""Abstract interpreter over staged ``ClosedJaxpr``\\s: per-variable
integer intervals, exact pre-wrap result ranges, and the escape check.

The walk mirrors :func:`flowsentryx_tpu.audit.graph.iter_eqns` (same
``eqns[i]:prim/param/`` paths, same descent through nested pjit / scan
/ shard_map / cond bodies), but *evaluates* along the dataflow instead
of merely visiting: every equation's output interval is computed from
its operands', and for the arithmetic set (add / sub / mul / neg /
shift_left / convert / reduce_sum / cumsum / scatter-add / dot_general
/ psum / integer_pow / abs) the EXACT mathematical result interval is
compared against the output dtype's representable range first.  An
escape is a silent mod-2^N wrap in the serving graph — a
:class:`~flowsentryx_tpu.audit.graph.Finding` with the ``fsx check`` /
``fsx audit`` diagnostic idiom (contract, equation path, equation
text), unless the equation matches an audited
:data:`~flowsentryx_tpu.ranges.registry.WRAP_OK` entry.

Soundness posture: every handler over-approximates (the computed
interval always contains every value the op can produce given operand
intervals), unknown primitives degrade to dtype-top and are counted in
the ``unmodeled`` census rather than silently trusted, and ``scan``
carries run to a joined fixpoint (with dtype-top widening after two
non-converging passes) so a bound proved on the body holds for every
iteration count.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

from flowsentryx_tpu.audit.graph import Finding, _eqn_txt
from flowsentryx_tpu.ranges import interval as iv
from flowsentryx_tpu.ranges import registry as reg
from flowsentryx_tpu.ranges.interval import IVal


def eqn_frames(eqn: Any) -> list[tuple[str, str]]:
    """(file_name, function_name) user frames of one equation,
    innermost first — the WRAP_OK matching key.  Degrades to [] when a
    jax upgrade reshapes source_info (matching then fails CLOSED: an
    unmatched escape is a finding, never a silent pass)."""
    try:
        from jax._src import source_info_util as siu

        return [(f.file_name, f.function_name)
                for f in siu.user_frames(eqn.source_info.traceback)]
    except Exception:
        return []


@dataclasses.dataclass
class Analysis:
    """One jaxpr's range-analysis result."""

    findings: list[Finding]
    wrap_matches: dict[str, int]   # WRAP_OK entry name -> eqns matched
    unmodeled: dict[str, int]      # primitive -> count (dtype-top'd)
    n_eqns: int
    n_checked: int                 # eqns that went through the escape check
    collected: dict[str, tuple]    # collect-hook key -> (lo, hi)

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> dict:
        return {
            "ok": self.ok,
            "findings": [f.to_json() for f in self.findings],
            "wrap_ok_matches": dict(self.wrap_matches),
            "unmodeled": dict(self.unmodeled),
            "n_eqns": self.n_eqns,
            "n_checked": self.n_checked,
        }


_STRUCT_SAME = ("copy", "stop_gradient", "reduce_precision",
                "optimization_barrier")


def _is_drop(v: Any) -> bool:
    return type(v).__name__ == "DropVar"


def _float_of(x):
    try:
        return float(x)
    except OverflowError:
        return float("inf") if x > 0 else float("-inf")


class _Prover:
    def __init__(self, entries, collect):
        self.entries = entries
        self.collect = collect
        self.findings: list[Finding] = []
        self.wrap_matches: dict[str, int] = {}
        self.unmodeled: dict[str, int] = {}
        self.collected: dict[str, tuple] = {}
        self.n_eqns = 0
        self.n_checked = 0

    # -- environment ----------------------------------------------------

    def _fit(self, val: IVal, aval: Any) -> IVal:
        shape = tuple(getattr(aval, "shape", ()) or ())
        if val.lo.shape not in ((), shape):
            val = val.collapse()
        return iv.guard_cap(val)

    def _read(self, env: dict, x: Any) -> IVal:
        if hasattr(x, "val"):  # Literal
            return iv.const_of(x.val)
        v = env.get(x)
        if v is None:
            return iv.top_for(getattr(x.aval, "dtype", np.int64))
        return v

    def run_closed(self, closed: Any, invals: list[IVal],
                   path: str = "", axis_env: dict | None = None,
                   record: bool = True) -> list[IVal]:
        return self.run_jaxpr(closed.jaxpr, list(closed.consts), invals,
                              path, axis_env or {}, record)

    def run_jaxpr(self, jaxpr: Any, consts: list, invals: list[IVal],
                  path: str, axis_env: dict, record: bool) -> list[IVal]:
        env: dict = {}
        for v, c in zip(jaxpr.constvars, consts):
            env[v] = self._fit(iv.const_of(np.asarray(c)), v.aval)
        for v, val in zip(jaxpr.invars, invals):
            env[v] = self._fit(val, v.aval)
        for i, eqn in enumerate(jaxpr.eqns):
            where = f"{path}eqns[{i}]:{eqn.primitive.name}"
            if record:
                self.n_eqns += 1
            ins = [self._read(env, x) for x in eqn.invars]
            outs = self._eqn(where, eqn, ins, axis_env, record)
            if record and self.collect is not None:
                key = self.collect(where, eqn)
                if key is not None and outs:
                    b = outs[0].bounds()
                    old = self.collected.get(key)
                    self.collected[key] = (
                        b if old is None
                        else (min(old[0], b[0]), max(old[1], b[1])))
            for v, val in zip(eqn.outvars, outs):
                if not _is_drop(v):
                    env[v] = self._fit(val, v.aval)
        return [self._read(env, x) for x in jaxpr.outvars]

    # -- escape check ---------------------------------------------------

    def _checked(self, where: str, eqn: Any, exact: IVal,
                 record: bool, *, narrowing: bool = False) -> IVal:
        """Compare the exact result interval against the output
        dtype's fence; on escape, either consume a WRAP_OK match or
        emit the finding, and continue with dtype-top (the wrapped
        value really can be anything representable)."""
        dtype = eqn.outvars[0].aval.dtype
        if not iv.is_int_dtype(dtype):
            return exact
        if record:
            self.n_checked += 1
        dmin, dmax = iv.dtype_bounds(dtype)
        lo, hi = exact.bounds()
        if lo >= dmin and hi <= dmax:
            return exact
        ent = reg.match(self.entries, eqn.primitive.name,
                        eqn_frames(eqn))
        if ent is not None:
            if record:
                self.wrap_matches[ent.name] = \
                    self.wrap_matches.get(ent.name, 0) + 1
            return iv.top_for(dtype)
        if record:
            kind = ("narrowing convert" if narrowing
                    else f"{eqn.primitive.name} result")
            self.findings.append(Finding(
                contract="range", where=where, eqn=_eqn_txt(eqn),
                reason=(f"{kind} interval [{lo}, {hi}] escapes "
                        f"{np.dtype(dtype).name} [{dmin}, {dmax}] — a "
                        "silent fixed-width wrap in the serving graph; "
                        "guard the arithmetic, widen the dtype, or "
                        "register an audited WRAP_OK entry if the "
                        "wrap is by design")))
        return iv.top_for(dtype)

    def _unmodeled(self, where: str, eqn: Any, record: bool) -> list[IVal]:
        if record:
            name = eqn.primitive.name
            self.unmodeled[name] = self.unmodeled.get(name, 0) + 1
        return [iv.top_for(getattr(v.aval, "dtype", np.int64))
                for v in eqn.outvars]

    # -- the per-primitive transfer functions ---------------------------

    def _eqn(self, where: str, eqn: Any, ins: list[IVal],
             axis_env: dict, record: bool) -> list[IVal]:
        name = eqn.primitive.name
        p = eqn.params
        out_aval = eqn.outvars[0].aval if eqn.outvars else None
        dtype = getattr(out_aval, "dtype", None)
        fdt = dtype is not None and not iv.is_int_dtype(dtype)

        # ---- control / call structure ----
        if name == "jit":
            sub = p["jaxpr"]
            return self.run_closed(sub, ins, f"{where}/jaxpr/",
                                   axis_env, record)
        if name in ("closed_call", "core_call", "remat", "checkpoint",
                    "custom_jvp_call", "custom_vjp_call"):
            sub = p.get("jaxpr") or p.get("call_jaxpr")
            if sub is not None and hasattr(sub, "jaxpr"):
                return self.run_closed(sub, ins, f"{where}/jaxpr/",
                                       axis_env, record)
            return self._unmodeled(where, eqn, record)
        if name == "scan":
            return self._scan(where, eqn, ins, axis_env, record)
        if name == "while":
            return self._while(where, eqn, ins, axis_env, record)
        if name == "platform_index":
            # jax.lax.platform_dependent's branch index, resolved at
            # lowering: which branch depends on the target, so the
            # cond below joins all of them
            return [iv.scalar(0, len(p["platforms"]) - 1)]
        if name == "cond":
            outs = None
            for bi, br in enumerate(p["branches"]):
                o = self.run_closed(br, ins[1:],
                                    f"{where}/branches[{bi}]/",
                                    axis_env, record)
                outs = o if outs is None else [
                    iv.join(a, b) for a, b in zip(outs, o)]
            return outs
        if name == "shard_map":
            mesh = p["mesh"]
            inner = dict(axis_env)
            try:
                inner.update({k: int(v)
                              for k, v in dict(mesh.shape).items()})
            except Exception:
                pass
            body = p["jaxpr"]
            return self.run_jaxpr(body, [], ins, f"{where}/jaxpr/",
                                  inner, record)

        # ---- elementwise arithmetic (escape-checked) ----
        if name == "add":
            if fdt:
                return [iv.add(*ins) if all(map(iv.finite, ins))
                        else iv.float_top()]
            return [self._checked(where, eqn, iv.add(*ins), record)]
        if name == "sub":
            if fdt:
                return [iv.sub(*ins) if all(map(iv.finite, ins))
                        else iv.float_top()]
            return [self._checked(where, eqn, iv.sub(*ins), record)]
        if name == "mul":
            if fdt:
                return [iv.mul(*ins) if all(map(iv.finite, ins))
                        else iv.float_top()]
            return [self._checked(where, eqn, iv.mul(*ins), record)]
        if name == "neg":
            if fdt:
                return [iv.neg(ins[0])]
            return [self._checked(where, eqn, iv.neg(ins[0]), record)]
        if name == "abs":
            if fdt:
                return [iv.absolute(ins[0])]
            return [self._checked(where, eqn, iv.absolute(ins[0]),
                                  record)]
        if name == "integer_pow":
            return [self._checked(where, eqn,
                                  iv.int_pow(ins[0], int(p["y"])),
                                  record)]
        if name == "shift_left":
            return [self._checked(where, eqn, iv.shift_left(*ins),
                                  record)]
        if name == "shift_right_logical":
            return [iv.shift_right_logical(ins[0], ins[1], dtype)]
        if name == "shift_right_arithmetic":
            return [iv.shift_right_arith(ins[0], ins[1])]
        if name == "and":
            return [iv.bit_and(ins[0], ins[1], dtype)]
        if name in ("or", "xor"):
            return [iv.bit_or_xor(ins[0], ins[1], dtype, name == "or")]
        if name == "not":
            return [iv.scalar(0, 1) if np.dtype(dtype).kind == "b"
                    else iv.top_for(dtype)]
        if name == "div":
            return [iv.div(ins[0], ins[1], dtype)]
        if name == "rem":
            return [iv.rem(ins[0], ins[1], dtype)]
        if name == "max":
            return [iv.vmax(*ins)]
        if name == "min":
            return [iv.vmin(*ins)]
        if name == "clamp":
            return [iv.clamp(ins[0], ins[1], ins[2])]
        if name == "select_n":
            # a decided predicate picks its case exactly (the jnp
            # negative-index normalization — select(i < 0, i+n, i) —
            # must stay constant or every raw[-1] metadata read
            # degrades to the full record-row join)
            plo, phi = ins[0].bounds()
            if plo == phi and 0 <= plo < len(ins) - 1:
                return [ins[1 + int(plo)]]
            return [iv.join_all(ins[1:])]
        if name == "sign":
            return [iv.scalar(-1, 1) if not fdt
                    else iv.scalar(-1.0, 1.0)]
        if name == "nextafter":
            return [iv.join(ins[0], ins[1])]

        # ---- conversions ----
        if name == "convert_element_type":
            src = ins[0]
            if iv.is_int_dtype(dtype):
                lo, hi = src.bounds()
                if isinstance(lo, float) or isinstance(hi, float):
                    import math as _m

                    lo = (_m.floor(lo) if _m.isfinite(lo)
                          else -(1 << 90))
                    hi = _m.ceil(hi) if _m.isfinite(hi) else (1 << 90)
                    src = iv.scalar(int(lo), int(hi))
                if np.dtype(dtype).kind == "b":
                    return [iv.scalar(0, 1)]
                return [self._checked(where, eqn, src, record,
                                      narrowing=True)]
            lo, hi = src.bounds()
            return [iv.scalar(_float_of(lo), _float_of(hi))]
        if name == "bitcast_convert_type":
            return [iv.top_for(p["new_dtype"])]

        # ---- comparisons ----
        if name in ("eq", "ne", "lt", "le", "gt", "ge"):
            alo, ahi = ins[0].bounds()
            blo, bhi = ins[1].bounds()
            decided = None
            if name == "lt":
                decided = (True if ahi < blo
                           else False if alo >= bhi else None)
            elif name == "le":
                decided = (True if ahi <= blo
                           else False if alo > bhi else None)
            elif name == "gt":
                decided = (True if alo > bhi
                           else False if ahi <= blo else None)
            elif name == "ge":
                decided = (True if alo >= bhi
                           else False if ahi < blo else None)
            elif name == "eq":
                decided = (True if alo == ahi == blo == bhi
                           else False if ahi < blo or alo > bhi
                           else None)
            elif name == "ne":
                decided = (False if alo == ahi == blo == bhi
                           else True if ahi < blo or alo > bhi
                           else None)
            if decided is None:
                return [iv.scalar(0, 1)]
            return [iv.scalar(int(decided), int(decided))]
        if name == "is_finite":
            return [iv.scalar(0, 1)]

        # ---- float transcendentals ----
        if name in ("exp", "exp2", "log", "log1p", "expm1", "logistic",
                    "tanh", "erf", "sin", "cos", "sqrt", "floor",
                    "ceil", "round"):
            return [iv.float_unary(name, ins[0])]
        if name == "rsqrt":
            return [iv.float_top()]
        if name == "pow":
            return [iv.float_top()]

        # ---- structure ----
        if name in _STRUCT_SAME:
            return [ins[0]]
        if name == "broadcast_in_dim":
            shape = tuple(p["shape"])
            v = ins[0]
            if v.is_scalar():
                return [v]
            bdims = tuple(p["broadcast_dimensions"])
            mid = [1] * len(shape)
            for src_d, out_d in enumerate(bdims):
                mid[out_d] = v.lo.shape[src_d]
            lo = np.broadcast_to(np.reshape(v.lo, mid), shape)
            hi = np.broadcast_to(np.reshape(v.hi, mid), shape)
            return [iv.guard_cap(IVal(lo, hi))]
        if name == "reshape":
            v = ins[0]
            if v.is_scalar():
                return [v]
            shape = tuple(p["new_sizes"])
            return [IVal(np.reshape(v.lo, shape),
                         np.reshape(v.hi, shape))]
        if name == "squeeze":
            v = ins[0]
            if v.is_scalar():
                return [v]
            dims = tuple(p["dimensions"])
            return [IVal(np.squeeze(v.lo, axis=dims),
                         np.squeeze(v.hi, axis=dims))]
        if name == "transpose":
            v = ins[0]
            if v.is_scalar():
                return [v]
            perm = tuple(p["permutation"])
            return [IVal(np.transpose(v.lo, perm),
                         np.transpose(v.hi, perm))]
        if name == "rev":
            v = ins[0]
            if v.is_scalar():
                return [v]
            return [IVal(np.flip(v.lo, tuple(p["dimensions"])),
                         np.flip(v.hi, tuple(p["dimensions"])))]
        if name == "slice":
            v = ins[0]
            if v.is_scalar():
                return [v]
            sl = tuple(
                slice(int(s), int(l), int(st))
                for s, l, st in zip(p["start_indices"],
                                    p["limit_indices"],
                                    p["strides"] or
                                    [1] * len(p["start_indices"])))
            return [IVal(v.lo[sl], v.hi[sl])]
        if name == "concatenate":
            dim = int(p["dimension"])
            pieces_lo, pieces_hi, total = [], [], 0
            for x, val in zip(eqn.invars, ins):
                shape = tuple(x.aval.shape)
                total += int(np.prod(shape, dtype=np.int64))
                if val.is_scalar():
                    pieces_lo.append(np.broadcast_to(val.lo, shape))
                    pieces_hi.append(np.broadcast_to(val.hi, shape))
                else:
                    pieces_lo.append(val.lo)
                    pieces_hi.append(val.hi)
            if total > iv.FULL_CAP:
                return [iv.join_all(ins)]
            return [IVal(np.concatenate(pieces_lo, axis=dim),
                         np.concatenate(pieces_hi, axis=dim))]
        if name == "pad":
            return [iv.join(ins[0].collapse(), ins[1].collapse())]
        if name == "iota":
            dim = int(p["dimension"])
            n = int(p["shape"][dim])
            return [iv.scalar(0, max(n - 1, 0))]
        if name == "dynamic_slice":
            v = ins[0]
            starts = [x.bounds() for x in ins[1:]]
            sizes = tuple(int(s) for s in p["slice_sizes"])
            if (not v.is_scalar()
                    and all(lo == hi for lo, hi in starts)):
                # constant starts: exact slice (with lax's clamping)
                dims = v.lo.shape
                sl = tuple(
                    slice(c := min(max(int(lo), 0), d - sz), c + sz)
                    for (lo, _), d, sz in zip(starts, dims, sizes))
                return [IVal(v.lo[sl], v.hi[sl])]
            return [v.collapse()]
        if name in ("gather", "all_to_all", "ppermute", "all_gather"):
            return [ins[0].collapse()]
        if name == "dynamic_update_slice":
            u = ins[1].collapse()
            return [IVal(iv.emin(ins[0].lo, u.lo),
                         iv.emax(ins[0].hi, u.hi))]

        # ---- reductions / scans ----
        if name == "reduce_sum":
            axes = tuple(p["axes"])
            v, shape = ins[0], tuple(eqn.invars[0].aval.shape)
            n = int(np.prod([shape[a] for a in axes], dtype=np.int64))
            if v.is_scalar():
                exact = IVal(v.lo * n, v.hi * n)
            else:
                exact = IVal(v.lo.sum(axis=axes), v.hi.sum(axis=axes))
            if fdt:
                return [exact if iv.finite(v) else iv.float_top()]
            return [self._checked(where, eqn, exact, record)]
        if name in ("reduce_max", "reduce_min", "reduce_or",
                    "reduce_and"):
            return [ins[0].collapse()]
        if name == "reduce_prod":
            return self._unmodeled(where, eqn, record)
        if name in ("argmax", "argmin"):
            shape = tuple(eqn.invars[0].aval.shape)
            axes = tuple(p["axes"])
            n = int(np.prod([shape[a] for a in axes], dtype=np.int64))
            return [iv.scalar(0, max(n - 1, 0))]
        if name == "cumsum":
            axis = int(p["axis"])
            v, shape = ins[0], tuple(eqn.invars[0].aval.shape)
            n = shape[axis]
            if v.is_scalar():
                lo, hi = v.bounds()
                exact = iv.scalar(min(lo, lo * n), max(hi, hi * n))
            elif bool(p.get("reverse")):
                # reverse cumsum = suffix sums: cumsum of the flipped
                # arrays (the forward prefix bounds do NOT cover it)
                exact = IVal(
                    np.flip(np.cumsum(np.flip(v.lo, axis), axis=axis),
                            axis),
                    np.flip(np.cumsum(np.flip(v.hi, axis), axis=axis),
                            axis))
            else:
                exact = IVal(np.cumsum(v.lo, axis=axis),
                             np.cumsum(v.hi, axis=axis))
            if fdt:
                return [exact if iv.finite(v) else iv.float_top()]
            return [self._checked(where, eqn, exact, record)]
        if name in ("cummax", "cummin", "cumlogsumexp", "cumprod"):
            return [ins[0].collapse()]
        if name == "sort":
            return [v.collapse() for v in ins]

        # ---- scatter family ----
        if name == "scatter":
            u = ins[2].collapse()
            return [IVal(iv.emin(ins[0].lo, u.lo),
                         iv.emax(ins[0].hi, u.hi))]
        if name in ("scatter-max", "scatter_max",
                    "scatter-min", "scatter_min"):
            u = ins[2].collapse()
            return [IVal(iv.emin(ins[0].lo, u.lo),
                         iv.emax(ins[0].hi, u.hi))]
        if name in ("scatter-add", "scatter_add"):
            op, u = ins[0].collapse(), ins[2].collapse()
            n_upd = int(np.prod(tuple(eqn.invars[2].aval.shape),
                                dtype=np.int64))
            ulo, uhi = u.bounds()
            olo, ohi = op.bounds()
            exact = iv.scalar(olo + n_upd * min(ulo, 0),
                              ohi + n_upd * max(uhi, 0))
            if fdt:
                return [exact if iv.finite(op) and iv.finite(u)
                        else iv.float_top()]
            return [self._checked(where, eqn, exact, record)]

        # ---- matmul ----
        if name == "dot_general":
            (lc, rc), _ = p["dimension_numbers"]
            lshape = tuple(eqn.invars[0].aval.shape)
            k = int(np.prod([lshape[d] for d in lc], dtype=np.int64))
            lhs = ins[0].collapse()
            rhs = ins[1]
            llo, lhi = lhs.bounds()
            prods = iv._minmax4(llo * rhs.lo, llo * rhs.hi,
                                lhi * rhs.lo, lhi * rhs.hi)
            if rhs.is_scalar():
                plo, phi = prods.bounds()
                exact = iv.scalar(k * plo, k * phi)
            else:
                slo = prods.lo.sum(axis=tuple(rc))
                shi = prods.hi.sum(axis=tuple(rc))
                exact = iv.scalar(slo.min(), shi.max())
            if fdt:
                return [exact if iv.finite(lhs) and iv.finite(rhs)
                        else iv.float_top()]
            return [self._checked(where, eqn, exact, record)]

        # ---- collectives ----
        if name == "psum":
            mult = 1
            for ax in p.get("axes", ()):
                size = axis_env.get(ax)
                if size is None:
                    return self._unmodeled(where, eqn, record)
                mult *= int(size)
            outs = []
            for x, v in zip(eqn.invars, ins):
                dt = x.aval.dtype
                lo, hi = v.bounds()
                exact = iv.scalar(lo * mult, hi * mult)
                if iv.is_int_dtype(dt):
                    # one outvar family: check against the first
                    # outvar's dtype fence (psum preserves dtypes)
                    dmin, dmax = iv.dtype_bounds(dt)
                    elo, ehi = exact.bounds()
                    if elo < dmin or ehi > dmax:
                        ent = reg.match(self.entries, name,
                                        eqn_frames(eqn))
                        if ent is not None:
                            if record:
                                self.wrap_matches[ent.name] = \
                                    self.wrap_matches.get(ent.name,
                                                          0) + 1
                        elif record:
                            self.findings.append(Finding(
                                contract="range", where=where,
                                eqn=_eqn_txt(eqn),
                                reason=(f"psum over {mult} devices of "
                                        f"interval [{lo}, {hi}] "
                                        "escapes "
                                        f"{np.dtype(dt).name}")))
                        exact = iv.top_for(dt)
                    if record:
                        self.n_checked += 1
                outs.append(exact)
            return outs
        if name in ("pmax", "pmin"):
            return [v.collapse() for v in ins]
        if name == "axis_index":
            size = axis_env.get(p.get("axis_name"))
            if size is None:
                return self._unmodeled(where, eqn, record)
            return [iv.scalar(0, int(size) - 1)]

        return self._unmodeled(where, eqn, record)

    # -- scan / while ----------------------------------------------------

    def _scan(self, where: str, eqn: Any, ins: list[IVal],
              axis_env: dict, record: bool) -> list[IVal]:
        p = eqn.params
        body = p["jaxpr"]
        nc, nk = int(p["num_consts"]), int(p["num_carry"])
        length = int(p["length"])
        consts, init, xs = ins[:nc], ins[nc:nc + nk], ins[nc + nk:]
        xelems = []
        for v in xs:
            if v.is_scalar():
                xelems.append(v)
            else:
                xelems.append(IVal(v.lo.min(axis=0), v.hi.max(axis=0)))
        carry = [self._fit(v, body.jaxpr.invars[nc + i].aval)
                 for i, v in enumerate(init)]
        converged = False
        for _ in range(2):
            outs = self.run_closed(body, consts + carry + xelems,
                                   f"{where}/jaxpr/", axis_env,
                                   record=False)
            new_carry = [iv.join(c, o)
                         for c, o in zip(carry, outs[:nk])]
            if all(iv.equal(c, n) for c, n in zip(carry, new_carry)):
                converged = True
                break
            carry = new_carry
        if not converged:
            carry = [iv.top_for(v.aval.dtype)
                     for v in body.jaxpr.invars[nc:nc + nk]]
        outs = self.run_closed(body, consts + carry + xelems,
                               f"{where}/jaxpr/", axis_env, record)
        carry_out = [iv.join(c, o) for c, o in zip(carry, outs[:nk])]
        ys = []
        for y, outv in zip(outs[nk:], eqn.outvars[nk:]):
            shape = tuple(outv.aval.shape)
            if (not y.is_scalar() and shape
                    and y.lo.shape == shape[1:]
                    and length * y.lo.size <= iv.FULL_CAP):
                ys.append(IVal(
                    np.broadcast_to(y.lo, (length,) + y.lo.shape),
                    np.broadcast_to(y.hi, (length,) + y.hi.shape)))
            else:
                ys.append(y.collapse())
        return carry_out + ys

    def _while(self, where: str, eqn: Any, ins: list[IVal],
               axis_env: dict, record: bool) -> list[IVal]:
        p = eqn.params
        cond = p["cond_jaxpr"]
        body = p["body_jaxpr"]
        ncc = int(p["cond_nconsts"])
        ncb = int(p["body_nconsts"])
        carry_in = ins[ncc + ncb:]
        # no iteration bound: widen the carry to dtype-top, prove the
        # body AND the condition once under it (sound for any trip
        # count; the cond's arithmetic must be escape-checked too)
        carry = [iv.top_for(v.aval.dtype)
                 for v in body.jaxpr.invars[ncb:]]
        self.run_closed(cond, ins[:ncc] + carry,
                        f"{where}/cond_jaxpr/", axis_env, record)
        outs = self.run_closed(body, ins[ncc:ncc + ncb] + carry,
                               f"{where}/body_jaxpr/", axis_env, record)
        return [iv.join(c, o) for c, o in zip(carry_in, outs)]


def analyze(closed: Any, seeds: list[IVal], *,
            entries: tuple = reg.WRAP_OK,
            collect: Callable[[str, Any], str | None] | None = None,
            ) -> Analysis:
    """Run the range proof over one staged ``ClosedJaxpr``.

    ``seeds`` align with the flattened ``closed.jaxpr.invars`` (the
    declared input contracts — see :mod:`flowsentryx_tpu.ranges.seeds`);
    ``entries`` is the WRAP_OK registry in force; ``collect`` optionally
    records the joined bounds of matching equations' first outputs
    (the BPF containment bridge reads the MAC interval this way)."""
    pr = _Prover(entries, collect)
    pr.run_closed(closed, seeds)
    return Analysis(
        findings=pr.findings, wrap_matches=pr.wrap_matches,
        unmodeled=pr.unmodeled, n_eqns=pr.n_eqns,
        n_checked=pr.n_checked, collected=pr.collected)
