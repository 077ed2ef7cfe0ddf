#!/usr/bin/env python3
"""Lint gate for the Python plane (the C plane is gated by
``-Wall -Wextra -Werror`` in both Makefiles already).

Stages, in order; the gate fails if any stage fails:

1. **syntax** — ``compileall`` over every tracked Python tree (always
   available; a SyntaxError in a lazily-imported module must not wait
   for the first operator to hit that code path).
2. **unused imports** — an AST pass with the same contract as
   pyflakes F401 (``# noqa`` lines and ``__init__.py`` re-exports are
   exempt).  Runs everywhere, even without ruff.
3. **local imports** — an AST pass over function bodies that bans the
   duplicated-local-import pattern: a function-local ``import jax`` /
   ``import jax.numpy`` in a module that ALREADY imports jax at module
   level (lazy-importing jax in a jax-free module stays legal — that
   is the CLI's multi-second-boot defense), and any local import that
   shadows a name a module-level import bound (the drift PR 3 had to
   clean out of the engine's sink paths by hand).  ``# noqa`` exempts
   a line.
4. **np default int** — an AST pass over the hot-path packages
   (core/ops/engine/ingest/cluster) that bans dtype-less
   ``np.array``/``np.zeros``/``np.ones``/``np.empty``/``np.arange``/
   ``np.full``: the default integer dtype is the platform C long,
   whose width varies by platform/ABI — an overflow hazard the
   ``fsx ranges`` prover cannot see from the staged graph.  ``# noqa``
   exempts a line.
5. **traced-region purity** — an AST pass over
   ``flowsentryx_tpu/ops/`` (the traced-region package: the step that
   every engine serves is written there and runs inside ``jit``) that
   bans host round-trips —
   ``device_get`` and the callback primitives (``pure_callback``,
   ``io_callback``, ``debug_callback``, ``jax.debug.print``) — at
   review speed.  ``fsx audit`` proves the same property statically on
   the staged graph; this stage catches it before anything compiles.
   ``# noqa`` exempts a line.
6. **sync contracts** — the thread-contract checker
   (``flowsentryx_tpu/sync/contracts.py``) in ``--quick`` mode: every
   registered shared field's thread discipline, the SPSC cursor
   single-writer rule and the ctl-block writer sides re-proved over
   the real source by AST walk.  ``fsx sync`` is the full surface
   (it adds the bounded-interleaving model checker); this stage is
   its review-speed gate, jax-free like the rest of the module.
7. **liveness waits** — an AST pass over the protocol scope
   (``flowsentryx_tpu/live/registry.py``'s ``SCAN_MODULES``) that
   bans UNTIMED ``*.wait()`` calls (a lost notify parks the thread
   forever; every wait re-polls on a named tuning quantum) and
   ``while True:`` loops with neither a bounded sleep nor a PROGRESS
   registry entry declaring their wake source and fairness
   assumption.  ``fsx live`` proves the registered loops' liveness by
   state-graph search; this stage is the review-speed gate that no
   blocking loop escapes the registry.  ``# noqa`` exempts a line.
8. **cluster jax-free** — an AST pass over
   ``flowsentryx_tpu/cluster/`` that bans MODULE-LEVEL imports of jax
   or the known jax-importing modules (``ops``/
   ``engine.writeback``/``engine.checkpoint``/``engine.engine``): the
   cluster plane is the supervisor's and every rank's process-spawn
   import path, and one module-level jax import there turns every
   fleet boot, adopt census, and chaos stub into a multi-second jax
   pay — the exact regression the supervisor inlined
   ``checkpoint.prev_path`` to avoid.  Function-LOCAL imports stay
   legal (the lazy-import defense; ``GossipPlane.tick``'s writeback
   import is the documented exception).  ``# noqa`` exempts a line.
9. **durable writes** — an AST pass over the durable-protocol scope
   (``flowsentryx_tpu/cluster/`` + ``engine/checkpoint.py``) that bans
   bare durable writes: ``open(..., "w"/"x"/"a")``,
   ``.write_text``/``.write_bytes``, and path-targeted ``np.savez*``.
   Protocol state must publish through ``core/durable.atomic_write``
   (write tmp → fsync → rotate → rename → dir fsync — the discipline
   the ``fsx crash`` checker proves crash-consistent; a bare write
   tears at power loss).  In-memory ``savez`` into a file-like handle
   stays legal (that is how checkpoint.py FEEDS atomic_write), and
   ``# noqa`` exempts a line (shm ring creates, report files).
10. **ruff** — ``ruff check`` with the repo config (pyproject.toml)
   when ruff is installed; SKIPPED (loudly, not silently) when not.
   The container this repo grows in has no ruff and nothing may be
   pip-installed, so the gate degrades to stages 1-9 there.
11. **mypy** — same availability contract as ruff.

Usage::

    python scripts/lint.py          # gate: exit 1 on any finding
    python scripts/lint.py --json   # machine-readable report
"""

from __future__ import annotations

import argparse
import ast
import compileall
import json
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
PY_TREES = ("flowsentryx_tpu", "tests", "scripts")
RUFF_MYPY_SCOPE = "flowsentryx_tpu"


def stage_syntax() -> list[str]:
    fails = []
    for tree in PY_TREES:
        ok = compileall.compile_dir(str(REPO / tree), quiet=2,
                                    force=True, workers=1)
        if not ok:
            fails.append(f"{tree}: compileall found syntax errors "
                         "(re-run verbosely for details)")
    return fails


def _unused_imports(path: Path) -> list[str]:
    """F401-shaped unused-import findings for one module."""
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError:
        return []  # stage_syntax owns reporting these
    lines = src.splitlines()
    imported: dict[str, int] = {}  # bound name -> line number
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = a.asname or a.name.split(".")[0]
                imported[name] = node.lineno
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                imported[a.asname or a.name] = node.lineno
    if not imported:
        return []
    used: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            # the root of a dotted use is a Name and already collected
            pass
    # __all__ re-exports count as uses
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__" \
                        and isinstance(node.value, (ast.List, ast.Tuple)):
                    for elt in node.value.elts:
                        if isinstance(elt, ast.Constant) \
                                and isinstance(elt.value, str):
                            used.add(elt.value)
    out = []
    for name, lineno in sorted(imported.items(), key=lambda kv: kv[1]):
        if name in used:
            continue
        line = lines[lineno - 1] if lineno <= len(lines) else ""
        if "noqa" in line:
            continue
        out.append(f"{path.relative_to(REPO)}:{lineno}: "
                   f"unused import {name!r}")
    return out


def stage_unused_imports() -> list[str]:
    fails = []
    for tree in PY_TREES:
        for path in sorted((REPO / tree).rglob("*.py")):
            if path.name == "__init__.py":
                continue  # re-export surface
            fails.extend(_unused_imports(path))
    return fails


def _import_bindings(node: ast.Import | ast.ImportFrom):
    """``(bound name, root module)`` pairs one import statement binds."""
    if isinstance(node, ast.Import):
        for a in node.names:
            yield a.asname or a.name.split(".")[0], a.name.split(".")[0]
    else:
        if node.module is None or node.level:  # relative: no root claim
            root = ""
        else:
            root = node.module.split(".")[0]
        for a in node.names:
            if a.name != "*":
                yield a.asname or a.name, root


def _local_import_findings(path: Path) -> list[str]:
    """The duplicated-local-import findings for one module (stage 3
    docstring: jax re-imports under a module-level jax import, and
    local imports shadowing module-level import bindings)."""
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError:
        return []  # stage_syntax owns reporting these
    lines = src.splitlines()
    module_binds: dict[str, int] = {}
    module_has_jax = False
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for name, root in _import_bindings(node):
                module_binds[name] = node.lineno
                module_has_jax |= root == "jax"
    out = []
    seen: set[int] = set()  # nested defs re-walk their imports: dedupe
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if (not isinstance(node, (ast.Import, ast.ImportFrom))
                    or id(node) in seen):
                continue
            seen.add(id(node))
            line = (lines[node.lineno - 1]
                    if node.lineno <= len(lines) else "")
            if "noqa" in line:
                continue
            rel = path.relative_to(REPO)
            for name, root in _import_bindings(node):
                if root == "jax" and module_has_jax:
                    out.append(
                        f"{rel}:{node.lineno}: function-local jax "
                        f"import ({name!r}) duplicates this module's "
                        "module-level jax import — hoist it")
                elif name in module_binds:
                    out.append(
                        f"{rel}:{node.lineno}: local import shadows "
                        f"module-level import {name!r} (line "
                        f"{module_binds[name]})")
    return out


def stage_local_imports() -> list[str]:
    fails = []
    for tree in PY_TREES:
        for path in sorted((REPO / tree).rglob("*.py")):
            fails.extend(_local_import_findings(path))
    return fails


#: Names that are host round-trips when they appear in traced-region
#: code (each is an unbounded mid-graph host sync; the serving step's
#: only host contact is the post-step wire fetch).
TRACED_REGION_BANNED = frozenset({
    "device_get", "pure_callback", "io_callback", "debug_callback",
    "host_callback", "block_until_ready",
})

#: The traced-region package: the modules here build the code that
#: runs INSIDE jit (ops/fused.py's step and megastep, and the table,
#: aggregation and limiter stages they call).
TRACED_REGION_TREE = "flowsentryx_tpu/ops"


def _traced_purity_findings(path: Path) -> list[str]:
    """Host-round-trip findings for one traced-region module."""
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError:
        return []  # stage_syntax owns reporting these
    lines = src.splitlines()
    out = []
    for node in ast.walk(tree):
        name = None
        if isinstance(node, ast.Attribute):
            name = node.attr
            # jax.debug.print / jax.debug.callback: the banned part is
            # the .debug chain, whatever the leaf method
            v = node.value
            if isinstance(v, ast.Attribute) and v.attr == "debug":
                name = f"debug.{node.attr}"
            elif isinstance(v, ast.Name) and v.id == "debug":
                name = f"debug.{node.attr}"
        elif isinstance(node, ast.Name):
            name = node.id
        if name is None:
            continue
        banned = (name in TRACED_REGION_BANNED
                  or name.startswith("debug."))
        if not banned:
            continue
        line = (lines[node.lineno - 1]
                if node.lineno <= len(lines) else "")
        if "noqa" in line:
            continue
        try:
            rel = path.relative_to(REPO)
        except ValueError:
            rel = path
        out.append(
            f"{rel}:{node.lineno}: host round-trip {name!r} in "
            "traced-region code — the step's graph must stay "
            "free of device_get/callbacks (fsx audit proves it on the "
            "staged jaxpr; fix it here first)")
    return out


def stage_traced_region_purity() -> list[str]:
    fails = []
    for path in sorted((REPO / TRACED_REGION_TREE).rglob("*.py")):
        fails.extend(_traced_purity_findings(path))
    return fails


#: Hot-path packages where a dtype-less numpy constructor is an
#: overflow hazard: the default integer dtype is the platform C long
#: (32-bit on Windows and 32-bit ABIs), so index/counter arrays built
#: without an explicit dtype silently change width across platforms —
#: a wrap class the ``fsx ranges`` prover cannot see (it analyzes the
#: staged graph, where the dtype is already whatever numpy picked).
NP_DEFAULT_INT_TREES = (
    "flowsentryx_tpu/core", "flowsentryx_tpu/ops",
    "flowsentryx_tpu/engine", "flowsentryx_tpu/ingest",
    "flowsentryx_tpu/cluster",
)

#: Banned-without-dtype numpy constructors -> positional index at
#: which a dtype argument may appear instead of the ``dtype=`` kwarg
#: (matching numpy's signatures: array/zeros/ones/empty take it
#: second, full third, arange fourth).
NP_DEFAULT_INT_CTORS = {
    "array": 1, "zeros": 1, "ones": 1, "empty": 1,
    "full": 2, "arange": 3,
}


def _np_default_int_findings(path: Path) -> list[str]:
    """Dtype-less ``np.<ctor>`` findings for one hot-path module."""
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError:
        return []  # stage_syntax owns reporting these
    lines = src.splitlines()
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute)
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "np"
                and fn.attr in NP_DEFAULT_INT_CTORS):
            continue
        if any(kw.arg == "dtype" for kw in node.keywords):
            continue
        if len(node.args) > NP_DEFAULT_INT_CTORS[fn.attr]:
            continue  # dtype passed positionally
        line = (lines[node.lineno - 1]
                if node.lineno <= len(lines) else "")
        if "noqa" in line:
            continue
        try:
            rel = path.relative_to(REPO)
        except ValueError:
            rel = path
        out.append(
            f"{rel}:{node.lineno}: dtype-less np.{fn.attr} in a "
            "hot-path package — the default int is the platform C "
            "long (width varies by platform/ABI), an overflow hazard "
            "the fsx ranges prover cannot see; pass an explicit dtype")
    return out


def stage_np_default_int() -> list[str]:
    fails = []
    for tree in NP_DEFAULT_INT_TREES:
        for path in sorted((REPO / tree).rglob("*.py")):
            fails.extend(_np_default_int_findings(path))
    return fails


#: The jax-free package: every module here sits on the fleet's
#: process-spawn import path (supervisor, adopt census, chaos stubs),
#: where one module-level jax import costs seconds per spawn.
CLUSTER_JAX_FREE_TREE = "flowsentryx_tpu/cluster"

#: Module-level import prefixes banned under the cluster tree: jax
#: itself plus the repo modules documented to import jax at module
#: level.  A prefix bans the module and everything under it.
CLUSTER_JAX_IMPORTERS = (
    "jax",
    "flowsentryx_tpu.ops",
    "flowsentryx_tpu.engine.writeback",
    "flowsentryx_tpu.engine.checkpoint",
    "flowsentryx_tpu.engine.engine",
)


def _cluster_jax_findings(path: Path) -> list[str]:
    """Module-level jax(-importing) import findings for one cluster
    module (stage 7 docstring)."""
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError:
        return []  # stage_syntax owns reporting these
    lines = src.splitlines()
    out = []
    for node in tree.body:  # MODULE level only: locals stay legal
        if not isinstance(node, (ast.Import, ast.ImportFrom)):
            continue
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif node.module is None or node.level:
            continue  # relative import: stays inside cluster/
        else:
            mods = [node.module]
        hits = [m for m in mods
                if any(m == p or m.startswith(p + ".")
                       for p in CLUSTER_JAX_IMPORTERS)]
        if not hits:
            continue
        line = (lines[node.lineno - 1]
                if node.lineno <= len(lines) else "")
        if "noqa" in line:
            continue
        try:
            rel = path.relative_to(REPO)
        except ValueError:
            rel = path
        for m in hits:
            out.append(
                f"{rel}:{node.lineno}: module-level import of {m!r} "
                "puts jax on the cluster plane's spawn path — every "
                "fleet boot/adopt/stub pays the jax import; move it "
                "function-local (the GossipPlane.tick discipline)")
    return out


def stage_cluster_jax_free() -> list[str]:
    fails = []
    for path in sorted((REPO / CLUSTER_JAX_FREE_TREE).rglob("*.py")):
        fails.extend(_cluster_jax_findings(path))
    return fails


#: The durable-protocol scope: modules whose file writes ARE protocol
#: state (layout.json, handoff.json, spools, checkpoints) — the files
#: the fsx crash checker reconstructs after simulated power loss.
#: Everything published here must go through durable.atomic_write.
DURABLE_WRITE_SCOPE = (
    "flowsentryx_tpu/cluster",
    "flowsentryx_tpu/engine/checkpoint.py",
    "flowsentryx_tpu/engine/compile_cache.py",
)


def _open_write_mode(node: ast.Call) -> str | None:
    """The write mode of an ``open()`` call, None when it reads."""
    mode = None
    if len(node.args) > 1:
        mode = node.args[1]
    for kw in node.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if not (isinstance(mode, ast.Constant)
            and isinstance(mode.value, str)):
        return None  # absent (= "r") or dynamic: not this stage's call
    return mode.value if any(c in mode.value for c in "wxa") else None


def _durable_write_findings(path: Path) -> list[str]:
    """Bare-durable-write findings for one protocol module (stage 8
    docstring)."""
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError:
        return []  # stage_syntax owns reporting these
    lines = src.splitlines()
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        what = None
        if isinstance(fn, ast.Name) and fn.id == "open":
            m = _open_write_mode(node)
            if m is not None:
                what = f"open(..., {m!r})"
        elif isinstance(fn, ast.Attribute) \
                and fn.attr in ("write_text", "write_bytes"):
            what = f".{fn.attr}(...)"
        elif (isinstance(fn, ast.Attribute)
              and isinstance(fn.value, ast.Name)
              and fn.value.id == "np"
              and fn.attr in ("savez", "savez_compressed")):
            # savez into a bare-Name handle is the in-memory BytesIO
            # idiom that FEEDS atomic_write; savez at anything else
            # (a literal/Path expression) writes the disk directly
            if not (node.args and isinstance(node.args[0], ast.Name)):
                what = f"np.{fn.attr}(<path>, ...)"
        if what is None:
            continue
        line = (lines[node.lineno - 1]
                if node.lineno <= len(lines) else "")
        if "noqa" in line:
            continue
        try:
            rel = path.relative_to(REPO)
        except ValueError:
            rel = path
        out.append(
            f"{rel}:{node.lineno}: bare durable write {what} in the "
            "durable-protocol scope — publish through "
            "core/durable.atomic_write (fsync file + parent dir, "
            "atomic rename; a bare write tears at power loss — the "
            "fsx crash checker's fsync_skipped plant); # noqa for "
            "non-protocol files (shm creates, reports)")
    return out


def stage_durable_writes() -> list[str]:
    fails = []
    for scope in DURABLE_WRITE_SCOPE:
        p = REPO / scope
        paths = [p] if p.suffix == ".py" else sorted(p.rglob("*.py"))
        for path in paths:
            if path.is_file():
                fails.extend(_durable_write_findings(path))
    return fails


def _liveness_wait_findings(path: Path, rel: str,
                            registered: set[tuple[str, str]]
                            ) -> list[str]:
    """Liveness-wait findings for one protocol module (stage docstring
    in main): an UNTIMED ``*.wait()`` (no quantum — a lost notify
    parks it forever), and a ``while True:`` loop that neither sleeps
    a bounded quantum nor is registered in the PROGRESS registry
    (flowsentryx_tpu/live/registry.py) under its ``(path, qualname)``.
    ``# noqa`` exempts a line."""
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError:
        return []  # stage_syntax owns reporting these
    lines = src.splitlines()
    out = []

    def noqa(lineno: int) -> bool:
        return lineno <= len(lines) and "noqa" in lines[lineno - 1]

    def walk(node, stack):
        for ch in ast.iter_child_nodes(node):
            sub = stack
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                sub = stack + [ch.name]
            if (isinstance(ch, ast.Call)
                    and isinstance(ch.func, ast.Attribute)
                    and ch.func.attr == "wait"
                    and not ch.args and not ch.keywords
                    and not noqa(ch.lineno)):
                out.append(
                    f"{rel}:{ch.lineno}: untimed .wait() — a lost "
                    "notify parks this thread forever; pass a "
                    "quantum (sync/tuning constant) so the wait "
                    "re-polls its predicate (# noqa if wedging is "
                    "the point, as in chaos fault threads)")
            if (isinstance(ch, ast.While)
                    and isinstance(ch.test, ast.Constant)
                    and ch.test.value is True
                    and not noqa(ch.lineno)):
                qn = ".".join(stack) or "<module>"
                sleeps = any(
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr == "sleep"
                    for n in ast.walk(ch))
                if not sleeps and (rel, qn) not in registered:
                    out.append(
                        f"{rel}:{ch.lineno}: while True: in {qn} has "
                        "no bounded sleep and no PROGRESS registry "
                        "entry — declare its wake source, fairness "
                        "assumption and bound in "
                        "flowsentryx_tpu/live/registry.py (what "
                        "licenses a blocking loop in the protocol "
                        "scope), or # noqa")
            walk(ch, sub)

    walk(tree, [])
    return out


def stage_liveness_waits() -> list[str]:
    """Every blocking loop in the protocol scope has a declared wake
    edge: untimed waits and unregistered ``while True:`` loops are
    findings (the ``fsx live`` leg's lint half)."""
    try:
        from flowsentryx_tpu.live.registry import (
            SCAN_MODULES, registered_sites,
        )
    except ImportError:
        # run as a script: scripts/ is sys.path[0] (same contract as
        # stage_sync_contracts — the REAL repo root, not REPO)
        import sys as _sys

        _sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
        from flowsentryx_tpu.live.registry import (
            SCAN_MODULES, registered_sites,
        )

    registered = registered_sites()
    fails = []
    for rel in SCAN_MODULES:
        p = REPO / rel
        if p.is_file():
            fails.extend(_liveness_wait_findings(p, rel, registered))
    return fails


def stage_sync_contracts() -> list[str]:
    """The thread-contract half of ``fsx sync`` as a lint stage (quick
    mode: pure AST, no model checking, no jax)."""
    try:
        from flowsentryx_tpu.sync.contracts import run_contracts
    except ImportError:
        # run as a script: scripts/ is sys.path[0].  Insert the REAL
        # repo root (from __file__, NOT the REPO global — tests point
        # that at throwaway trees the import system must never see).
        import sys as _sys

        _sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
        from flowsentryx_tpu.sync.contracts import run_contracts

    rep = run_contracts(root=REPO, quick=True)
    return [str(f) for f in rep.findings]


def _run_tool(cmd: list[str]) -> list[str]:
    r = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
    if r.returncode == 0:
        return []
    out = (r.stdout + r.stderr).strip()
    return out.splitlines()[-40:] or [f"{cmd[0]} failed "
                                      f"(exit {r.returncode})"]


def stage_ruff() -> list[str] | None:
    if shutil.which("ruff") is None:
        return None
    return _run_tool(["ruff", "check", RUFF_MYPY_SCOPE])


def stage_mypy() -> list[str] | None:
    if shutil.which("mypy") is None:
        return None
    return _run_tool(["mypy", RUFF_MYPY_SCOPE])


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    stages: dict[str, list[str] | None] = {
        "syntax": stage_syntax(),
        "unused_imports": stage_unused_imports(),
        "local_imports": stage_local_imports(),
        "np_default_int": stage_np_default_int(),
        "traced_region_purity": stage_traced_region_purity(),
        "sync_contracts": stage_sync_contracts(),
        "liveness_waits": stage_liveness_waits(),
        "cluster_jax_free": stage_cluster_jax_free(),
        "durable_writes": stage_durable_writes(),
        "ruff": stage_ruff(),
        "mypy": stage_mypy(),
    }
    ok = not any(stages.values())
    if args.json:
        print(json.dumps({
            "ok": ok,
            "stages": {n: ("skipped (tool not installed)" if v is None
                           else {"ok": not v, "findings": v})
                       for n, v in stages.items()},
        }, indent=2))
    else:
        for name, findings in stages.items():
            if findings is None:
                print(f"lint: {name}: SKIPPED (tool not installed)")
            elif findings:
                print(f"lint: {name}: FAILED")
                for f in findings:
                    print(f"  {f}")
            else:
                print(f"lint: {name}: OK")
        print(f"lint: {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
