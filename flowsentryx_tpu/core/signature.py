"""The staged-shape signature: ONE definition of what keys a compiled
serving artifact.

Three subsystems cache or prove work per *staged shape* — the audit
boot cache (``audit/runner.boot_audit``), the range certifier riding
the same staging surface (``ranges/runner``), and the persistent AOT
compile cache (``engine/compile_cache.py``).  Each used to be one
hand-rolled key away from drifting on what "the same shape" means
(the r-audit params-signature bug was exactly such a drift: a cache
that ignored params dtypes kept serving a stale verdict for an
f64-poisoned artifact).  This module is the single copy of the rule:

    a staged shape is keyed by everything that changes the compiled
    graph — the full config JSON (eviction knobs included), the wire
    format, the mesh device count, the coalescing-ladder size set,
    donation, and the params leaves' dtypes/shapes.

What it deliberately does NOT include: toolchain versions (jax /
jaxlib / XLA backend).  Version drift invalidates *serialized
executables* but not *proofs about the staged jaxpr re-derived per
process* — so the compile cache layers versions on top (in its entry
header, counted distinctly as ``version_drift``) while the in-process
audit cache does not need them.

jax-free at module level (function-local import for params leaves):
``core/`` sits on jax-free import paths (cluster supervisor spawn).
"""

from __future__ import annotations

import hashlib
import json
from typing import Any

import numpy as np


def params_signature(params: Any | None, model_name: str) -> list:
    """Dtype/shape signature of a params pytree — the part of the
    staged shape the config cannot describe.  ``None`` params key on
    the model's default-init identity instead (the model name), which
    is what makes two default-booted engines shape-equal."""
    if params is None:
        return ["default", model_name]
    import jax  # function-local: keep core importable jax-free

    leaves = jax.tree_util.tree_leaves(params)
    return [
        [str(np.dtype(getattr(leaf, "dtype", type(leaf)))),
         [int(d) for d in getattr(leaf, "shape", ())]]
        for leaf in leaves
    ]


def staging_signature(
    cfg: Any,
    *,
    wire: str,
    mesh_devices: int = 1,
    mega_sizes: tuple[int, ...] | list[int] | None = None,
    params: Any | None = None,
    donate: bool | None = None,
) -> dict:
    """Build the canonical signature dict of one staged serving shape.

    Pure data (JSON-able, deterministic ordering via
    :func:`signature_digest`): callers hash it, tuple it, or embed it
    in artifacts.  ``donate=None`` means the caller does not key on it
    (the audit boot cache) and is kept distinct from a bool (the
    compile cache passes the engine's)."""
    return {
        "cfg": cfg.to_json(),
        "wire": wire,
        "mesh_devices": int(mesh_devices or 1),
        "mega_sizes": [int(s) for s in (mega_sizes or ())],
        "donate": None if donate is None else bool(donate),
        "params": params_signature(params, cfg.model.name),
    }


def signature_digest(sig: dict) -> str:
    """Stable hex digest of a signature dict (sorted-key canonical
    JSON, sha256) — the compile cache's filename key and the audit
    cache's hashable key half."""
    blob = json.dumps(sig, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()
