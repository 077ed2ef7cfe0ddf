"""How a process of this program meets JAX: which platform it may run
on, and where its compiles are cached.

Two rules, each with one home so every entry point (``fsx`` verbs,
cluster engine children, ``bench.py`` phase children) applies the same
one:

* **No silent CPU.**  The stock TPU runtime falls back to the CPU
  backend without a word when it finds no chip.  A serving or measuring
  process that lands anywhere but the TPU without having been told to
  (``JAX_PLATFORMS=cpu``, as the tests and CPU comparisons set it)
  fails here, at the first point that knows —
  :func:`require_platform`.
* **The compile cache is placed from outside.**  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  here sets another; where it is not, JAX's persistent compilation
  cache goes to ``<checkout>/.jax_cache`` (ignored by git).  The path is
  part of the cache key's stability — never a temporary name, pid or
  time — :func:`place_compile_cache`.  The key covers each program's
  metadata too (operation names with their ``jax.named_scope`` path,
  source lines): by JAX's default it does not, and a boot after a
  change of scopes alone then loads the executable compiled before it,
  whose device trace carries the OLD names (measured, PR 29: the
  ``fsx.*`` stage scopes were absent from a traced run until the cache
  was cold).  A trace has to name what the source says.

:class:`CompileCounters` reads JAX's own cache events so a boot can
show what it compiled and what it loaded (``EngineReport.boot
["jax_cache"]``).
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

#: The checkout root (the directory holding ``flowsentryx_tpu/``).
CHECKOUT = Path(__file__).resolve().parents[2]


def require_platform(who: str) -> str:
    """The backend this process will run on; exits with a message when
    that is not the TPU and ``JAX_PLATFORMS`` did not ask for it."""
    import jax

    platform = jax.default_backend()
    asked = os.environ.get("JAX_PLATFORMS", "").split(",")
    if platform != "tpu" and platform not in asked:
        raise SystemExit(
            f"{who}: JAX found no TPU (it would run on {platform!r}) and "
            f"JAX_PLATFORMS does not ask for that; set "
            f"JAX_PLATFORMS={platform} to run there on purpose")
    return platform


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place (module
    docstring) before the first compile; returns the directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


#: JAX compile/cache events of this process, summed by ONE pair of
#: listeners (JAX's listener registry is process-global and a listener
#: per :class:`CompileCounters` would pile up in a process that boots
#: many engines, as the tests do).  Compiles can come from the warm-fill
#: thread too, hence the lock.
_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "stores",
}
_COMPILE_S = "/jax/core/compile/backend_compile_duration"
_totals = {"requests": 0, "hits": 0, "stores": 0, "backend_compile_s": 0.0}
_totals_lock = threading.Lock()
_listening = False


def _on_event(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        with _totals_lock:
            _totals[name] += 1


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _COMPILE_S:
        with _totals_lock:
            _totals["backend_compile_s"] += seconds


class CompileCounters:
    """JAX's own compile/cache events, counted from construction.

    ``stores`` are entries this process wrote to the persistent cache
    (JAX records its ``cache_misses`` event exactly when it writes one:
    programs under its minimum compile time are compiled every boot and
    never stored), ``hits`` are executables it loaded from there, and
    ``backend_compile_s`` is the wall spent in backend compile-or-load."""

    def __init__(self, cache_dir: str):
        import jax.monitoring

        global _listening
        with _totals_lock:
            if not _listening:
                jax.monitoring.register_event_listener(_on_event)
                jax.monitoring.register_event_duration_secs_listener(
                    _on_duration)
                _listening = True
            self._base = dict(_totals)
        self.cache_dir = cache_dir

    def report(self) -> dict:
        with _totals_lock:
            since = {k: _totals[k] - self._base[k] for k in _totals}
        since["backend_compile_s"] = round(since["backend_compile_s"], 4)
        return {"dir": self.cache_dir, **since}
