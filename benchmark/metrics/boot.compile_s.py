"""Wall spent in backend compile-or-load since the process began
(``boot.jax_cache.backend_compile_s``)."""

NAME = "boot.compile_s"
UNIT = "s"
LAYER = "boot"
MOVES = "setup_s"


def read(ctx):
    boot = ctx.snap0["rep"].get("boot") or {}
    s = (boot.get("jax_cache") or {}).get("backend_compile_s")
    return s if s else None
