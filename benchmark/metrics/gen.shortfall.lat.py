"""How far ``fsxd --sim --pace`` fell short of its schedule
(``harness.generator_shortfall``)."""

NAME = "gen.shortfall.lat"
UNIT = "%"
LAYER = "load generator"
MOVES = "verdict_p95_ms"


def read(ctx):
    return ctx.harness.generator_shortfall(ctx)
