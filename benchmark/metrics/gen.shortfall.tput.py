"""How far ``fsxd --sim --pace`` fell short of its schedule
(``harness.generator_shortfall``)."""

NAME = "gen.shortfall.tput"
UNIT = "%"
LAYER = "load generator"
MOVES = "records_per_s"


def read(ctx):
    return ctx.harness.generator_shortfall(ctx)
