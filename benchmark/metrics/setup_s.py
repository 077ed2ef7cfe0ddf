"""JAX has the chip -> window start: the program's imports, engine
construction, compile or cache load, warm, the resident population's
prefill, generator start and the warm-up serve.  (Process start -> chip
is the machine's runtime coming up, 6-14 s that no change to the program
moves; it is printed as ``chip_start_s`` on an earlier line.)"""

NAME = "setup_s"
UNIT = "s"
LAYER = "end to end"
MOVES = ""


def read(ctx):
    return ctx.setup_s
