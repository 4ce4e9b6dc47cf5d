"""Set-up: process start to the window's start (imports, weights, warm-up,
warm traffic)."""

LAYER = "end to end"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"
BETTER = "lower"


def read(ctx):
    return ctx.setup_s
