"""New tokens stamped inside the window over the window's seconds."""

from bench import window

LAYER = "end to end"
UNIT = "tokens/s"
SOURCE = "host_clock"
MOVES = "out_tok_s"
BETTER = "higher"


def read(ctx):
    return window.out_tok_s((r.stamps for r in ctx.recs), ctx.w0, ctx.w1)
