"""Share of the chip's bf16 peak over the whole window: the FLOPs that the
window's fed tokens (prompt and new) need, over window seconds x 197
TFLOP/s.  Counted from the benchmark's record of every step dispatched in
the window."""

LAYER = "device"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "out_tok_s"
BETTER = "higher"


def read(ctx):
    steps = [s for s in ctx.steps if ctx.w0 <= s.t < ctx.w1]
    if not steps:
        return None
    flops = sum(s.flops for s in steps)
    return 100.0 * flops / (ctx.seconds * ctx.peaks.bf16_flops)
