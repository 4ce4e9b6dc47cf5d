"""Share of the chip's bf16 peak that the traced decode steps reach: the
FLOPs their fed tokens need over their device time at 197 TFLOP/s."""

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
BETTER = "higher"


def read(ctx):
    steps = ctx.traced_steps("decode")
    if not steps:
        return None
    flops = sum(s.flops for s in steps)
    return 100.0 * flops / (sum(s.device_s for s in steps) * ctx.peaks.bf16_flops)
