"""Roofline share of the traced decode steps: the least time their work
needs at the chip's peaks (bench.work) over their device time."""

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
BETTER = "higher"


def read(ctx):
    steps = ctx.traced_steps("decode")
    if not steps:
        return None
    return 100.0 * sum(s.least_s for s in steps) / sum(s.device_s for s in steps)
