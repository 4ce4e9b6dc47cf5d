"""Mean device time of one jitted decode step, from the profiler trace:
each device execution is attributed to the benchmark's host span around
the engine's ``_decode_paged`` call that dispatched it."""

LAYER = "jitted steps"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
BETTER = "lower"


def read(ctx):
    steps = ctx.traced_steps("decode")
    if not steps:
        return None
    return 1e3 * sum(s.device_s for s in steps) / len(steps)
