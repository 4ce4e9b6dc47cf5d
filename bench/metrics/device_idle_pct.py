"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals / traced window)."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tok_s"
BETTER = "lower"


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
