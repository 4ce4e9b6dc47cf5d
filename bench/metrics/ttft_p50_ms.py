"""Median time from due to first token over every request due in the window
(requests never served rank above every served one)."""

from bench import window

LAYER = "end to end"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "ttft_p50_ms"
BETTER = "lower"


def read(ctx):
    v = window.ttft_s(ctx.recs, ctx.w0, ctx.w1, ctx.end, 50)
    return None if v is None else v * 1e3
