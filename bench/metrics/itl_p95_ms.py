"""95th percentile of all gaps between consecutive tokens of a request whose
later token falls in the window, pooled over every request."""

from bench import window

LAYER = "end to end"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"
BETTER = "lower"


def read(ctx):
    gaps = window.gaps_in_window((r.stamps for r in ctx.recs), ctx.w0, ctx.w1)
    return window.percentile(gaps, 95) * 1e3 if gaps else None
