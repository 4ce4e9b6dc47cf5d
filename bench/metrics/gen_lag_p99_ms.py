"""How late the load generator submitted (submit - due), 99th percentile
over requests due in the window.  Open-loop traffic only: a closed-loop
client sends the moment it is due."""

from bench import window

LAYER = "load generator"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "ttft_p50_ms"
BETTER = "lower"


def read(ctx):
    lags = [r.submitted - r.due for r in ctx.due_in_window()]
    return window.percentile(lags, 99) * 1e3 if lags else None
