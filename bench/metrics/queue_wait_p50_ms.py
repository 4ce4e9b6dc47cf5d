"""Median time from due to admission into a slot (``Request.started_s``)
over requests due in the window; one never admitted ranks above all."""

from bench import window

LAYER = "scheduler"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "ttft_p50_ms"
BETTER = "lower"


def read(ctx):
    due = ctx.due_in_window()
    if not due:
        return None
    started = [r.req.started_s - r.due for r in due if r.req.started_s]
    waiting = [ctx.end - r.due for r in due if not r.req.started_s]
    return window.ranked_percentile(started, waiting, 50) * 1e3
