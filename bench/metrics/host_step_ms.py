"""Host time of one scheduling iteration of the engine's drain, per device
step: the mean, over the traced window's ``serve.step`` spans that hold a
``serve.dispatch``, of the span's length less the ``serve.sync`` time in
it (the wait for the selected tokens).  The engine's own spans, read by
``bench.host_gaps``; left out where the program has none."""

from bench import host_gaps

LAYER = "scheduler"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"
BETTER = "lower"


def read(ctx):
    et = host_gaps.of(ctx)
    s = et.host_step_s() if et is not None else None
    return None if s is None else 1e3 * s
