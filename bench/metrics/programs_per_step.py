"""Device programs per device step: device 0's ``XLA Modules`` executions
that start in the traced window over the engine's ``serve.dispatch`` spans
there (the step itself, token selection, and every eager op between
steps).  Read by ``bench.host_gaps``; left out where the program has no
spans."""

from bench import host_gaps

LAYER = "jitted steps"
UNIT = "programs"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
BETTER = "lower"


def read(ctx):
    et = host_gaps.of(ctx)
    return et.programs_per_step() if et is not None else None
