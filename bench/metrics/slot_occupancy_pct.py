"""Share of slot-steps in the window that carried a request: the engine's
busy_slot_steps over steps x slots, as deltas across the window."""

LAYER = "scheduler"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tok_s"
BETTER = "higher"


def read(ctx):
    steps = ctx.c1.steps - ctx.c0.steps
    if steps <= 0:
        return None
    busy = ctx.c1.busy_slot_steps - ctx.c0.busy_slot_steps
    return 100.0 * busy / (steps * ctx.max_batch)
