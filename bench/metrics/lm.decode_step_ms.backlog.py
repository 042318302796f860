"""Median device time of one decode program call, ms (XLA modules named
`MODULE`: the Scheduler's batched one-token decode step). Layer: model
step.
Moves lm_tokens_per_s."""
from bench.window import median

MODULE = "jit__one"


def read(ctx):
    d = ctx.trace.module_durations(MODULE)
    return 1e3 * median(d) if d else None
