"""Median device time of one prefill program call, ms (XLA modules named
`MODULE`: the Scheduler's batch-1 bucket prefill). Layer: model step.
Moves lm_tokens_per_s."""
from bench.window import median

MODULE = "jit__prefill"


def read(ctx):
    d = ctx.trace.module_durations(MODULE)
    return 1e3 * median(d) if d else None
