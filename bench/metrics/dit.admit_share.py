"""Share of device busy time spent in the admission programs of the
DiffusionScheduler (the batch-1 first step of each admitted request),
percent, by XLA module name. Layer: scheduler (serving/diffusion.py).
Moves dit_tokens_per_s."""

MODULE = "admit_fresh"


def read(ctx):
    admit = ctx.trace.module_busy_seconds(MODULE)
    if admit <= 0:
        return None
    return 100.0 * admit / ctx.trace.busy_s
