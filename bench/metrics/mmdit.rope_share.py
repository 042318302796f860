"""Share of device busy time in the MMDiT's q/k RMSNorm and 3-D RoPE,
percent: the self time of the ops under the `mmdit.rope` named scope
(models/mmdit.py) over the busy time of the traced window: memory-bound
passes over q and k. Layer: model step. Moves dit_tokens_per_s."""
from bench import trace_scopes

SCOPE = "mmdit.rope"


def read(ctx):
    seconds = trace_scopes.of(ctx).seconds(SCOPE)
    if seconds <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
