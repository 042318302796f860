"""Share of device busy time in the DiT's gated MLP, percent: the self
time of the ops under the `dit.mlp` named scope (models/dit.py) over the
busy time of the traced window. Layer: model step. Moves
dit_tokens_per_s."""
from bench import trace_scopes

SCOPE = "dit.mlp"


def read(ctx):
    seconds = trace_scopes.of(ctx).seconds(SCOPE)
    if seconds <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
