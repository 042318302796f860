"""Share of device busy time in the MMDiT's dual-stream blocks, percent:
the self time of the ops under the `mmdit.double` named scope
(models/mmdit.py: both streams' modulation, projections, norms and
MLPs, the joint attention of those layers) over the busy time of the
traced window. Layer: model step. Moves dit_tokens_per_s."""
from bench import trace_scopes

SCOPE = "mmdit.double"


def read(ctx):
    seconds = trace_scopes.of(ctx).seconds(SCOPE)
    if seconds <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
