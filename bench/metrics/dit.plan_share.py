"""Share of device busy time spent planning SLA, percent: the self time
of the ops under the `sla.plan` named scope (core/plan.py: pooled score
map, top-k classification, row and column LUTs, drift sums and the
per-row select) over the busy time of the traced window. Layer: plan.
Moves dit_tokens_per_s."""
from bench import trace_scopes

SCOPE = "sla.plan"


def read(ctx):
    seconds = trace_scopes.of(ctx).seconds(SCOPE)
    if seconds <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
