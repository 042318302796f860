"""Share of device busy time in the XLA half of SLA's linear branch,
percent: the self time of the ops under the `sla.linear` named scope
(core/backends.py phi maps; kernels/ops.py per-block h_j, z_j and their
aggregation over marginal blocks) over the busy time of the traced
window. The branch's in-kernel part runs inside `sla_fwd`, not here.
Layer: backends. Moves dit_tokens_per_s."""
from bench import trace_scopes

SCOPE = "sla.linear"


def read(ctx):
    seconds = trace_scopes.of(ctx).seconds(SCOPE)
    if seconds <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
