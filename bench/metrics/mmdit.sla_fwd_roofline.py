"""Roofline share of the fused SLA forward kernel (kernels/sla_fwd.py)
in the MMDiT cell, percent: the least time its calls need
(bench/counts/kernels.sla_fwd over the joint sequence of video and text
tokens, FLOPs at the configured critical count per row, least bytes)
over the summed device time of its events. Every head-run launch
counts: each tick runs every slot and each admission one sample,
through all layers. Layer: kernels. Moves dit_tokens_per_s."""
from bench.counts.kernels import sla_fwd
from bench.counts.sla import num_critical

KERNEL = "sla_fwd"


def read(ctx):
    seconds = ctx.trace.kernel_seconds(KERNEL)
    if seconds <= 0:
        return None
    m, sla = ctx.model, ctx.model["sla"]
    n = int(ctx.traffic["seq_len"]) + m["cond_len"]
    tn = n // sla["block_kv"]
    in_bytes = 4 if ctx.config["compute_dtype"] == "float32" else 2
    flops, nbytes = sla_fwd(n, m["head_dim"], m["num_heads"],
                            m["num_kv_heads"], sla["block_q"],
                            num_critical(tn, sla["kh_frac"]), in_bytes)
    samples = (ctx.runner.ticks * int(ctx.traffic["slots"])
               + ctx.counters.get("admissions", 0)) * m["num_layers"]
    return ctx.roofline_share(samples * flops, samples * nbytes, seconds)
