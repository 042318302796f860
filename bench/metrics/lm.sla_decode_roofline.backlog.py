"""Roofline share of the fused decode kernel (kernels/sla_decode.py),
percent: the least time its calls need (bench/counts/kernels.sla_decode:
one launch per layer per decode step over every slot, the decode
budget's critical blocks per query head) over the summed device time of
its events (custom calls whose HLO names start with `KERNEL`). Layer: kernels. Moves
lm_tokens_per_s."""
from bench.counts.kernels import sla_decode
from bench.counts.sla import num_critical

KERNEL = "_fused_decode_paged"


def read(ctx):
    seconds = ctx.trace.kernel_seconds(KERNEL)
    if seconds <= 0:
        return None
    m, sla, serve = ctx.model, ctx.model["sla"], ctx.config["serving"]
    slots = int(serve["slots"])
    tn = int(serve["max_len"]) // sla["block_kv"]
    k_sel = num_critical(tn, sla["kh_frac"], sla.get("decode_budget"))
    flops, nbytes = sla_decode(slots, m["head_dim"], m["num_heads"],
                               m["num_kv_heads"], sla["block_kv"], k_sel,
                               2 if ctx.config["compute_dtype"] == "bfloat16"
                               else 4)
    launches = ctx.counters.get("slot_steps_total", 0) // slots \
        * m["num_layers"]
    return ctx.roofline_share(launches * flops, launches * nbytes, seconds)
