"""Model FLOP/s utilization of the MMDiT step, percent of the bf16 peak:
model FLOPs per slot-step (bench/counts/mmdit.slot_step: products of
both kinds of block over the joint video and text tokens, SLA at the
joint length and the configured critical fraction, modulation and
embedders; no planning, no recomputation) times the slot-steps of the
window (admission steps included), over the window. Layer: model step
(models/mmdit.py). Moves dit_tokens_per_s."""
from bench.counts.mmdit import slot_step


def read(ctx):
    steps = ctx.counters.get("denoise_steps", 0)
    if not steps:
        return None
    flops = steps * slot_step(ctx.model, int(ctx.traffic["seq_len"]))
    return ctx.share_of_peak_flops(flops)
