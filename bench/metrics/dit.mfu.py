"""Model FLOP/s utilization of the DiT step, percent of the bf16 peak:
model FLOPs per slot-step (bench/counts/models.dit_slot_step: matrix
products, SLA sparse + linear + Proj at the configured critical
fraction, cross-attention; no planning, no recomputation) times the
slot-steps of the window (admission steps included), over the window.
Layer: model step (models/dit.py). Moves dit_tokens_per_s."""
from bench.counts.models import dit_slot_step


def read(ctx):
    steps = ctx.counters.get("denoise_steps", 0)
    if not steps:
        return None
    flops = steps * dit_slot_step(ctx.model, int(ctx.traffic["seq_len"]))
    return ctx.share_of_peak_flops(flops)
