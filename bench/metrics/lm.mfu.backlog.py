"""Model FLOP/s utilization of LM serving, percent of the bf16 peak:
(prompt tokens admitted x FLOPs per prefill token + decoded tokens x
FLOPs per decode token + one output-head row per admission) over the
window. Prompt tokens are the prompts' own lengths, not the padded
bucket. Counts: bench/counts/models.py (decode-time SLA from
sla_decode_flops). Layer: model step (models/transformer.py). Moves
lm_tokens_per_s."""
from bench.counts.models import lm_decode_token, lm_logits, lm_prefill_token


def read(ctx):
    serve = ctx.config["serving"]
    c = ctx.counters
    prompt = ctx.runner.admitted_prompt_tokens
    if not prompt and not c.get("decode_tokens"):
        return None
    flops = (prompt * lm_prefill_token(ctx.model, serve["prefill_bucket"])
             + c.get("decode_tokens", 0) * lm_decode_token(ctx.model,
                                                           serve["max_len"])
             + c.get("admissions", 0) * lm_logits(ctx.model))
    return ctx.share_of_peak_flops(flops)
