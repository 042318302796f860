"""Decode-slot occupancy over the window, percent: the Scheduler's
ServeStats slot_steps_active / slot_steps_total. Layer: scheduler
(serving/api.py). Moves lm_tokens_per_s."""


def read(ctx):
    total = ctx.counters.get("slot_steps_total", 0)
    if not total:
        return None
    return 100.0 * ctx.counters["slot_steps_active"] / total
