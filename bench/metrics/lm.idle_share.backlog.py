"""Device idle share of the traced window, percent: 1 - (union of the
device-op intervals / window). Layer: device. Moves lm_tokens_per_s."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.window_s)
