"""Faults planted in the timed path, underneath a run, to show that the
comparison catches them: a step that returns its state unchanged, and an
answer (a latent, a token) altered where it is produced. Each takes the
runner after set-up and wraps the program's compiled step."""
from __future__ import annotations


def dit_unchanged(runner):
    tick = runner.sched._tick_jit

    def broken(params, latents, *rest):
        _, plans, info = tick(params, latents, *rest)
        return latents, plans, info
    runner.sched._tick_jit = broken


def dit_altered(runner):
    """Every slot's latent shifted by one token along the sequence."""
    import jax.numpy as jnp

    tick = runner.sched._tick_jit

    def broken(*args):
        lat, plans, info = tick(*args)
        return jnp.roll(lat, 1, axis=1), plans, info
    runner.sched._tick_jit = broken


def lm_unchanged(runner):
    decode = runner.sched._decode

    def broken(params, token, cache):
        logits, _ = decode(params, token, cache)
        return logits, cache
    runner.sched._decode = broken


def lm_altered(runner):
    """Every decode step's logits shifted by one token id."""
    import jax.numpy as jnp

    decode = runner.sched._decode

    def broken(params, token, cache):
        logits, cache = decode(params, token, cache)
        return jnp.roll(logits, 1, axis=-1), cache
    runner.sched._decode = broken


FAULTS = {"dit": {"unchanged": dit_unchanged, "altered": dit_altered},
          "lm": {"unchanged": lm_unchanged, "altered": lm_altered}}
