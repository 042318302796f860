"""train/prefill/serve step builders, uniform across families."""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import registry
from repro.optim import adamw


def cast_params_bf16(params):
    """One-shot f32->bf16 compute-copy of the parameters (mixed precision:
    f32 master weights live only in the optimizer path). Doing this ONCE
    before the layer scan keeps every weight all-gather / dynamic-slice on
    bf16 buffers — XLA otherwise hoists the f32->bf16 converts above the
    per-layer collectives and doubles their wire bytes (measured;
    EXPERIMENTS.md §Perf)."""
    return jax.tree.map(
        lambda t: t.astype(jnp.bfloat16)
        if t.dtype == jnp.float32 else t, params)


def make_train_step(cfg: ArchConfig, opt_cfg: adamw.AdamWConfig,
                    backend: str = "gather") -> Callable:
    mdl = registry.get_model(cfg)

    def train_step(params, opt_state, batch):
        def loss_of(p):
            return mdl.loss_fn(cast_params_bf16(p), cfg, batch, backend=backend)

        loss, grads = jax.value_and_grad(loss_of)(params)
        params, opt_state, metrics = adamw.update(params, grads, opt_state,
                                                  opt_cfg)
        return params, opt_state, loss, metrics["grad_norm"]

    return train_step


def make_prefill_step(cfg: ArchConfig, backend: str = "gather") -> Callable:
    mdl = registry.get_model(cfg)

    if cfg.family == "encdec":
        def prefill_step(params, batch):
            return mdl.prefill(params, cfg, batch, backend=backend)
    elif cfg.is_dit:
        def prefill_step(params, batch):
            # DiT "prefill" = one denoising forward (its inference step)
            return mdl.forward(params, cfg, batch["latents"], batch["t"],
                               batch.get("cond"), backend=backend)
    elif cfg.family == "vlm":
        def prefill_step(params, batch):
            x, _, (kc, vc) = mdl.forward(
                params, cfg, batch["tokens"],
                prefix_embeds=batch["patch_embeds"], backend=backend,
                return_cache=True)
            cache = {"k": kc, "v": vc,
                     "pos": jnp.int32(batch["tokens"].shape[1]
                                      + cfg.num_patches)}
            return x[:, -1], cache
    else:
        def prefill_step(params, batch):
            return mdl.prefill(params, cfg, batch["tokens"], backend=backend)

    return prefill_step


def make_serve_step(cfg: ArchConfig) -> Callable:
    mdl = registry.get_model(cfg)

    def serve_step(params, token, cache):
        return mdl.decode_step(params, cfg, token, cache)

    return serve_step


def abstract_state(cfg: ArchConfig) -> Tuple:
    """(params, opt_state) as ShapeDtypeStructs — no allocation."""
    mdl = registry.get_model(cfg)
    params = jax.eval_shape(
        lambda: mdl.init(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(adamw.init, params)
    return params, opt
