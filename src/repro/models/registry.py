"""Model registry: family -> module, plus per-(arch x shape) input specs.

Every module exposes: init(rng, cfg), loss_fn(params, cfg, batch),
and for decoder families prefill / decode_step / make_cache.
`input_specs(cfg, shape)` returns the exact ShapeDtypeStruct pytree the
dry-run lowers against (the pattern: weak-type-correct, shardable, zero
allocation).
"""
from __future__ import annotations

import types
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import dit, encdec, hybrid, mmdit, rwkv6, transformer

_FAMILY = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": rwkv6,
    "hybrid": hybrid,
    "encdec": encdec,
    "dit": dit,
    "mmdit": mmdit,
}


def get_model(cfg: ArchConfig) -> types.ModuleType:
    return _FAMILY[cfg.family]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    if cfg.is_dit:
        return {
            "latents": _sds((b, s, cfg.patch_dim), jnp.float32),
            "noise": _sds((b, s, cfg.patch_dim), jnp.float32),
            "t": _sds((b,), jnp.float32),
            "cond": _sds((b, cfg.cond_len or 64, cfg.d_model), jnp.float32)
            if cfg.text_conditioned else None,
        }
    if cfg.family == "encdec":
        st = max(s // 8, 8)
        return {
            "audio_embeds": _sds((b, s, cfg.d_model), jnp.float32),
            "tokens": _sds((b, st), jnp.int32),
            "targets": _sds((b, st), jnp.int32),
        }
    batch = {
        "tokens": _sds((b, s), jnp.int32),
        "targets": _sds((b, s), jnp.int32),
    }
    if cfg.family == "vlm":
        batch["patch_embeds"] = _sds((b, cfg.num_patches, cfg.d_model),
                                     jnp.float32)
        batch["tokens"] = _sds((b, s - cfg.num_patches), jnp.int32)
        batch["targets"] = _sds((b, s - cfg.num_patches), jnp.int32)
    return batch


def decode_specs(cfg: ArchConfig, shape: ShapeConfig):
    """(token, cache) specs for serve_step at this shape."""
    b, s = shape.global_batch, shape.seq_len
    mdl = get_model(cfg)
    cache = jax.eval_shape(
        lambda: mdl.make_cache(cfg, b, s, dtype=jnp.bfloat16))
    token = _sds((b,), jnp.int32)
    return token, cache


def prefill_specs(cfg: ArchConfig, shape: ShapeConfig):
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        return {"audio_embeds": _sds((b, s, cfg.d_model), jnp.float32)}
    if cfg.family == "vlm":
        return {"tokens": _sds((b, s - cfg.num_patches), jnp.int32),
                "patch_embeds": _sds((b, cfg.num_patches, cfg.d_model),
                                     jnp.float32)}
    if cfg.is_dit:
        # DiT "prefill" = one full denoising forward (its inference step)
        return {"latents": _sds((b, s, cfg.patch_dim), jnp.float32),
                "t": _sds((b,), jnp.float32),
                "cond": _sds((b, cfg.cond_len or 64, cfg.d_model),
                             jnp.float32) if cfg.text_conditioned else None}
    return {"tokens": _sds((b, s), jnp.int32)}


def make_concrete_batch(rng, cfg: ArchConfig, shape: ShapeConfig):
    """Random concrete batch matching train_batch_specs (smoke tests)."""
    specs = train_batch_specs(cfg, shape)
    out = {}
    for key, sp in specs.items():
        if sp is None:
            continue
        rng, sub = jax.random.split(rng)
        if sp.dtype == jnp.int32:
            out[key] = jax.random.randint(sub, sp.shape, 0,
                                          max(cfg.vocab_size - 1, 2))
        else:
            out[key] = jax.random.normal(sub, sp.shape, sp.dtype)
        if key == "t":
            out[key] = jax.random.uniform(sub, sp.shape)
    return out
