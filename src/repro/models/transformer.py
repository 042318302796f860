"""Decoder-only transformer LM (dense / MoE / VLM families).

Layers are scanned (stacked params, single trace per layer kind) for
compile-time sanity at 88-layer scale. Per-layer attention kind is a
static-shaped int array consumed by lax.switch: 0=SLA, 1=full, 2=sliding
window (gemma3 local layers). SLA layers carry the learnable Proj.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import masks as masks_lib
from repro.core import plan as plan_lib
from repro.distributed import ctx
from repro.models import moe as moe_lib
from repro.models.common import (NEG_INF, attention, chunked_softmax_xent,
                                 dense_init, embed_init, logits_from_hidden,
                                 mse_loss, rms_norm, rope)

KIND_SLA, KIND_FULL, KIND_SWA = 0, 1, 2


def layer_kinds_list(cfg: ArchConfig) -> list:
    """Static per-layer attention kinds."""
    l = cfg.num_layers
    if cfg.local_global_pattern:
        p = cfg.local_global_pattern
        return [KIND_SLA if (i + 1) % p == 0 else KIND_SWA for i in range(l)]
    if cfg.attention_kind == "full":
        return [KIND_FULL] * l
    if cfg.attention_kind == "swa":
        return [KIND_SWA] * l
    return [KIND_SLA] * l


def layer_kinds(cfg: ArchConfig) -> jnp.ndarray:
    return jnp.asarray(layer_kinds_list(cfg), jnp.int32)


def _layer_init(rng, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    r = list(jax.random.split(rng, 8))
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "ln1": jnp.zeros((d,), dtype),
        "ln2": jnp.zeros((d,), dtype),
        "wq": dense_init(r[0], d, h * dh, dtype),
        "wk": dense_init(r[1], d, hkv * dh, dtype),
        "wv": dense_init(r[2], d, hkv * dh, dtype),
        "wo": dense_init(r[3], h * dh, d, dtype),
        "sla_proj": jnp.zeros((h, dh, dh), dtype),
    }
    if cfg.sla.routing_mode == "learned":
        # identity init: the learned router reproduces the threshold
        # rule bitwise until fine-tuning moves it (no RNG consumed, so
        # threshold-mode params are unchanged)
        p["routing"] = masks_lib.routing_init(h, dh, dtype)
    if cfg.qk_norm:
        p["qnorm"] = jnp.zeros((dh,), dtype)
        p["knorm"] = jnp.zeros((dh,), dtype)
    if cfg.num_experts:
        p["moe"] = moe_lib.moe_init(r[4], cfg, dtype)
    else:
        p["mlp_wi"] = dense_init(r[5], d, 2 * cfg.d_ff, dtype)
        p["mlp_wo"] = dense_init(r[6], cfg.d_ff, d, dtype)
    return p


def init(rng, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    r = jax.random.split(rng, cfg.num_layers + 2)
    layers = jax.vmap(lambda k: _layer_init(k, cfg, dtype))(
        jnp.stack(r[: cfg.num_layers]))
    params = {
        "embed": embed_init(r[-1], cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(r[-2], cfg.vocab_size, cfg.d_model,
                                       dtype)
    return params


# --------------------------------------------------------------------------
# attention sub-block
# --------------------------------------------------------------------------
def _qkv(p, x, cfg: ArchConfig, positions):
    b, s, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x,
                   ctx.fsdp_gather(p["wq"].astype(x.dtype), "col"))
    k = jnp.einsum("bsd,de->bse", x,
                   ctx.fsdp_gather(p["wk"].astype(x.dtype), "col"))
    v = jnp.einsum("bsd,de->bse", x,
                   ctx.fsdp_gather(p["wv"].astype(x.dtype), "col"))
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"])
        k = rms_norm(k, p["knorm"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn(p, x, kind, cfg: ArchConfig, positions, backend,
          layer_plan=None, drift_threshold=None, want_plan=False,
          decode_plan_cfg=None):
    """Returns (attn_out (B,S,d), k_cache, v_cache, plan, retention,
    replanned, decode_mc).

    Plan reuse for LM prefill (DESIGN.md "Plan lifetime & drift"):
    `want_plan=True` with layer_plan=None plans inline and returns the
    plan; a given `layer_plan` is reused — and, when `drift_threshold`
    is set (a scalar: per-layer callers pass their layer's entry),
    refreshed under `lax.cond` when its retained critical mass decays
    (same drift metric as the DiT sampler). The plan is built outside
    the kind switch so it rides the layer scan with static shapes even
    in mixed-kind stacks (non-SLA layers just carry it).

    `decode_plan_cfg` (DESIGN.md "Decode-time SLA") additionally
    returns this layer's decode-grid block classification of the
    prompt — the rows that seed the incremental decode plan."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    sla_cfg = cfg.sla
    if cfg.sliding_window:
        sla_cfg = dataclasses.replace(sla_cfg, window=cfg.sliding_window)
    sla_params = {"proj": p["sla_proj"]}
    # the layer's learned-routing scorer (DESIGN.md "Learned routing");
    # None under threshold routing — every planning path below threads it
    routing = p.get("routing") if sla_cfg.routing_mode == "learned" \
        else None
    retention = jnp.float32(1.0)
    replanned = jnp.bool_(False)
    decode_mc = None
    if decode_plan_cfg is not None:
        kr = k if k.shape[1] == q.shape[1] else \
            jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
        decode_mc = masks_lib.compute_mask(q, kr, decode_plan_cfg,
                                           routing=routing)
    if want_plan or layer_plan is not None:
        plan_cfg = dataclasses.replace(sla_cfg, causal=True)
        if layer_plan is None:
            layer_plan = plan_lib.plan_attention(q, k, plan_cfg,
                                                 routing=routing)
        elif drift_threshold is not None:
            layer_plan, retention, replanned = plan_lib.refresh_plan(
                layer_plan, q, k, plan_cfg, drift_threshold,
                routing=routing)

    def do_sla(q, k, v):
        return attention(sla_params, q, k, v, "sla", sla_cfg,
                         causal=True, backend=backend, plan=layer_plan,
                         routing=routing)

    def do_full(q, k, v):
        return attention(None, q, k, v, "full", sla_cfg, causal=True)

    def do_swa(q, k, v):
        return attention(None, q, k, v, "swa", sla_cfg,
                         window=cfg.local_window or cfg.sliding_window,
                         causal=True)

    # Only compile branches that actually occur (a dead full-attention
    # branch would put N^2 temporaries into every lowered cell).
    branches = [do_sla, do_full, do_swa]
    used = sorted(set(layer_kinds_list(cfg)))
    if len(used) == 1:
        out = branches[used[0]](q, k, v)
    else:
        import numpy as np
        remap = np.zeros((3,), np.int32)
        for pos, orig in enumerate(used):
            remap[orig] = pos
        out = jax.lax.switch(jnp.asarray(remap)[kind],
                             [branches[u] for u in used], q, k, v)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    out = jnp.einsum("bse,ed->bsd", out,
                     ctx.fsdp_gather(p["wo"].astype(x.dtype), "row"))
    return out, k, v, layer_plan, retention, replanned, decode_mc


def _ffn(p, x, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    if cfg.num_experts:
        return moe_lib.moe_apply(p["moe"], x, cfg)
    h = jnp.einsum("bsd,df->bsf", x,
                   ctx.fsdp_gather(p["mlp_wi"].astype(x.dtype), "col"))
    g, u = jnp.split(h, 2, axis=-1)
    out = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                     ctx.fsdp_gather(p["mlp_wo"].astype(x.dtype), "row"))
    return out, jnp.float32(0.0)


# --------------------------------------------------------------------------
# forward / loss
# --------------------------------------------------------------------------
def forward(params, cfg: ArchConfig, tokens: Optional[jax.Array] = None,
            prefix_embeds: Optional[jax.Array] = None,
            compute_dtype=jnp.bfloat16, backend: str = "gather",
            return_cache: bool = False,
            plans=None, return_plans: bool = False,
            drift_threshold=None, decode_plan_cfg=None):
    """Returns hidden states (B, S, d); optionally the per-layer KV cache.

    VLM (cfg.frontend == "vision_stub"): prefix_embeds (B, P, d) are
    prepended to the token embeddings (patch positions share the rope
    position space, positions 0..P-1).

    LM-prefill plan reuse (DESIGN.md "Plan lifetime & drift"): with
    `return_plans=True` the per-layer SLAPlan stack rides out of the
    layer scan; pass it back as `plans=` on a later same-shape prefill
    to reuse the block structure, optionally with `drift_threshold=` to
    refresh drifted layers under `lax.cond`. `drift_threshold` may be
    a scalar or a per-layer (L,) array/tuple — each layer's refresh
    decision uses its own entry (never min-reduced across the stack).

    Decode-plan seeding (DESIGN.md "Decode-time SLA"): with
    `decode_plan_cfg=` (an `SLAConfig.decode_plan_cfg(...)` result) the
    per-layer decode-grid block classification of the prompt is also
    returned — `prefill(..., decode_max_len=)` embeds it into the
    static decode plan. Return value order:
    (x, aux[, caches][, plans][, decode_mc][, drift info dict]).
    """
    emb = params["embed"]
    parts = []
    if prefix_embeds is not None:
        parts.append(prefix_embeds.astype(compute_dtype))
    if tokens is not None:
        parts.append(jnp.take(emb, tokens, axis=0).astype(compute_dtype))
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    kinds = layer_kinds(cfg)
    want_plan = return_plans or plans is not None
    adaptive = drift_threshold is not None and plans is not None
    if adaptive:
        thresholds = jnp.broadcast_to(
            jnp.asarray(drift_threshold, jnp.float32), (cfg.num_layers,))

    def body(x, layer):
        layer = list(layer)
        p, kind = layer.pop(0), layer.pop(0)
        layer_plan = layer.pop(0) if plans is not None else None
        thr = layer.pop(0) if adaptive else None
        a, k, v, layer_plan, ret, rep, dmc = _attn(
            p, rms_norm(x, p["ln1"]), kind, cfg, positions, backend,
            layer_plan=layer_plan, drift_threshold=thr,
            want_plan=want_plan, decode_plan_cfg=decode_plan_cfg)
        # constraining the block OUTPUT (pre-residual-add) turns the TP
        # boundary all-reduce into a reduce-scatter (half the wire bytes)
        x = ctx.shard_residual(x + ctx.shard_residual(a))
        f, aux = _ffn(p, rms_norm(x, p["ln2"]), cfg)
        x = ctx.shard_residual(x + ctx.shard_residual(f))
        ys = (aux, (k, v) if return_cache else None,
              layer_plan if want_plan else None,
              dmc if decode_plan_cfg is not None else None,
              (ret, rep) if adaptive else None)
        return x, ys

    xs = (params["layers"], kinds)
    if plans is not None:
        xs = xs + (plans,)
    if adaptive:
        xs = xs + (thresholds,)
    x, (auxs, caches, out_plans, decode_mcs, drift_ys) = jax.lax.scan(
        ctx.maybe_remat(body), x, xs)
    x = rms_norm(x, params["ln_f"])
    aux = jnp.sum(auxs)
    rets = (x, aux)
    if return_cache:
        rets += (caches,)  # caches: (k (L,B,Hkv,S,Dh), v ...)
    if return_plans:
        rets += (out_plans,)
    if decode_plan_cfg is not None:
        rets += (decode_mcs,)  # (L, B, H, Tm, Tn) int8 decode-grid rows
    if adaptive:
        rets += ({"retention": drift_ys[0], "replanned": drift_ys[1]},)
    return rets


def loss_fn(params, cfg: ArchConfig, batch: dict,
            compute_dtype=jnp.bfloat16, backend: str = "gather") -> jax.Array:
    """Next-token cross-entropy (+ MoE aux). batch: tokens, targets[, mask,
    patch_embeds]."""
    x, aux = forward(params, cfg, batch["tokens"],
                     prefix_embeds=batch.get("patch_embeds"),
                     compute_dtype=compute_dtype, backend=backend)
    npatch = 0
    if batch.get("patch_embeds") is not None:
        npatch = batch["patch_embeds"].shape[1]
        x = x[:, npatch:]
    table = params.get("unembed", params["embed"])
    loss = chunked_softmax_xent(x, table, batch["targets"],
                                batch.get("mask"))
    return loss + 0.01 * aux


def distill_loss_fn(params, cfg: ArchConfig, batch: dict,
                    compute_dtype=jnp.bfloat16,
                    backend: str = "gather") -> jax.Array:
    """End-to-end distillation (the paper's fine-tuning objective):
    MSE between the SLA student's final hidden states and a
    gradient-stopped exact-attention teacher running the SAME params.

    The student runs under cfg as-is (SLA layers, learned routing if
    cfg.sla.routing_mode == "learned"), so the sla_proj merge and —
    via the straight-through marginal gates — the routing parameters
    receive gradients; a few steps at a fixed critical-block budget
    recover the exact-attention behavior (paper Sec. 5). Requires an
    autodiff backend for routing grads ("gather"/"reference"; the
    fused kernel treats the plan as a constant)."""
    tcfg = dataclasses.replace(
        cfg, sla=cfg.sla.replace(mode="full", routing_mode="threshold"))
    x_t, _ = forward(params, tcfg, batch["tokens"],
                     prefix_embeds=batch.get("patch_embeds"),
                     compute_dtype=compute_dtype, backend=backend)
    x_s, aux = forward(params, cfg, batch["tokens"],
                       prefix_embeds=batch.get("patch_embeds"),
                       compute_dtype=compute_dtype, backend=backend)
    return mse_loss(x_s, jax.lax.stop_gradient(x_t)) + 0.01 * aux


# --------------------------------------------------------------------------
# serving: prefill + single-token decode over a static-size KV cache
# --------------------------------------------------------------------------
def _seed_decode_state(cfg: ArchConfig, kc, vc, decode_mcs, max_len: int):
    """Decode-SLA cache state from the prefill caches (DESIGN.md
    "Decode-time SLA").

    kc, vc: (L, B, Hkv, S, Dh) prompt caches; decode_mcs: (L, B, H,
    Tm_p, Tn_p) decode-grid classification of the prompt rows. Builds
    the static-grid incremental plan plus the linear branch's running
    state: per-block h_j = sum phi(k) v^T / z_j = sum phi(k) partials
    and their running totals (updated O(1) per decoded token)."""
    from repro.core.phi import phi
    sla = cfg.sla
    bq, bkv = sla.block_q, sla.block_kv
    nl, b, hkv, s, dh = kc.shape
    tn = max_len // bkv
    tm_p, tn_p = s // bq, s // bkv
    dcfg = sla.decode_plan_cfg(tn)
    mc = jnp.full((nl, b, cfg.num_heads, tn, tn), -1, jnp.int8)
    mc = mc.at[..., :tm_p, :tn_p].set(decode_mcs)
    # col_width=1: decode never runs the dK/dV backward, so the plan
    # skips the O(Tn^2)-per-head column LUT (it would otherwise ride —
    # and be where()-selected — in every decode step's scan carry)
    plan = plan_lib.plan_from_mask(mc, dcfg, col_width=1)
    kp = phi(kc, sla.phi)  # f32
    kpb = kp.reshape(nl, b, hkv, tn_p, bkv, dh)
    vb = vc.astype(jnp.float32).reshape(nl, b, hkv, tn_p, bkv, dh)
    pad = [(0, 0)] * 3 + [(0, tn - tn_p)]
    hblk = jnp.pad(jnp.einsum("...nkd,...nke->...nde", kpb, vb),
                   pad + [(0, 0), (0, 0)])
    zblk = jnp.pad(jnp.sum(kpb, axis=-2), pad + [(0, 0)])
    kpool = jnp.pad(
        jnp.sum(kc.astype(jnp.float32)
                .reshape(nl, b, hkv, tn_p, bkv, dh), axis=-2),
        pad + [(0, 0)])
    k_sel = dcfg.num_critical(tn)
    return {
        "hblk": hblk, "zblk": zblk,
        "htot": jnp.sum(hblk, axis=3), "ztot": jnp.sum(zblk, axis=3),
        "kpool": kpool,
        "qpool": jnp.zeros((nl, b, cfg.num_heads, dh), jnp.float32),
        "plan": plan,
        "rows": jnp.int32(tm_p),
        "live_lut": jnp.zeros((nl, b, cfg.num_heads, k_sel), jnp.int32),
        "live_cnt": jnp.zeros((nl, b, cfg.num_heads), jnp.int32),
        "live_marg": jnp.zeros((nl, b, cfg.num_heads), jnp.int32),
        "extends": jnp.zeros((nl,), jnp.int32),
        "replans": jnp.zeros((nl,), jnp.int32),
        "reuses": jnp.zeros((nl,), jnp.int32),
        "retention": jnp.ones((nl,), jnp.float32),
    }


def _check_decode_grid(cfg: ArchConfig, seq_len: int, max_len: int):
    sla = cfg.sla
    if sla.block_q != sla.block_kv:
        raise ValueError("decode-time SLA requires block_q == block_kv")
    if sla.window or cfg.sliding_window:
        # the subtractive linear state cannot exclude out-of-window past
        # blocks (decode_plan_cfg classifies with window=0), so decode
        # would silently diverge from the window-constrained prefill —
        # fail loudly instead
        raise ValueError(
            "decode-time SLA does not support window-constrained SLA "
            "layers (SLAConfig.window / cfg.sliding_window); use dense "
            "decode for sliding-window configs")
    if seq_len % sla.block_q or max_len % sla.block_q:
        raise ValueError(
            f"decode-time SLA needs block-aligned lengths: prompt "
            f"{seq_len} and max_len {max_len} must be multiples of "
            f"sla.block_q={sla.block_q}")


def prefill(params, cfg: ArchConfig, tokens, compute_dtype=jnp.bfloat16,
            backend: str = "gather", plans=None, drift_threshold=None,
            return_plans: bool = False,
            decode_max_len: Optional[int] = None):
    """Run the prompt; returns (last_hidden (B, d), cache dict).

    Plan reuse across prefill chunks (serving): `return_plans=True`
    additionally returns the per-layer SLAPlan stack; pass it back as
    `plans=` (with `drift_threshold=` for drift-gated refresh) on the
    next same-shape prefill chunk — the serving engine amortizes block
    planning across the request stream this way. Return value order:
    (last_hidden, cache[, plans][, drift info]).

    Decode-time SLA (DESIGN.md "Decode-time SLA"): `decode_max_len=`
    sizes a static decode block grid, pads the KV caches out to it, and
    seeds the cache with the incremental decode plan (prompt rows
    classified on the decode grid) plus the linear branch's running
    H/Z state — `decode_step` then runs SLA decode instead of dense."""
    dcfg = None
    if decode_max_len is not None:
        _check_decode_grid(cfg, tokens.shape[1], decode_max_len)
        dcfg = cfg.sla.decode_plan_cfg(decode_max_len // cfg.sla.block_kv)
    out = forward(params, cfg, tokens, compute_dtype=compute_dtype,
                  backend=backend, return_cache=True, plans=plans,
                  return_plans=return_plans,
                  drift_threshold=drift_threshold, decode_plan_cfg=dcfg)
    x, (kc, vc) = out[0], out[2]
    extras = out[3:]
    cache = {"k": kc, "v": vc, "pos": jnp.int32(tokens.shape[1])}
    if decode_max_len is not None:
        i = 1 if return_plans else 0
        decode_mcs, extras = extras[i], extras[:i] + extras[i + 1:]
        cache["sla"] = _seed_decode_state(cfg, kc, vc, decode_mcs,
                                          decode_max_len)
        grow = decode_max_len - kc.shape[-2]
        if grow > 0:
            pad = [(0, 0)] * 3 + [(0, grow), (0, 0)]
            cache["k"] = jnp.pad(kc, pad)
            cache["v"] = jnp.pad(vc, pad)
    return (x[:, -1], cache) + extras


# --------------------------------------------------------------------------
# chunked admission prefill (DESIGN.md "Chunked admission prefill"):
# consume the prompt one block-aligned span at a time so the scheduler can
# interleave decode ticks between chunks. The carry maintains exactly the
# state later chunks (and `_seed_decode_state`) need: per-layer KV written
# so far, the mean-pooled q/k block features (so every chunk can re-score
# the FULL block map via `masks.score_map_pooled` — bitwise what blocking
# prefill scores), and the decode-grid classification rows. Finalization
# goes through `_seed_decode_state` on the carried KV + rows, so every
# cache leaf is bitwise identical to blocking `prefill` BY CONSTRUCTION.
# --------------------------------------------------------------------------
def check_chunked_prefill(cfg: ArchConfig, backend: str = "gather"):
    """Loudly reject configs the chunked-prefill machine cannot serve
    bitwise. Chunk plan rows are sliced from a full-map classification,
    which is only row-decomposable without the column-capacity demotion
    pass (it couples rows); the execution path covers SLA layers on the
    gather/kernel backends only."""
    from repro.core import backends as backend_lib

    sla = cfg.sla
    if sla.mode != "sla":
        raise ValueError(
            f"chunked admission prefill requires sla.mode='sla' (got "
            f"{sla.mode!r})")
    if sorted(set(layer_kinds_list(cfg))) != [KIND_SLA]:
        raise ValueError(
            "chunked admission prefill requires an all-SLA layer stack "
            "(mixed full/swa stacks prefill blocking)")
    if sla.col_capacity_factor is not None:
        raise ValueError(
            "chunked admission prefill requires "
            "sla.col_capacity_factor=None: the column-capacity demotion "
            "pass couples query rows, so chunk plan rows could not be "
            "sliced from the full classification bitwise")
    if sla.window or cfg.sliding_window:
        raise ValueError(
            "chunked admission prefill does not support window-"
            "constrained SLA layers")
    if sla.block_q != sla.block_kv:
        raise ValueError(
            f"chunked admission prefill requires block_q == block_kv "
            f"(got {sla.block_q} vs {sla.block_kv})")
    if backend_lib.resolve(backend) not in ("gather", "kernel"):
        raise ValueError(
            f"chunked admission prefill supports backends "
            f"'gather'/'kernel' (got {backend!r})")


def make_prefill_carry(cfg: ArchConfig, bucket: int,
                       compute_dtype=jnp.bfloat16,
                       decode_sla: bool = False) -> dict:
    """Zero-initialized chunked-prefill carry for a (1, bucket) admission.

    Leaves (all stacked (L, ...) so `prefill_chunk` scans them):
      k/v  (L, 1, Hkv, bucket, Dh)  KV written so far (future rows zero)
      qpm  (L, 1, H, Tm, Dh) f32    mean-pooled q per written block row
      kpm  (L, 1, H, Tm, Dh) f32    mean-pooled (GQA-repeated) k per block
      dmc  (L, 1, H, Tm, Tm) int8   decode-grid rows (decode_sla only)
    """
    sla = cfg.sla
    if bucket % sla.block_q:
        raise ValueError(
            f"chunked prefill needs a block-aligned bucket (got {bucket} "
            f"for block_q={sla.block_q})")
    nl, hkv, h, dh = (cfg.num_layers, cfg.num_kv_heads, cfg.num_heads,
                      cfg.head_dim)
    tm = bucket // sla.block_q
    carry = {
        "k": jnp.zeros((nl, 1, hkv, bucket, dh), compute_dtype),
        "v": jnp.zeros((nl, 1, hkv, bucket, dh), compute_dtype),
        "qpm": jnp.zeros((nl, 1, h, tm, dh), jnp.float32),
        "kpm": jnp.zeros((nl, 1, h, tm, dh), jnp.float32),
    }
    if decode_sla:
        carry["dmc"] = jnp.full((nl, 1, h, tm, tm), -1, jnp.int8)
    return carry


def prefill_chunk(params, cfg: ArchConfig, tokens, carry, start,
                  compute_dtype=jnp.bfloat16, backend: str = "gather",
                  decode_max_len: Optional[int] = None):
    """Consume one block-aligned span of prompt tokens against the
    already-prefilled prefix.

    tokens: (1, C) int32, C a multiple of block_q; `start` the span's
    absolute token offset (block-aligned; python int or TRACED int32 —
    traced keeps every chunk index on one compiled graph). Returns
    (new_carry, last_hidden (1, d)) — the final chunk's last hidden
    feeds `logits_from_hidden` for the admission's first token.

    Bitwise contract (tests/test_serving.py chunked-parity suite): after
    the last chunk, carry k/v/dmc equal blocking `prefill`'s caches and
    decode rows bit-for-bit. Per layer the chunk (a) writes its KV and
    pooled q/k rows into the carry, (b) re-scores the FULL block map
    from the pooled carry (`masks.score_map_pooled` — masked-softmax
    rows depend only on columns <= row, all written) and slices its
    rows, (c) replicates `backends.execute`'s glue against the
    full-bucket carried KV (zero-padded future blocks contribute exact
    zeros through the marginal mask), (d) classifies its decode-grid
    rows from the same pooled maps. `decode_max_len` must match the
    value blocking prefill would get (required when carry has "dmc").
    """
    from repro.core import backends as backend_lib
    from repro.core.block_sparse_xla import sla_forward_gather
    from repro.core.phi import phi
    from repro.kernels import ops as kops

    check_chunked_prefill(cfg, backend)
    backend = backend_lib.resolve(backend)
    sla = cfg.sla
    bq = sla.block_q
    b, c = tokens.shape
    if b != 1:
        raise ValueError(f"prefill_chunk takes a batch-1 span (got {b})")
    if c % bq:
        raise ValueError(
            f"chunk length {c} must be a multiple of block_q={bq}")
    bucket = carry["k"].shape[-2]
    tm = bucket // bq
    nb = c // bq
    decode_sla = "dmc" in carry
    if decode_sla and decode_max_len is None:
        raise ValueError(
            "carry tracks decode-grid rows ('dmc') — pass the same "
            "decode_max_len blocking prefill would use")
    plan_cfg = dataclasses.replace(sla, causal=True)
    dcfg = (sla.decode_plan_cfg(decode_max_len // sla.block_kv)
            if decode_sla else None)
    start = jnp.asarray(start, jnp.int32)
    sb = start // bq
    positions = jnp.broadcast_to(
        (start + jnp.arange(c, dtype=jnp.int32))[None, :], (b, c))
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)

    def body(x, layer):
        layer = list(layer)
        p, kc, vc, qpm, kpm = (layer.pop(0), layer.pop(0), layer.pop(0),
                               layer.pop(0), layer.pop(0))
        dmc = layer.pop(0) if decode_sla else None
        xn = rms_norm(x, p["ln1"])
        q, k, v = _qkv(p, xn, cfg, positions)
        kc = jax.lax.dynamic_update_slice_in_dim(
            kc, k.astype(kc.dtype), start, axis=2)
        vc = jax.lax.dynamic_update_slice_in_dim(
            vc, v.astype(vc.dtype), start, axis=2)
        h, hkv = q.shape[1], k.shape[1]
        g = h // hkv
        kr = jnp.repeat(k, g, axis=1) if g > 1 else k
        # pooled-map rows: mean over each block's own tokens only, so
        # chunk-local pooling equals full-prefill pooling bitwise (and
        # repeat/pool commute for the GQA broadcast)
        qpm = jax.lax.dynamic_update_slice_in_dim(
            qpm, masks_lib.pool_blocks(q, bq), sb, axis=2)
        kpm = jax.lax.dynamic_update_slice_in_dim(
            kpm, masks_lib.pool_blocks(kr, sla.block_kv), sb, axis=2)
        routing = p.get("routing") if sla.routing_mode == "learned" \
            else None
        # full-map re-score + slice: rows <= written region are exact
        # (masked softmax rows never read unwritten columns; argsort is
        # per-row with col_capacity None)
        mc = masks_lib.classify_blocks(
            masks_lib.score_map_pooled(routing, qpm, kpm, plan_cfg),
            plan_cfg)
        mc_rows = jax.lax.dynamic_slice_in_dim(mc, sb, nb, axis=2)
        lut, counts = plan_lib.build_lut(mc_rows,
                                         plan_cfg.num_critical(tm))
        # inference-only: the hard indicator is bitwise the forward
        # value of the learned-routing straight-through gates
        marginal = (mc_rows == 0).astype(jnp.float32)
        if decode_sla:
            mcd = masks_lib.classify_blocks(
                masks_lib.score_map_pooled(routing, qpm, kpm, dcfg),
                dcfg)
            dmc = jax.lax.dynamic_update_slice_in_dim(
                dmc, jax.lax.dynamic_slice_in_dim(mcd, sb, nb, axis=2),
                sb, axis=2)
        # chunk attention: replicate backends.execute's glue with the
        # chunk's rows against the full-bucket carry KV
        krf = jnp.repeat(kc, g, axis=1) if g > 1 else kc
        vrf = jnp.repeat(vc, g, axis=1) if g > 1 else vc
        qp, kp = phi(q, sla.phi), phi(krf, sla.phi)
        if backend == "gather":
            rows_plan = plan_lib.SLAPlan(
                mc=mc_rows, lut=lut, counts=counts,
                col_lut=jnp.zeros((b, h, tm, 1), jnp.int32),
                col_counts=jnp.zeros((b, h, tm), jnp.int32),
                marginal=marginal)
            o_s, o_l = sla_forward_gather(q, krf, vrf, qp, kp, rows_plan,
                                          plan_cfg, row_offset=sb)
        else:
            o_s, o_l = kops.sla_attention_rows(
                q, krf, vrf, qp, kp, marginal, lut, counts, plan_cfg,
                row_offset=sb)
        proj = p["sla_proj"].astype(jnp.float32)
        o = (o_s + jnp.einsum("bhnd,hde->bhne", o_l, proj)).astype(x.dtype)
        out = o.transpose(0, 2, 1, 3).reshape(b, c, -1)
        out = jnp.einsum("bse,ed->bsd", out,
                         ctx.fsdp_gather(p["wo"].astype(x.dtype), "row"))
        x = ctx.shard_residual(x + ctx.shard_residual(out))
        f, _ = _ffn(p, rms_norm(x, p["ln2"]), cfg)
        x = ctx.shard_residual(x + ctx.shard_residual(f))
        ys = (kc, vc, qpm, kpm) + ((dmc,) if decode_sla else ())
        return x, ys

    xs = (params["layers"], carry["k"], carry["v"], carry["qpm"],
          carry["kpm"])
    if decode_sla:
        xs = xs + (carry["dmc"],)
    x, ys = jax.lax.scan(body, x, xs)
    new_carry = {"k": ys[0], "v": ys[1], "qpm": ys[2], "kpm": ys[3]}
    if decode_sla:
        new_carry["dmc"] = ys[4]
    x = rms_norm(x, params["ln_f"])
    return new_carry, x[:, -1]


def finalize_chunked_prefill(cfg: ArchConfig, carry,
                             decode_max_len: Optional[int] = None) -> dict:
    """Chunked-prefill carry -> the cache dict blocking `prefill`
    returns. Deliberately mirrors `prefill`'s tail exactly — the decode
    state is rebuilt with `_seed_decode_state` (`plan_from_mask` on the
    full carried rows), NOT `plan_extend`, because the incremental path
    leaves stale values in dead col_lut padding slots and the serving
    bitwise bar covers every cache leaf."""
    kc, vc = carry["k"], carry["v"]
    bucket = kc.shape[-2]
    cache = {"k": kc, "v": vc, "pos": jnp.int32(bucket)}
    if decode_max_len is not None:
        _check_decode_grid(cfg, bucket, decode_max_len)
        cache["sla"] = _seed_decode_state(cfg, kc, vc, carry["dmc"],
                                          decode_max_len)
        grow = decode_max_len - bucket
        if grow > 0:
            pad = [(0, 0)] * 3 + [(0, grow), (0, 0)]
            cache["k"] = jnp.pad(kc, pad)
            cache["v"] = jnp.pad(vc, pad)
    return cache


def _dense_decode_attn(q, kc, vc, pos, kind, cfg: ArchConfig):
    """Masked softmax over the full static cache — O(S) per token.

    q: (B, H, 1, Dh); kc, vc: (B, Hkv, Smax, Dh); pos: scalar (aligned
    static-batch decode) or (B,) per-slot positions (continuous
    batching). GQA decode without materializing repeated KV: fold the
    head group into the query ("bkgd" layout) — scores are
    (B, Hkv, G, S) against the cache directly. Returns (B, 1, H * Dh)
    in q.dtype."""
    b, h = q.shape[0], q.shape[1]
    hkv, smax = kc.shape[1], kc.shape[2]
    g = h // hkv
    qg = q[:, :, 0, :].reshape(b, hkv, g, cfg.head_dim)
    s = jnp.einsum("bkgd,bksd->bkgs", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) * (cfg.head_dim**-0.5)
    idx = jnp.arange(smax)[None, None, None, :]
    posb = pos if jnp.ndim(pos) == 0 else pos[:, None, None, None]
    ok = idx <= posb

    def swa_mask(s):
        w = cfg.local_window or cfg.sliding_window
        return jnp.where(idx > posb - w, s, NEG_INF)

    s = jnp.where(ok, s, NEG_INF)
    s = jax.lax.cond(kind == KIND_SWA, swa_mask, lambda s: s, s)
    p_attn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p_attn, vc.astype(jnp.float32))
    return o.astype(q.dtype).reshape(b, 1, h * cfg.head_dim)


def _cache_write(c, new, pos):
    """Write one new-token KV at `pos`: c (B, Hn, S, D), new (B, Hn, 1, D).

    Scalar `pos` is the aligned static-batch O(1) write; a (B,) vector
    writes each slot at its own position (vmapped per-example update —
    the continuous-batching layout, DESIGN.md "Serving API v2")."""
    new = new.astype(c.dtype)
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice_in_dim(c, new, pos, axis=2)
    return jax.vmap(lambda cb, nb, pb: jax.lax.dynamic_update_slice_in_dim(
        cb, nb, pb, axis=1))(c, new, pos)


def _blk_update(buf, upd, row):
    """Add `upd` into block `row` of a per-block running buffer.

    buf: (B, Hn, Tn, ...); upd: (B, Hn, ...); row: scalar or (B,)."""
    if jnp.ndim(row) == 0:
        j = jax.lax.dynamic_slice_in_dim(buf, row, 1, axis=2)
        return jax.lax.dynamic_update_slice_in_dim(
            buf, j + upd[:, :, None], row, axis=2)

    def one(bb, ub, rb):
        j = jax.lax.dynamic_slice_in_dim(bb, rb, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            bb, j + ub[:, None], rb, axis=1)

    return jax.vmap(one)(buf, upd, row)


def _page_gather(pool, pt):
    """Per-layer page pool (P, Hkv, ...) -> per-slot block view
    (B, Hkv, Tn, ...) through the page table pt (B, Tn) int32."""
    return jnp.moveaxis(jnp.take(pool, pt, axis=0), 2, 1)


def _page_gather_kv(pool, pt):
    """KV page pool (P, Hkv, bkv, Dh) -> the contiguous (B, Hkv, S, Dh)
    cache view a monolithic per-slot cache would hold."""
    g = _page_gather(pool, pt)                  # (B, Hkv, Tn, bkv, Dh)
    return g.reshape(g.shape[:2] + (g.shape[2] * g.shape[3], g.shape[4]))


def _page_write_kv(pool, new, pid, off):
    """Write one new-token KV into its page: pool (P, Hkv, bkv, Dh),
    new (B, Hkv, 1, Dh), pid/off (B,). Page-table invariant (enforced
    by the scheduler's copy-on-write pass): every active slot's write
    page is privately owned, so the pids are distinct and the scatter
    is conflict-free."""
    return pool.at[pid, :, off].set(new[:, :, 0, :].astype(pool.dtype))


def decode_step(params, cfg: ArchConfig, token, cache,
                compute_dtype=jnp.bfloat16, backend: str = "gather",
                drift_threshold=None):
    """One decode step. token: (B,) int32; cache k/v: (L, B, Hkv, S, Dh);
    cache['pos'] is a scalar (static-batch serving, aligned sequences)
    or a (B,) vector of per-slot positions (continuous batching —
    every slot advances through its own sequence independently).

    The new KV is written at `pos` via dynamic_update_slice (O(1)
    write; vmapped per slot under vector positions). Attention: caches
    made with `prefill(decode_max_len=)` or `make_cache(decode_sla=True)`
    carry decode-SLA state and run incremental-plan SLA decode
    (`_decode_step_sla`); otherwise dense masked attention over the
    full static cache (O(S) per token — exactly the decode_* cells'
    old cost model).

    Paged caches (`make_paged_cache`, DESIGN.md "Paged KV & prefix
    caching") carry `kp`/`vp` page pools plus a `pt` page table instead
    of monolithic k/v; the same step math runs against page-gathered
    views, so paged and monolithic decode are bitwise identical.
    """
    if "sla" in cache:
        return _decode_step_sla(params, cfg, token, cache, compute_dtype,
                                backend, drift_threshold)
    paged = "kp" in cache
    emb = params["embed"]
    x = jnp.take(emb, token[:, None], axis=0).astype(compute_dtype)
    b = x.shape[0]
    pos = cache["pos"]  # scalar or (B,) int32
    positions = jnp.broadcast_to(pos, (b,))[:, None]
    kinds = layer_kinds(cfg)
    if paged:
        pt = cache["pt"]
        bkv = cache["kp"].shape[3]
        tn = pt.shape[1]
        # runaway inactive slots clamp onto their scratch page
        wpid = pt[jnp.arange(b), jnp.minimum(pos // bkv, tn - 1)]
        woff = pos % bkv

    def body(x, layer):
        p, kind, kc, vc = layer
        xn = rms_norm(x, p["ln1"])
        q, k_new, v_new = _qkv(p, xn, cfg, positions)
        if paged:
            kc = _page_write_kv(kc, k_new, wpid, woff)
            vc = _page_write_kv(vc, v_new, wpid, woff)
            kd, vd = _page_gather_kv(kc, pt), _page_gather_kv(vc, pt)
        else:
            kc = _cache_write(kc, k_new, pos)
            vc = _cache_write(vc, v_new, pos)
            kd, vd = kc, vc
        o = _dense_decode_attn(q, kd, vd, pos, kind, cfg)
        x = x + jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype))
        f, _ = _ffn(p, rms_norm(x, p["ln2"]), cfg)
        return x + f, (kc, vc)

    kv_in = (cache["kp"], cache["vp"]) if paged else (cache["k"], cache["v"])
    x, (kc, vc) = jax.lax.scan(body, x, (params["layers"], kinds) + kv_in)
    x = rms_norm(x, params["ln_f"])
    logits = logits_from_hidden(params, x[:, 0])
    if paged:
        new_cache = {"kp": kc, "vp": vc, "pt": pt, "pos": pos + 1}
    else:
        new_cache = {"k": kc, "v": vc, "pos": pos + 1}
    return logits, new_cache


def _decode_step_sla(params, cfg: ArchConfig, token, cache, compute_dtype,
                     backend: str, drift_threshold=None):
    """Decode-time SLA step (DESIGN.md "Decode-time SLA").

    Per token: O(1) running-state update (phi(k) v^T into the current
    block's h/z partials and totals), then attention over only the live
    row's critical KV blocks plus the O(1) subtractive linear branch —
    per-step attention cost is critical-blocks + O(1) instead of O(S).

    Incremental plan maintenance happens at block boundaries
    (pos % b_q == 0): the just-completed row is classified from its
    full pooled q and appended with `plan_extend` ("extend"), and the
    new live row's structure is drift-gated per layer — inherit the
    previous row's critical set (+ forced diagonal, SLA2-style reuse,
    "reuse") unless its drift against a fresh classification from the
    first token's q reaches that layer's threshold ("replan").
    Boundary quantities are computed unconditionally and selected with
    `where` — they are O(Tn) block-level ops, noise next to the
    attention itself — which keeps the step a single static-shape jit.
    The exception is row *scoring* (and only it): the learned routing
    head projects the whole pooled-K cache (O(Tn d^2) per head), so
    both score_row calls sit under `lax.cond(boundary, ...)` — the
    amortized-per-boundary cost `flops.sla_decode_flops` reports.

    Per-slot positions (DESIGN.md "Serving API v2"): a (B,) `pos`
    vector runs every piece of the above per slot — each slot crosses
    its own block boundaries, appends its own plan rows, and makes its
    own drift decision (min over ITS heads only, where the aligned
    scalar-pos batch keeps the historical min-over-batch decision).
    Boundary scoring then runs whenever ANY slot is at a boundary
    (`lax.cond(jnp.any(boundary))`), so the amortized-cost claim
    holds per slot on average but individual steps may pay it for a
    single slot. Plan/state counters become per-slot (L, B) arrays.

    Paged caches (DESIGN.md "Paged KV & prefix caching") swap the
    monolithic per-slot k/v/hblk/zblk/kpool for global page pools
    indexed by the `pt` page table; every read goes through a
    page-gathered view that is value-identical to the monolithic
    layout, and every write lands in the slot's (privately owned)
    current page — so the step stays bitwise equal to unpaged decode.
    """
    from repro.core import backends as backend_lib
    from repro.core.phi import phi

    backend_lib.resolve_decode(backend)
    paged = "kp" in cache
    emb = params["embed"]
    x = jnp.take(emb, token[:, None], axis=0).astype(compute_dtype)
    b = x.shape[0]
    pos = cache["pos"]
    vec = jnp.ndim(pos) > 0  # per-slot positions (continuous batching)
    if paged and not vec:
        raise ValueError("paged decode requires per-slot (B,) positions")
    st = cache["sla"]
    sla = cfg.sla
    bq = sla.block_q
    if paged:
        pt = cache["pt"]
        tn = pt.shape[1]
        smax = tn * sla.block_kv
        # runaway inactive slots clamp onto their scratch page
        wpid = pt[jnp.arange(b), jnp.minimum(pos // sla.block_kv, tn - 1)]
        woff = pos % sla.block_kv
    else:
        smax = cache["k"].shape[-2]
        tn = smax // sla.block_kv
    dcfg = sla.decode_plan_cfg(tn)
    kinds = layer_kinds(cfg)
    used = sorted(set(layer_kinds_list(cfg)))
    if drift_threshold is None:
        thresholds = jnp.asarray(sla.drift_thresholds(cfg.num_layers),
                                 jnp.float32)
    else:
        thresholds = jnp.broadcast_to(
            jnp.asarray(drift_threshold, jnp.float32), (cfg.num_layers,))

    row = pos // bq                      # current (partial) query row(s)
    boundary = (pos % bq) == 0           # block(s) just completed
    any_boundary = jnp.any(boundary)
    append = jnp.logical_and(boundary, st["rows"] < row)
    rowm = row[:, None] if vec else row  # row arg for masks_lib helpers
    positions = jnp.broadcast_to(pos, (b,))[:, None]
    blk = jnp.arange(tn)
    # tokens per KV block AFTER this step's write (for pooled-k means)
    posx = pos[:, None] if vec else pos
    blk_cnt = jnp.clip(jnp.minimum((posx + 1) - blk * sla.block_kv,
                                   sla.block_kv), 1, sla.block_kv)
    # shaped to divide kp_sum (B, Hkv, Tn, D)
    cnt_div = blk_cnt[:, None, :, None] if vec else blk_cnt[:, None]

    def bsel(m, a, o):
        """where(m, a, o) with m a scalar bool or a per-slot (B,) bool."""
        mm = m if jnp.ndim(m) == 0 else m.reshape((b,) + (1,) * (a.ndim - 1))
        return jnp.where(mm, a, o)

    def body(x, layer):
        (p, kind, thr, kc, vc, hb, zb, ht, zt, kp_sum, qp_sum, plan,
         llut, lcnt, lmarg, ret_prev) = layer
        xn = rms_norm(x, p["ln1"])
        q, k_new, v_new = _qkv(p, xn, cfg, positions)
        if paged:
            kc = _page_write_kv(kc, k_new, wpid, woff)
            vc = _page_write_kv(vc, v_new, wpid, woff)
        else:
            kc = _cache_write(kc, k_new, pos)
            vc = _cache_write(vc, v_new, pos)
        h, hkv = q.shape[1], k_new.shape[1]
        g = h // hkv
        qf = q[:, :, 0, :].astype(jnp.float32)       # (B, H, D)
        kf = k_new[:, :, 0, :].astype(jnp.float32)   # (B, Hkv, D)
        vf = v_new[:, :, 0, :].astype(jnp.float32)

        # same row scorer as prefill (learned routing included), so
        # decode rows classify exactly as the one-shot classifier would.
        # Scoring runs under lax.cond on the block boundary: it is the
        # one boundary quantity whose cost is NOT O(Tn) block-level
        # noise (the learned head projects the whole pooled-K cache,
        # O(Tn d^2) per head), and flops.sla_decode_flops amortizes it
        # by /b_q — the cond makes that accounting true.
        routing = p.get("routing") if dcfg.routing_mode == "learned" \
            else None
        pc_zeros = jnp.zeros(qf.shape[:2] + (tn,), jnp.float32)

        # ---- 1. finalize the just-completed row (uses the PRE-update
        # kpool: the completed row cannot see the current block) ----
        kp_view = _page_gather(kp_sum, pt) if paged else kp_sum
        kpool_mean = kp_view / sla.block_kv
        kpm = jnp.repeat(kpool_mean, g, axis=1)      # (B, H, Tn, D)
        pc_prev = jax.lax.cond(
            any_boundary,
            lambda _: masks_lib.score_row(routing, qp_sum / bq, kpm,
                                          rowm - 1, dcfg),
            lambda _: pc_zeros, None)
        mc_prev = masks_lib.classify_row(pc_prev, rowm - 1, dcfg)
        if vec:
            ext = jax.vmap(plan_lib.plan_extend)(plan, mc_prev, row - 1)
        else:
            ext = plan_lib.plan_extend(plan, mc_prev, row - 1)
        plan = jax.tree_util.tree_map(
            lambda a, o: bsel(append, a, o), ext, plan)

        # ---- 2. O(1) running-state update for the new token ----
        phik = phi(kf, sla.phi)                      # (B, Hkv, D) f32
        hupd = jnp.einsum("bkd,bke->bkde", phik, vf)
        if paged:
            # distinct private write pages -> conflict-free update; the
            # gather/add/set form (not scatter-add) mirrors the
            # monolithic slice/add/write so XLA fuses the phi-derived
            # update identically and the partials stay BITWISE equal
            hb = hb.at[wpid].set(hb[wpid] + hupd)
            zb = zb.at[wpid].set(zb[wpid] + phik)
            kp_sum = kp_sum.at[wpid].set(kp_sum[wpid] + kf)
        else:
            hb = _blk_update(hb, hupd, row)
            zb = _blk_update(zb, phik, row)
            kp_sum = _blk_update(kp_sum, kf, row)
        ht = ht + hupd
        zt = zt + phik

        # ---- 3. live-row structure (boundary only): drift-gated
        # inherit-vs-fresh, per-layer threshold ----
        kp_view = _page_gather(kp_sum, pt) if paged else kp_sum
        kpm_live = jnp.repeat(kp_view / cnt_div, g, axis=1)
        pc_live = jax.lax.cond(
            any_boundary,
            lambda _: masks_lib.score_row(routing, qf, kpm_live, rowm,
                                          dcfg),
            lambda _: pc_zeros, None)
        mc_fresh = masks_lib.classify_row(pc_live, rowm, dcfg)
        if vec:
            mc_inh = jax.vmap(lambda m, r: jax.lax.dynamic_slice_in_dim(
                m, r, 1, axis=1)[:, 0, :])(plan.mc, row - 1)
            diag = (blk[None, :] == row[:, None])[:, None, :]
        else:
            mc_inh = jax.lax.dynamic_slice_in_dim(
                plan.mc, row - 1, 1, axis=2)[..., 0, :]  # (B, H, Tn)
            diag = blk == row
        mc_inh = jnp.where(diag, jnp.int8(1), mc_inh)
        stale = jnp.sum(pc_live * (mc_inh == 1), axis=-1)
        fresh = jnp.sum(pc_live * (mc_fresh == 1), axis=-1)
        r = jnp.clip(stale / jnp.maximum(fresh, plan_lib.EPS), 0.0, 1.0)
        if vec:
            # per-slot decision: each slot's own heads gate its row
            retention = jnp.min(r, axis=1)                      # (B,)
            replan = jnp.logical_and((1.0 - retention) >= thr,
                                     thr < 1.0)
            rep_m = replan[:, None, None]
        else:
            # aligned static batch: one decision for every row
            retention = jnp.min(r)
            replan = jnp.logical_and((1.0 - retention) >= thr,
                                     thr < 1.0)
            rep_m = replan
        mc_live = jnp.where(rep_m, mc_fresh, mc_inh)
        llut_n, lcnt_n = plan_lib.build_lut(mc_live[..., None, :],
                                            plan.k_sel)
        llut = bsel(boundary, llut_n[..., 0, :], llut)
        lcnt = bsel(boundary, lcnt_n[..., 0], lcnt)
        lmarg = bsel(boundary,
                     jnp.sum((mc_live == 0).astype(jnp.int32), -1),
                     lmarg)

        # ---- 4. attention: critical blocks + O(1) linear state ----
        state = {"k": kc, "v": vc, "hblk": hb, "zblk": zb, "htot": ht,
                 "ztot": zt, "lut": llut, "cnt": lcnt, "marg": lmarg}
        if paged:
            state["pt"] = pt

        def do_sla(_):
            return backend_lib.decode_execute(
                state, {"proj": p["sla_proj"]}, q, pos, dcfg,
                backend=backend).reshape(b, 1, h * cfg.head_dim) \
                .astype(x.dtype)

        def do_dense(_):
            if paged:
                return _dense_decode_attn(q, _page_gather_kv(kc, pt),
                                          _page_gather_kv(vc, pt), pos,
                                          kind, cfg)
            return _dense_decode_attn(q, kc, vc, pos, kind, cfg)

        if used == [KIND_SLA]:
            o = do_sla(None)
        else:
            o = jax.lax.cond(kind == KIND_SLA, do_sla, do_dense, None)
        x2 = x + jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype))
        f, _ = _ffn(p, rms_norm(x2, p["ln2"]), cfg)
        qp_sum = bsel(boundary, qf, qp_sum + qf)
        ys = (kc, vc, hb, zb, ht, zt, kp_sum, qp_sum, plan, llut, lcnt,
              lmarg, append.astype(jnp.int32),
              jnp.logical_and(boundary, replan).astype(jnp.int32),
              jnp.logical_and(boundary, ~replan).astype(jnp.int32),
              jnp.where(boundary, retention, ret_prev))
        return x2 + f, ys

    if paged:
        slap = cache["slap"]
        xs = (params["layers"], kinds, thresholds, cache["kp"],
              cache["vp"], slap["hblk"], slap["zblk"], st["htot"],
              st["ztot"], slap["kpool"], st["qpool"], st["plan"],
              st["live_lut"], st["live_cnt"], st["live_marg"],
              st["retention"])
    else:
        xs = (params["layers"], kinds, thresholds, cache["k"], cache["v"],
              st["hblk"], st["zblk"], st["htot"], st["ztot"], st["kpool"],
              st["qpool"], st["plan"], st["live_lut"], st["live_cnt"],
              st["live_marg"], st["retention"])
    x, ys = jax.lax.scan(body, x, xs)
    (kc, vc, hb, zb, ht, zt, kp_sum, qp_sum, plan, llut, lcnt, lmarg,
     exts, reps, reuses, rets) = ys
    x = rms_norm(x, params["ln_f"])
    logits = logits_from_hidden(params, x[:, 0])
    new_st = {
        "htot": ht, "ztot": zt,
        "qpool": qp_sum, "plan": plan, "rows": st["rows"] + append,
        "live_lut": llut, "live_cnt": lcnt, "live_marg": lmarg,
        "extends": st["extends"] + exts, "replans": st["replans"] + reps,
        "reuses": st["reuses"] + reuses, "retention": rets,
    }
    if paged:
        return logits, {"kp": kc, "vp": vc, "pt": pt, "pos": pos + 1,
                        "slap": {"hblk": hb, "zblk": zb, "kpool": kp_sum},
                        "sla": new_st}
    new_st.update({"hblk": hb, "zblk": zb, "kpool": kp_sum})
    return logits, {"k": kc, "v": vc, "pos": pos + 1, "sla": new_st}


def _dense_decode_chunk_attn(q, kc, vc, pos_c, kind, cfg: ArchConfig):
    """Chunked `_dense_decode_attn`: q (B, H, C, Dh) against the full
    static cache, token c masked to columns <= pos_c[c]. Returns
    (B, C, H * Dh) in q.dtype."""
    b, h, cdim = q.shape[0], q.shape[1], q.shape[2]
    hkv, smax = kc.shape[1], kc.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, cdim, cfg.head_dim)
    s = jnp.einsum("bkgcd,bksd->bkgcs", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) * (cfg.head_dim**-0.5)
    idx = jnp.arange(smax)
    ok = idx[None, :] <= pos_c[:, None]                  # (C, S)

    def swa_mask(s):
        w = cfg.local_window or cfg.sliding_window
        return jnp.where(idx[None, :] > pos_c[:, None] - w, s, NEG_INF)

    s = jnp.where(ok, s, NEG_INF)
    s = jax.lax.cond(kind == KIND_SWA, swa_mask, lambda s: s, s)
    p_attn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgcs,bksd->bkgcd", p_attn, vc.astype(jnp.float32))
    return (o.astype(q.dtype).transpose(0, 3, 1, 2, 4)
            .reshape(b, cdim, h * cfg.head_dim))


def decode_chunk(params, cfg: ArchConfig, tokens, cache,
                 compute_dtype=jnp.bfloat16, backend: str = "gather",
                 drift_threshold=None, chunk: Optional[int] = None):
    """Score a chunk of C given tokens against the cache in one pass
    (verify-style multi-token decode, for speculative drafts).

    tokens: (B, C) int32. Returns (logits (B, C, V) f32, new_cache):
    logits[:, c] are the next-token logits after consuming
    tokens[:, :c + 1] — the values C successive `decode_step` calls
    produce — and new_cache is the state after all C tokens. One
    attention launch per layer covers the whole chunk (per-token plan
    rows ride the kernel's scalar-prefetch LUT; see
    `backends.decode_execute_chunk`), and the O(1) H/Z running-state
    updates plus `plan_extend` boundary work fold into a single scanned
    update per layer instead of C jit steps — launch and
    boundary-scoring overhead amortize C-fold.

    `chunk=` splits a longer token run into sub-chunks of that size
    (a python loop over at most two compiled shapes). Requires a scalar
    `cache['pos']` (aligned static batch); the continuous-batching
    scheduler decodes per token.
    """
    if jnp.ndim(cache["pos"]) > 0:
        raise ValueError(
            "decode_chunk requires a scalar cache['pos'] (aligned "
            "static-batch decode); per-slot continuous batching decodes "
            "one token at a time via decode_step")
    cdim = tokens.shape[1]
    if chunk is not None and cdim > chunk:
        outs = []
        for lo in range(0, cdim, chunk):
            logits, cache = decode_chunk(
                params, cfg, tokens[:, lo:lo + chunk], cache,
                compute_dtype, backend, drift_threshold)
            outs.append(logits)
        return jnp.concatenate(outs, axis=1), cache
    if "sla" in cache:
        return _decode_chunk_sla(params, cfg, tokens, cache, compute_dtype,
                                 backend, drift_threshold)
    emb = params["embed"]
    x = jnp.take(emb, tokens, axis=0).astype(compute_dtype)
    b = x.shape[0]
    pos = cache["pos"]
    pos_c = pos + jnp.arange(cdim, dtype=jnp.int32)
    positions = jnp.broadcast_to(pos_c, (b, cdim))
    kinds = layer_kinds(cfg)

    def body(x, layer):
        p, kind, kc, vc = layer
        xn = rms_norm(x, p["ln1"])
        q, k_new, v_new = _qkv(p, xn, cfg, positions)
        kc = jax.lax.dynamic_update_slice_in_dim(
            kc, k_new.astype(kc.dtype), pos, axis=2)
        vc = jax.lax.dynamic_update_slice_in_dim(
            vc, v_new.astype(vc.dtype), pos, axis=2)
        o = _dense_decode_chunk_attn(q, kc, vc, pos_c, kind, cfg)
        x = x + jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype))
        f, _ = _ffn(p, rms_norm(x, p["ln2"]), cfg)
        return x + f, (kc, vc)

    x, (kc, vc) = jax.lax.scan(
        body, x, (params["layers"], kinds, cache["k"], cache["v"]))
    x = rms_norm(x, params["ln_f"])
    logits = logits_from_hidden(params, x)
    return logits, {"k": kc, "v": vc, "pos": pos + cdim}


def _decode_chunk_sla(params, cfg: ArchConfig, tokens, cache, compute_dtype,
                      backend: str, drift_threshold=None):
    """Chunked decode-time SLA (ISSUE 6 tentpole, multi-token decode).

    Per layer: one inner `lax.scan` over the C tokens replays
    `_decode_step_sla`'s boundary/state phases 1-3 op-for-op (so the
    final cache state is bitwise the per-token state), emitting each
    token's live plan row (lut/cnt/marg) and its at-time-c H/Z totals;
    then ONE chunked attention call covers all C tokens.

    Snapshot protocol (why one end-of-chunk hblk suffices): token c's
    marginal set contains only completed blocks j < row_c, and no later
    chunk token writes those (tokens only write their own row, which is
    >= row_c) — so end-of-chunk hblk/zblk are already the at-time-c
    values for every marginal block. The one exception is the forced
    critical diagonal block row_c, still accumulating inside the chunk;
    the scan emits its at-time partial per token (state["hdiag"] /
    ["zdiag"]) and the kernel substitutes it for the streamed block at
    the LUT's diagonal entry — every term in H_marg = htot_c -
    sum_lut h_c[j] is then the per-token value in the per-token order,
    so chunked logits match the per-token ones bitwise. The sparse
    branch needs no protocol at all: the chunk's KV is written before
    attention and token c masks columns > pos + c.
    """
    from repro.core import backends as backend_lib
    from repro.core.phi import phi

    backend_lib.resolve_decode(backend)
    emb = params["embed"]
    x = jnp.take(emb, tokens, axis=0).astype(compute_dtype)
    b, cdim = tokens.shape
    pos = cache["pos"]
    st = cache["sla"]
    sla = cfg.sla
    bq = sla.block_q
    smax = cache["k"].shape[-2]
    tn = smax // sla.block_kv
    dcfg = sla.decode_plan_cfg(tn)
    kinds = layer_kinds(cfg)
    used = sorted(set(layer_kinds_list(cfg)))
    if drift_threshold is None:
        thresholds = jnp.asarray(sla.drift_thresholds(cfg.num_layers),
                                 jnp.float32)
    else:
        thresholds = jnp.broadcast_to(
            jnp.asarray(drift_threshold, jnp.float32), (cfg.num_layers,))

    offs = jnp.arange(cdim, dtype=jnp.int32)
    pos_c = pos + offs                       # (C,) per-token positions
    row_c = pos_c // bq
    boundary_c = (pos_c % bq) == 0
    positions = jnp.broadcast_to(pos_c, (b, cdim))
    blk = jnp.arange(tn)
    blk_cnt_c = jnp.clip(jnp.minimum(
        (pos_c[:, None] + 1) - blk * sla.block_kv, sla.block_kv),
        1, sla.block_kv)                     # (C, Tn)

    # rows bookkeeping is layer-independent: replay the append decisions
    def rows_scan(rows, cc):
        app = jnp.logical_and(boundary_c[cc], rows < row_c[cc])
        return rows + app.astype(jnp.int32), app

    rows_after, append_c = jax.lax.scan(rows_scan, st["rows"], offs)

    def body(x, layer):
        (p, kind, thr, kc, vc, hb, zb, ht, zt, kp_sum, qp_sum, plan,
         llut, lcnt, lmarg, ret_prev) = layer
        xn = rms_norm(x, p["ln1"])
        q, k_new, v_new = _qkv(p, xn, cfg, positions)   # q (B, H, C, D)
        kc = jax.lax.dynamic_update_slice_in_dim(
            kc, k_new.astype(kc.dtype), pos, axis=2)
        vc = jax.lax.dynamic_update_slice_in_dim(
            vc, v_new.astype(vc.dtype), pos, axis=2)
        h, hkv = q.shape[1], k_new.shape[1]
        g = h // hkv
        qf = q.astype(jnp.float32)                      # (B, H, C, D)
        kf = k_new.astype(jnp.float32)                  # (B, Hkv, C, D)
        vf = v_new.astype(jnp.float32)
        phik = phi(kf, sla.phi)
        routing = p.get("routing") if dcfg.routing_mode == "learned" \
            else None
        pc_zeros = jnp.zeros((b, h, tn), jnp.float32)

        def tok(carry, cc):
            (hb, zb, ht, zt, kp_sum, qp_sum, plan, llut, lcnt, lmarg,
             ret) = carry
            rowc, bnd = row_c[cc], boundary_c[cc]
            app = append_c[cc]
            qf_c, kf_c, vf_c = qf[:, :, cc], kf[:, :, cc], vf[:, :, cc]
            phik_c = phik[:, :, cc]
            # ---- 1. finalize the just-completed row (PRE-update kpool)
            kpm = jnp.repeat(kp_sum / sla.block_kv, g, axis=1)
            pc_prev = jax.lax.cond(
                bnd,
                lambda _: masks_lib.score_row(routing, qp_sum / bq, kpm,
                                              rowc - 1, dcfg),
                lambda _: pc_zeros, None)
            mc_prev = masks_lib.classify_row(pc_prev, rowc - 1, dcfg)
            ext = plan_lib.plan_extend(plan, mc_prev, rowc - 1)
            plan = jax.tree_util.tree_map(
                lambda a, o: jnp.where(app, a, o), ext, plan)
            # ---- 2. O(1) running-state update ----
            hupd = jnp.einsum("bkd,bke->bkde", phik_c, vf_c)
            hb = _blk_update(hb, hupd, rowc)
            zb = _blk_update(zb, phik_c, rowc)
            ht = ht + hupd
            zt = zt + phik_c
            kp_sum = _blk_update(kp_sum, kf_c, rowc)
            hdiag = jax.lax.dynamic_slice_in_dim(hb, rowc, 1,
                                                 axis=2)[:, :, 0]
            zdiag = jax.lax.dynamic_slice_in_dim(zb, rowc, 1,
                                                 axis=2)[:, :, 0]
            # ---- 3. live-row structure (boundary only) ----
            cnt_div = blk_cnt_c[cc][:, None]
            kpm_live = jnp.repeat(kp_sum / cnt_div, g, axis=1)
            pc_live = jax.lax.cond(
                bnd,
                lambda _: masks_lib.score_row(routing, qf_c, kpm_live,
                                              rowc, dcfg),
                lambda _: pc_zeros, None)
            mc_fresh = masks_lib.classify_row(pc_live, rowc, dcfg)
            mc_inh = jax.lax.dynamic_slice_in_dim(
                plan.mc, rowc - 1, 1, axis=2)[..., 0, :]
            mc_inh = jnp.where(blk == rowc, jnp.int8(1), mc_inh)
            stale = jnp.sum(pc_live * (mc_inh == 1), axis=-1)
            fresh = jnp.sum(pc_live * (mc_fresh == 1), axis=-1)
            r = jnp.clip(stale / jnp.maximum(fresh, plan_lib.EPS),
                         0.0, 1.0)
            retention = jnp.min(r)
            replan = jnp.logical_and((1.0 - retention) >= thr, thr < 1.0)
            mc_live = jnp.where(replan, mc_fresh, mc_inh)
            llut_n, lcnt_n = plan_lib.build_lut(mc_live[..., None, :],
                                                plan.k_sel)
            llut = jnp.where(bnd, llut_n[..., 0, :], llut)
            lcnt = jnp.where(bnd, lcnt_n[..., 0], lcnt)
            lmarg = jnp.where(bnd,
                              jnp.sum((mc_live == 0).astype(jnp.int32), -1),
                              lmarg)
            qp_sum = jnp.where(bnd, qf_c, qp_sum + qf_c)
            ret = jnp.where(bnd, retention, ret)
            carry = (hb, zb, ht, zt, kp_sum, qp_sum, plan, llut, lcnt,
                     lmarg, ret)
            ys = (llut, lcnt, lmarg, ht, zt, hdiag, zdiag,
                  jnp.logical_and(bnd, replan).astype(jnp.int32),
                  jnp.logical_and(bnd, ~replan).astype(jnp.int32))
            return carry, ys

        carry0 = (hb, zb, ht, zt, kp_sum, qp_sum, plan, llut, lcnt, lmarg,
                  ret_prev)
        carryn, tys = jax.lax.scan(tok, carry0, offs)
        (hb, zb, ht, zt, kp_sum, qp_sum, plan, llut, lcnt, lmarg,
         ret) = carryn
        (llut_t, lcnt_t, lmarg_t, ht_t, zt_t, hdiag_t, zdiag_t, reps_t,
         reuse_t) = tys
        # per-token plan rows / totals: scan axis (C) -> chunk axis
        lut_ct = jnp.moveaxis(llut_t, 0, 2)             # (B, H, C, K)
        cnt_ct = jnp.moveaxis(lcnt_t, 0, 2)             # (B, H, C)
        marg_ct = jnp.moveaxis(lmarg_t, 0, 2)
        ht_ct = jnp.moveaxis(ht_t, 0, 2)                # (B, Hkv, C, D, D)
        zt_ct = jnp.moveaxis(zt_t, 0, 2)

        # ---- 4. attention: one chunked launch over C tokens ----
        state = {"k": kc, "v": vc, "hblk": hb, "zblk": zb,
                 "hdiag": jnp.moveaxis(hdiag_t, 0, 2),
                 "zdiag": jnp.moveaxis(zdiag_t, 0, 2),
                 "htot": ht_ct, "ztot": zt_ct,
                 "lut": lut_ct, "cnt": cnt_ct, "marg": marg_ct}

        def do_sla(_):
            return backend_lib.decode_execute_chunk(
                state, {"proj": p["sla_proj"]}, q, pos, dcfg,
                backend=backend).transpose(0, 2, 1, 3) \
                .reshape(b, cdim, h * cfg.head_dim).astype(x.dtype)

        def do_dense(_):
            return _dense_decode_chunk_attn(q, kc, vc, pos_c, kind, cfg)

        if used == [KIND_SLA]:
            o = do_sla(None)
        else:
            o = jax.lax.cond(kind == KIND_SLA, do_sla, do_dense, None)
        x2 = x + jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype))
        f, _ = _ffn(p, rms_norm(x2, p["ln2"]), cfg)
        ys = (kc, vc, hb, zb, ht, zt, kp_sum, qp_sum, plan, llut, lcnt,
              lmarg, jnp.sum(append_c.astype(jnp.int32)),
              jnp.sum(reps_t), jnp.sum(reuse_t), ret)
        return x2 + f, ys

    xs = (params["layers"], kinds, thresholds, cache["k"], cache["v"],
          st["hblk"], st["zblk"], st["htot"], st["ztot"], st["kpool"],
          st["qpool"], st["plan"], st["live_lut"], st["live_cnt"],
          st["live_marg"], st["retention"])
    x, ys = jax.lax.scan(body, x, xs)
    (kc, vc, hb, zb, ht, zt, kp_sum, qp_sum, plan, llut, lcnt, lmarg,
     exts, reps, reuses, rets) = ys
    x = rms_norm(x, params["ln_f"])
    logits = logits_from_hidden(params, x)
    new_st = {
        "hblk": hb, "zblk": zb, "htot": ht, "ztot": zt, "kpool": kp_sum,
        "qpool": qp_sum, "plan": plan, "rows": rows_after,
        "live_lut": llut, "live_cnt": lcnt, "live_marg": lmarg,
        "extends": st["extends"] + exts, "replans": st["replans"] + reps,
        "reuses": st["reuses"] + reuses, "retention": rets,
    }
    return logits, {"k": kc, "v": vc, "pos": pos + cdim, "sla": new_st}


def make_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16,
               decode_sla: Optional[bool] = None,
               per_slot: bool = False) -> dict:
    """Empty decode cache. `decode_sla` (default: cfg.sla.decode_mode ==
    "sla") adds the decode-time SLA state (empty incremental plan +
    zeroed running H/Z); production callers seed a *filled* decode
    cache via `prefill(decode_max_len=...)` instead.

    `per_slot=True` lays the cache out for continuous batching
    (DESIGN.md "Serving API v2"): `pos` (and the decode-SLA `rows` /
    counter state) become per-slot vectors, so each batch row advances
    through its own sequence and `insert_slot` can scatter a fresh
    prefill into any slot independently."""
    shape = (cfg.num_layers, batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
             "pos": (jnp.zeros((batch,), jnp.int32) if per_slot
                     else jnp.int32(0))}
    if decode_sla is None:
        decode_sla = cfg.sla.decode_mode == "sla"
    if decode_sla:
        _check_decode_grid(cfg, max_len, max_len)
        mc = jnp.full((cfg.num_layers, batch, cfg.num_heads, 0, 0),
                      -1, jnp.int8)
        st = _seed_decode_state(
            cfg, cache["k"][..., :0, :], cache["v"][..., :0, :],
            mc, max_len)
        if per_slot:
            st["rows"] = jnp.full((batch,), st["rows"], jnp.int32)
            for key in ("extends", "replans", "reuses", "retention"):
                st[key] = jnp.repeat(st[key][:, None], batch, axis=1)
        cache["sla"] = st
    return cache


def insert_slot(cache: dict, single: dict, slot) -> dict:
    """Scatter a batch-1 prefill cache into decode slot `slot` of a
    per-slot cache (`make_cache(..., per_slot=True)`).

    `single` comes from `prefill(params, cfg, prompt[None, :], ...)`
    over the SAME max_len — decode-SLA prefills size their caches via
    `decode_max_len`; dense callers pad k/v before inserting. Every
    piece of request state rides along: KV rows, the incremental
    decode plan's rows, the running H/Z linear state, and the pooled
    q/k features, so the admitted request decodes exactly as it would
    have in a fresh aligned batch (DESIGN.md "Serving API v2"). The
    write is jit-traceable with a traced `slot` — admission compiles
    to one scatter.
    """
    if single["k"].shape[1] != 1:
        raise ValueError(
            f"insert_slot takes a batch-1 prefill cache (got batch "
            f"{single['k'].shape[1]})")
    if ("sla" in cache) != ("sla" in single):
        raise ValueError(
            "decode-SLA 'sla' state mismatch: the slot cache and the "
            "prefill cache must both (or neither) carry it")
    if single["k"].shape[-2] != cache["k"].shape[-2]:
        raise ValueError(
            f"cache length mismatch: the slot cache holds "
            f"{cache['k'].shape[-2]} positions but the prefill cache "
            f"has {single['k'].shape[-2]}; prefill with decode_max_len "
            f"(or pad k/v) to the scheduler's max_len first")

    def upd(live, one):
        return jax.lax.dynamic_update_slice_in_dim(
            live, one.astype(live.dtype), slot, axis=1)

    out = {"k": upd(cache["k"], single["k"]),
           "v": upd(cache["v"], single["v"]),
           "pos": cache["pos"].at[slot].set(single["pos"])}
    if "sla" in cache:
        s, t = cache["sla"], single["sla"]
        ns = {key: upd(s[key], t[key])
              for key in ("hblk", "zblk", "htot", "ztot", "kpool",
                          "qpool", "live_lut", "live_cnt", "live_marg")}
        ns["plan"] = jax.tree_util.tree_map(upd, s["plan"], t["plan"])
        ns["rows"] = s["rows"].at[slot].set(t["rows"])
        for key in ("extends", "replans", "reuses", "retention"):
            # (L,) single-request counters -> column `slot` of (L, B)
            ns[key] = s[key].at[:, slot].set(t[key])
        out["sla"] = ns
    return out


# --------------------------------------------------------------------------
# paged serving: page pools + page table (DESIGN.md "Paged KV & prefix
# caching"). Host-side refcounting/CoW lives in serving/pages.py; these
# are the device-side constructors and scatters.
# --------------------------------------------------------------------------
PAGED_POOL_KEYS = ("hblk", "zblk", "kpool")  # per-block leaves that move
#                                              from per-slot state into the
#                                              global page pools under paging
PAGED_SLOT_KEYS = ("htot", "ztot", "qpool", "live_lut", "live_cnt",
                   "live_marg")


def make_paged_cache(cfg: ArchConfig, batch: int, max_len: int,
                     num_pages: int, dtype=jnp.bfloat16,
                     decode_sla: Optional[bool] = None) -> dict:
    """Paged decode cache: global pools of block_kv-sized pages plus a
    per-slot page table, replacing make_cache(per_slot=True)'s
    monolithic (L, B, Hkv, max_len, Dh) slabs.

      kp/vp   (L, P, Hkv, bkv, Dh)  KV page pools
      pt      (B, Tn) int32         logical block -> physical page,
                                    shared by every layer (page ids are
                                    allocated per logical block, and all
                                    layers of one block live at one id)
      slap.*  (L, P, Hkv, ...)      decode-SLA per-block h/z/kpool
                                    partials, pooled at the same ids

    Physical page 0 is the permanent all-zero page; the scheduler pins
    one private scratch page per slot on top so inactive slots (which
    keep stepping through every batched dispatch) always write
    somewhere harmless. Per-slot decode-SLA state (plan rows, totals,
    live-row LUT, counters) keeps the monolithic per-slot layout."""
    sla = cfg.sla
    if max_len % sla.block_kv:
        raise ValueError(
            f"paged cache needs block-aligned max_len (got {max_len} "
            f"for block_kv={sla.block_kv})")
    tn = max_len // sla.block_kv
    nl, hkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    pshape = (nl, num_pages, hkv, sla.block_kv, dh)
    cache = {"kp": jnp.zeros(pshape, dtype), "vp": jnp.zeros(pshape, dtype),
             "pt": jnp.zeros((batch, tn), jnp.int32),
             "pos": jnp.zeros((batch,), jnp.int32)}
    if decode_sla is None:
        decode_sla = sla.decode_mode == "sla"
    if decode_sla:
        _check_decode_grid(cfg, max_len, max_len)
        mc = jnp.full((nl, batch, cfg.num_heads, 0, 0), -1, jnp.int8)
        empty = jnp.zeros((nl, batch, hkv, 0, dh), dtype)
        st = _seed_decode_state(cfg, empty, empty, mc, max_len)
        st["rows"] = jnp.full((batch,), st["rows"], jnp.int32)
        for key in ("extends", "replans", "reuses", "retention"):
            st[key] = jnp.repeat(st[key][:, None], batch, axis=1)
        cache["slap"] = {
            "hblk": jnp.zeros((nl, num_pages, hkv, dh, dh), jnp.float32),
            "zblk": jnp.zeros((nl, num_pages, hkv, dh), jnp.float32),
            "kpool": jnp.zeros((nl, num_pages, hkv, dh), jnp.float32)}
        for key in PAGED_POOL_KEYS:
            del st[key]
        cache["sla"] = st
    return cache


def insert_slot_state_paged(cache: dict, single: dict, slot) -> dict:
    """Scatter only the PER-SLOT half of a batch-1 prefill into `slot`
    of a paged cache: pos plus (under decode-SLA) plan rows, running
    totals, pooled q and counters. Page contents are written separately
    by `insert_slot_paged` — or not at all when every prompt page was a
    prefix-cache hit (the full-prompt snapshot fast path)."""
    if ("sla" in cache) != ("sla" in single):
        raise ValueError(
            "decode-SLA 'sla' state mismatch: the paged cache and the "
            "prefill state must both (or neither) carry it")
    out = dict(cache)
    out["pos"] = cache["pos"].at[slot].set(single["pos"])
    if "sla" in cache:
        s, t = cache["sla"], single["sla"]

        def upd(live, one):
            return jax.lax.dynamic_update_slice_in_dim(
                live, one.astype(live.dtype), slot, axis=1)

        ns = {key: upd(s[key], t[key]) for key in PAGED_SLOT_KEYS}
        ns["plan"] = jax.tree_util.tree_map(upd, s["plan"], t["plan"])
        ns["rows"] = s["rows"].at[slot].set(t["rows"])
        for key in ("extends", "replans", "reuses", "retention"):
            ns[key] = s[key].at[:, slot].set(t[key])
        out["sla"] = ns
    return out


def slot_state_from_prefill(single: dict) -> dict:
    """The per-slot half of a batch-1 prefill cache (what
    `insert_slot_state_paged` consumes): everything except KV rows and
    per-block partials. This is the full-prompt snapshot the scheduler
    caches for exact prefix hits."""
    out = {"pos": single["pos"]}
    if "sla" in single:
        st = single["sla"]
        out["sla"] = {key: st[key] for key in st if key
                      not in PAGED_POOL_KEYS}
    return out


def insert_slot_paged(cache: dict, single: dict, slot, page_ids) -> dict:
    """Scatter a batch-1 prefill cache into `slot` of a paged cache.

    `page_ids` (n_prompt_pages,) int32 names the physical page for each
    prompt block, host-allocated/interned before the call. KV rows and
    (under decode-SLA) the per-block h/z/kpool partials land in the
    pools at those ids; the per-slot state goes through
    `insert_slot_state_paged`. Prefix-interned hit pages are rewritten
    with byte-identical contents (causal attention makes page j a pure
    function of the padded tokens below its end), which keeps admission
    a single static-shape jit per bucket size. The page table itself is
    host-owned and pushed separately."""
    if single["k"].shape[1] != 1:
        raise ValueError(
            f"insert_slot_paged takes a batch-1 prefill cache (got "
            f"batch {single['k'].shape[1]})")
    bkv = cache["kp"].shape[3]
    npp = page_ids.shape[0]
    if single["k"].shape[-2] < npp * bkv:
        raise ValueError(
            f"prefill cache holds {single['k'].shape[-2]} positions but "
            f"{npp} pages of {bkv} were requested")
    out = insert_slot_state_paged(cache, single, slot)
    nl, hkv = cache["kp"].shape[0], cache["kp"].shape[2]

    def kv_pages(x):  # (L, 1, Hkv, S, Dh) -> (L, npp, Hkv, bkv, Dh)
        xs = x[:, 0, :, :npp * bkv, :].reshape(nl, hkv, npp, bkv, -1)
        return jnp.moveaxis(xs, 1, 2)

    out["kp"] = cache["kp"].at[:, page_ids].set(
        kv_pages(single["k"]).astype(cache["kp"].dtype))
    out["vp"] = cache["vp"].at[:, page_ids].set(
        kv_pages(single["v"]).astype(cache["vp"].dtype))
    if "sla" in cache:

        def blk_pages(x):  # (L, 1, Hkv, Tn, ...) -> (L, npp, Hkv, ...)
            return jnp.moveaxis(x[:, 0, :, :npp], 1, 2)

        out["slap"] = {
            key: cache["slap"][key].at[:, page_ids].set(
                blk_pages(single["sla"][key]))
            for key in PAGED_POOL_KEYS}
    return out


def copy_page(cache: dict, dst, src) -> dict:
    """Device-side page copy `src -> dst` across every pool (KV and,
    under decode-SLA, the h/z/kpool partials). The scheduler's
    copy-on-write pass uses this both to duplicate a shared page before
    a divergent write and to ZERO a freshly allocated decode page
    (src = the permanent zero page — the h/z partials accumulate onto
    the page via gather/add/set, so recycled pages must start clean)."""
    out = dict(cache)
    for key in ("kp", "vp"):
        out[key] = cache[key].at[:, dst].set(cache[key][:, src])
    if "slap" in cache:
        out["slap"] = {k: v.at[:, dst].set(v[:, src])
                       for k, v in cache["slap"].items()}
    return out


def paged_dense_view(cfg: ArchConfig, cache: dict) -> dict:
    """Materialize the monolithic per-slot cache a paged cache
    represents (page-gathered KV slabs + per-block partials). Test /
    debugging aid: the paged-vs-monolithic parity suite compares active
    slots of this view bitwise against the unpaged scheduler's cache."""
    pt = cache["pt"]

    def kv(pool):  # (L, P, Hkv, bkv, Dh) -> (L, B, Hkv, S, Dh)
        g = jnp.moveaxis(jnp.take(pool, pt, axis=1), 3, 2)
        return g.reshape(g.shape[:3] + (g.shape[3] * g.shape[4],
                                        g.shape[5]))

    out = {"k": kv(cache["kp"]), "v": kv(cache["vp"]),
           "pos": cache["pos"]}
    if "sla" in cache:
        st = dict(cache["sla"])
        for key in PAGED_POOL_KEYS:
            st[key] = jnp.moveaxis(
                jnp.take(cache["slap"][key], pt, axis=1), 3, 2)
        out["sla"] = st
    return out
