"""Diffusion Transformer (the paper's home architecture).

Wan2.1-style video DiT: patchified latent tokens, AdaLN-zero timestep
modulation, bidirectional self-attention (SLA's target workload), optional
cross-attention to text conditioning, flow-matching training objective.
Covers both `wan2_1_1_3b` (video, seq ~32K) and `lightningdit_1b`
(image, seq 1024).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import masks as masks_lib
from repro.core import plan as plan_lib
from repro.distributed import ctx
from repro.models.common import attention, dense_init, mse_loss, rms_norm


def _layer_init(rng, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    d, h, dh = cfg.d_model, cfg.num_heads, cfg.head_dim
    r = list(jax.random.split(rng, 10))
    p = {
        "ln1": jnp.zeros((d,), dtype),
        "ln2": jnp.zeros((d,), dtype),
        "wq": dense_init(r[0], d, h * dh, dtype),
        "wk": dense_init(r[1], d, cfg.num_kv_heads * dh, dtype),
        "wv": dense_init(r[2], d, cfg.num_kv_heads * dh, dtype),
        "wo": dense_init(r[3], h * dh, d, dtype),
        "sla_proj": jnp.zeros((h, dh, dh), dtype),
        "mlp_wi": dense_init(r[4], d, 2 * cfg.d_ff, dtype),
        "mlp_wo": dense_init(r[5], cfg.d_ff, d, dtype),
        # AdaLN-zero: 6 modulation vectors from the timestep embedding
        "ada": (jax.random.normal(r[6], (d, 6 * d), jnp.float32)
                * 0.01).astype(dtype),
    }
    if cfg.sla.routing_mode == "learned":
        # identity init reproduces the threshold router bitwise (no RNG
        # consumed — threshold-mode params are unchanged)
        p["routing"] = masks_lib.routing_init(h, dh, dtype)
    if cfg.cross_attn:
        p["ln_x"] = jnp.zeros((d,), dtype)
        p["xq"] = dense_init(r[7], d, h * dh, dtype)
        p["xk"] = dense_init(r[8], d, cfg.num_kv_heads * dh, dtype)
        p["xv"] = dense_init(r[9], d, cfg.num_kv_heads * dh, dtype)
        p["xo"] = dense_init(r[7], h * dh, d, dtype)
    return p


def init(rng, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    r = jax.random.split(rng, cfg.num_layers + 3)
    layers = jax.vmap(lambda k: _layer_init(k, cfg, dtype))(
        jnp.stack(r[: cfg.num_layers]))
    d = cfg.d_model
    return {
        "patch_in": dense_init(r[-1], cfg.patch_dim, d, dtype),
        "t_embed": dense_init(r[-2], 256, d, dtype),
        "layers": layers,
        "ln_f": jnp.zeros((d,), dtype),
        "patch_out": (jnp.zeros((d, cfg.patch_dim), dtype)),
    }


def _timestep_embedding(t: jax.Array, dim: int = 256) -> jax.Array:
    half = dim // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = t.astype(jnp.float32)[:, None] * freq[None, :]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def forward(params, cfg: ArchConfig, latents, t,
            cond: Optional[jax.Array] = None,
            compute_dtype=jnp.bfloat16, backend: str = "gather",
            sla_mode: Optional[str] = None,
            plans=None, return_plans: bool = False,
            drift_threshold=None, per_sample_refresh: bool = False):
    """latents: (B, N, patch_dim); t: per-sample (B,) diffusion time in
    [0,1], or a scalar broadcast to the batch — bitwise-equal to the
    equivalent uniform (B,) vector (the timestep embedding and AdaLN
    modulation are row-independent). Mixed-timestep batches are the
    serving case (serving/diffusion.py): each row denoises at its own t.
    cond: (B, Lc, d) stub text embeddings. Returns velocity prediction
    with the same shape as latents.

    sla_mode overrides cfg.sla.mode (used by the ablation benchmarks to
    run full / linear_only / sparse_only / l_plus_s variants).

    Cross-timestep plan reuse (DESIGN.md "Plan/execute split"): pass
    `return_plans=True` to also return the per-layer SLAPlan pytree
    (leading axis = layer, stacked by the layer scan); pass that pytree
    back as `plans=` on a later denoising step to skip block planning
    entirely. With plans given and drift_threshold=None, this function
    performs zero planning.

    Drift-adaptive refresh (DESIGN.md "Plan lifetime & drift"): with
    `plans=` AND `drift_threshold=` (float, traced scalar, or a
    per-layer (L,) array/tuple — each layer's refresh decision uses its
    own entry, never min-reduced across the stack), each layer measures
    the retained critical mass of its reused plan against the current
    (q, k) and re-plans under `lax.cond` only when drift reaches the
    threshold — jit-traceable, static shapes. The return value gains a
    trailing info dict {"retention": (L,), "replanned": (L,)}.

    Per-sample refresh (serving): with `per_sample_refresh=True` the
    drift decision decouples across batch rows
    (plan_lib.refresh_plan_per_sample) — `drift_threshold` broadcasts
    to (L, B) and the info dict carries (L, B) retention/replanned, so
    one slot's re-plan never rebuilds (or blocks) its neighbours'."""
    t = jnp.asarray(t, jnp.float32)
    if t.ndim == 0:
        t = jnp.broadcast_to(t, (latents.shape[0],))
    with jax.named_scope("dit.embed"):
        x = jnp.einsum("bnp,pd->bnd", latents.astype(compute_dtype),
                       params["patch_in"].astype(compute_dtype))
        temb = jnp.einsum("be,ed->bd", _timestep_embedding(t * 1000.0),
                          params["t_embed"].astype(jnp.float32))
        temb = jax.nn.silu(temb).astype(compute_dtype)
    b, n, d = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    import dataclasses
    sla_cfg = dataclasses.replace(cfg.sla, causal=False)
    if sla_mode is not None:
        sla_cfg = dataclasses.replace(sla_cfg, mode=sla_mode)
    kind = "sla" if cfg.attention_kind == "sla" else cfg.attention_kind
    if sla_mode is not None:
        kind = "sla"
    # Self-attention needs a block plan only in the sparse SLA modes.
    plan_needed = (kind == "sla"
                   and sla_cfg.mode not in ("full", "linear_only"))
    adaptive = (drift_threshold is not None and plans is not None
                and plan_needed)
    if adaptive:
        thr_shape = ((cfg.num_layers, latents.shape[0])
                     if per_sample_refresh else (cfg.num_layers,))
        thresholds = jnp.broadcast_to(
            jnp.asarray(drift_threshold, jnp.float32), thr_shape)

    def body(x, xs):
        if adaptive:
            p, layer_plan, thr = xs
        else:
            p, layer_plan = xs
        if adaptive and per_sample_refresh:
            retention = jnp.ones((b,), jnp.float32)
            replanned = jnp.zeros((b,), bool)
        else:
            retention = jnp.float32(1.0)
            replanned = jnp.bool_(False)
        with jax.named_scope("dit.adaln"):
            mod = jnp.einsum("bd,de->be", temb, p["ada"].astype(temb.dtype))
            sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
            xn = rms_norm(x, p["ln1"]) * (1 + sc1[:, None]) + sh1[:, None]
        with jax.named_scope("dit.qkv"):
            q = jnp.einsum("bsd,de->bse", xn, p["wq"].astype(x.dtype)) \
                .reshape(b, n, h, dh).transpose(0, 2, 1, 3)
            k = jnp.einsum("bsd,de->bse", xn, p["wk"].astype(x.dtype)) \
                .reshape(b, n, hkv, dh).transpose(0, 2, 1, 3)
            v = jnp.einsum("bsd,de->bse", xn, p["wv"].astype(x.dtype)) \
                .reshape(b, n, hkv, dh).transpose(0, 2, 1, 3)
        routing = p.get("routing") if sla_cfg.routing_mode == "learned" \
            else None
        if plan_needed and layer_plan is None:
            layer_plan = plan_lib.plan_attention(q, k, sla_cfg,
                                                 routing=routing)
        elif adaptive:
            refresh = (plan_lib.refresh_plan_per_sample
                       if per_sample_refresh else plan_lib.refresh_plan)
            layer_plan, retention, replanned = refresh(
                layer_plan, q, k, sla_cfg, thr, routing=routing)
        o = attention({"proj": p["sla_proj"]}, q, k, v, kind, sla_cfg,
                      causal=False, backend=backend,
                      plan=layer_plan if plan_needed else None,
                      routing=routing)
        with jax.named_scope("dit.attn_out"):
            o = o.transpose(0, 2, 1, 3).reshape(b, n, h * dh)
            x = ctx.shard_residual(
                x + g1[:, None] * jnp.einsum("bse,ed->bsd", o,
                                             p["wo"].astype(x.dtype)))
        if cfg.cross_attn and cond is not None:
            with jax.named_scope("dit.cross_attn"):
                cx = cond.astype(x.dtype)
                lc = cx.shape[1]
                xq = jnp.einsum("bsd,de->bse", rms_norm(x, p["ln_x"]),
                                p["xq"].astype(x.dtype)) \
                    .reshape(b, n, h, dh).transpose(0, 2, 1, 3)
                xk = jnp.einsum("bsd,de->bse", cx, p["xk"].astype(x.dtype)) \
                    .reshape(b, lc, hkv, dh).transpose(0, 2, 1, 3)
                xv = jnp.einsum("bsd,de->bse", cx, p["xv"].astype(x.dtype)) \
                    .reshape(b, lc, hkv, dh).transpose(0, 2, 1, 3)
                xo = attention(None, xq, xk, xv, "full", sla_cfg,
                               causal=False)
                xo = xo.transpose(0, 2, 1, 3).reshape(b, n, h * dh)
                x = x + jnp.einsum("bse,ed->bsd", xo,
                                   p["xo"].astype(x.dtype))
        with jax.named_scope("dit.adaln"):
            xn2 = rms_norm(x, p["ln2"]) * (1 + sc2[:, None]) + sh2[:, None]
        with jax.named_scope("dit.mlp"):
            hmid = jnp.einsum("bsd,df->bsf", xn2,
                              p["mlp_wi"].astype(x.dtype))
            g, u = jnp.split(hmid, 2, axis=-1)
            x = ctx.shard_residual(x + g2[:, None] * jnp.einsum(
                "bsf,fd->bsd", jax.nn.silu(g) * u,
                p["mlp_wo"].astype(x.dtype)))
        ys = (layer_plan if return_plans and plan_needed else None,
              (retention, replanned) if adaptive else None)
        return x, ys

    # `plans=None` cannot ride through scan xs (no leading layer axis), so
    # the no-plan path scans params only and the body plans inline.
    if plans is None:
        x, (out_plans, drift_ys) = jax.lax.scan(
            ctx.maybe_remat(lambda x, p: body(x, (p, None))),
            x, params["layers"])
    else:
        xs = ((params["layers"], plans, thresholds) if adaptive
              else (params["layers"], plans))
        x, (out_plans, drift_ys) = jax.lax.scan(
            ctx.maybe_remat(body), x, xs)
    with jax.named_scope("dit.head"):
        x = rms_norm(x, params["ln_f"])
        out = jnp.einsum("bnd,dp->bnp", x,
                         params["patch_out"].astype(x.dtype))
    rets = (out,)
    if return_plans:
        rets += (out_plans,)
    if adaptive:
        rets += ({"retention": drift_ys[0], "replanned": drift_ys[1]},)
    return rets if len(rets) > 1 else out


def sample(params, cfg: ArchConfig, noise, *, num_steps: int = 8,
           cond: Optional[jax.Array] = None, compute_dtype=jnp.bfloat16,
           backend: str = "gather",
           refresh_interval: Optional[int] = None,
           refresh_mode: Optional[str] = None,
           drift_threshold=None,
           t_start=None,
           return_trace: bool = False):
    """Euler rectified-flow sampler with cross-timestep plan reuse.

    Integrates dx/dt = v(x, t) from t=1 (noise, (B, N, patch_dim)) down
    to t=0 over `num_steps` uniform steps. `t_start` (scalar or (B,),
    default None = 1.0) starts the trajectory mid-way — SDEdit-style
    partial denoise, and the sequential reference for serving requests
    admitted at an arbitrary timestep: each sample integrates from its
    own t_start to 0 over `num_steps` steps of dt = t_start/num_steps.
    t_start=None keeps the original python-scalar dt path untouched.

    Plan refresh policy (`refresh_mode`, default
    cfg.sla.plan_refresh_mode):

    * "fixed": every `refresh_interval` steps (default
      cfg.sla.plan_refresh_interval) the forward pass re-plans each
      layer's block structure and the plans are reused in between —
      block-sparsity patterns are stable across adjacent denoising
      timesteps, so planning cost amortizes by ~1/K. With
      refresh_interval >= num_steps, each layer plans exactly once.
    * "adaptive": plan once on the first step, then carry
      (x, plans) through a `lax.scan` over the remaining steps; each
      layer measures the drift of its reused plan against the current
      (q, k) and re-plans under `lax.cond` only when drift reaches
      `drift_threshold` (default cfg.sla.plan_drift_threshold; may be a
      traced scalar — one jit covers every threshold). The per-step
      re-plan decision is data-dependent but fully jit-traceable: no
      python-level branching inside the scanned body.

    With `return_trace=True` also returns {"retention": (S-1, L),
    "replanned": (S-1, L), "replan_count": (L,)} — counts exclude the
    mandatory step-0 planning. In fixed mode the trace is the static
    schedule (retention is reported as 1, unmeasured).
    """
    mode = (cfg.sla.plan_refresh_mode if refresh_mode is None
            else refresh_mode)
    if mode not in ("fixed", "adaptive"):
        raise ValueError(f"unknown plan_refresh_mode {mode!r}; "
                         "expected 'fixed' or 'adaptive'")
    b = noise.shape[0]
    x = noise
    nl = cfg.num_layers

    if t_start is None:
        dt = 1.0 / num_steps

        def tvec(step):
            """(B,) diffusion time for a python-int or traced step."""
            return (jnp.full((b,), 1.0, jnp.float32)
                    - jnp.asarray(step, jnp.float32) * dt)

        def euler(x, vel):
            return x - dt * vel.astype(x.dtype)
    else:
        # per-sample start time: t(step) = t0 - step * (t0/num_steps),
        # computed positionally (not by iterated subtraction) so the
        # serving scheduler's host-side f32 bookkeeping reproduces the
        # same rounded values (serving/diffusion.py parity contract)
        t0 = jnp.broadcast_to(
            jnp.asarray(t_start, jnp.float32), (b,))
        dtv = t0 / jnp.float32(num_steps)

        def tvec(step):
            return t0 - jnp.asarray(step, jnp.float32) * dtv

        def euler(x, vel):
            return x - dtv[:, None, None] * vel.astype(x.dtype)

    def static_trace(replan_flags):
        """Trace dict for modes whose refresh schedule is static
        (retention is reported as 1, unmeasured). Flags cover steps
        1..num_steps-1 (step 0 always plans)."""
        rep = (jnp.asarray(replan_flags, bool)[:, None]
               .repeat(nl, 1).reshape(num_steps - 1, nl))
        return {"retention": jnp.ones((num_steps - 1, nl)),
                "replanned": rep,
                "replan_count": jnp.sum(rep, axis=0)}

    if mode == "fixed":
        k_refresh = (cfg.sla.plan_refresh_interval
                     if refresh_interval is None else refresh_interval)
        k_refresh = max(1, int(k_refresh))
        # rolled (ISSUE 6): step 0 plans outside the loop, then one
        # scanned body whose lax.cond either re-plans or reuses the
        # carried plans — the compiled graph is horizon-independent and
        # the planning pipeline traces exactly twice (once per branch)
        # no matter how many steps or refreshes run.
        vel, plans = forward(params, cfg, x, tvec(0), cond, compute_dtype,
                             backend, return_plans=True)
        x = euler(x, vel)
        if num_steps > 1:
            def fixed_body(carry, step):
                x, plans = carry

                def replan(_):
                    return forward(params, cfg, x, tvec(step), cond,
                                   compute_dtype, backend,
                                   return_plans=True)

                def reuse(_):
                    return (forward(params, cfg, x, tvec(step), cond,
                                    compute_dtype, backend, plans=plans),
                            plans)

                vel, new_plans = jax.lax.cond(step % k_refresh == 0,
                                              replan, reuse, None)
                return (euler(x, vel), new_plans), None

            (x, plans), _ = jax.lax.scan(fixed_body, (x, plans),
                                         jnp.arange(1, num_steps))
        if return_trace:
            return x, static_trace([s % k_refresh == 0
                                    for s in range(1, num_steps)])
        return x

    # adaptive: mandatory plan on step 0, then a scanned drift-gated loop
    thr = (cfg.sla.plan_drift_threshold if drift_threshold is None
           else drift_threshold)
    plan_needed = (cfg.attention_kind == "sla"
                   and cfg.sla.mode not in ("full", "linear_only"))
    if not plan_needed:
        # plan-free attention: nothing to refresh — one scanned Euler
        # body (rolled, ISSUE 6: horizon-independent compiled graph)
        def pf_body(x, step):
            return euler(x, forward(params, cfg, x, tvec(step), cond,
                                    compute_dtype, backend)), None

        x, _ = jax.lax.scan(pf_body, x, jnp.arange(num_steps))
        if return_trace:
            return x, static_trace([False] * (num_steps - 1))
        return x

    vel, plans = forward(params, cfg, x, tvec(0), cond, compute_dtype,
                         backend, return_plans=True)
    x = euler(x, vel)

    def step_body(carry, step):
        x, plans = carry
        vel, plans, info = forward(params, cfg, x, tvec(step), cond,
                                   compute_dtype, backend, plans=plans,
                                   return_plans=True, drift_threshold=thr)
        return (euler(x, vel), plans), (info["retention"],
                                        info["replanned"])

    (x, _), (rets, reps) = jax.lax.scan(
        step_body, (x, plans), jnp.arange(1, num_steps))
    if return_trace:
        trace = {"retention": rets, "replanned": reps,
                 "replan_count": jnp.sum(reps, axis=0)}
        return x, trace
    return x


# ---------------------------------------------------------------------------
# serving slot surgery (serving/diffusion.py; the DiT analogue of
# transformer.insert_slot — per-request state here is a latent row plus
# its per-layer plan rows, not a KV cache)
# ---------------------------------------------------------------------------
def insert_denoise_slot(latents, plans, slot: int, latent_row, plan_row):
    """Scatter one admitted request into batch slot `slot`.

    latents: (B, N, P) live pool; latent_row: (1, N, P). plans: stacked
    per-layer plan pytree with leaves (L, B, ...); plan_row: the same
    pytree with leaves (L, 1, ...) — scattered along the batch axis
    (axis 1, after the layer axis). Either plan argument may be None
    (plan-free attention modes carry no plan state)."""
    latents = jax.lax.dynamic_update_slice_in_dim(
        latents, latent_row.astype(latents.dtype), slot, axis=0)
    if plans is not None and plan_row is not None:
        plans = jax.tree_util.tree_map(
            lambda full, row: jax.lax.dynamic_update_slice_in_dim(
                full, row.astype(full.dtype), slot, axis=1),
            plans, plan_row)
    return latents, plans


def retire_denoise_slot(latents, slot: int):
    """Read a finished request's final latent (N, P) out of the pool."""
    return latents[slot]


def take_slot_plans(plans, slot: int):
    """One slot's per-layer plan rows (leaves (L, 1, ...)) — the unit
    the cross-request plan cache stores per timestep bucket."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1),
        plans)


def loss_fn(params, cfg: ArchConfig, batch, compute_dtype=jnp.bfloat16,
            backend: str = "gather", sla_mode: Optional[str] = None):
    """Flow-matching (rectified flow): x_t = (1-t) x0 + t noise; the model
    predicts the velocity (noise - x0). batch: latents (B,N,P), noise,
    t (B,), cond (optional)."""
    x0 = batch["latents"]
    noise = batch["noise"]
    t = batch["t"]
    xt = (1.0 - t[:, None, None]) * x0 + t[:, None, None] * noise
    target = noise - x0
    pred = forward(params, cfg, xt, t, batch.get("cond"), compute_dtype,
                   backend, sla_mode)
    return mse_loss(pred, target)


def distill_loss_fn(params, cfg: ArchConfig, batch,
                    compute_dtype=jnp.bfloat16,
                    backend: str = "gather"):
    """End-to-end distillation (paper Sec. 5): MSE between the SLA
    student's velocity prediction and a gradient-stopped exact-attention
    teacher running the SAME params on the same noised latents.

    This is the fine-tuning objective that wires the learned routing
    head (DESIGN.md "Learned routing") to a training signal: sla_proj
    gets ordinary gradients and the routing parameters straight-through
    gradients via the plan's marginal gates, so a few steps at a fixed
    critical-block budget recover exact-attention quality. Use an
    autodiff backend ("gather"/"reference") — the fused kernel's
    custom_vjp treats the plan as a constant."""
    x0, noise, t = batch["latents"], batch["noise"], batch["t"]
    xt = (1.0 - t[:, None, None]) * x0 + t[:, None, None] * noise
    teacher = forward(params, cfg, xt, t, batch.get("cond"),
                      compute_dtype, backend, sla_mode="full")
    student = forward(params, cfg, xt, t, batch.get("cond"),
                      compute_dtype, backend)
    return mse_loss(student, jax.lax.stop_gradient(teacher))
