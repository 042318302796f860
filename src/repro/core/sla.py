"""SLA attention module — functional public API (plan/execute wrapper).

Usage:
    cfg = SLAConfig(kh_frac=0.05, kl_frac=0.10, phi="softmax")
    params = sla_init(rng, num_heads, head_dim, cfg)
    out = sla_attention(params, q, k, v, cfg)                 # (B, H, N, D)

    # plan once, execute many times (cross-timestep reuse):
    plan = plan_attention(q, k, cfg)
    out = sla_attention(params, q, k, v, cfg, plan=plan)

    # drift-gated refresh (DESIGN.md "Plan lifetime & drift"): keep the
    # plan while it retains critical mass, rebuild when it decays:
    plan, retention, replanned = refresh_plan(plan, q, k, cfg,
                                              cfg.plan_drift_threshold)

Modes (cfg.mode):
  "sla"          O = O^s + Proj(O^l)                      (paper, Eq. 6)
  "sparse_only"  O = O^s                                   (Table 2 baseline)
  "linear_only"  O = full linear attention                 (Table 2 baseline)
  "l_plus_s"     O = O^s + full-linear(O)                  (Table 2 baseline)
  "full"         exact softmax attention

`backend` selects the execution path from the core.backends registry:
"reference" (dense oracle), "gather" (LUT-gather XLA — true sparse
compiled FLOPs), or "kernel" (fused Pallas; interpreted on the CPU).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import backends
from repro.core.config import SLAConfig
from repro.core.plan import SLAPlan, refresh_plan  # noqa: F401 — re-export

Params = Dict[str, jax.Array]


def sla_init(rng: jax.Array, num_heads: int, head_dim: int,
             cfg: SLAConfig, dtype=jnp.float32) -> Params:
    """Learnable parameters: the per-head d x d Proj on the linear branch."""
    if cfg.proj_init == "identity":
        proj = jnp.tile(jnp.eye(head_dim, dtype=dtype)[None], (num_heads, 1, 1))
    elif cfg.proj_init == "zeros":
        proj = jnp.zeros((num_heads, head_dim, head_dim), dtype)
    else:
        raise ValueError(cfg.proj_init)
    return {"proj": proj}


def sla_attention(
    params: Optional[Params],
    q: jax.Array, k: jax.Array, v: jax.Array,
    cfg: SLAConfig,
    scale: Optional[float] = None,
    backend: str = "reference",
    plan: Optional[SLAPlan] = None,
    routing: Optional[Params] = None,
) -> jax.Array:
    """SLA attention. q: (B, H, N, D); k, v: (B, Hkv, N, D) with Hkv | H.

    `plan`: a precomputed SLAPlan (from `plan_attention`) — pass it to
    amortize planning across calls; None plans inline from (q, k).
    `routing`: learned-routing scorer parameters (`routing_init`) for
    inline planning when cfg.routing_mode == "learned".

    Returns (B, H, N, D) in q.dtype.
    """
    return backends.execute(plan, params, q, k, v, cfg,
                            scale=scale, backend=backend, routing=routing)
