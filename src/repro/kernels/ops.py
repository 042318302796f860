"""jit'd SLA attention op: Pallas kernels + custom_vjp (Alg. 1 + Alg. 2).

`sla_attention_core(q, k, v, qp, kp, plan, cfg)` returns (O^s, O^l); the
caller applies Proj and the sum (Eq. 6). Differentiable w.r.t. q, k, v,
qp, kp (the plan is a constant, as in the paper: TopK is not
differentiated).

The block structure arrives as an `SLAPlan` (core/plan.py): row LUT for
the forward/dQ kernels, column LUT for the dK/dV kernel. Both are
threaded through the custom_vjp residuals so the backward pass consumes
the forward's plan verbatim — no LUT is ever rebuilt here.

Division of labor (DESIGN.md §3):
  * sparse fwd + linear merge ........ Pallas kernel (sla_fwd)
  * sparse bwd dQ / dK,dV ............ Pallas kernels (sla_bwd, row/col LUTs)
  * per-block h_j, z_j + marginal agg  XLA einsum (MXU matmul — the paper's
    App. A.3 pre-aggregation in its TPU-native dense form)
  * linear-branch gradients .......... XLA einsums (Alg. 2 lines 4-5, 17)
"""
from __future__ import annotations

import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import SLAConfig
from repro.core.plan import SLAPlan, plan_from_mask
from repro.kernels.mode import default_interpret
from repro.kernels.sla_fwd import sla_fwd
from repro.kernels.sla_bwd import sla_bwd_dq, sla_bwd_dkv

EPS = 1e-6


def _flat(x):
    """(B, H, ...) -> (B*H, ...)."""
    b, h = x.shape[:2]
    return x.reshape(b * h, *x.shape[2:])


def _block(x, blk):
    """(BH, N, D) -> (BH, T, blk, D)."""
    bh, n, d = x.shape
    return x.reshape(bh, n // blk, blk, d)


def _hz_blocks(kp, v, block_kv):
    """Per-KV-block linear-attention state: h_j = phi(K_j)^T V_j, z_j."""
    with jax.named_scope("sla.linear"):
        kpb = _block(kp.astype(jnp.float32), block_kv)
        vb = _block(v.astype(jnp.float32), block_kv)
        h = jnp.einsum("gnkd,gnke->gnde", kpb, vb)
        z = jnp.sum(kpb, axis=-2)
    return h, z


def _aggregate(a, h, z):
    """H_i = sum_{j marginal} h_j, Z_i likewise (dense-matmul form)."""
    with jax.named_scope("sla.linear"):
        hi = jnp.einsum("gmn,gnde->gmde", a, h)
        zi = jnp.einsum("gmn,gnd->gmd", a, z)
    return hi, zi


def _linear_bwd(do_l, qp, hi, zi, a, kp, v, block_q, block_kv):
    """Linear-branch gradients (Alg. 2 lines 2, 4-5, 14, 17)."""
    qpb = _block(qp.astype(jnp.float32), block_q)  # (g, Tm, bq, d)
    num = jnp.einsum("gmqd,gmde->gmqe", qpb, hi)
    den = jnp.einsum("gmqd,gmd->gmq", qpb, zi)[..., None]
    live = den > EPS
    sden = jnp.where(live, den, 1.0)
    o_l = jnp.where(live, num / sden, 0.0)
    dob = _block(do_l.astype(jnp.float32), block_q)
    dob = jnp.where(live, dob, 0.0)
    d_l = jnp.sum(dob * o_l, axis=-1, keepdims=True)  # D^l (g,Tm,bq,1)
    qp_over = jnp.where(live, qpb / sden, 0.0)
    dhi = jnp.einsum("gmqd,gmqe->gmde", qp_over, dob)
    dzi = -jnp.einsum("gmqd,gmq->gmd", qp_over, d_l[..., 0])
    dqp = (jnp.einsum("gmqe,gmde->gmqd", dob, hi) - d_l * zi[..., None, :])
    dqp = jnp.where(live, dqp / sden, 0.0)
    # Aggregate row gradients back to per-column dh_j, dz_j (A^T matmul).
    dh = jnp.einsum("gmn,gmde->gnde", a, dhi)
    dz = jnp.einsum("gmn,gmd->gnd", a, dzi)
    vb = _block(v.astype(jnp.float32), block_kv)
    kpb = _block(kp.astype(jnp.float32), block_kv)
    dkp = jnp.einsum("gnke,gnde->gnkd", vb, dh) + dz[..., None, :]
    dv_l = jnp.einsum("gnkd,gnde->gnke", kpb, dh)
    bh, tm, bq, d = dqp.shape
    return (dqp.reshape(bh, tm * bq, d),
            dkp.reshape(bh, -1, d),
            dv_l.reshape(bh, -1, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12))
def _sla_core(q, k, v, qp, kp, marginal, lut, counts, col_lut, col_counts,
              cfg: SLAConfig, scale: float, interpret: bool):
    o_s, o_l = _fwd_impl(q, k, v, qp, kp, marginal, lut, counts, cfg,
                         scale, interpret)[:2]
    return o_s.reshape(q.shape), o_l.reshape(q.shape)


def _fwd_impl(q, k, v, qp, kp, marginal, lut, counts, cfg, scale,
              interpret):
    fq, fk, fv, fqp, fkp = map(_flat, (q, k, v, qp, kp))
    a, flut, fcounts = map(_flat, (marginal, lut, counts))
    hb, zb = _hz_blocks(fkp, fv, cfg.block_kv)
    hi, zi = _aggregate(a, hb, zb)
    o_s, o_l, lse = sla_fwd(flut, fcounts, fq, fk, fv, fqp, hi, zi,
                            scale=scale, causal=cfg.causal,
                            block_q=cfg.block_q, block_kv=cfg.block_kv,
                            interpret=interpret)
    return o_s, o_l, lse, a, hi, zi, flut, fcounts


def _sla_core_fwd(q, k, v, qp, kp, marginal, lut, counts, col_lut,
                  col_counts, cfg, scale, interpret):
    o_s, o_l, lse, a, hi, zi, flut, fcounts = _fwd_impl(
        q, k, v, qp, kp, marginal, lut, counts, cfg, scale, interpret)
    shape = q.shape
    res = (q, k, v, qp, kp, o_s, lse, a, hi, zi,
           flut, fcounts, _flat(col_lut), _flat(col_counts))
    out = (o_s.reshape(shape), o_l.reshape(shape))
    return out, res


def _sla_core_bwd(cfg, scale, interpret, res, cts):
    (q, k, v, qp, kp, o_s, lse, a, hi, zi,
     flut, fcounts, fcol_lut, fcol_counts) = res
    do_s, do_l = cts
    shape = q.shape
    fq, fk, fv, fqp, fkp = map(_flat, (q, k, v, qp, kp))
    fdo_s, fdo_l = map(_flat, (do_s, do_l))
    fdo_s = fdo_s.astype(jnp.float32)

    # --- sparse component (Pallas kernels, LUTs reused from the fwd plan) ---
    d_s = jnp.sum(fdo_s * o_s, axis=-1)  # (BH, N)
    dq = sla_bwd_dq(flut, fcounts, fq, fk, fv, fdo_s, lse, d_s,
                    scale=scale, causal=cfg.causal,
                    block_q=cfg.block_q, block_kv=cfg.block_kv,
                    interpret=interpret)
    dk, dv_s = sla_bwd_dkv(fcol_lut, fcol_counts, fq, fk, fv, fdo_s, lse,
                           d_s, scale=scale, causal=cfg.causal,
                           block_q=cfg.block_q, block_kv=cfg.block_kv,
                           interpret=interpret)

    # --- linear component (XLA einsums) ---
    dqp, dkp, dv_l = _linear_bwd(fdo_l, fqp, hi, zi, a, fkp, fv,
                                 cfg.block_q, cfg.block_kv)
    dv = dv_s + dv_l

    b, h = shape[0], shape[1]
    unflat = lambda x: x.reshape(b, h, shape[2], shape[3])
    tm, tn = a.shape[-2:]
    k_sel, w_col = flut.shape[-1], fcol_lut.shape[-1]
    f0 = lambda *s: np.zeros((b, h) + s, dtype=jax.dtypes.float0)
    d_marginal = jnp.zeros((b, h, tm, tn), jnp.float32)  # plan: constant
    return (unflat(dq).astype(q.dtype), unflat(dk).astype(k.dtype),
            unflat(dv).astype(v.dtype), unflat(dqp).astype(qp.dtype),
            unflat(dkp).astype(kp.dtype),
            d_marginal, f0(tm, k_sel), f0(tm), f0(tn, w_col), f0(tn))


_sla_core.defvjp(_sla_core_fwd, _sla_core_bwd)


def sla_attention_rows(
    q: jax.Array, k: jax.Array, v: jax.Array,
    qp: jax.Array, kp: jax.Array,
    marginal: jax.Array, lut: jax.Array, counts: jax.Array,
    cfg: SLAConfig, scale: float | None = None,
    interpret: bool | None = None, row_offset=0,
) -> Tuple[jax.Array, jax.Array]:
    """Forward-only fused kernel over a SPAN of query-row blocks.

    Chunked-prefill entry point (DESIGN.md "Chunked admission prefill"):
    q/qp cover `C = Cm * block_q` query tokens whose first row block
    sits at absolute block id `row_offset` (python int or traced int32
    — traced keeps every chunk index on one compiled kernel), while
    k/v/kp cover the FULL (B, H, N, D) KV bucket. `marginal`
    (B, H, Cm, Tn), `lut` (B, H, Cm, K) and `counts` (B, H, Cm) are the
    chunk's rows of the full plan. Mirrors `_fwd_impl` op-for-op — the
    per-block h/z einsums run at full bucket width and the row
    reductions are batch-independent, so chunk outputs are bitwise
    equal to the same rows of the blocking forward. No custom_vjp:
    prefill chunks are inference-only. `interpret` None decides by the
    default backend (`repro.kernels.mode.default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    scale = float(q.shape[-1] ** -0.5) if scale is None else float(scale)
    fq, fk, fv, fqp, fkp = map(_flat, (q, k, v, qp, kp))
    a, flut, fcounts = map(_flat, (marginal, lut, counts))
    hb, zb = _hz_blocks(fkp, fv, cfg.block_kv)
    hi, zi = _aggregate(a, hb, zb)
    base = jnp.asarray(row_offset, jnp.int32).reshape(1)
    o_s, o_l, _ = sla_fwd(flut, fcounts, fq, fk, fv, fqp, hi, zi,
                          scale=scale, causal=cfg.causal,
                          block_q=cfg.block_q, block_kv=cfg.block_kv,
                          interpret=interpret, base=base)
    return o_s.reshape(q.shape), o_l.reshape(q.shape)


def sla_attention_core(
    q: jax.Array, k: jax.Array, v: jax.Array,
    qp: jax.Array, kp: jax.Array,
    plan: Union[SLAPlan, jax.Array], cfg: SLAConfig,
    scale: float | None = None, interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused-kernel SLA core. All of q,k,v,qp,kp are (B, H, N, D); `plan`
    is an SLAPlan (or, for convenience, a raw (B, H, Tm, Tn) int8 M_c,
    from which a plan is derived). Returns (O^s, O^l) f32, (B, H, N, D).
    `interpret` None decides by the default backend
    (`repro.kernels.mode.default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    if not isinstance(plan, SLAPlan):
        plan = plan_from_mask(plan, cfg)
    scale = float(q.shape[-1] ** -0.5) if scale is None else float(scale)
    return _sla_core(q, k, v, qp, kp, plan.marginal, plan.lut,
                     plan.counts, plan.col_lut, plan.col_counts, cfg,
                     scale, bool(interpret))
