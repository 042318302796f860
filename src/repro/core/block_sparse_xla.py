"""Gather-based XLA implementation of SLA (no Pallas).

Purpose:
  1. Dry-run / roofline honesty: the dense reference computes the full
     N x N score matrix and masks it, so XLA cost_analysis would report
     *full-attention* FLOPs. This path gathers only the critical KV blocks
     (jnp.take_along_axis over the row LUT), so compiled HLO FLOPs equal
     the true sparse cost — what lands on a real TPU.
  2. A differentiable production path on any backend (autodiff-compatible;
     gather -> scatter-add in the backward).

Sharding note: batch and head axes are kept SEPARATE throughout (no
(B*H,...) flattening) so GSPMD propagates data-axis batch sharding and
model-axis head sharding into every intermediate — flattening them was
measured to replicate the (.., Tm, D, D) linear-branch aggregates on
every device (see EXPERIMENTS.md §Perf iteration log).

The query-row loop runs as a lax.scan over chunks of `chunk` row blocks
(compiles once, keeps the gathered working set small); the chunk body is
rematerialized so the backward does not store gathered KV.
"""
from __future__ import annotations


from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.config import SLAConfig
from repro.core.plan import SLAPlan
from repro.core.reference import _safe_div

NEG_INF = -1e30


def _gather_blocks(xb: jax.Array, idx: jax.Array) -> jax.Array:
    """xb: (B, H, Tn, bkv, D); idx: (B, H, C, K) -> (B, H, C, K, bkv, D)."""
    b, h, tn, bkv, d = xb.shape
    c, ks = idx.shape[2], idx.shape[3]
    flat = idx.reshape(b, h, c * ks)
    out = jnp.take_along_axis(xb, flat[:, :, :, None, None], axis=2)
    return out.reshape(b, h, c, ks, bkv, d)


def _row_chunk(qc, kg, vg, idxc, cntc, i0, scale, causal, block_q,
               block_kv):
    """Attend one chunk of query-row blocks to their gathered critical
    blocks.

    qc: (B, H, C, bq, D); kg, vg: (B, H, C, K, bkv, D);
    idxc: (B, H, C, K); cntc: (B, H, C); i0: (C,) absolute row-block ids.
    Returns (o (B, H, C, bq, D), lse (B, H, C, bq)).
    """
    s = jnp.einsum("bhcqd,bhckvd->bhcqkv", qc.astype(jnp.float32),
                   kg.astype(jnp.float32)) * scale
    ks = kg.shape[3]
    slot = jnp.arange(ks)
    live = slot[None, None, None, :] < cntc[..., None]  # (B, H, C, K)
    s = jnp.where(live[:, :, :, None, :, None], s, NEG_INF)
    if causal:
        rows = (i0[:, None] * block_q
                + jnp.arange(block_q)[None, :])  # (C, bq)
        cols = (idxc[..., None] * block_kv
                + jnp.arange(block_kv))  # (B, H, C, K, bkv)
        ok = rows[None, None, :, :, None, None] >= \
            cols[:, :, :, None, :, :]
        s = jnp.where(ok, s, NEG_INF)
    b, h, c, bq = s.shape[:4]
    sf = s.reshape(b, h, c, bq, ks * kg.shape[4])
    m = jnp.max(sf, axis=-1, keepdims=True)
    p = jnp.exp(sf - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    vgf = vg.reshape(b, h, c, ks * vg.shape[4], vg.shape[5]) \
        .astype(jnp.float32)
    o = jnp.einsum("bhcqk,bhckd->bhcqd", p / l, vgf)
    lse = (m + jnp.log(l))[..., 0]
    return o, lse


def sparse_component_gather(
    q: jax.Array, k: jax.Array, v: jax.Array,
    lut: jax.Array, counts: jax.Array, cfg: SLAConfig,
    scale: float | None = None, chunk: int = 8,
    row_offset=0,
) -> Tuple[jax.Array, jax.Array]:
    """O^s via LUT gather. q,k,v: (B, H, N, D); lut: (B, H, Tm, K).

    `row_offset` (python int or traced int32 scalar) shifts the
    absolute query-row-block ids used by the causal mask: chunked
    prefill attends a (N = chunk) query span starting at block
    `row_offset` against the full KV bucket, and a TRACED offset keeps
    every chunk index on one compiled graph (DESIGN.md "Chunked
    admission prefill").

    Returns (o_s (B, H, N, D) f32, lse (B, H, N) f32).
    """
    b, h, n, d = q.shape
    scale = (d**-0.5) if scale is None else scale
    bq, bkv = cfg.block_q, cfg.block_kv
    tm = n // bq
    chunk = min(chunk, tm)
    while tm % chunk:
        chunk -= 1
    kb = k.reshape(b, h, -1, bkv, d)
    vb = v.reshape(b, h, -1, bkv, d)
    qc = q.reshape(b, h, tm // chunk, chunk, bq, d)
    lutc = lut.reshape(b, h, tm // chunk, chunk, -1)
    cntc = counts.reshape(b, h, tm // chunk, chunk)

    # The WHOLE body (gather included) is rematerialized: otherwise the
    # scan stacks every step's gathered KV as a backward residual —
    # measured at 5.2 GiB/device x dozens of buffers at the wan2.1 cell.
    @jax.checkpoint
    def body(_, args):
        qi, idxc, cnt, i0 = args
        kg = _gather_blocks(kb, idxc)
        vg = _gather_blocks(vb, idxc)
        o, lse = _row_chunk(qi, kg, vg, idxc, cnt, i0, scale, cfg.causal,
                            bq, bkv)
        return None, (o, lse)

    i0s = (row_offset + jnp.arange(tm)).reshape(tm // chunk, chunk)
    _, (o, lse) = jax.lax.scan(
        body, None,
        (jnp.moveaxis(qc, 2, 0), jnp.moveaxis(lutc, 2, 0),
         jnp.moveaxis(cntc, 2, 0), i0s))
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n, d)
    lse = jnp.moveaxis(lse, 0, 2).reshape(b, h, n)
    return o, lse


def sla_forward_gather(
    q: jax.Array, k: jax.Array, v: jax.Array,
    qp: jax.Array, kp: jax.Array, plan: SLAPlan, cfg: SLAConfig,
    scale: float | None = None, chunk: int = 8, row_offset=0,
) -> Tuple[jax.Array, jax.Array]:
    """(O^s, O^l) with gather-based sparse part and matmul-aggregated
    linear part. The block structure (row LUT + marginal aggregation
    matrix) comes from the precomputed `plan`. Shapes: (B, H, N, D).
    `row_offset` as in `sparse_component_gather` (the plan's row axis
    then covers only the chunk's query blocks)."""
    b, h, n, d = q.shape
    tn = plan.num_kv_blocks
    with jax.named_scope("sla.sparse"):
        o_s, _ = sparse_component_gather(q, k, v, plan.lut, plan.counts,
                                         cfg, scale, chunk, row_offset)

    with jax.named_scope("sla.linear"):
        kpb = kp.astype(jnp.float32).reshape(b, h, tn, cfg.block_kv, d)
        vb = v.astype(jnp.float32).reshape(b, h, tn, cfg.block_kv, d)
        hb = jnp.einsum("bhnkd,bhnke->bhnde", kpb, vb)
        zb = jnp.sum(kpb, axis=-2)
        a = plan.marginal
        hi = jnp.einsum("bhmn,bhnde->bhmde", a, hb)
        zi = jnp.einsum("bhmn,bhnd->bhmd", a, zb)
        tm = plan.num_q_blocks
        qpb = qp.astype(jnp.float32).reshape(b, h, tm, cfg.block_q, d)
        num = jnp.einsum("bhmqd,bhmde->bhmqe", qpb, hi)
        den = jnp.einsum("bhmqd,bhmd->bhmq", qpb, zi)[..., None]
        o_l = _safe_div(num, den).reshape(b, h, n, d)
    return o_s, o_l
