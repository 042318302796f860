"""SLA backward Pallas TPU kernels (paper Alg. 2, sparse component).

Two kernels (TPU has no atomics, so each gradient is produced by the pass
whose grid axis owns it — the FlashAttention-2 decomposition):

  dQ kernel : grid (BH, T_m, K_sel) over the *row* LUT — accumulates
              dQ_i += dS_ij K_j in VMEM scratch across the critical blocks
              of row i.
  dKV kernel: grid (BH, T_n, W_col) over the *column* LUT — accumulates
              dK_j += dS_ij^T Q_i and dV_j += P_ij^T dO_i. The column LUT
              has static width W_col thanks to the column-capacity
              constraint on the mask (DESIGN.md §3).

P_ij is recomputed from the stored row log-sum-exp L_i (no O(N^2) residual
is ever materialized). The linear-branch gradients are dense matmuls and
live in ops.py (XLA/MXU path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import default_interpret
from repro.kernels.sla_fwd import head_runs

NEG_INF = -1e30


def _dot(a, b, trans_a=False, trans_b=False):
    dims = (((0 if trans_a else 1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _recompute_p(q, kk, lse_col, *, scale, causal, i, j, block_q, block_kv):
    """P_ij = exp(S_ij - L_i), with the token-causal mask zeroing inside the
    diagonal block (exp(NEG_INF - L) underflows to exactly 0). lse_col is
    the (bq, 1) column of L_i."""
    sij = _dot(q, kk, trans_b=True) * scale
    if causal:
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        cols = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        sij = jnp.where(rows >= cols, sij, NEG_INF)
    return jnp.exp(sij - lse_col)


def _dq_kernel(lut_ref, counts_ref,
               q_ref, k_ref, v_ref, do_ref, lse_ref, ds_ref,
               dq_ref, dq_acc,
               *, scale: float, tm: int, k_sel: int, causal: bool,
               block_q: int, block_kv: int):
    bh, i, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(s < counts_ref[bh, i])
    def _step():
        j = lut_ref[(bh * tm + i) * k_sel + s]
        q = q_ref[0].astype(jnp.float32)
        kk = k_ref[0].astype(jnp.float32)
        p = _recompute_p(q, kk, lse_ref[0], scale=scale, causal=causal,
                         i=i, j=j, block_q=block_q, block_kv=block_kv)
        do = do_ref[0].astype(jnp.float32)
        dp = _dot(do, v_ref[0].astype(jnp.float32), trans_b=True)
        dsij = p * (dp - ds_ref[0]) * scale
        dq_acc[...] += _dot(dsij, kk)

    @pl.when(s == k_sel - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(col_lut_ref, col_counts_ref,
                q_ref, k_ref, v_ref, do_ref, lse_ref, ds_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale: float, tn: int, w_col: int, causal: bool,
                block_q: int, block_kv: int):
    bh, j, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(c < col_counts_ref[bh, j])
    def _step():
        i = col_lut_ref[(bh * tn + j) * w_col + c]
        q = q_ref[0].astype(jnp.float32)
        kk = k_ref[0].astype(jnp.float32)
        p = _recompute_p(q, kk, lse_ref[0], scale=scale, causal=causal,
                         i=i, j=j, block_q=block_q, block_kv=block_kv)
        do = do_ref[0].astype(jnp.float32)
        dv_acc[...] += _dot(p, do, trans_a=True)
        dp = _dot(do, v_ref[0].astype(jnp.float32), trans_b=True)
        dsij = p * (dp - ds_ref[0]) * scale
        dk_acc[...] += _dot(dsij, q, trans_a=True)

    @pl.when(c == w_col - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _launch_runs(make_spec, kern, lut, counts, q, k, v, do_s, lse, d_s,
                 *, n_out, per_head, interpret, name):
    """Launch the kernel over head runs that fit SMEM (`head_runs`).
    `make_spec(h0, hr)` gives the grid spec whose index maps add h0, so
    operands pass whole and only the flat LUT and counts are sliced;
    lse and d_s enter as (BH, N, 1) columns; `name` names the kernel in
    compiled programs and profiles. Returns `n_out` (BH, N, D)
    f32 outputs."""
    bh_q, n, d = q.shape
    runs = head_runs(bh_q, bh_q // k.shape[0], per_head)
    hr = runs[0][1]
    outs = [pl.pallas_call(
        kern, grid_spec=make_spec(h0, hr),
        out_shape=[jax.ShapeDtypeStruct((hr, n, d), jnp.float32)] * n_out,
        interpret=interpret, name=name,
    )(lut[h0:h0 + hr].reshape(-1), counts[h0:h0 + hr], q, k, v, do_s,
      lse[..., None], d_s[..., None]) for h0, _ in runs]
    return tuple(jnp.concatenate(x) for x in zip(*outs))


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "block_q", "block_kv", "interpret"))
def sla_bwd_dq(lut, counts, q, k, v, do_s, lse, d_s, *, scale, causal,
               block_q, block_kv, interpret=None):
    """dQ of the sparse component. Shapes as in sla_fwd; d_s=(BH,N) rowsum
    (dO^s . O^s). lse and d_s enter the kernel as (BH, N, 1) columns, so
    their blocks are Mosaic-tileable. Returns dq (BH, N, D) f32."""
    if interpret is None:
        interpret = default_interpret()
    bh_q, n, d = q.shape
    group = bh_q // k.shape[0]
    tm = n // block_q
    k_sel = lut.shape[-1]

    def make_spec(h0, hr):
        def row(bh, i, s, *_):
            return (h0 + bh, i, 0)

        def kv_map(bh, i, s, lut_ref, counts_ref):
            return ((h0 + bh) // group, lut_ref[(bh * tm + i) * k_sel + s], 0)

        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(hr, tm, k_sel),
            in_specs=[
                pl.BlockSpec((1, block_q, d), row),                   # q
                pl.BlockSpec((1, block_kv, d), kv_map),               # k
                pl.BlockSpec((1, block_kv, d), kv_map),               # v
                pl.BlockSpec((1, block_q, d), row),                   # do
                pl.BlockSpec((1, block_q, 1), row),                   # lse
                pl.BlockSpec((1, block_q, 1), row),                   # d_s
            ],
            out_specs=[pl.BlockSpec((1, block_q, d),
                                    lambda bh, i, s, *_: (bh, i, 0))],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        )

    kern = functools.partial(_dq_kernel, scale=scale, tm=tm, k_sel=k_sel,
                             causal=causal, block_q=block_q, block_kv=block_kv)
    (dq,) = _launch_runs(make_spec, kern, lut, counts, q, k, v, do_s, lse,
                         d_s, n_out=1, per_head=tm * k_sel,
                         interpret=interpret, name="sla_bwd_dq")
    return dq


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "block_q", "block_kv", "interpret"))
def sla_bwd_dkv(col_lut, col_counts, q, k, v, do_s, lse, d_s, *, scale,
                causal, block_q, block_kv, interpret=None):
    """dK, dV of the sparse component via the column LUT.

    k, v may be GQA-shared: (BH_kv, N, D). The kernel runs per *query* head
    (grid BH) and the wrapper reduces over the head group afterwards.
    Returns (dk, dv): (BH, N, D) f32 (per query head — caller group-sums).
    """
    if interpret is None:
        interpret = default_interpret()
    bh_q, n, d = q.shape
    group = bh_q // k.shape[0]
    tn = n // block_kv
    w_col = col_lut.shape[-1]

    def make_spec(h0, hr):
        def row_map(bh, j, c, col_lut_ref, col_counts_ref):
            return (h0 + bh, col_lut_ref[(bh * tn + j) * w_col + c], 0)

        def kv(bh, j, c, *_):
            return ((h0 + bh) // group, j, 0)

        def out(bh, j, c, *_):
            return (bh, j, 0)

        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(hr, tn, w_col),
            in_specs=[
                pl.BlockSpec((1, block_q, d), row_map),               # q
                pl.BlockSpec((1, block_kv, d), kv),                   # k
                pl.BlockSpec((1, block_kv, d), kv),                   # v
                pl.BlockSpec((1, block_q, d), row_map),               # do
                pl.BlockSpec((1, block_q, 1), row_map),               # lse
                pl.BlockSpec((1, block_q, 1), row_map),               # d_s
            ],
            out_specs=[pl.BlockSpec((1, block_kv, d), out)] * 2,
            scratch_shapes=[
                pltpu.VMEM((block_kv, d), jnp.float32),
                pltpu.VMEM((block_kv, d), jnp.float32),
            ],
        )

    kern = functools.partial(_dkv_kernel, scale=scale, tn=tn, w_col=w_col,
                             causal=causal, block_q=block_q, block_kv=block_kv)
    return _launch_runs(make_spec, kern, col_lut, col_counts, q, k, v, do_s,
                        lse, d_s, n_out=2, per_head=tn * w_col,
                        interpret=interpret, name="sla_bwd_dkv")
