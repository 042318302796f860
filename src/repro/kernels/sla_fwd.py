"""Fused SLA forward Pallas TPU kernel (paper Alg. 1, TPU adaptation).

Grid: (B*H, T_m, K_sel) — the trailing axis iterates the *critical* KV
blocks of one query row, streamed HBM->VMEM through a scalar-prefetched
lookup table (`lut`) so only selected blocks are ever copied. Online
softmax state (m, l, acc) lives in VMEM scratch, carried across the
sequential trailing grid axis. At the last step the kernel finalizes the
sparse output O^s, the log-sum-exp L (for the backward pass), and merges
the linear branch O^l = phi(Q_i) H_i / (phi(Q_i) Z_i) from the
pre-aggregated per-row (H_i, Z_i) — the single-pass fusion of sparse +
linear that is the paper's kernel contribution.

All matmuls accumulate in f32 (MXU-native); inputs may be bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import default_interpret

NEG_INF = -1e30
EPS = 1e-6
LANES = 128
# Scalar-prefetched LUT entries one pallas_call may hold. Scalar
# prefetch lives in SMEM (1 MiB on TPU v5e), shared with the row counts
# and the kernel's own scalars; a longer LUT is split over head runs.
SMEM_LUT_ENTRIES = 128 * 1024


def head_runs(bh, group, per_head):
    """Split `bh` query heads into equal runs of whole GQA groups whose
    flattened LUTs (`per_head` entries per head) fit SMEM_LUT_ENTRIES.
    Returns [(h0, h1), ...]; one run when everything fits."""
    n_kv = bh // group
    m = next((m for m in range(n_kv, 0, -1)
              if n_kv % m == 0 and m * group * per_head <= SMEM_LUT_ENTRIES),
             1)
    return [(h0, h0 + m * group) for h0 in range(0, bh, m * group)]


def _dot(a, b, trans_b=False):
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(lut_ref, counts_ref, base_ref,  # scalar prefetch
                q_ref, k_ref, v_ref, qp_ref, hi_ref, zi_ref,  # inputs
                os_ref, ol_ref, lse_ref,  # outputs
                acc_ref, m_ref, l_ref,  # VMEM scratch
                *, scale: float, tm: int, k_sel: int, causal: bool,
                block_q: int, block_kv: int):
    bh, i, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(s < counts_ref[bh, i])
    def _step():
        q = q_ref[0].astype(jnp.float32)
        kk = k_ref[0].astype(jnp.float32)
        sij = _dot(q, kk, trans_b=True) * scale  # (bq, bkv) f32
        if causal:
            j = lut_ref[(bh * tm + i) * k_sel + s]
            rows = (base_ref[0] + i) * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            sij = jnp.where(rows >= cols, sij, NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(sij, axis=-1))
        p = jnp.exp(sij - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + _dot(p, v_ref[0].astype(jnp.float32)))
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(s == k_sel - 1)
    def _finalize():
        m, l = m_ref[:, :1], l_ref[:, :1]  # (bq, 1) columns
        os_ref[0] = (acc_ref[...] / l).astype(os_ref.dtype)
        lse_ref[0] = (m + jnp.log(l)).astype(lse_ref.dtype)
        # Linear branch (Eq. 5): one (bq,d)x(d,d) matmul + normalizer.
        qp = qp_ref[0].astype(jnp.float32)
        num = _dot(qp, hi_ref[0, 0])
        den = _dot(qp, zi_ref[0, 0], trans_b=True)  # (bq,d)x(1,d)^T: (bq, 1)
        live = den > EPS
        ol = jnp.where(live, num / jnp.where(live, den, 1.0), 0.0)
        ol_ref[0] = ol.astype(ol_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "block_q", "block_kv", "interpret"))
def sla_fwd(lut, counts, q, k, v, qp, hi, zi, *, scale, causal,
            block_q, block_kv, interpret=None, base=None):
    """Run the fused forward kernel.

    Args:
      lut:    (BH, Tm, K_sel) int32 critical block indices (padded).
      counts: (BH, Tm) int32 live entries per row.
      q, qp:  (BH, N, D); k, v: (BH_kv, N, D) with BH % BH_kv == 0.
      hi:     (BH, Tm, D, D) f32 aggregated marginal H per row.
      zi:     (BH, Tm, D) f32 aggregated marginal Z per row.
      base:   (1,) int32 absolute block id of query row 0 (default 0) —
        shifts the causal mask so a chunked-prefill span attends its
        true positions; TRACED (scalar-prefetched), so every chunk
        index shares one compiled kernel.
      interpret: Pallas interpret mode; None decides by the default
        backend (`repro.kernels.mode.default_interpret`).

    Returns: (o_s (BH,N,D) f32, o_l (BH,N,D) f32, lse (BH,N) f32)

    Every block's last two dims are (8, 128)-tileable or the array's
    full dims, as Mosaic requires: zi travels as a (1, D) slab per row
    and lse as an (N, 1) column. The LUT is scalar-prefetched flat, and
    heads run in as many launches as `head_runs` needs to fit it in SMEM.
    """
    if interpret is None:
        interpret = default_interpret()
    bh_q, n, d = q.shape
    bh_kv = k.shape[0]
    group = bh_q // bh_kv
    tm = n // block_q
    k_sel = lut.shape[-1]

    kern = functools.partial(
        _fwd_kernel, scale=scale, tm=tm, k_sel=k_sel, causal=causal,
        block_q=block_q, block_kv=block_kv)
    runs = head_runs(bh_q, group, tm * k_sel)
    hr = runs[0][1]

    def launch(h0):
        """One pallas_call over heads [h0, h0 + hr): the index maps add
        h0, so operands are passed whole and only the LUT is sliced."""
        def row(bh, i, s, *_):
            return (h0 + bh, i, 0)

        def kv_map(bh, i, s, lut_ref, counts_ref, base_ref):
            return ((h0 + bh) // group,
                    lut_ref[(bh * tm + i) * k_sel + s], 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(hr, tm, k_sel),
            in_specs=[
                pl.BlockSpec((1, block_q, d), row),                     # q
                pl.BlockSpec((1, block_kv, d), kv_map),                 # k
                pl.BlockSpec((1, block_kv, d), kv_map),                 # v
                pl.BlockSpec((1, block_q, d), row),                     # qp
                pl.BlockSpec((1, 1, d, d), lambda bh, i, s, *_:
                             (h0 + bh, i, 0, 0)),                       # hi
                pl.BlockSpec((1, 1, 1, d), lambda bh, i, s, *_:
                             (h0 + bh, i, 0, 0)),                       # zi
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, i, s, *_:
                             (bh, i, 0)),                               # o_s
                pl.BlockSpec((1, block_q, d), lambda bh, i, s, *_:
                             (bh, i, 0)),                               # o_l
                pl.BlockSpec((1, block_q, 1), lambda bh, i, s, *_:
                             (bh, i, 0)),                               # lse
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),      # acc
                pltpu.VMEM((block_q, LANES), jnp.float32),  # m (lane-bcast)
                pltpu.VMEM((block_q, LANES), jnp.float32),  # l (lane-bcast)
            ],
        )
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((hr, n, d), jnp.float32),
                jax.ShapeDtypeStruct((hr, n, d), jnp.float32),
                jax.ShapeDtypeStruct((hr, n, 1), jnp.float32),
            ],
            interpret=interpret,
            name="sla_fwd",
        )(lut[h0:h0 + hr].reshape(-1), counts[h0:h0 + hr], base,
          q, k, v, qp, hi, zi[:, :, None, :])

    with jax.named_scope("sla.sparse"):
        if base is None:
            base = jnp.zeros((1,), jnp.int32)
        outs = [launch(h0) for h0, _ in runs]
        o_s, o_l, lse = (jnp.concatenate(x) for x in zip(*outs))
        return o_s, o_l, lse[:, :, 0]
