"""Fused SLA forward Pallas TPU kernel (paper Alg. 1, TPU adaptation).

Grid: (heads in the run, T_m) — one step per query row. K and V stay in
HBM; the step streams the row's *live* critical KV blocks (its first
`counts` entries of the scalar-prefetched lookup table `lut`) through
SLOTS VMEM slots with manual DMAs, two blocks ahead of the one it
computes, so only selected blocks are ever copied and padded LUT slots
are neither fetched nor computed. Near its end a row starts the first
block of the grid's next row (the next head's row 0 after the last
row), so no row waits on an exposed copy; both grid axes are therefore
sequential. The online softmax state (m, l, acc) is carried through the
loop and updated one block at a time in LUT order. After the loop the
step finalizes the sparse output O^s, the log-sum-exp L (for the
backward pass), and merges the linear branch O^l = phi(Q_i) H_i /
(phi(Q_i) Z_i) from the pre-aggregated per-row (H_i, Z_i) — the
single-pass fusion of sparse + linear that is the paper's kernel
contribution.

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
# VMEM slots for K/V blocks: the block being computed and two in flight.
SLOTS = 3


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
                q_ref, k_hbm, v_hbm, qp_ref, hi_ref, zi_ref,  # inputs
                os_ref, ol_ref, lse_ref,  # outputs
                k_buf, v_buf, k_sem, v_sem, slot_ref,  # KV slots
                *, scale: float, tm: int, k_sel: int, causal: bool,
                block_q: int, block_kv: int, h0: int, group: int, hr: int):
    bh, i = pl.program_id(0), pl.program_id(1)
    count = counts_ref[bh, i]
    d = q_ref.shape[-1]
    row = (bh * tm + i) * k_sel  # the row's first LUT entry
    kv_head = (h0 + bh) // group

    def copies(kvh, j, slot):
        return (pltpu.make_async_copy(k_hbm.at[kvh, j], k_buf.at[slot],
                                      k_sem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[kvh, j], v_buf.at[slot],
                                      v_sem.at[slot]))

    def start(kvh, j, slot):
        for c in copies(kvh, j, slot):
            c.start()

    def start_next_row(slot):
        """Start the first block of the grid's next row (the next head's
        row 0 after the last row), if there is one and it has any."""
        wrap = i == tm - 1
        nb = jnp.where(wrap, bh + 1, bh)
        nr = jnp.where(wrap, 0, i + 1)

        @pl.when(nb < hr)
        def _():
            @pl.when(counts_ref[nb, nr] > 0)
            def _():
                start((h0 + nb) // group, lut_ref[(nb * tm + nr) * k_sel],
                      slot)

    @pl.when((bh == 0) & (i == 0))
    def _first_row():
        slot_ref[0] = 0

        @pl.when(count > 0)
        def _():
            start(kv_head, lut_ref[row], 0)

    # Entry s of this row sits in slot (slot0 + s) % SLOTS; entry 0 was
    # started by the previous row, entries 1 .. SLOTS-2 start here.
    slot0 = slot_ref[0]
    slot_of = lambda s: jax.lax.rem(slot0 + s, SLOTS)
    for s in range(1, SLOTS - 1):
        @pl.when(s < count)
        def _():
            start(kv_head, lut_ref[row + s], slot_of(s))

    def block(s, state):
        """Online-softmax update by LUT entry `s`; m and l are carried
        lane-broadcast, (bq, LANES)."""
        m_prev, l_prev, acc = state
        slot = slot_of(s)
        for c in copies(0, 0, slot):  # a wait needs only the slot
            c.wait()
        q = q_ref[0].astype(jnp.float32)
        kk = k_buf[slot, :, :d].astype(jnp.float32)
        sij = _dot(q, kk, trans_b=True) * scale  # (bq, bkv) f32
        if causal:
            j = lut_ref[row + s]
            rows = (base_ref[0] + i) * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            sij = jnp.where(rows >= cols, sij, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(sij, axis=-1, keepdims=True))
        p = jnp.exp(sij - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = (acc * alpha[:, :1]
               + _dot(p, v_buf[slot, :, :d].astype(jnp.float32)))
        return m_new, l_new, acc

    def steady(s, state):
        """Entries with SLOTS-1 more of the row behind them: start entry
        s + SLOTS - 1 with no branch, so the copy and the block's
        compute share one basic block."""
        start(kv_head, lut_ref[row + s + SLOTS - 1], slot_of(s + SLOTS - 1))
        return block(s, state)

    n_steady = jnp.maximum(count - (SLOTS - 1), 0)

    def last(s, state):
        """The row's last entries: the first of them starts the next
        row's entry 0, in the slot this row's entry `count` would take."""
        @pl.when(s == n_steady)
        def _():
            start_next_row(slot_of(count))

        return block(s, state)

    state = (jnp.full((block_q, LANES), NEG_INF, jnp.float32),
             jnp.zeros((block_q, LANES), jnp.float32),
             jnp.zeros((block_q, d), jnp.float32))
    state = jax.lax.fori_loop(0, n_steady, steady, state)
    m, l, acc = jax.lax.fori_loop(n_steady, count, last, state)

    @pl.when(count == 0)
    def _():
        start_next_row(slot0)

    slot_ref[0] = slot_of(count)

    m, l = m[:, :1], l[:, :1]  # (bq, 1) columns
    os_ref[0] = (acc / l).astype(os_ref.dtype)
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

    runs = head_runs(bh_q, group, tm * k_sel)
    hr = runs[0][1]
    d_pad = -(-d // LANES) * LANES

    def launch(h0):
        """One pallas_call over heads [h0, h0 + hr): the index maps add
        h0, so operands are passed whole and only the LUT is sliced."""
        def row(bh, i, *_):
            return (h0 + bh, i, 0)

        kern = functools.partial(
            _fwd_kernel, scale=scale, tm=tm, k_sel=k_sel, causal=causal,
            block_q=block_q, block_kv=block_kv, h0=h0, group=group, hr=hr)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(hr, tm),
            in_specs=[
                pl.BlockSpec((1, block_q, d), row),                     # q
                pl.BlockSpec(memory_space=pl.ANY),                      # k
                pl.BlockSpec(memory_space=pl.ANY),                      # v
                pl.BlockSpec((1, block_q, d), row),                     # qp
                pl.BlockSpec((1, 1, d, d), lambda bh, i, *_:
                             (h0 + bh, i, 0, 0)),                       # hi
                pl.BlockSpec((1, 1, 1, d), lambda bh, i, *_:
                             (h0 + bh, i, 0, 0)),                       # zi
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, i, *_:
                             (bh, i, 0)),                               # o_s
                pl.BlockSpec((1, block_q, d), lambda bh, i, *_:
                             (bh, i, 0)),                               # o_l
                pl.BlockSpec((1, block_q, 1), lambda bh, i, *_:
                             (bh, i, 0)),                               # lse
            ],
            scratch_shapes=[
                pltpu.VMEM((SLOTS, block_kv, d_pad), k.dtype),  # K
                pltpu.VMEM((SLOTS, block_kv, d_pad), v.dtype),  # V
                pltpu.SemaphoreType.DMA((SLOTS,)),              # K sems
                pltpu.SemaphoreType.DMA((SLOTS,)),              # V sems
                pltpu.SMEM((1,), jnp.int32),        # slot of entry 0
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
            # Blocks are prefetched across grid steps, so the steps run
            # in order on one core.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="sla_fwd",
        )(lut[h0:h0 + hr].reshape(-1), counts[h0:h0 + hr], base,
          q, kb, vb, qp, hi, zi[:, :, None, :])

    with jax.named_scope("sla.sparse"):
        if base is None:
            base = jnp.zeros((1,), jnp.int32)
        # K/V stay in HBM, viewed as (BH_kv, T_n, block_kv, D_pad) so a
        # LUT entry names one block along a leading axis. Mosaic slices
        # an HBM ref only along whole 128-lane tiles, so an unaligned
        # head dim (LightningDiT's 108) is zero-padded; the kernel reads
        # the first D lanes.
        kb, vb = (
            (x if d_pad == d
             else jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - d))))
            .reshape(bh_kv, -1, block_kv, d_pad) for x in (k, v))
        outs = [launch(h0) for h0, _ in runs]
        o_s, o_l, lse = (jnp.concatenate(x) for x in zip(*outs))
        return o_s, o_l, lse[:, :, 0]
