"""Fused SLA decode Pallas TPU kernel (ISSUE 6 tentpole).

One launch covers a *chunk* of C decode tokens (C = 1 is the plain
`decode_step` shape): grid (B*H, C, K_sel). The trailing axis streams
the critical KV pages named by the per-token scalar-prefetched LUT
(`state["lut"]` / `state["cnt"]`), carrying online-softmax state in
VMEM scratch exactly like the prefill kernel (`sla_fwd`). Fused into
the same launch, the selected blocks' linear summaries (hblk / zblk)
accumulate into scratch so the finalize step can apply the subtractive
marginal aggregation of paper App. A.3 —

    H_marg = htot - sum_{j in lut} hblk[j]

against the running H/Z totals, replacing the 6-gather/einsum chain of
`backends._decode_gather_backend` with a single kernel. Exact because
decode plans classify with kl_frac = 0 (every valid non-critical block
is marginal; `SLAConfig.decode_plan_cfg`).

The public entry is wrapped in a `custom_vjp` whose backward runs
plain-JAX autodiff over `_decode_math` — a chunk-aware twin of the
gather backend's math — so learned-routing gradients flow through the
plan's marginal aggregation with the gather backend's contract. Integer
plan inputs (lut / cnt / marg / positions) get float0 tangents.

Where JAX's default backend is the CPU the kernel runs in Pallas
interpret mode (`repro.kernels.mode.default_interpret`); numerics are
identical either way: f32 accumulation, bf16 inputs cast on load.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import reference as ref
from repro.core.config import SLAConfig
from repro.kernels.mode import default_interpret

NEG_INF = -1e30
EPS = 1e-6
LANES = 128


def _dot(a, b, trans_b=False):
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _decode_kernel(lut_ref, cnt_ref, marg_ref, pos_ref,  # scalar prefetch
                   q_ref, qp_ref, k_ref, v_ref, hb_ref, zb_ref,
                   hd_ref, zd_ref, ht_ref, zt_ref,       # inputs
                   os_ref, ol_ref,                       # outputs
                   acc_ref, m_ref, l_ref, hsel_ref, zsel_ref,  # VMEM scratch
                   *, scale: float, k_sel: int, block_kv: int):
    bh, c, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        hsel_ref[...] = jnp.zeros_like(hsel_ref)
        zsel_ref[...] = jnp.zeros_like(zsel_ref)

    @pl.when(s < cnt_ref[bh, c])
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)               # (1, d)
        kk = k_ref[0, 0].astype(jnp.float32)              # (bkv, d)
        sij = _dot(q, kk, trans_b=True) * scale           # (1, bkv)
        j = lut_ref[bh, c, s]
        cols = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        sij = jnp.where(cols <= pos_ref[bh] + c, sij, NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(sij, axis=-1))
        p = jnp.exp(sij - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + _dot(p, v_ref[0, 0].astype(jnp.float32)))
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)
        # the diagonal block is still accumulating mid-chunk: its
        # streamed hblk/zblk are end-of-chunk values, so substitute the
        # per-token at-time partials (chunk boundary protocol; for
        # single-token decode hd/zd == the streamed block, a no-op)
        is_diag = j == (pos_ref[bh] + c) // block_kv
        hsel_ref[...] += jnp.where(is_diag, hd_ref[0, 0], hb_ref[0, 0])
        zsel_ref[...] += jnp.where(is_diag, zd_ref[0, 0], zb_ref[0, 0])

    @pl.when(s == k_sel - 1)
    def _finalize():
        l = l_ref[:, 0]
        alive = l > 0.0
        os_ref[0, 0] = (acc_ref[...] / jnp.where(alive, l, 1.0)[:, None]
                        ).astype(os_ref.dtype)
        # subtractive marginal linear branch against the H/Z totals
        qp = qp_ref[0, 0].astype(jnp.float32)             # (1, d)
        h_m = ht_ref[0, 0] - hsel_ref[...]                # (d, d)
        z_m = zt_ref[0, 0] - zsel_ref[...]                # (1, d)
        num = _dot(qp, h_m)                               # (1, d)
        den = jnp.sum(qp * z_m, axis=-1, keepdims=True)   # (1, 1)
        live = jnp.logical_and(den > EPS, marg_ref[bh, c] > 0)
        ol = jnp.where(live, num / jnp.where(live, den, 1.0), 0.0)
        ol_ref[0, 0] = ol.astype(ol_ref.dtype)


def _slab(x):
    """(..., D) -> (..., 1, D): a per-row vector becomes a (1, D) slab, the
    block shape Mosaic tiles (last two block dims equal the array's)."""
    return x[..., None, :]


def _decode_call(kern, prefetch, operands, *, page_map, block_kv, group,
                 interpret, name):
    """One pallas_call for both decode layouts. `prefetch` holds the
    scalar-prefetch operands (LUT first); `page_map(bh, c, s, *refs)`
    names the (kv-head, page) each LUT entry streams K/V/h/z from.

    Per-row vectors (q, qp, zblk, zdiag, ztot, outputs) travel as (1, D)
    slabs so every block's last two dims are the array's own, as Mosaic
    requires. Returns (o_s, o_l), both (BH, C, D) f32."""
    q, qp, k, v, hblk, zblk, hdiag, zdiag, htot, ztot = operands
    bh, c, k_sel = prefetch[0].shape
    d = q.shape[-1]

    def kv_map(*a):
        return (*page_map(*a), 0, 0)

    def tok_map(bh_i, c_i, s, *_):
        return (bh_i, c_i, 0, 0)

    def kv_tok_map(bh_i, c_i, s, *_):
        return (bh_i // group, c_i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(bh, c, k_sel),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), tok_map),                 # q
            pl.BlockSpec((1, 1, 1, d), tok_map),                 # qp
            pl.BlockSpec((1, 1, block_kv, d), kv_map),           # k
            pl.BlockSpec((1, 1, block_kv, d), kv_map),           # v
            pl.BlockSpec((1, 1, d, d), kv_map),                  # hblk
            pl.BlockSpec((1, 1, 1, d), kv_map),                  # zblk
            pl.BlockSpec((1, 1, d, d), kv_tok_map),              # hdiag
            pl.BlockSpec((1, 1, 1, d), kv_tok_map),              # zdiag
            pl.BlockSpec((1, 1, d, d), kv_tok_map),              # htot
            pl.BlockSpec((1, 1, 1, d), kv_tok_map),              # ztot
        ],
        out_specs=[pl.BlockSpec((1, 1, 1, d), tok_map)] * 2,
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),       # acc
            pltpu.VMEM((1, LANES), jnp.float32),   # m
            pltpu.VMEM((1, LANES), jnp.float32),   # l
            pltpu.VMEM((d, d), jnp.float32),       # hsel
            pltpu.VMEM((1, d), jnp.float32),       # zsel
        ],
    )
    o_s, o_l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bh, c, 1, d), jnp.float32)] * 2,
        interpret=interpret,
        name=name,
    )(*prefetch, _slab(q), _slab(qp), k, v, hblk, _slab(zblk), hdiag,
      _slab(zdiag), htot, _slab(ztot))
    return o_s[:, :, 0], o_l[:, :, 0]


@functools.partial(
    jax.jit, static_argnames=("scale", "block_kv", "group", "interpret"))
def _fused_decode(lut, cnt, marg, posv, q, qp, k, v, hblk, zblk,
                  hdiag, zdiag, htot, ztot,
                  *, scale, block_kv, group, interpret):
    """Flat-layout fused decode: one launch for C tokens x K_sel blocks.

    lut: (BH, C, K) int32; cnt/marg: (BH, C) int32; posv: (BH,) int32
    base positions (token c sits at posv + c). q/qp: (BH, C, D).
    k/v: (BH_kv, Tn, bkv, D); hblk: (BH_kv, Tn, D, D); zblk: (BH_kv,
    Tn, D); hdiag/htot: per-token snapshots (BH_kv, C, D, D);
    zdiag/ztot: (BH_kv, C, D). Returns (o_s, o_l) both (BH, C, D) f32.
    """
    kern = functools.partial(_decode_kernel, scale=scale,
                             k_sel=lut.shape[-1], block_kv=block_kv)

    def page_map(bh_i, c_i, s, lut_ref, *_):
        return (bh_i // group, lut_ref[bh_i, c_i, s])

    return _decode_call(
        kern, (lut, cnt, marg, posv),
        (q, qp, k, v, hblk, zblk, hdiag, zdiag, htot, ztot),
        page_map=page_map, block_kv=block_kv, group=group,
        interpret=interpret, name="sla_decode")


def _decode_kernel_paged(lut_ref, plut_ref, cnt_ref, marg_ref, pos_ref,
                         *args, **kw):
    """Paged kernel body: identical math to `_decode_kernel` — the extra
    `plut_ref` (physical page ids) is consumed only by the BlockSpec
    index maps that stream KV/h/z pages out of the global pools; all
    masking arithmetic (column ids, diagonal detection) stays on the
    LOGICAL block ids in `lut_ref`."""
    return _decode_kernel(lut_ref, cnt_ref, marg_ref, pos_ref, *args, **kw)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_kv", "group", "hkv", "interpret"))
def _fused_decode_paged(lut, plut, cnt, marg, posv, q, qp, k, v, hblk, zblk,
                        hdiag, zdiag, htot, ztot,
                        *, scale, block_kv, group, hkv, interpret):
    """Paged fused decode (DESIGN.md "Paged KV & prefix caching"): the
    scalar-prefetched LUT points at the PAGE TABLE instead of contiguous
    cache rows.

    lut: (BH, C, K) logical block ids (masking math); plut: (BH, C, K)
    physical page ids (plut = pt[b, lut] — the block-streaming index).
    k/v: (Hkv, P, bkv, D) and hblk: (Hkv, P, D, D) / zblk: (Hkv, P, D)
    are the global page pools, head-major so the index map addresses
    them as ((bh // group) % hkv, page); per-token operands (q/qp,
    hdiag/zdiag, htot/ztot) keep the flat (B*H / B*Hkv, C, ...) layout
    of `_fused_decode`. Returns (o_s, o_l) both (BH, C, D) f32."""
    kern = functools.partial(_decode_kernel_paged, scale=scale,
                             k_sel=lut.shape[-1], block_kv=block_kv)

    def page_map(bh_i, c_i, s, lut_ref, plut_ref, *_):
        return ((bh_i // group) % hkv, plut_ref[bh_i, c_i, s])

    return _decode_call(
        kern, (lut, plut, cnt, marg, posv),
        (q, qp, k, v, hblk, zblk, hdiag, zdiag, htot, ztot),
        page_map=page_map, block_kv=block_kv, group=group,
        interpret=interpret, name="sla_decode_paged")


def _decode_attention_paged(state, qg, qpg, pos, cfg: SLAConfig, scale,
                            interpret: bool):
    """Paged entry: route the live-row LUT through the page table and
    launch `_fused_decode_paged` against the global pools. Single-token
    steps only (the chunked path snapshots per-token state the paged
    scheduler never builds); inference-only — no custom VJP (serving
    decode never differentiates)."""
    b, hkv, g, cdim, d = qg.shape
    if cdim != 1:
        raise ValueError(
            "paged fused decode supports single-token steps only "
            f"(got chunk of {cdim})")
    h = hkv * g
    bh = b * h
    pt = state["pt"]
    tn = pt.shape[1]
    bkv = cfg.block_kv
    lut, cnt, marg = state["lut"], state["cnt"], state["marg"]
    plut = jax.vmap(lambda row, l: row[l])(pt, lut)       # (B, H, K)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    # the diagonal (still-accumulating) block's partials, read from the
    # pool at the slot's current page (clamp keeps runaway inactive
    # slots on a valid — scratch — page)
    dpid = pt[jnp.arange(b), jnp.minimum(posv // bkv, tn - 1)]
    hdiag = state["hblk"][dpid]                           # (B, Hkv, D, D)
    zdiag = state["zblk"][dpid]                           # (B, Hkv, D)
    k_sel = lut.shape[-1]
    scale = float(d**-0.5) if scale is None else float(scale)
    o_s, o_l = _fused_decode_paged(
        lut.reshape(bh, 1, k_sel).astype(jnp.int32),
        plut.reshape(bh, 1, k_sel).astype(jnp.int32),
        cnt.reshape(bh, 1).astype(jnp.int32),
        marg.reshape(bh, 1).astype(jnp.int32),
        jnp.repeat(posv, h),
        qg.astype(jnp.float32).reshape(bh, 1, d),
        qpg.astype(jnp.float32).reshape(bh, 1, d),
        jnp.moveaxis(state["k"], 0, 1), jnp.moveaxis(state["v"], 0, 1),
        jnp.moveaxis(state["hblk"], 0, 1),
        jnp.moveaxis(state["zblk"], 0, 1),
        hdiag.reshape(b * hkv, 1, d, d), zdiag.reshape(b * hkv, 1, d),
        state["htot"].reshape(b * hkv, 1, d, d),
        state["ztot"].reshape(b * hkv, 1, d),
        scale=scale, block_kv=bkv, group=g, hkv=hkv,
        interpret=bool(interpret))
    shape = (b, hkv, g, 1, d)
    return o_s.reshape(shape), o_l.reshape(shape)


# ---------------------------------------------------------------------------
# plain-JAX twin: the gather backend's math with a chunk axis
# ---------------------------------------------------------------------------
def _decode_math(q, qp, kc, vc, hblk, zblk, hdiag, zdiag, htot, ztot,
                 lut, cnt, marg, posv, cfg: SLAConfig, scale: float):
    """Chunk-aware gather-backend math (autodiff reference + VJP body).

    q/qp: (B, Hkv, G, C, D) f32; kc/vc: (B, Hkv, Smax, D);
    hblk: (B, Hkv, Tn, D, D); zblk: (B, Hkv, Tn, D); hdiag/htot:
    per-token snapshots (B, Hkv, C, D, D); zdiag/ztot: (B, Hkv, C, D);
    lut: (B, Hkv, G, C, K) int32; cnt/marg: (B, Hkv, G, C) int32;
    posv: (B,) int32 base positions. Returns (o_s, o_l), both
    (B, Hkv, G, C, D) f32 — for C = 1 this reduces term-for-term to
    `backends._decode_gather_backend`.
    """
    b, hkv, g, cdim, d = q.shape
    bkv = cfg.block_kv
    tn = kc.shape[2] // bkv
    k_sel = lut.shape[-1]
    idx = lut.reshape(b, hkv, -1)

    def gat(x):
        pad = (1,) * (x.ndim - 3)
        out = jnp.take_along_axis(x, idx.reshape(b, hkv, -1, *pad), axis=2)
        return out.reshape(b, hkv, g, cdim, k_sel, *x.shape[3:])

    kg = gat(kc.astype(jnp.float32).reshape(b, hkv, tn, bkv, d))
    vg = gat(vc.astype(jnp.float32).reshape(b, hkv, tn, bkv, d))
    s = jnp.einsum("bngcd,bngckvd->bngckv", q, kg) * scale
    pos_tok = posv[:, None] + jnp.arange(cdim)           # (B, C)
    cols = lut[..., None] * bkv + jnp.arange(bkv)        # (B,Hkv,G,C,K,bkv)
    live = jnp.arange(k_sel) < cnt[..., None]            # (B,Hkv,G,C,K)
    ok = jnp.logical_and(
        cols <= pos_tok[:, None, None, :, None, None], live[..., None])
    sf = jnp.where(ok, s, NEG_INF).reshape(b, hkv, g, cdim, k_sel * bkv)
    m = jnp.max(sf, axis=-1, keepdims=True)
    p = jnp.exp(sf - m)
    o_s = jnp.einsum("bngck,bngckd->bngcd",
                     p / jnp.sum(p, -1, keepdims=True),
                     vg.reshape(b, hkv, g, cdim, k_sel * bkv, d))
    # subtractive marginal aggregation against the per-token totals;
    # the mid-chunk diagonal block reads its at-time partial (chunk
    # boundary protocol, same substitution as the kernel)
    is_diag = lut == (pos_tok // bkv)[:, None, None, :, None]
    hg = jnp.where(is_diag[..., None, None],
                   hdiag[:, :, None, :, None], gat(hblk))
    zg = jnp.where(is_diag[..., None], zdiag[:, :, None, :, None], gat(zblk))
    hg = jnp.where(live[..., None, None], hg, 0.0)
    zg = jnp.where(live[..., None], zg, 0.0)
    h_m = htot[:, :, None] - jnp.sum(hg, axis=4)         # (B,Hkv,G,C,D,D)
    z_m = ztot[:, :, None] - jnp.sum(zg, axis=4)
    num = jnp.einsum("bngcd,bngcde->bngce", qp, h_m)
    den = jnp.einsum("bngcd,bngcd->bngc", qp, z_m)[..., None]
    o_l = ref._safe_div(num, den)
    o_l = jnp.where(marg[..., None] > 0, o_l, 0.0)
    return o_s, o_l


# ---------------------------------------------------------------------------
# custom_vjp: Pallas forward, gather-math backward
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(14, 15, 16))
def _decode_core(q, qp, kc, vc, hblk, zblk, hdiag, zdiag, htot, ztot,
                 lut, cnt, marg, posv, cfg, scale, interpret):
    out, _ = _decode_core_fwd(q, qp, kc, vc, hblk, zblk, hdiag, zdiag,
                              htot, ztot, lut, cnt, marg, posv,
                              cfg, scale, interpret)
    return out


def _decode_core_fwd(q, qp, kc, vc, hblk, zblk, hdiag, zdiag, htot, ztot,
                     lut, cnt, marg, posv, cfg, scale, interpret):
    b, hkv, g, cdim, d = q.shape
    h = hkv * g
    bh = b * h
    bkv = cfg.block_kv
    tn = kc.shape[2] // bkv
    k_sel = lut.shape[-1]
    # (b, hkv, g, ...) flattens so flat bh // g == b * hkv + n exactly
    # as the prefill kernel's head layout (bh = b*H + n*g + gi).
    o_s, o_l = _fused_decode(
        lut.reshape(bh, cdim, k_sel),
        cnt.reshape(bh, cdim).astype(jnp.int32),
        marg.reshape(bh, cdim).astype(jnp.int32),
        jnp.repeat(posv.astype(jnp.int32), h),
        q.reshape(bh, cdim, d), qp.reshape(bh, cdim, d),
        kc.reshape(b * hkv, tn, bkv, d), vc.reshape(b * hkv, tn, bkv, d),
        hblk.reshape(b * hkv, tn, d, d), zblk.reshape(b * hkv, tn, d),
        hdiag.reshape(b * hkv, cdim, d, d), zdiag.reshape(b * hkv, cdim, d),
        htot.reshape(b * hkv, cdim, d, d), ztot.reshape(b * hkv, cdim, d),
        scale=scale, block_kv=bkv, group=g, interpret=interpret)
    shape = (b, hkv, g, cdim, d)
    out = (o_s.reshape(shape), o_l.reshape(shape))
    res = (q, qp, kc, vc, hblk, zblk, hdiag, zdiag, htot, ztot,
           lut, cnt, marg, posv)
    return out, res


def _decode_core_bwd(cfg, scale, interpret, res, cts):
    (q, qp, kc, vc, hblk, zblk, hdiag, zdiag, htot, ztot,
     lut, cnt, marg, posv) = res

    def f(q_, qp_, k_, v_, hb_, zb_, hd_, zd_, ht_, zt_):
        return _decode_math(q_, qp_, k_, v_, hb_, zb_, hd_, zd_, ht_, zt_,
                            lut, cnt, marg, posv, cfg, scale)

    _, vjp = jax.vjp(f, q, qp, kc, vc, hblk, zblk, hdiag, zdiag, htot, ztot)
    grads = vjp(cts)
    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # noqa: E731
    return grads + (f0(lut), f0(cnt), f0(marg), f0(posv))


_decode_core.defvjp(_decode_core_fwd, _decode_core_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
def decode_attention(state, qg, qpg, pos, cfg: SLAConfig, scale=None,
                     interpret: bool | None = None):
    """Fused decode attention for a chunk of C tokens.

    qg / qpg: (B, Hkv, G, C, D) grouped queries (C = 1 for single-token
    decode). `state` is the decode-cache slice of `backends`: k/v
    (B, Hkv, Smax, D); hblk (B, Hkv, Tn, D, D); zblk (B, Hkv, Tn, D);
    htot/ztot either running totals (B, Hkv, D, D) — broadcast to every
    token — or per-token chunk snapshots with a C axis at dim 2;
    lut/cnt/marg either live-row (B, H, K)/(B, H) or per-token with a C
    axis before K. `pos` is the base position: scalar or (B,) per-slot
    (token c sits at pos + c). Returns (o_s, o_l), both
    (B, Hkv, G, C, D) f32; gradients flow through q/qp/k/v/hblk/zblk/
    htot/ztot via the gather-math VJP. `interpret` None decides by the
    default backend (`repro.kernels.mode.default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    if "pt" in state:
        return _decode_attention_paged(state, qg, qpg, pos, cfg, scale,
                                       interpret)
    b, hkv, g, cdim, d = qg.shape
    lut, cnt, marg = state["lut"], state["cnt"], state["marg"]
    if lut.ndim == 3:                       # (B, H, K) live-row layout:
        # every chunk token shares the one live plan row
        lut = jnp.broadcast_to(lut[:, :, None],
                               (*lut.shape[:2], cdim, lut.shape[-1]))
        cnt = jnp.broadcast_to(cnt[..., None], (*cnt.shape, cdim))
        marg = jnp.broadcast_to(marg[..., None], (*marg.shape, cdim))
    htot, ztot = state["htot"], state["ztot"]
    if htot.ndim == 4:                      # (B, Hkv, D, D) running total
        htot = jnp.broadcast_to(htot[:, :, None], (b, hkv, cdim, d, d))
        ztot = jnp.broadcast_to(ztot[:, :, None], (b, hkv, cdim, d))
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    hdiag, zdiag = state.get("hdiag"), state.get("zdiag")
    if hdiag is None:
        # live-row decode: the at-time diagonal partial IS the stored
        # block — slice it so the kernel's substitution is a no-op
        rows = (posv[:, None] + jnp.arange(cdim)) // cfg.block_kv  # (B, C)
        hdiag = jnp.take_along_axis(
            state["hblk"], rows[:, None, :, None, None], axis=2)
        zdiag = jnp.take_along_axis(
            state["zblk"], rows[:, None, :, None], axis=2)
    k_sel = lut.shape[-1]
    lutg = lut.reshape(b, hkv, g, cdim, k_sel)
    cntg = cnt.reshape(b, hkv, g, cdim)
    margg = marg.reshape(b, hkv, g, cdim)
    scale = float(d**-0.5) if scale is None else float(scale)
    return _decode_core(qg.astype(jnp.float32), qpg.astype(jnp.float32),
                        state["k"], state["v"], state["hblk"], state["zblk"],
                        hdiag, zdiag, htot, ztot, lutg, cntg, margg, posv,
                        cfg, scale, bool(interpret))
