"""Attention execution backends: one registry, one `execute` interface.

The plan/execute split (DESIGN.md): `core/plan.py` classifies blocks and
builds LUTs once; this module runs the actual attention math given that
plan. Three built-in backends, all returning (O^s, O^l):

  reference  dense pure-jnp oracle (autodiff; O(N^2) compiled FLOPs —
             validation only)
  gather     LUT-gather XLA path whose compiled FLOPs equal the true
             sparse cost (training / dry-run / any-backend production)
  kernel     fused Pallas TPU kernels with custom_vjp (compiled on the
             TPU; interpret mode where the default backend is the CPU,
             `repro.kernels.mode.default_interpret`)

`execute(plan, params, q, k, v, cfg, backend=...)` is the single entry
point every model goes through — it owns mode dispatch ("sla" /
"sparse_only" / "linear_only" / "l_plus_s" / "full"), the phi feature
maps, GQA head broadcast, and the learned Proj merge (Eq. 6). New
backends register with `@register_backend("name")`.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import SLAConfig
from repro.core.phi import phi
from repro.core.plan import SLAPlan, plan_attention
from repro.core import reference as ref

Params = Dict[str, jax.Array]
# A backend maps (plan, q, k, v, qp, kp, cfg, scale) -> (O^s, O^l).
BackendFn = Callable[..., Tuple[jax.Array, jax.Array]]

_BACKENDS: Dict[str, BackendFn] = {}

# Legacy spellings from the pre-registry stringly-typed API.
_ALIASES = {"pallas": "kernel", "xla": "gather", "dense": "reference"}


def register_backend(name: str) -> Callable[[BackendFn], BackendFn]:
    """Decorator: register `fn` as the SLA execution backend `name`."""

    def deco(fn: BackendFn) -> BackendFn:
        _BACKENDS[name] = fn
        return fn

    return deco


def resolve(name: str) -> str:
    """Canonical backend name for `name` (resolving legacy aliases).

    The ONE validation/error path for stringly-typed backend selection:
    drivers, benchmarks, and examples call this at entry so an unknown
    `backend=` fails loudly up front instead of silently falling back
    (or failing deep inside a jit trace)."""
    key = _ALIASES.get(name, name)
    if key not in _BACKENDS:
        raise ValueError(
            f"unknown SLA backend {name!r}; available: "
            f"{sorted(_BACKENDS)} (aliases: "
            f"{ {a: t for a, t in sorted(_ALIASES.items())} })")
    return key


def get_backend(name: str) -> BackendFn:
    return _BACKENDS[resolve(name)]


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


@register_backend("reference")
def _reference_backend(plan, q, k, v, qp, kp, cfg, scale):
    # plan.marginal is value-identical to (mc == 0) but carries the
    # learned-routing straight-through gradients when present
    return ref.sla_forward_reference(q, k, v, qp, kp, plan.mc, cfg, scale,
                                     marginal=plan.marginal)


@register_backend("gather")
def _gather_backend(plan, q, k, v, qp, kp, cfg, scale):
    from repro.core.block_sparse_xla import sla_forward_gather
    return sla_forward_gather(q, k, v, qp, kp, plan, cfg, scale)


@register_backend("kernel")
def _kernel_backend(plan, q, k, v, qp, kp, cfg, scale):
    from repro.kernels import ops as kops
    return kops.sla_attention_core(q, k, v, qp, kp, plan, cfg, scale=scale)


def _repeat_kv(x: jax.Array, num_q_heads: int) -> jax.Array:
    """GQA: broadcast KV heads to match Q heads. (B, Hkv, N, D) -> (B, H, N, D)."""
    hkv = x.shape[1]
    if hkv == num_q_heads:
        return x
    assert num_q_heads % hkv == 0
    return jnp.repeat(x, num_q_heads // hkv, axis=1)


def execute(
    plan: Optional[SLAPlan],
    params: Optional[Params],
    q: jax.Array, k: jax.Array, v: jax.Array,
    cfg: SLAConfig,
    scale: Optional[float] = None,
    backend: str = "reference",
    routing: Optional[Params] = None,
) -> jax.Array:
    """Run SLA attention under `cfg.mode` with the given execution backend.

    q: (B, H, N, D); k, v: (B, Hkv, N, D) with Hkv | H. `plan` is the
    precomputed SLAPlan for (q, k); pass None to plan inline (the
    classic fused path — planning then costs on every call). Modes that
    need no block structure ("full", "linear_only") ignore the plan.
    `routing` holds the learned-routing scorer parameters for inline
    planning under cfg.routing_mode == "learned" (ignored when a plan
    is given — the plan already encodes its routing decisions).

    Returns (B, H, N, D) in q.dtype.
    """
    backend = resolve(backend)  # fail loudly even in plan-free modes
    cfg.validate()
    in_dtype = q.dtype
    h = q.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)

    if cfg.mode == "full":
        return ref.full_attention(q, k, v, cfg.causal, scale).astype(in_dtype)

    if cfg.mode == "linear_only":
        qp, kp = phi(q, cfg.phi), phi(k, cfg.phi)
        o = ref.full_linear(qp, kp, v)
        if params is not None:
            o = jnp.einsum("bhnd,hde->bhne", o, params["proj"].astype(jnp.float32))
        return o.astype(in_dtype)

    if plan is None:
        plan = plan_attention(q, k, cfg, scale, routing=routing)
    else:
        tm, tn = q.shape[2] // cfg.block_q, k.shape[2] // cfg.block_kv
        if plan.mc.shape[-2:] != (tm, tn):
            raise ValueError(
                f"stale SLAPlan: plan is for {plan.mc.shape[-2:]} blocks "
                f"but (q, k) need ({tm}, {tn}) — re-plan with "
                f"plan_attention(q, k, cfg)")

    if cfg.mode == "sparse_only":
        o_s, _ = ref.sparse_component(q, k, v, plan.mc, cfg, scale)
        return o_s.astype(in_dtype)

    with jax.named_scope("sla.linear"):
        qp, kp = phi(q, cfg.phi), phi(k, cfg.phi)

    if cfg.mode == "l_plus_s":
        o_s, _ = ref.sparse_component(q, k, v, plan.mc, cfg, scale)
        o_l = ref.full_linear(qp, kp, v)
        return (o_s + o_l).astype(in_dtype)

    if cfg.mode != "sla":
        raise ValueError(f"unknown SLA mode {cfg.mode!r}")

    o_s, o_l = get_backend(backend)(plan, q, k, v, qp, kp, cfg, scale)

    with jax.named_scope("sla.merge"):
        proj = params["proj"].astype(jnp.float32)
        o = o_s + jnp.einsum("bhnd,hde->bhne", o_l, proj)
        return o.astype(in_dtype)


# ---------------------------------------------------------------------------
# decode execution: one token against the static decode cache
# (DESIGN.md "Decode-time SLA")
# ---------------------------------------------------------------------------
# A decode backend maps (state, qg, qpg, pos, cfg, scale) -> (O^s, O^l),
# both (B, Hkv, G, D) f32, where G = H // Hkv is the GQA group size and
# `state` is the per-layer decode-cache slice:
#   k, v   : (B, Hkv, Smax, D)   static KV cache (Smax = Tn * block_kv)
#   hblk   : (B, Hkv, Tn, D, D)  per-block running  h_j = sum phi(k) v^T
#   zblk   : (B, Hkv, Tn, D)     per-block running  z_j = sum phi(k)
#   htot   : (B, Hkv, D, D)      running total      H   = sum_j h_j
#   ztot   : (B, Hkv, D)         running total      Z   = sum_j z_j
#   lut    : (B, H, K) int32     live row's critical block ids
#   cnt    : (B, H)    int32     live entries in lut
#   marg   : (B, H)    int32     live row's marginal block count
# The linear branch is the subtractive aggregation (paper App. A.3):
#   H_marg = htot - sum_{j in lut} hblk[j]
# exact because the decode plan classifies with kl_frac = 0 (every valid
# non-critical block is marginal; SLAConfig.decode_plan_cfg).
_DECODE_BACKENDS: Dict[str, BackendFn] = {}

# "kernel" is the real fused Pallas decode kernel (kernels/sla_decode);
# "xla" names the un-fused gather/einsum chain explicitly.
_DECODE_ALIASES = {"pallas": "kernel", "xla": "gather", "dense": "reference"}


def register_decode_backend(name: str) -> Callable[[BackendFn], BackendFn]:
    def deco(fn: BackendFn) -> BackendFn:
        _DECODE_BACKENDS[name] = fn
        return fn

    return deco


def resolve_decode(name: str) -> str:
    """Canonical decode-backend name (loud failure, like `resolve`)."""
    key = _DECODE_ALIASES.get(name, name)
    if key not in _DECODE_BACKENDS:
        raise ValueError(
            f"unknown SLA decode backend {name!r}; available: "
            f"{sorted(_DECODE_BACKENDS)} (aliases: "
            f"{ {a: t for a, t in sorted(_DECODE_ALIASES.items())} })")
    return key


def _group_heads(x: jax.Array, hkv: int) -> jax.Array:
    """(B, H, ...) -> (B, Hkv, G, ...): the same head layout jnp.repeat
    produces (q head h <-> (h // G, h % G))."""
    b, h = x.shape[:2]
    return x.reshape(b, hkv, h // hkv, *x.shape[2:])


def _gather_state(x: jax.Array, idx: jax.Array, k_sel: int) -> jax.Array:
    """x: (B, Hkv, Tn, ...); idx: (B, Hkv, G*K) -> (B, Hkv, G, K, ...)."""
    b, hkv = x.shape[:2]
    pad = (1,) * (x.ndim - 3)
    out = jnp.take_along_axis(x, idx.reshape(b, hkv, -1, *pad), axis=2)
    return out.reshape(b, hkv, -1, k_sel, *x.shape[3:])


def _gather_pool(pool: jax.Array, idx: jax.Array, k_sel: int) -> jax.Array:
    """Paged analogue of `_gather_state`: pool (P, Hkv, ...) gathered by
    PHYSICAL page ids idx (B, Hkv, G*K) -> (B, Hkv, G, K, ...).

    The ids come from routing the logical LUT through the page table
    (`plut = pt[b, lut]`), so the gathered blocks are byte-identical to
    what the monolithic layout's take_along_axis would read. Dead LUT
    entries (beyond `cnt`) may land on arbitrary live pages — exactly
    like the monolithic path they are masked to exact zeros downstream."""
    out = jax.vmap(lambda pn, ixn: pn[ixn], in_axes=(1, 1), out_axes=1)(
        pool, idx)
    return out.reshape(out.shape[0], out.shape[1], -1, k_sel,
                       *pool.shape[2:])


def _physical_lut(pt: jax.Array, lut: jax.Array) -> jax.Array:
    """Logical block ids -> physical page ids: pt (B, Tn), lut
    (B, H, K) -> (B, H, K)."""
    return jax.vmap(lambda row, l: row[l])(pt, lut)


def _paged_dense_state(state, bkv: int):
    """Materialize a monolithic decode-state slice from a paged one
    (page-gathered KV + per-block partials) for backends that want the
    contiguous layout (the dense reference oracle)."""
    pt = state["pt"]

    def blk(pool):  # (P, Hkv, ...) -> (B, Hkv, Tn, ...)
        return jnp.moveaxis(jnp.take(pool, pt, axis=0), 2, 1)

    out = {k: v for k, v in state.items() if k != "pt"}
    kd, vd = blk(state["k"]), blk(state["v"])
    out["k"] = kd.reshape(kd.shape[:2] + (-1, kd.shape[-1]))
    out["v"] = vd.reshape(vd.shape[:2] + (-1, vd.shape[-1]))
    out["hblk"] = blk(state["hblk"])
    out["zblk"] = blk(state["zblk"])
    return out


@register_decode_backend("gather")
def _decode_gather_backend(state, qg, qpg, pos, cfg, scale):
    """O(K * bkv * d) sparse + O(K * d^2) subtractive linear per token.

    Paged decode state (`"pt"` present; DESIGN.md "Paged KV & prefix
    caching") gathers the SAME K critical blocks straight out of the
    global page pools through the page table — physical ids replace
    logical ones at the gather and nowhere else (masking math keeps the
    logical LUT), so paged and monolithic outputs are bitwise equal."""
    paged = "pt" in state
    kc, vc = state["k"], state["v"]
    bkv = cfg.block_kv
    if paged:
        b, tn = state["pt"].shape
        hkv, d = kc.shape[1], kc.shape[-1]
    else:
        b, hkv, smax, d = kc.shape
        tn = smax // bkv
    lutg = _group_heads(state["lut"], hkv)          # (B, Hkv, G, K)
    cntg = _group_heads(state["cnt"], hkv)          # (B, Hkv, G)
    k_sel = lutg.shape[-1]
    if paged:
        pidx = _group_heads(_physical_lut(state["pt"], state["lut"]),
                            hkv).reshape(b, hkv, -1)
        kg = _gather_pool(kc, pidx, k_sel)
        vg = _gather_pool(vc, pidx, k_sel)
    else:
        idx = lutg.reshape(b, hkv, -1)
        kg = _gather_state(kc.reshape(b, hkv, tn, bkv, d), idx, k_sel)
        vg = _gather_state(vc.reshape(b, hkv, tn, bkv, d), idx, k_sel)
    s = jnp.einsum("bngd,bngkvd->bngkv", qg,
                   kg.astype(jnp.float32)) * scale
    cols = lutg[..., None] * bkv + jnp.arange(bkv)  # (B, Hkv, G, K, bkv)
    live = jnp.arange(k_sel) < cntg[..., None]      # (B, Hkv, G, K)
    # pos: scalar (static-batch decode) or (B,) per-slot positions
    # (continuous-batching scheduler; DESIGN.md "Serving API v2")
    posc = pos if jnp.ndim(pos) == 0 else pos[:, None, None, None, None]
    s = jnp.where(jnp.logical_and(cols <= posc, live[..., None]), s, -1e30)
    sf = s.reshape(b, hkv, -1, k_sel * bkv)
    m = jnp.max(sf, axis=-1, keepdims=True)
    p = jnp.exp(sf - m)
    o_s = jnp.einsum("bngk,bngkd->bngd", p / jnp.sum(p, -1, keepdims=True),
                     vg.reshape(b, hkv, -1, k_sel * bkv, d)
                     .astype(jnp.float32))
    # subtractive marginal aggregation from the running state
    if paged:
        hg = _gather_pool(state["hblk"], pidx, k_sel)
        zg = _gather_pool(state["zblk"], pidx, k_sel)
    else:
        hg = _gather_state(state["hblk"], idx, k_sel)  # (B,Hkv,G,K,D,D)
        zg = _gather_state(state["zblk"], idx, k_sel)  # (B,Hkv,G,K,D)
    hg = jnp.where(live[..., None, None], hg, 0.0)
    zg = jnp.where(live[..., None], zg, 0.0)
    h_m = state["htot"][:, :, None] - jnp.sum(hg, axis=3)
    z_m = state["ztot"][:, :, None] - jnp.sum(zg, axis=3)
    num = jnp.einsum("bngd,bngde->bnge", qpg, h_m)
    den = jnp.einsum("bngd,bngd->bng", qpg, z_m)[..., None]
    o_l = ref._safe_div(num, den)
    # rows with an empty marginal set produce exact zeros (the residual
    # of the subtraction is f32 noise; never divide noise by noise)
    margg = _group_heads(state["marg"], hkv)
    o_l = jnp.where(margg[..., None] > 0, o_l, 0.0)
    return o_s, o_l


@register_decode_backend("kernel")
def _decode_kernel_backend(state, qg, qpg, pos, cfg, scale):
    """Fused Pallas decode kernel (kernels/sla_decode): one launch for
    sparse softmax over the LUT pages + the subtractive marginal linear
    branch. Interpreted only where the default backend is the CPU
    (identical numerics, no Mosaic lowering)."""
    from repro.kernels import sla_decode

    o_s, o_l = sla_decode.decode_attention(
        state, qg[..., None, :], qpg[..., None, :], pos, cfg, scale)
    return o_s[..., 0, :], o_l[..., 0, :]


@register_decode_backend("reference")
def _decode_reference_backend(state, qg, qpg, pos, cfg, scale):
    """Dense O(S) oracle: expands the live row's block structure to a
    token mask and aggregates marginal blocks directly (validation).
    Paged state is densified up front (the oracle wants the contiguous
    layout anyway — it reads every position)."""
    if "pt" in state:
        state = _paged_dense_state(state, cfg.block_kv)
    kc, vc = state["k"], state["v"]
    b, hkv, smax, d = kc.shape
    bkv = cfg.block_kv
    tn = smax // bkv
    lutg = _group_heads(state["lut"], hkv)
    cntg = _group_heads(state["cnt"], hkv)
    k_sel = lutg.shape[-1]
    live = jnp.arange(k_sel) < cntg[..., None]
    crit_blk = jnp.any(
        jnp.logical_and(lutg[..., None] == jnp.arange(tn), live[..., None]),
        axis=3)                                     # (B, Hkv, G, Tn)
    crit_tok = jnp.repeat(crit_blk, bkv, axis=-1)   # (B, Hkv, G, Smax)
    s = jnp.einsum("bngd,bnsd->bngs", qg, kc.astype(jnp.float32)) * scale
    # pos: scalar or (B,) per-slot positions (continuous batching)
    post = pos if jnp.ndim(pos) == 0 else pos[:, None, None, None]
    keep = jnp.logical_and(crit_tok, jnp.arange(smax) <= post)
    s = jnp.where(keep, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    o_s = jnp.einsum("bngs,bnsd->bngd", p / jnp.sum(p, -1, keepdims=True),
                     vc.astype(jnp.float32))
    valid = jnp.arange(tn) <= post // bkv
    marg = jnp.logical_and(valid, ~crit_blk).astype(jnp.float32)
    h_m = jnp.einsum("bngt,bntde->bngde", marg, state["hblk"])
    z_m = jnp.einsum("bngt,bntd->bngd", marg, state["zblk"])
    num = jnp.einsum("bngd,bngde->bnge", qpg, h_m)
    den = jnp.einsum("bngd,bngd->bng", qpg, z_m)[..., None]
    return o_s, ref._safe_div(num, den)


def decode_execute(
    state: Dict[str, jax.Array],
    params: Optional[Params],
    q: jax.Array, pos, cfg: SLAConfig,
    scale: Optional[float] = None,
    backend: str = "gather",
) -> jax.Array:
    """One-token SLA attention against the decode cache state.

    q: (B, H, 1, D) the new token's query; `pos` its (traced) position —
    a scalar (static-batch decode: every row shares it) or a (B,) vector
    of per-slot positions (continuous-batching scheduler; DESIGN.md
    "Serving API v2"). Returns (B, H, D) in q.dtype — O^s + Proj(O^l)
    under cfg.mode "sla", O^s alone under "sparse_only".
    """
    backend = resolve_decode(backend)
    cfg.validate()
    in_dtype = q.dtype
    b, h, _, d = q.shape
    hkv = state["k"].shape[1]
    scale = (d**-0.5) if scale is None else scale
    qg = _group_heads(q[:, :, 0, :].astype(jnp.float32), hkv)
    qpg = _group_heads(phi(q[:, :, 0, :], cfg.phi), hkv)
    o_s, o_l = _DECODE_BACKENDS[backend](state, qg, qpg, pos, cfg, scale)
    o_s = o_s.reshape(b, h, d)
    if cfg.mode == "sparse_only":
        return o_s.astype(in_dtype)
    if cfg.mode != "sla":
        raise ValueError(
            f"decode_execute supports modes 'sla'/'sparse_only', got "
            f"{cfg.mode!r}")
    proj = params["proj"].astype(jnp.float32)
    o = o_s + jnp.einsum("bhd,hde->bhe", o_l.reshape(b, h, d), proj)
    return o.astype(in_dtype)


def decode_execute_chunk(
    state: Dict[str, jax.Array],
    params: Optional[Params],
    q: jax.Array, pos, cfg: SLAConfig,
    scale: Optional[float] = None,
    backend: str = "gather",
) -> jax.Array:
    """C-token chunked SLA attention against the decode cache state.

    q: (B, H, C, D) chunk queries; `pos` the (traced) base position —
    token c sits at pos + c. Unlike the single-token path, `state`
    carries *per-token* plan rows and linear-state snapshots: lut
    (B, H, C, K), cnt/marg (B, H, C), htot (B, Hkv, C, D, D), ztot
    (B, Hkv, C, D) — the at-time-c values each token attends with
    (transformer.decode_chunk builds them in one scan). One kernel
    launch (backend "kernel") or one gather chain (backend "gather" /
    "reference" — both run the same chunk-aware math, fully
    differentiable) covers the whole chunk. Returns (B, H, C, D) in
    q.dtype.
    """
    backend = resolve_decode(backend)
    cfg.validate()
    in_dtype = q.dtype
    b, h, cdim, d = q.shape
    hkv = state["k"].shape[1]
    scale = (d**-0.5) if scale is None else scale
    qg = _group_heads(q.astype(jnp.float32), hkv)
    qpg = _group_heads(phi(q, cfg.phi), hkv)
    if backend == "kernel":
        o_s, o_l = _decode_kernel_backend_chunk(state, qg, qpg, pos, cfg,
                                                scale)
    else:
        from repro.kernels import sla_decode

        o_s, o_l = sla_decode._decode_math(
            qg, qpg, state["k"], state["v"], state["hblk"], state["zblk"],
            state["hdiag"], state["zdiag"], state["htot"], state["ztot"],
            _group_heads(state["lut"], hkv),
            _group_heads(state["cnt"], hkv), _group_heads(state["marg"], hkv),
            jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)), cfg, scale)
    o_s = o_s.reshape(b, h, cdim, d)
    if cfg.mode == "sparse_only":
        return o_s.astype(in_dtype)
    if cfg.mode != "sla":
        raise ValueError(
            f"decode_execute_chunk supports modes 'sla'/'sparse_only', got "
            f"{cfg.mode!r}")
    proj = params["proj"].astype(jnp.float32)
    o = o_s + jnp.einsum("bhcd,hde->bhce", o_l.reshape(b, h, cdim, d), proj)
    return o.astype(in_dtype)


def _decode_kernel_backend_chunk(state, qg, qpg, pos, cfg, scale):
    from repro.kernels import sla_decode

    return sla_decode.decode_attention(state, qg, qpg, pos, cfg, scale)
