"""SLA planning subsystem: classify once, execute many times.

SLA's cost model (PAPER.md Eq. 2-3) splits attention into a cheap
*planning* step — pool(Q) pool(K)^T -> P_c -> three-way block
classification -> row/column lookup tables — and the *execution* step
that consumes the resulting block structure.  This module owns the
planning step end to end: `plan_attention(q, k, cfg)` returns an
`SLAPlan`, an immutable pytree carrying every derived structure any
backend (reference / gather / Pallas kernel) needs, so

  * the backward pass reuses the forward's LUTs (threaded through the
    `custom_vjp` residuals in kernels/ops.py — never rebuilt), and
  * a plan computed at one diffusion timestep can be reused for the
    next K steps (`SLAConfig.plan_refresh_interval`; DiT block-sparsity
    patterns are stable across adjacent denoising steps — see
    DESIGN.md "Plan/execute split").

This is the ONLY place LUTs are constructed; `core/masks.py` keeps the
classification math (P_c, M_c) and `core/backends.py` the execution.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import SLAConfig
from repro.core.masks import classify_blocks, routing_gates, score_map

EPS = 1e-12


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SLAPlan:
    """Immutable result of SLA block planning — a pure-array pytree.

    Shapes (B = batch, H = q heads, Tm/Tn = q/kv block counts):
      mc:         (B, H, Tm, Tn) int8   three-way classification (Eq. 3)
      lut:        (B, H, Tm, K)  int32  critical block ids per query row
      counts:     (B, H, Tm)     int32  live entries per row LUT
      col_lut:    (B, H, Tn, W)  int32  critical row ids per KV column
                                        (dK/dV kernel; capacity-capped)
      col_counts: (B, H, Tn)     int32  live entries per column LUT
      marginal:   (B, H, Tm, Tn) f32    aggregation matrix A (1 where a
                                        block is marginal; the App. A.3
                                        pre-aggregation matmul operand)

    All leaves are arrays, so a plan jit-traces, shards, and scans like
    any activation; static facts (K, W, block sizes) are recovered from
    leaf shapes + the SLAConfig at execution time.
    """

    mc: jax.Array
    lut: jax.Array
    counts: jax.Array
    col_lut: jax.Array
    col_counts: jax.Array
    marginal: jax.Array

    @property
    def k_sel(self) -> int:
        return self.lut.shape[-1]

    @property
    def w_col(self) -> int:
        return self.col_lut.shape[-1]

    @property
    def num_q_blocks(self) -> int:
        return self.mc.shape[-2]

    @property
    def num_kv_blocks(self) -> int:
        return self.mc.shape[-1]

    def stats(self) -> dict:
        """Sparsity statistics (fractions of each block class)."""
        total = self.mc.size
        crit = jnp.sum(self.mc == 1) / total
        marg = jnp.sum(self.mc == 0) / total
        neg = jnp.sum(self.mc == -1) / total
        return {
            "critical_frac": crit,
            "marginal_frac": marg,
            "negligible_frac": neg,
            "sparsity": 1.0 - crit,  # paper: 1 - computed fraction
        }


def build_lut(mc: jax.Array, k_sel: int) -> Tuple[jax.Array, jax.Array]:
    """Static-shape critical-block lookup table for the TPU kernel.

    Args:
      mc: (..., Tm, Tn) int8 classification.
      k_sel: static LUT width (>= max #critical per row; use
        cfg.num_critical(Tn)).

    Returns:
      lut:    (..., Tm, k_sel) int32 — critical block indices, ascending,
              padded with the row's first critical index (always valid).
      counts: (..., Tm) int32 — number of live entries per row.
    """
    tn = mc.shape[-1]
    is_crit = (mc == 1).astype(jnp.int32)
    counts = jnp.sum(is_crit, axis=-1)
    # Sort key: critical blocks first (ascending j), then the rest.
    j = jnp.arange(tn, dtype=jnp.int32)
    key = is_crit * (2 * tn) - j
    idx = jnp.argsort(-key, axis=-1, stable=True)[..., :k_sel].astype(jnp.int32)
    slot = jnp.arange(k_sel, dtype=jnp.int32)
    live = slot < counts[..., None]
    pad = idx[..., :1]  # first critical index — always a real block
    lut = jnp.where(live, idx, pad)
    return lut, counts


def build_col_lut(mc: jax.Array, w_col: int) -> Tuple[jax.Array, jax.Array]:
    """Column LUT for the dK/dV kernel: per KV column, the critical row idxs.

    Requires the column-capacity constraint (counts <= w_col by construction).
    Returns (col_lut (..., Tn, w_col) int32, col_counts (..., Tn) int32).
    """
    tm = mc.shape[-2]
    is_crit = (mc == 1).astype(jnp.int32)
    counts = jnp.sum(is_crit, axis=-2)
    i = jnp.arange(tm, dtype=jnp.int32)[:, None]
    key = is_crit * (2 * tm) - i
    idx = jnp.argsort(-key, axis=-2, stable=True)[..., :w_col, :].astype(jnp.int32)
    idx = jnp.swapaxes(idx, -1, -2)  # (..., Tn, w_col)
    slot = jnp.arange(w_col, dtype=jnp.int32)
    live = slot < counts[..., None]
    pad = idx[..., :1]
    lut = jnp.where(live, idx, pad)
    return lut, counts


def plan_from_mask(mc: jax.Array, cfg: SLAConfig,
                   col_width: Optional[int] = None,
                   pc: Optional[jax.Array] = None) -> SLAPlan:
    """Derive every execution structure from a classification M_c.

    `col_width` overrides the column-LUT width (cfg.col_capacity).
    Inference-only consumers that never run the dK/dV backward pass —
    the decode cache — pass 1 so the plan does not carry a dead
    O(Tm x Tn)-per-head structure.

    `pc` (learned routing only): the routing probability map `mc` was
    classified from. When given, the plan's marginal aggregation
    matrix carries the straight-through gates (`masks.routing_gates`)
    — forward-identical to the hard indicator, but differentiable
    w.r.t. the routing parameters."""
    tm, tn = mc.shape[-2], mc.shape[-1]
    with jax.named_scope("sla.plan"):
        with jax.named_scope("lut"):
            lut, counts = build_lut(mc, cfg.num_critical(tn))
        with jax.named_scope("col_lut"):
            col_lut, col_counts = build_col_lut(
                mc, cfg.col_capacity(tm, tn) if col_width is None
                else col_width)
        if pc is not None and cfg.routing_mode == "learned":
            marginal = routing_gates(pc, mc, cfg)
        else:
            marginal = (mc == 0).astype(jnp.float32)
    return SLAPlan(mc=mc, lut=lut, counts=counts,
                   col_lut=col_lut, col_counts=col_counts,
                   marginal=marginal)


def plan_attention(
    q: jax.Array, k: jax.Array, cfg: SLAConfig,
    scale: Optional[float] = None,
    routing: Optional[dict] = None,
) -> SLAPlan:
    """Build an SLAPlan from (q, k): score map -> M_c -> LUTs -> A.

    q: (B, H, N, D); k: (B, Hkv, N, D) with Hkv | H (GQA heads are
    broadcast so the plan always has one row of structure per q head).
    (q, k) are gradient-stopped — the block structure is a constant
    w.r.t. the loss (TopK is not differentiated, matching the paper).
    With cfg.routing_mode == "learned", `routing` (the per-head scorer
    from `masks.routing_init`) ranks the blocks instead of the raw
    pooled P_c, and the plan's marginal matrix carries straight-through
    gradients to the routing parameters (DESIGN.md "Learned routing").
    """
    cfg.validate()  # typo'd knob strings die here, not deep in a trace
    with jax.named_scope("sla.plan"):
        h = q.shape[1]
        if k.shape[1] != h:
            assert h % k.shape[1] == 0
            k = jnp.repeat(k, h // k.shape[1], axis=1)
        with jax.named_scope("score"):
            pc = score_map(routing, jax.lax.stop_gradient(q),
                           jax.lax.stop_gradient(k), cfg, scale)
        with jax.named_scope("classify"):
            mc = classify_blocks(pc, cfg)
        return plan_from_mask(mc, cfg, pc=pc)


# ---------------------------------------------------------------------------
# incremental plan maintenance (decode-time SLA; DESIGN.md "Decode-time SLA")
# ---------------------------------------------------------------------------
def empty_plan(
    cfg: SLAConfig, batch: int, heads: int, tm: int, tn: int,
) -> SLAPlan:
    """All-negligible plan over a static (tm, tn) block grid — the
    decode-time starting point that `plan_extend` appends rows into."""
    mc = jnp.full((batch, heads, tm, tn), -1, jnp.int8)
    return plan_from_mask(mc, cfg)


def plan_extend(plan: SLAPlan, mc_row: jax.Array, row) -> SLAPlan:
    """Append one query-block row to a plan: O(Tn * K), no argsort rebuild.

    mc_row: (..., Tn) int8 classification of row `row` (a python int or
    traced scalar). Precondition: `row` is the first unwritten row of
    the plan (rows are appended monotonically, each exactly once — the
    decode path crosses each block boundary once), so the column-LUT
    update is a pure append at each column's current fill level.

    Equality contract (tests/test_decode_sla.py property suite):
    starting from `empty_plan` and appending rows 0..R-1 of a full
    classification M_c reproduces `plan_from_mask(M_c)` exactly on
    `mc`, `lut`, `counts`, `col_counts`, and `marginal`, and on every
    *live* `col_lut` slot (slot < col_counts). Dead col_lut padding
    slots may differ — plan_from_mask pads with the column's first
    critical row id, the incremental path leaves stale values — and no
    backend reads them (every consumer gates on counts).
    """
    nd = plan.mc.ndim
    row = jnp.asarray(row, jnp.int32)
    mc_row = mc_row.astype(plan.mc.dtype)
    mc = jax.lax.dynamic_update_slice_in_dim(
        plan.mc, mc_row[..., None, :], row, axis=nd - 2)
    lut_r, cnt_r = build_lut(mc_row[..., None, :], plan.k_sel)
    lut = jax.lax.dynamic_update_slice_in_dim(
        plan.lut, lut_r, row, axis=nd - 2)
    counts = jax.lax.dynamic_update_slice_in_dim(
        plan.counts, cnt_r, row, axis=nd - 2)
    # Column-LUT append: the new row becomes the *last* critical entry of
    # every column it is critical in (rows arrive in ascending order, and
    # build_col_lut lists critical rows ascending, so live entries agree).
    is_crit = mc_row == 1  # (..., Tn)
    cc = plan.col_counts
    can = jnp.logical_and(is_crit, cc < plan.w_col)
    slot_hit = jnp.arange(plan.w_col, dtype=cc.dtype) == cc[..., None]
    write = jnp.logical_and(can[..., None], slot_hit)
    col_lut = jnp.where(write, row.astype(plan.col_lut.dtype),
                        plan.col_lut)
    col_counts = cc + can.astype(plan.col_counts.dtype)
    marginal = jax.lax.dynamic_update_slice_in_dim(
        plan.marginal,
        (mc_row == 0).astype(plan.marginal.dtype)[..., None, :],
        row, axis=nd - 2)
    return SLAPlan(mc=mc, lut=lut, counts=counts, col_lut=col_lut,
                   col_counts=col_counts, marginal=marginal)


# ---------------------------------------------------------------------------
# plan lifetime: drift measurement + adaptive refresh
# (DESIGN.md "Plan lifetime & drift")
# ---------------------------------------------------------------------------
def plan_retention(
    plan: SLAPlan, q: jax.Array, k: jax.Array, cfg: SLAConfig,
    scale: Optional[float] = None,
    routing: Optional[dict] = None,
) -> jax.Array:
    """Critical-mass retention of a (possibly stale) plan at (q, k).

    Recomputes the pooled compressed map P_c for the *current* (q, k)
    (cheap: O(T^2) in blocks, not tokens) and measures what fraction of
    the P_c mass a fresh critical set would capture is still covered by
    the stale plan's critical set:

        r = sum(P_c * [mc_stale == +1]) / sum(P_c * [mc_fresh == +1])

    clipped to [0, 1]. r == 1.0 exactly when (q, k) still classify to
    the plan's structure; r decays toward 0 as the denoising trajectory
    (or prefill content) moves away from the state the plan was built
    on. Drift is `1 - r` (see `plan_drift`).

    Under learned routing (cfg.routing_mode == "learned", `routing`
    given) both the stale-mass numerator and the fresh classification
    use the learned scorer's map, so drift is measured against the
    structure the router would actually build today.

    Gradient-stopped like planning itself. Returns (B, H) float32.
    """
    with jax.named_scope("sla.plan"):
        return _retention_and_fresh_mc(plan, q, k, cfg, scale, routing)[0]


def _retention_and_fresh_mc(
    plan: SLAPlan, q: jax.Array, k: jax.Array, cfg: SLAConfig,
    scale: Optional[float] = None,
    routing: Optional[dict] = None,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Retention (B, H) plus the fresh classification M_c it was measured
    against — `refresh_plan` rebuilds from the latter so a drift-triggered
    re-plan never recomputes the pool/score-map/top-k front half. The
    third element is the score map itself under learned routing (None
    otherwise), so the rebuild can carry straight-through gates.

    Like every scoring path, learned mode REQUIRES the routing params
    (loud failure in `score_map`) — drift must be measured with the
    same scorer the plan was built with, never a silent P_c fallback."""
    h = q.shape[1]
    if k.shape[1] != h:
        assert h % k.shape[1] == 0
        k = jnp.repeat(k, h // k.shape[1], axis=1)
    q = jax.lax.stop_gradient(q)
    k = jax.lax.stop_gradient(k)
    learned = cfg.routing_mode == "learned"
    with jax.named_scope("score"):
        pc = score_map(routing, q, k, cfg, scale)  # (B, H, Tm, Tn) f32
    if pc.shape[-2:] != plan.mc.shape[-2:]:
        raise ValueError(
            f"stale SLAPlan: plan is for {plan.mc.shape[-2:]} blocks but "
            f"(q, k) pool to {pc.shape[-2:]} — shapes must match to "
            f"measure drift")
    with jax.named_scope("classify"):
        mc_fresh = classify_blocks(pc, cfg)
    with jax.named_scope("drift"):
        stale = jnp.sum(pc * (plan.mc == 1), axis=(-2, -1))
        fresh = jnp.sum(pc * (mc_fresh == 1), axis=(-2, -1))
        r = jnp.clip(stale / jnp.maximum(fresh, EPS), 0.0, 1.0)
    return r, mc_fresh, (pc if learned else None)


def plan_drift(
    plan: SLAPlan, q: jax.Array, k: jax.Array, cfg: SLAConfig,
    scale: Optional[float] = None,
    routing: Optional[dict] = None,
) -> jax.Array:
    """Plan drift `1 - plan_retention(...)` in [0, 1], shape (B, H).

    0 means the reused plan still captures everything a fresh plan
    would; 1 means the stale critical set covers none of the current
    P_c mass. `SLAConfig.plan_drift_threshold` gates re-planning on
    this value (re-plan when drift >= threshold)."""
    return 1.0 - plan_retention(plan, q, k, cfg, scale, routing)


def refresh_plan(
    plan: SLAPlan, q: jax.Array, k: jax.Array, cfg: SLAConfig,
    threshold, scale: Optional[float] = None,
    routing: Optional[dict] = None,
) -> Tuple[SLAPlan, jax.Array, jax.Array]:
    """Drift-gated re-plan: keep `plan` while it retains critical mass.

    Measures `plan_drift` (reduced with max over batch/heads — one
    over-drifted head forces the re-plan, the conservative choice) and
    rebuilds the plan under `lax.cond` when drift >= threshold, so the
    planning pipeline only runs when the structure has actually moved
    and the whole decision stays jit-traceable with static shapes.

    `threshold` may be a python float or a traced scalar:
      0.0 -> re-plan on every call (exact paper behavior),
      1.0 -> never re-plan after the first (blind reuse).

    Returns (plan', retention_scalar f32, replanned bool).
    """
    with jax.named_scope("sla.plan"):
        r, mc_fresh, pc = _retention_and_fresh_mc(plan, q, k, cfg, scale,
                                                  routing)
        with jax.named_scope("drift"):
            retention = jnp.min(r)
            # threshold >= 1.0 means "never", even at the clipped
            # drift == 1.0 extreme — the docs' blind-reuse contract
            # beats the >= comparison
            replanned = jnp.logical_and((1.0 - retention) >= threshold,
                                        jnp.asarray(threshold) < 1.0)
        # the drift metric already classified the fresh structure; the
        # rebuild only derives LUTs from it (and is guaranteed to match
        # the classification the decision was based on)
        new_plan = jax.lax.cond(
            replanned,
            lambda ops: plan_from_mask(ops[0], cfg, pc=pc),
            lambda ops: ops[1],
            (mc_fresh, plan))
    return new_plan, retention, replanned


def refresh_plan_per_sample(
    plan: SLAPlan, q: jax.Array, k: jax.Array, cfg: SLAConfig,
    thresholds, scale: Optional[float] = None,
    routing: Optional[dict] = None,
) -> Tuple[SLAPlan, jax.Array, jax.Array]:
    """Per-sample drift-gated re-plan: each batch row decides alone.

    `refresh_plan` min-reduces retention over batch AND heads, coupling
    the refresh decision across every row of the batch — correct for a
    single request, wrong for a serving batch where each slot holds an
    unrelated request at its own timestep. Here retention is reduced
    over heads only, giving a (B,) decision vector; replanned rows take
    the freshly classified structure, kept rows carry their old leaves
    bitwise-unchanged via a per-row select. Because every structure in
    `plan_from_mask` is per-(batch, head) independent, a row's refresh
    here is bitwise-identical to `refresh_plan` on that row alone — the
    DiffusionScheduler's batched-vs-sequential parity rests on this.

    `thresholds`: (B,) float drift thresholds (broadcast from a scalar).
    Per-row schedule override: 0.0 forces that row's re-plan, >= 1.0
    pins blind reuse — the fixed refresh interval is expressed as a
    0/1 threshold vector, so one traced step covers both modes.

    Unlike `refresh_plan`'s `lax.cond`, the rebuild always runs (the
    select needs fresh leaves for any subset of rows) — the extra cost
    is the LUT argsorts, O(T log T) in blocks, dwarfed by attention.

    Returns (plan', retention (B,), replanned (B,) bool).
    """
    def sel(new_leaf, old_leaf):
        m = replanned.reshape(
            replanned.shape + (1,) * (new_leaf.ndim - replanned.ndim))
        return jnp.where(m, new_leaf, old_leaf)

    with jax.named_scope("sla.plan"):
        r, mc_fresh, pc = _retention_and_fresh_mc(plan, q, k, cfg, scale,
                                                  routing)
        with jax.named_scope("drift"):
            retention = jnp.min(r, axis=-1)  # (B,) — min over heads only
            thr = jnp.broadcast_to(jnp.asarray(thresholds, jnp.float32),
                                   retention.shape)
            replanned = jnp.logical_and((1.0 - retention) >= thr, thr < 1.0)
        fresh = plan_from_mask(mc_fresh, cfg, pc=pc)
        with jax.named_scope("drift"):
            new_plan = jax.tree_util.tree_map(sel, fresh, plan)
    return new_plan, retention, replanned


# ---------------------------------------------------------------------------
# plan serialization + config compatibility (serving/plan_cache.py)
# ---------------------------------------------------------------------------
_PLAN_WIRE_VERSION = 1
_PLAN_LEAVES = ("mc", "lut", "counts", "col_lut", "col_counts", "marginal")


def plan_compat_key(cfg: SLAConfig, heads: int, tm: int, tn: int) -> tuple:
    """Hashable key under which two SLAPlans are interchangeable.

    Two plans built under configs that agree on every field this key
    names produce the same leaf shapes/dtypes AND the same
    classification semantics, so a cached plan may be handed to a
    request that never saw the original (q, k). Fields that only affect
    execution (phi, proj_init, decode_*) are deliberately absent —
    changing them must NOT invalidate cached structure."""
    return (
        "sla-plan-v%d" % _PLAN_WIRE_VERSION,
        cfg.block_q, cfg.block_kv, cfg.kh_frac, cfg.kl_frac, cfg.mode,
        bool(cfg.causal), bool(cfg.force_diagonal), cfg.fixed_budget,
        cfg.col_capacity_factor, cfg.routing_mode, cfg.window,
        int(heads), int(tm), int(tn),
    )


def serialize_plan(plan: SLAPlan) -> dict:
    """SLAPlan -> device-free dict of numpy leaves (+ wire version).

    The inverse of `deserialize_plan`; round-trips bitwise. Host numpy
    (not bytes) so a cache entry costs one device->host copy and no
    codec, yet holds no device memory."""
    import numpy as np
    out = {"__version__": _PLAN_WIRE_VERSION}
    for name in _PLAN_LEAVES:
        out[name] = np.asarray(getattr(plan, name))
    return out


def deserialize_plan(data: dict) -> SLAPlan:
    """Dict from `serialize_plan` -> SLAPlan with device arrays."""
    v = data.get("__version__")
    if v != _PLAN_WIRE_VERSION:
        raise ValueError(
            f"serialized SLAPlan wire version {v!r} != "
            f"{_PLAN_WIRE_VERSION} — refusing to guess leaf layout")
    return SLAPlan(**{name: jnp.asarray(data[name])
                      for name in _PLAN_LEAVES})
