"""Plain reference of the Qwen3-style decoder under SLA, as served:
a left-padded prompt prefilled at once, then decode-time SLA token by
token. Given the whole served sequence it computes every position's
logits in one pass (teacher forcing), since each position depends only
on the positions before it.

Per layer: RMSNorm, q/k/v projections with per-head q/k RMSNorm and
rotary embedding, GQA attention, output projection, gated SiLU MLP;
tied embeddings; logits in float32.

Attention of prompt positions (causal SLA on the prompt's block grid):
blocks classified from the pooled score map (Eq. 2-3) with the
column cap; sparse softmax over critical blocks (token-causal inside
the diagonal block) plus linear attention over marginal blocks.

Attention of decode positions (decode-time SLA on the cache's block
grid, `max_len / block` blocks, a fixed budget of critical blocks and
no negligible class):
  * a completed row r is classified from its pooled q against the
    pooled k of blocks <= r;
  * at the first token of row r the live row either inherits row r-1's
    critical set plus the diagonal block, or is classified afresh from
    that token's q against the pooled k (the current block's pool being
    that one token's k). It is classified afresh when, for some head,
    the inherited set keeps less than 1 - threshold of the critical
    score mass the fresh set would (the retention rule);
  * the sparse branch attends every critical block of the live row,
    the current (diagonal) block always among them: `budget` blocks
    when classified afresh, and the inherited `budget` blocks plus the
    current one when inherited (the design's "previous row's critical
    set + forced diagonal");
  * the linear branch covers every position up to the current one
    outside the sparse branch's blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.numerics import (NEG, Numerics, block_pool, classify,
                                      critical_lut, feature, rms_norm,
                                      rope, safe_div, sla_counts)


def _attend(q, k, v, keep_s, keep_l, proj, nm: Numerics):
    """O^s + Proj(O^l) from token masks. q: (H, M, D); k, v: (H, S, D);
    keep_s / keep_l: (H, M, S) sparse-branch and linear-branch keys."""
    d = q.shape[-1]
    s = jnp.where(keep_s, nm.ein("hmd,hsd->hms", q, k) * d ** -0.5, NEG)
    o_s = nm.ein("hms,hsd->hmd", jax.nn.softmax(s, axis=-1), v)
    w = jnp.where(keep_l, nm.ein("hmd,hsd->hms", feature(q), feature(k)),
                  0.0)
    o_l = safe_div(nm.ein("hms,hsd->hmd", w, v),
                   jnp.sum(w, axis=-1, keepdims=True))
    return o_s + nm.ein("hmd,hde->hme", o_l, proj)


def _prefill_attention(q, k, v, proj, sla, nm):
    h, p, d = q.shape
    b = sla["block_q"]
    tm = p // b
    n_crit, n_neg, cap = sla_counts(tm, sla["kh_frac"], sla["kl_frac"], tm,
                                    sla["col_capacity_factor"])
    i, j = jnp.arange(tm)[:, None], jnp.arange(tm)[None, :]
    valid = j <= i
    s = nm.ein("hmd,hnd->hmn", block_pool(q, b), block_pool(k, b)) * d ** -0.5
    pc = jax.nn.softmax(jnp.where(valid, s, NEG), axis=-1)
    mc = classify(pc, valid, i == j, n_crit, n_neg, cap)
    tok = jnp.arange(p)
    crit = jnp.repeat(jnp.repeat(mc == 1, b, -2), b, -1)
    marg = jnp.repeat(jnp.repeat(mc == 0, b, -2), b, -1)
    causal = tok[None, :] <= tok[:, None]
    return _attend(q, k, v, crit & causal, marg, proj, nm)


def _decode_attention(q, k, v, proj, prompt_len, sla, thr, nm):
    """Decode positions prompt_len .. S-1 of one layer (all heads)."""
    h, s_len, d = q.shape
    b = sla["block_q"]
    tn = s_len // b
    budget = sla["decode_budget"] or sla_counts(tn, sla["kh_frac"], 0.0)[0]
    budget = max(1, min(budget, tn))
    scale = d ** -0.5
    rows = jnp.arange(tn)
    # completed rows on the decode grid (causal, fixed budget, no
    # negligible class, no column cap)
    valid = rows[None, :] <= rows[:, None]
    s = nm.ein("hrd,hjd->hrj", block_pool(q, b), block_pool(k, b)) * scale
    pc = jax.nn.softmax(jnp.where(valid, s, NEG), axis=-1)
    diag = rows[None, :] == rows[:, None]
    mc_done = classify(pc, valid, diag, budget, 0)
    # live rows: the first token's q against block means (the current
    # block's mean is that token's k)
    kb = k.reshape(h, tn, b, d)
    kmean = jnp.where(diag[None, :, :, None], kb[:, :, 0][:, None],
                      block_pool(k, b)[:, None])          # (h, r, j, d)
    q_first = q.reshape(h, tn, b, d)[:, :, 0]             # (h, r, d)
    s_live = nm.ein("hrd,hrjd->hrj", q_first, kmean) * scale
    pc_live = jax.nn.softmax(jnp.where(valid, s_live, NEG), axis=-1)
    mc_fresh = classify(pc_live, valid, diag, budget, 0)
    mc_prev = jnp.concatenate([mc_done[:, :1], mc_done[:, :-1]], axis=1)
    mc_inh = jnp.where(diag, 1, mc_prev)
    kept = jnp.sum(pc_live * (mc_inh == 1), -1)
    best = jnp.sum(pc_live * (mc_fresh == 1), -1)
    retention = jnp.min(jnp.clip(kept / jnp.maximum(best, 1e-12), 0.0, 1.0),
                        axis=0)                           # (r,)
    fresh = ((1.0 - retention) >= thr) & (thr < 1.0)
    mc_live = jnp.where(fresh[None, :, None], mc_fresh, mc_inh)
    width = min(budget + 1, tn)          # an inherited row's blocks
    lut, cnt = critical_lut(mc_live, width)               # (h, r, width)
    live = jnp.arange(width) < cnt[..., None]
    in_lut = jnp.any((lut[..., None] == rows) & live[..., None],
                     axis=-2)                             # (h, r, j)
    has_marg = jnp.any(mc_live == 0, axis=-1)             # (h, r)
    # token masks for the decode positions
    pos = jnp.arange(prompt_len, s_len)
    prow = pos // b
    tok = jnp.arange(s_len)
    causal = tok[None, :] <= pos[:, None]                 # (m, s)
    blk_in = jnp.take_along_axis(
        in_lut[:, prow], jnp.broadcast_to(tok // b, (h, len(pos), s_len)),
        axis=-1)                                          # (h, m, s)
    keep_s = blk_in & causal
    keep_l = ~blk_in & causal & has_marg[:, prow][..., None]
    return _attend(q[:, prompt_len:], k, v, keep_s, keep_l, proj, nm)


def logits(params, arch: dict, tokens, prompt_len: int,
           nm: Numerics = Numerics()):
    """Logits (S - prompt_len + 1, V) at positions prompt_len-1 .. S-1 of
    the served sequence `tokens` (S,): the left-padded prompt followed
    by the tokens fed to decode. S is the cache length (max_len)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    sla = arch["sla"]
    s_len = tokens.shape[0]
    pos = jnp.arange(s_len)
    x = f32(params["embed"])[tokens]

    def heads(a, nh):
        return a.reshape(s_len, nh, dh).transpose(1, 0, 2)

    def layer(x, p):
        xn = rms_norm(x, p["ln1"])
        q = rms_norm(heads(nm.ein("sd,de->se", xn, f32(p["wq"])), h),
                     p["qnorm"])
        k = rms_norm(heads(nm.ein("sd,de->se", xn, f32(p["wk"])), hkv),
                     p["knorm"])
        v = heads(nm.ein("sd,de->se", xn, f32(p["wv"])), hkv)
        q, k = rope(q, pos, arch["rope_theta"]), rope(k, pos, arch["rope_theta"])
        k, v = jnp.repeat(k, h // hkv, 0), jnp.repeat(v, h // hkv, 0)
        proj = f32(p["sla_proj"])
        pl = prompt_len
        o = jnp.concatenate([
            _prefill_attention(q[:, :pl], k[:, :pl], v[:, :pl], proj, sla,
                               nm),
            _decode_attention(q, k, v, proj, pl, sla,
                              sla["plan_drift_threshold"], nm)], axis=1)
        o = o.transpose(1, 0, 2).reshape(s_len, h * dh)
        x = x + nm.ein("se,ed->sd", o, f32(p["wo"]))
        gate, up = jnp.split(nm.ein("sd,df->sf", rms_norm(x, p["ln2"]),
                                    f32(p["mlp_wi"])), 2, axis=-1)
        return x + nm.ein("sf,fd->sd", jax.nn.silu(gate) * up,
                          f32(p["mlp_wo"])), None

    x, _ = lax.scan(layer, x, params["layers"])
    x = rms_norm(x[prompt_len - 1:], params["ln_f"])
    return nm.ein("sd,vd->sv", x, f32(params["embed"]))


def _freeze(arch: dict):
    top = tuple(sorted((k, v) for k, v in arch.items()
                       if not isinstance(v, dict)))
    return top, tuple(sorted(arch["sla"].items()))


@functools.partial(jax.jit, static_argnames=("arch_key", "prompt_len",
                                             "inputs"))
def _logits_jit(params, tokens, arch_key, prompt_len, inputs):
    arch = dict(arch_key[0])
    arch["sla"] = dict(arch_key[1])
    return logits(params, arch, tokens, prompt_len, Numerics(inputs))


def served_logits(params, arch: dict, tokens, prompt_len: int,
                  inputs: str = "float32"):
    return _logits_jit(params, jnp.asarray(tokens, jnp.int32),
                       _freeze(arch), prompt_len, inputs)
