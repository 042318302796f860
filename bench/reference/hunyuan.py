"""Plain reference of HunyuanVideo's HYVideo-T/2 denoising step (dual-stream
then single-stream MMDiT; `hyvideo/modules/models.py`), with SLA in place
of its dense joint attention.

One Euler step of rectified flow, x' = x - dt * v(x, t). The video tokens
(patch embedding of the latent) and the text tokens (the conditioning,
at hidden width) form one joint sequence [video; text]. vec is the
two-layer SiLU MLP of the timestep's sinusoidal embedding, and every
modulation is a matrix product of SiLU(vec); modulate(x, s, c) = x (1 +
c) + s, LN a LayerNorm without affine parameters.

- Dual-stream block: each stream computes (shift1, scale1, gate1, shift2,
  scale2, gate2), q, k, v from modulate(LN(x), shift1, scale1) with
  RMSNorm on q and k; RoPE turns the video q and k; attention runs over
  the joint sequence; each stream adds gate1 * proj(its part) and then
  gate2 * MLP(modulate(LN(x), shift2, scale2)), MLP = GELU-tanh between
  two products.
- Single-stream block: one weight set over the joint sequence: h =
  modulate(LN(x), shift, scale), q, k, v and the MLP input from h, RMSNorm
  and RoPE as above, x += gate * (attention @ W_o + MLP(h)).
- Final layer: modulate(LN(video), shift, scale) @ W_out.

RoPE is 3-D, over (frame, row, column) of the latent grid, in interleaved
pairs: `rope_axes` of the head dim per axis, the pair j of an axis with
d_a dims turning by position * theta^(-2j / d_a). SLA is the mathematics
of `dit.py`'s reference (plan from pooled scores, sparse softmax over
each query block's critical key blocks, linear attention over its
marginal ones, Proj). Everything is computed one head at a time, the
sparse branch in groups of query blocks and the MLP in slices of its
hidden width, so that it fits at 65,536 joint tokens: the products are
the same, only grouped (under the float8 control each group's inputs
take their own scale). `arch` is the configuration file's "model" group;
SLA plans afresh at every step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.reference.numerics import (NEG, Numerics, block_pool, classify,
                                      critical_lut, feature, rms_norm,
                                      safe_div, sla_counts)

ROW_GROUP = 64      # query blocks per group of the sparse branch
MLP_SLICES = 4      # slices of the MLP's hidden width


def _f32(a):
    return a.astype(jnp.float32)


def _timestep_embedding(t, dim=256):
    half = dim // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = t * freq
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])


def _layer_norm(x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _modulate(x, shift, scale):
    return x * (1 + scale) + shift


def rope_angles(arch: dict, n: int):
    """(n, head_dim / 2) angle of each interleaved pair of each video
    token: token i sits at frame i // (h w), row (i // w) % h, column
    i % w; each axis owns `rope_axes` dims of the head, in that order."""
    hh, ww = arch["latent_hw"]
    i = jnp.arange(n)
    pos = (i // (hh * ww), (i // ww) % hh, i % ww)
    cols = []
    for p, dim in zip(pos, arch["rope_axes"]):
        j = jnp.arange(dim // 2, dtype=jnp.float32)
        cols.append(p[:, None].astype(jnp.float32)
                    * arch["rope_theta"] ** (-2.0 * j / dim))
    return jnp.concatenate(cols, axis=1)


def rope(x, angles):
    """Turn each pair (x[2j], x[2j+1]) of x (n, D) by angles (n, D / 2)."""
    a, b = x[:, 0::2], x[:, 1::2]
    c, s = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1) \
        .reshape(x.shape)


def _sla_head(qh, kh, vh, proj, sla, nm: Numerics):
    """Bidirectional SLA for one head over the joint sequence (N, D)."""
    n, d = qh.shape
    b = sla["block_q"]
    tm = tn = n // b
    n_crit, n_neg, cap = sla_counts(tn, sla["kh_frac"], sla["kl_frac"], tm,
                                    sla["col_capacity_factor"])
    scale = d ** -0.5
    pc = jax.nn.softmax(nm.ein("md,nd->mn", block_pool(qh, b),
                               block_pool(kh, b)) * scale, axis=-1)
    mc = classify(pc, jnp.ones((tm, tn), bool), jnp.eye(tm, tn, dtype=bool),
                  n_crit, n_neg, cap)
    # sparse branch: each query block against its critical key blocks,
    # ROW_GROUP query blocks at a time
    lut, cnt = critical_lut(mc, n_crit)                  # (tm, K), (tm,)
    kb, vb = kh.reshape(tn, b, d), vh.reshape(tn, b, d)
    rg = next(g for g in range(min(ROW_GROUP, tm), 0, -1) if tm % g == 0)

    def rows(args):
        qg, lg, cg = args                                # (rg, b, d), ...
        s = nm.ein("iqd,ijkd->iqjk", qg, kb[lg]) * scale
        live = (jnp.arange(n_crit)[None, :] < cg[:, None])[:, None, :, None]
        s = jnp.where(live, s, NEG).reshape(rg, b, n_crit * b)
        p = jax.nn.softmax(s, axis=-1).reshape(rg, b, n_crit, b)
        return nm.ein("iqjk,ijkd->iqd", p, vb[lg])

    o_s = lax.map(rows, (qh.reshape(tm // rg, rg, b, d),
                         lut.reshape(tm // rg, rg, n_crit),
                         cnt.reshape(tm // rg, rg))).reshape(n, d)
    # linear branch over marginal blocks (paper Eq. 5)
    fk = feature(kh).reshape(tn, b, d)
    h = nm.ein("jkd,jke->jde", fk, vb)                   # (tn, D, D)
    z = jnp.sum(fk, axis=1)                              # (tn, D)
    a = (mc == 0).astype(jnp.float32)
    hi = nm.ein("ij,jde->ide", a, h)
    zi = nm.ein("ij,jd->id", a, z)
    fq = feature(qh).reshape(tm, b, d)
    num = nm.ein("iqd,ide->iqe", fq, hi)
    den = nm.ein("iqd,id->iq", fq, zi)[..., None]
    o_l = safe_div(num, den).reshape(n, d)
    return o_s + nm.ein("nd,de->ne", o_l, proj)


def _mlp(x, p, nm: Numerics):
    """GELU-tanh MLP, its hidden width in MLP_SLICES slices."""
    ff = p["mlp_wi"].shape[-1]
    wi = _f32(p["mlp_wi"]).reshape(-1, MLP_SLICES, ff // MLP_SLICES)
    wo = _f32(p["mlp_wo"]).reshape(MLP_SLICES, ff // MLP_SLICES, -1)

    def part(acc, w):
        wi_s, wo_s = w
        hid = jax.nn.gelu(nm.ein("nd,df->nf", x, wi_s), approximate=True)
        return acc + nm.ein("nf,fd->nd", hid, wo_s), None

    out, _ = lax.scan(part, jnp.zeros_like(x),
                      (jnp.moveaxis(wi, 1, 0), wo))
    return out


def _heads_of(w, h):
    """(d, H * Dh) -> (H, d, Dh): one projection per head."""
    return jnp.moveaxis(_f32(w).reshape(w.shape[0], h, -1), 1, 0)


def _joint_attention(streams, sla_proj, angles, n, arch, nm: Numerics):
    """SLA over the joint sequence of `streams` [(x, p), ...] (video
    first), one head at a time: each stream's q, k, v from its x and
    weights p, q/k RMS-normed, the video rows turned by RoPE. Returns each
    stream's attention output times its W_o."""
    h = arch["num_heads"]
    sizes = [x.shape[0] for x, _ in streams]
    cut = np.cumsum(sizes)[:-1]

    def head(accs, w):
        proj, ws, wos = w
        qs, ks, vs = [], [], []
        for (x, p), (wq, wk, wv) in zip(streams, ws):
            qs.append(rms_norm(nm.ein("nd,de->ne", x, wq), p["qnorm"]))
            ks.append(rms_norm(nm.ein("nd,de->ne", x, wk), p["knorm"]))
            vs.append(nm.ein("nd,de->ne", x, wv))
        q, k, v = (jnp.concatenate(a) for a in (qs, ks, vs))
        q = q.at[:n].set(rope(q[:n], angles))
        k = k.at[:n].set(rope(k[:n], angles))
        o = jnp.split(_sla_head(q, k, v, proj, arch["sla"], nm), cut)
        return [acc + nm.ein("ne,ed->nd", oi, wo_h)
                for acc, oi, wo_h in zip(accs, o, wos)], None

    per_head = [tuple(_heads_of(p[m], h) for m in ("wq", "wk", "wv"))
                for _, p in streams]
    wo = [_f32(p["wo"]).reshape(h, -1, p["wo"].shape[-1])
          for _, p in streams]
    accs, _ = lax.scan(head, [jnp.zeros_like(x) for x, _ in streams],
                       (_f32(sla_proj), per_head, wo))
    return accs


def velocity(params, arch: dict, x, t, cond, nm: Numerics = Numerics()):
    """v(x, t) for one sample. x: (N, P) f32; t: scalar; cond: (Lc, d)."""
    n = x.shape[0]
    img = nm.ein("np,pd->nd", x, _f32(params["patch_in"]))
    txt = _f32(cond)
    tin = params["time_in"]
    vec = jax.nn.silu(nm.ein("e,ed->d", _timestep_embedding(t * 1000.0),
                             _f32(tin["mlp_wi"])))
    svec = jax.nn.silu(nm.ein("d,de->e", vec, _f32(tin["mlp_wo"])))
    angles = rope_angles(arch, n)

    def mods(p, k):
        return jnp.split(nm.ein("d,de->e", svec, _f32(p["ada"])), k)

    def double(carry, p):
        img, txt = carry
        pi, pt = p["img"], p["txt"]
        mi, mt = mods(pi, 6), mods(pt, 6)
        xi = _modulate(_layer_norm(img), mi[0], mi[1])
        xt = _modulate(_layer_norm(txt), mt[0], mt[1])
        ai, at = _joint_attention([(xi, pi), (xt, pt)], p["sla_proj"],
                                  angles, n, arch, nm)
        img, txt = img + mi[2] * ai, txt + mt[2] * at
        img = img + mi[5] * _mlp(_modulate(_layer_norm(img), mi[3], mi[4]),
                                 pi, nm)
        txt = txt + mt[5] * _mlp(_modulate(_layer_norm(txt), mt[3], mt[4]),
                                 pt, nm)
        return (img, txt), None

    def single(xx, p):
        shift, scale, gate = mods(p, 3)
        hn = _modulate(_layer_norm(xx), shift, scale)
        (att,) = _joint_attention([(hn, p)], p["sla_proj"], angles, n,
                                  arch, nm)
        return xx + gate * (att + _mlp(hn, p, nm)), None

    (img, txt), _ = lax.scan(double, (img, txt), params["double"])
    xx, _ = lax.scan(single, jnp.concatenate([img, txt]), params["single"])
    shift, scale = mods(params["final"], 2)
    return nm.ein("nd,dp->np", _modulate(_layer_norm(xx[:n]), shift, scale),
                  _f32(params["final"]["patch_out"]))


def _freeze(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, list):
        return tuple(value)
    return value


def _thaw(key):
    return {k: (_thaw(v) if isinstance(v, tuple) and v
                and isinstance(v[0], tuple) else v) for k, v in key}


@functools.partial(jax.jit, static_argnames=("arch_key", "inputs"))
def _step(params, x, t, dt, cond, arch_key, inputs):
    return x - dt * velocity(params, _thaw(arch_key), x, t, cond,
                             Numerics(inputs))


def euler_steps(params, arch: dict, x, t_start: float, num_steps: int,
                first_step: int, steps: int, cond,
                inputs: str = "float32"):
    """`steps` Euler steps of a request (t_start, num_steps) starting at
    its step index `first_step`, from latent x. The timestep of step k
    is t_start - k * (t_start / num_steps), rounded to float32 as the
    request's own bookkeeping rounds it. Every product is computed from
    inputs rounded to `inputs` (see Numerics)."""
    t0 = np.float32(t_start)
    dt = np.float32(t0 / np.float32(num_steps))
    x = jnp.asarray(x, jnp.float32)
    key = _freeze(arch)
    for k in range(first_step, first_step + steps):
        t = np.float32(t0 - np.float32(k) * dt)
        x = _step(params, x, t, dt, jnp.asarray(cond, jnp.float32), key,
                  inputs)
    return x
