"""Plain reference of the Wan2.1-style DiT denoising step.

One Euler step of rectified flow, x' = x - dt * v(x, t), where v is the
DiT's velocity: patch embedding, per layer AdaLN modulation from the
timestep embedding, SLA self-attention (sparse softmax over each query
block's critical key blocks, linear attention over its marginal blocks,
merged through Proj), cross-attention to the text conditioning, and a
gated SiLU MLP; then the output head. `arch` is the configuration file's
"model" group; SLA plans afresh at every step (plan refresh interval 1).
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


def _timestep_embedding(t, dim=256):
    half = dim // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = t * freq
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])


def _sla_head(qh, kh, vh, proj, sla, nm: Numerics):
    """Bidirectional SLA for one head. qh, kh, vh: (N, D)."""
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
    # sparse branch: each query block against its critical key blocks
    lut, cnt = critical_lut(mc, n_crit)                  # (tm, K), (tm,)
    kb, vb = kh.reshape(tn, b, d), vh.reshape(tn, b, d)
    qb = qh.reshape(tm, b, d)
    s = nm.ein("iqd,ijkd->iqjk", qb, kb[lut]) * scale    # (tm, b, K, b)
    live = (jnp.arange(n_crit)[None, :] < cnt[:, None])[:, None, :, None]
    s = jnp.where(live, s, NEG).reshape(tm, b, n_crit * b)
    p = jax.nn.softmax(s, axis=-1).reshape(tm, b, n_crit, b)
    o_s = nm.ein("iqjk,ijkd->iqd", p, vb[lut]).reshape(n, d)
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


def velocity(params, arch: dict, x, t, cond, nm: Numerics = Numerics()):
    """v(x, t) for one sample. x: (N, P) f32; t: scalar; cond: (Lc, d)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    n = x.shape[0]
    sla = arch["sla"]
    xx = nm.ein("np,pd->nd", x, f32(params["patch_in"]))
    temb = jax.nn.silu(nm.ein("e,ed->d", _timestep_embedding(t * 1000.0),
                              f32(params["t_embed"])))
    cond = f32(cond)

    def heads(a, nh):
        return a.reshape(a.shape[0], nh, dh).transpose(1, 0, 2)

    def layer(xx, p):
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(
            nm.ein("d,de->e", temb, f32(p["ada"])), 6)
        xn = rms_norm(xx, p["ln1"]) * (1 + sc1) + sh1
        q = heads(nm.ein("nd,de->ne", xn, f32(p["wq"])), h)
        k = heads(nm.ein("nd,de->ne", xn, f32(p["wk"])), hkv)
        v = heads(nm.ein("nd,de->ne", xn, f32(p["wv"])), hkv)
        k, v = jnp.repeat(k, h // hkv, 0), jnp.repeat(v, h // hkv, 0)
        o = lax.map(lambda a: _sla_head(*a, sla, nm),
                    (q, k, v, f32(p["sla_proj"])))
        o = o.transpose(1, 0, 2).reshape(n, h * dh)
        xx = xx + g1 * nm.ein("ne,ed->nd", o, f32(p["wo"]))
        # cross-attention to the conditioning (full softmax)
        xq = heads(nm.ein("nd,de->ne", rms_norm(xx, p["ln_x"]),
                          f32(p["xq"])), h)
        xk = jnp.repeat(heads(nm.ein("ld,de->le", cond, f32(p["xk"])), hkv),
                        h // hkv, 0)
        xv = jnp.repeat(heads(nm.ein("ld,de->le", cond, f32(p["xv"])), hkv),
                        h // hkv, 0)
        att = jax.nn.softmax(nm.ein("hnd,hld->hnl", xq, xk) * dh ** -0.5,
                             axis=-1)
        xo = nm.ein("hnl,hld->hnd", att, xv).transpose(1, 0, 2) \
            .reshape(n, h * dh)
        xx = xx + nm.ein("ne,ed->nd", xo, f32(p["xo"]))
        xn2 = rms_norm(xx, p["ln2"]) * (1 + sc2) + sh2
        gate, up = jnp.split(nm.ein("nd,df->nf", xn2, f32(p["mlp_wi"])), 2,
                             axis=-1)
        xx = xx + g2 * nm.ein("nf,fd->nd", jax.nn.silu(gate) * up,
                              f32(p["mlp_wo"]))
        return xx, None

    xx, _ = lax.scan(layer, xx, params["layers"])
    return nm.ein("nd,dp->np", rms_norm(xx, params["ln_f"]),
                  f32(params["patch_out"]))


@functools.partial(jax.jit, static_argnames=("arch_key", "inputs"))
def _step(params, x, t, dt, cond, arch_key, inputs):
    arch = dict(arch_key[0])
    arch["sla"] = dict(arch_key[1])
    return x - dt * velocity(params, arch, x, t, cond, Numerics(inputs))


def _freeze(arch: dict):
    top = tuple(sorted((k, v) for k, v in arch.items()
                       if not isinstance(v, dict)))
    return top, tuple(sorted(arch["sla"].items()))


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
