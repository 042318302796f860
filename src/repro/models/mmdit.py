"""Multimodal DiT with dual-stream then single-stream blocks (HunyuanVideo,
HYVideo-T/2; `hyvideo/modules/models.py`).

The video tokens and the text tokens attend jointly, as one sequence
[video; text] of N + Lc tokens. The first `cfg.double_layers` blocks are
dual-stream: each stream has its own AdaLN modulation, qkv, q/k RMSNorm,
output projection and MLP, and only the attention is joint. The rest are
single-stream: one weight set over the joint sequence, whose first
product gives q, k, v and the MLP input at once (the published
`linear1`, split here by its columns) and whose last sums the attention
output and the MLP (`linear2`, split by its rows). 3-D RoPE (interleaved
pairs, `cfg.rope_axes` of the head dim over frame, row and column) turns
the video part of q and k only. LayerNorms carry no affine parameters;
the modulation of every block and of the final layer comes from
SiLU(vec), vec the timestep embedding's two-layer MLP.

Self-attention is SLA through `dit.SelfAttention`, the step `dit.forward`
runs: one layer scan covers both kinds of block, `lax.cond` choosing
the kind by the layer's index (DESIGN.md "MMDiT" says why), and their
per-layer plans form one stack of `cfg.num_layers` (dual-stream first), so
sampling, serving and plan reuse treat this model as they treat `dit`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import dit
from repro.models.common import dense_init, rms_norm

# the losses (like sampling and serving) are dit's: only forward differs
from repro.models.dit import distill_loss_fn, loss_fn  # noqa: F401

# the joint attention's heads execute in this many groups, one after the
# other: at 65k tokens the linear branch's state for all 24 heads at
# once (H_i, 1024 rows x 128 x 128 f32 a head: 1.6 GB, and the copy XLA
# makes into the kernel's layout) does not fit one chip beside the
# weights and the plans
HEAD_GROUPS = 3


def _stream_init(rng, cfg: ArchConfig, mods: int, dtype) -> dict:
    """qkv, output, q/k norm, MLP and `mods` modulation vectors."""
    d, hdh = cfg.d_model, cfg.num_heads * cfg.head_dim
    r = jax.random.split(rng, 7)
    return {
        "ada": (jax.random.normal(r[0], (d, mods * d), jnp.float32)
                * 0.01).astype(dtype),
        "wq": dense_init(r[1], d, hdh, dtype),
        "wk": dense_init(r[2], d, hdh, dtype),
        "wv": dense_init(r[3], d, hdh, dtype),
        "wo": dense_init(r[4], hdh, d, dtype),
        "qnorm": jnp.zeros((cfg.head_dim,), dtype),
        "knorm": jnp.zeros((cfg.head_dim,), dtype),
        "mlp_wi": dense_init(r[5], d, cfg.d_ff, dtype),
        "mlp_wo": dense_init(r[6], cfg.d_ff, d, dtype),
    }


def _proj_init(cfg: ArchConfig, dtype):
    return jnp.zeros((cfg.num_heads, cfg.head_dim, cfg.head_dim), dtype)


def init(rng, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    nd = cfg.double_layers
    ns = cfg.num_layers - nd
    r = jax.random.split(rng, 6)

    def double(k):
        ki, kt = jax.random.split(k)
        return {"img": _stream_init(ki, cfg, 6, dtype),
                "txt": _stream_init(kt, cfg, 6, dtype),
                "sla_proj": _proj_init(cfg, dtype)}

    def single(k):
        return dict(_stream_init(k, cfg, 3, dtype),
                    sla_proj=_proj_init(cfg, dtype))

    d = cfg.d_model
    return {
        "patch_in": dense_init(r[0], cfg.patch_dim, d, dtype),
        "time_in": {"mlp_wi": dense_init(r[1], 256, d, dtype),
                    "mlp_wo": dense_init(r[2], d, d, dtype)},
        "double": jax.vmap(double)(jax.random.split(r[3], nd)),
        "single": jax.vmap(single)(jax.random.split(r[4], ns)),
        "final": {"ada": jnp.zeros((d, 2 * d), dtype),
                  "patch_out": jnp.zeros((d, cfg.patch_dim), dtype)},
    }


def attention_len(cfg: ArchConfig, seq_len: int) -> int:
    """Tokens the joint attention runs over: video, then text."""
    return seq_len + cfg.cond_len


def layer_norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """LayerNorm without affine parameters, statistics in f32."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def modulate(x, shift, scale):
    """x * (1 + scale) + shift, with (B, d) modulation over (B, N, d)."""
    return x * (1 + scale[:, None]) + shift[:, None]


def rope_tables(cfg: ArchConfig, seq_len: int):
    """(cos, sin), each (seq_len + Lc, head_dim) f32: 3-D RoPE of the
    video tokens (frame-major over a grid of seq_len // (h * w) frames of
    `cfg.latent_hw`), each axis's `cfg.rope_axes` share of the head dim
    in interleaved pairs; the text rows are the identity (cos 1, sin 0),
    so the joint sequence turns its video part only."""
    hh, ww = cfg.latent_hw
    if seq_len % (hh * ww):
        raise ValueError(f"{seq_len} video tokens are not whole frames of "
                         f"{hh} x {ww} latent patches")
    if sum(cfg.rope_axes) != cfg.head_dim:
        raise ValueError(f"rope_axes {cfg.rope_axes} do not split "
                         f"head_dim {cfg.head_dim}")
    grid = jnp.meshgrid(jnp.arange(seq_len // (hh * ww)), jnp.arange(hh),
                        jnp.arange(ww), indexing="ij")
    angles = []
    for pos, dim in zip(grid, cfg.rope_axes):
        freq = cfg.rope_theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32)
                                  / dim)
        angles.append(pos.reshape(-1, 1).astype(jnp.float32) * freq)
    ang = jnp.repeat(jnp.concatenate(angles, axis=-1), 2, axis=-1)
    text = jnp.zeros((cfg.cond_len, cfg.head_dim), jnp.float32)
    return (jnp.concatenate([jnp.cos(ang), jnp.cos(text)]),
            jnp.concatenate([jnp.sin(ang), jnp.sin(text)]))


def apply_rope(x, cos, sin):
    """x: (B, N, H, Dh); pairs (2i, 2i+1) turned by cos/sin (N, Dh)."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    turned = jnp.stack([-pairs[..., 1], pairs[..., 0]], axis=-1) \
        .reshape(x.shape)
    return (x * cos[:, None].astype(x.dtype)
            + turned * sin[:, None].astype(x.dtype))


def forward(params, cfg: ArchConfig, latents, t,
            cond: Optional[jax.Array] = None,
            compute_dtype=jnp.bfloat16, backend: str = "gather",
            sla_mode: Optional[str] = None,
            plans=None, return_plans: bool = False,
            drift_threshold=None, per_sample_refresh: bool = False):
    """Velocity of the video latents (B, N, patch_dim) at time t, with
    text states cond (B, Lc, d_model) (zeros when None). Arguments and
    return values as `dit.forward`: the plan stack covers the dual-stream
    layers, then the single-stream ones."""
    t = jnp.asarray(t, jnp.float32)
    b, n = latents.shape[:2]
    if t.ndim == 0:
        t = jnp.broadcast_to(t, (b,))
    h, dh = cfg.num_heads, cfg.head_dim
    if cond is None:
        cond = jnp.zeros((b, cfg.cond_len, cfg.d_model), compute_dtype)
    with jax.named_scope("dit.embed"):
        img = jnp.einsum("bnp,pd->bnd", latents.astype(compute_dtype),
                         params["patch_in"].astype(compute_dtype))
        txt = cond.astype(compute_dtype)
        tin = params["time_in"]
        vec = jax.nn.silu(jnp.einsum(
            "be,ed->bd", dit.timestep_embedding(t * 1000.0),
            tin["mlp_wi"].astype(jnp.float32)))
        vec = jnp.einsum("bd,de->be", vec, tin["mlp_wo"].astype(jnp.float32))
        svec = jax.nn.silu(vec).astype(compute_dtype)
        cos, sin = rope_tables(cfg, n)
    attn = dit.SelfAttention.build(
        cfg, b, backend=backend, sla_mode=sla_mode, plans=plans,
        drift_threshold=drift_threshold,
        per_sample_refresh=per_sample_refresh, return_plans=return_plans,
        head_groups=HEAD_GROUPS if h % HEAD_GROUPS == 0 else 1)
    nd = cfg.double_layers

    def mods(p, k):
        m = jnp.einsum("bd,de->be", svec, p["ada"].astype(svec.dtype))
        return jnp.split(m, k, axis=-1)

    def project(p, xn):
        """q, k, v as (B, L, H, Dh)."""
        def heads(w):
            return jnp.einsum("bsd,de->bse", xn, p[w].astype(xn.dtype)) \
                .reshape(xn.shape[0], xn.shape[1], h, dh)
        return heads("wq"), heads("wk"), heads("wv")

    def qk_norm(p, q, k):
        return rms_norm(q, p["qnorm"]), rms_norm(k, p["knorm"])

    def joint_attention(p, q, k, v, layer_plan, thr):
        """RoPE, then SLA over the joint sequence: (B, L, H * Dh)."""
        with jax.named_scope("mmdit.rope"):
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        o, ys = attn(p, q, k, v, layer_plan, thr)
        return o.transpose(0, 2, 1, 3).reshape(b, -1, h * dh), ys

    def mlp(p, xn):
        hmid = jnp.einsum("bsd,df->bsf", xn, p["mlp_wi"].astype(xn.dtype))
        return jnp.einsum("bsf,fd->bsd", jax.nn.gelu(hmid, approximate=True),
                          p["mlp_wo"].astype(xn.dtype))

    def double(x, p, layer_plan, thr):
        pi, pt = p["img"], p["txt"]
        with jax.named_scope("mmdit.double"):
            with jax.named_scope("mmdit.joint"):
                img, txt = x[:, :n], x[:, n:]
            with jax.named_scope("dit.adaln"):
                mi, mt = mods(pi, 6), mods(pt, 6)
                xi = modulate(layer_norm(img), mi[0], mi[1])
                xt = modulate(layer_norm(txt), mt[0], mt[1])
            with jax.named_scope("dit.qkv"):
                qi, ki, vi = project(pi, xi)
                qt, kt, vt = project(pt, xt)
            with jax.named_scope("mmdit.rope"):
                qi, ki = qk_norm(pi, qi, ki)
                qt, kt = qk_norm(pt, qt, kt)
            with jax.named_scope("mmdit.joint"):
                q, k, v = (jnp.concatenate(a, axis=1) for a in
                           ((qi, qt), (ki, kt), (vi, vt)))
            o, ys = joint_attention(p, q, k, v, layer_plan, thr)
            with jax.named_scope("mmdit.joint"):
                oi, ot = o[:, :n], o[:, n:]
            with jax.named_scope("dit.attn_out"):
                img = img + mi[2][:, None] * jnp.einsum(
                    "bse,ed->bsd", oi, pi["wo"].astype(oi.dtype))
                txt = txt + mt[2][:, None] * jnp.einsum(
                    "bse,ed->bsd", ot, pt["wo"].astype(ot.dtype))
            with jax.named_scope("dit.adaln"):
                xi = modulate(layer_norm(img), mi[3], mi[4])
                xt = modulate(layer_norm(txt), mt[3], mt[4])
            with jax.named_scope("dit.mlp"):
                img = img + mi[5][:, None] * mlp(pi, xi)
                txt = txt + mt[5][:, None] * mlp(pt, xt)
            with jax.named_scope("mmdit.joint"):
                x = jnp.concatenate([img, txt], axis=1)
        return x, ys

    def single(x, p, layer_plan, thr):
        with jax.named_scope("mmdit.single"):
            with jax.named_scope("dit.adaln"):
                shift, scale, gate = mods(p, 3)
                xn = modulate(layer_norm(x), shift, scale)
            with jax.named_scope("dit.qkv"):
                q, k, v = project(p, xn)
            with jax.named_scope("mmdit.rope"):
                q, k = qk_norm(p, q, k)
            o, ys = joint_attention(p, q, k, v, layer_plan, thr)
            with jax.named_scope("dit.attn_out"):
                a = jnp.einsum("bse,ed->bsd", o, p["wo"].astype(o.dtype))
            with jax.named_scope("dit.mlp"):
                x = x + gate[:, None] * (a + mlp(p, xn))
        return x, ys

    def layer(x, xs):
        """Layer i of the stack: dual-stream below nd, single after. One
        scan over every layer reads and writes the plan stack whole."""
        i, layer_plan, thr = xs

        def kind(block, stack, j):
            p = jax.tree_util.tree_map(lambda a: a[j], stack)
            return lambda: block(x, p, layer_plan, thr)

        if nd == 0:
            return kind(single, params["single"], i)()
        if nd == cfg.num_layers:
            return kind(double, params["double"], i)()
        return jax.lax.cond(
            i < nd, kind(double, params["double"], jnp.minimum(i, nd - 1)),
            kind(single, params["single"], jnp.maximum(i - nd, 0)))

    thresholds = (attn.thresholds(cfg, drift_threshold) if attn.adaptive
                  else None)
    with jax.named_scope("mmdit.joint"):
        x = jnp.concatenate([img, txt], axis=1)
    x, ys = attn.scan(layer, x, jnp.arange(cfg.num_layers), plans,
                      thresholds)
    with jax.named_scope("dit.head"):
        shift, scale = mods(params["final"], 2)
        x = modulate(layer_norm(x[:, :n]), shift, scale)
        out = jnp.einsum("bnd,dp->bnp", x,
                         params["final"]["patch_out"].astype(x.dtype))
    return attn.outputs(out, ys)
