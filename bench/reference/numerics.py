"""Shared plain mathematics of the references: matrix products at a
stated precision, norms, rotary embedding and SLA block classification."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

NEG = -1e30
F8_MAX = 448.0  # largest finite float8_e4m3fn
# each product-input precision and the next one below it
BELOW = {"float32": "bfloat16", "bfloat16": "float8"}


@dataclasses.dataclass(frozen=True)
class Numerics:
    """Matrix products from inputs rounded to `inputs`, accumulated in
    float32 at `highest` precision: "float32" (the reference), "bfloat16"
    (round to nearest), or "float8" (e4m3 with a per-tensor scale)."""

    inputs: str = "float32"

    def q(self, x):
        x = x.astype(jnp.float32)
        if self.inputs == "float32":
            return x
        if self.inputs == "bfloat16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        if self.inputs == "float8":
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
            return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        raise ValueError(f"no product precision {self.inputs!r}")

    def ein(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b),
                          precision=lax.Precision.HIGHEST)


def rms_norm(x, w, eps=1e-6):
    x = x.astype(jnp.float32)
    r = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r * (1.0 + w.astype(jnp.float32))


def rope(x, positions, theta):
    """Rotary embedding over the last axis, halves rotated as pairs."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def feature(x):
    """The linear branch's feature map phi: softmax over the head dim."""
    return jax.nn.softmax(x.astype(jnp.float32), axis=-1)


def block_pool(x, block):
    """(..., N, D) -> (..., N / block, D) block means."""
    return x.reshape(*x.shape[:-2], x.shape[-2] // block, block,
                     x.shape[-1]).mean(axis=-2)


def desc_rank(score, axis=-1):
    """Rank of each entry in descending order along `axis`; ties go to
    the lower index first."""
    order = jnp.argsort(-score, axis=axis, stable=True)
    return jnp.argsort(order, axis=axis, stable=True)


def classify(pc, valid, diag, n_crit, n_neg, col_cap=None):
    """SLA's three-way block classification (paper Eq. 3) of a score
    map pc (..., Tm, Tn): the n_crit highest blocks of each row are
    critical (+1), the n_neg lowest negligible (-1), the rest marginal
    (0). The diagonal ranks first; invalid (causally masked) blocks rank
    last and are negligible. With `col_cap`, a column holding more than
    col_cap critical blocks keeps its highest-scoring ones and turns the
    rest marginal (the TPU kernel's static column budget)."""
    tn = pc.shape[-1]
    score = jnp.where(valid, pc, -1.0)
    score = jnp.where(diag, 2.0, score)
    rank = desc_rank(score)
    mc = jnp.where(rank < n_crit, 1, 0)
    if n_neg > 0:
        mc = jnp.where(rank >= tn - n_neg, -1, mc)
    mc = jnp.where(valid, mc, -1)
    if col_cap is not None:
        crit = mc == 1
        col_rank = desc_rank(jnp.where(crit, score, -2.0), axis=-2)
        mc = jnp.where(crit & (col_rank >= col_cap), 0, mc)
    return mc


def critical_lut(mc, width):
    """The first `width` critical blocks of each row, ascending, and how
    many critical blocks each row has."""
    tn = mc.shape[-1]
    j = jnp.arange(tn)
    crit = mc == 1
    lut = jnp.argsort(jnp.where(crit, j, tn + j), axis=-1)[..., :width]
    return lut, jnp.sum(crit, axis=-1)


def safe_div(num, den, eps=1e-6):
    live = den > eps
    return jnp.where(live, num / jnp.where(live, den, 1.0), 0.0)


def sla_counts(tn, kh_frac, kl_frac, tm=None, col_capacity_factor=None):
    """(critical per row, negligible per row, column cap) as SLA sizes
    them: round(k_h * Tn) at least 1, round(k_l * Tn), and the column
    cap round(cf * Tm * n_crit / Tn) clipped to [1, Tm]."""
    n_crit = max(1, round(kh_frac * tn))
    n_neg = max(0, round(kl_frac * tn))
    cap = None
    if col_capacity_factor is not None:
        cap = max(1, min(tm, round(col_capacity_factor * (tm * n_crit / tn))))
    return n_crit, n_neg, cap
