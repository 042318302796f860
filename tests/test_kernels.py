"""Pallas kernel validation (interpret mode): backward-kernel and
property sweeps against the pure-jnp oracle. Forward backend parity
(dtype x causal x fresh/reused plan, incl. phi variants) lives in the
table-driven matrix in test_conformance.py."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import SLAConfig, compute_mask
from repro.core.phi import phi
from repro.kernels.ops import sla_attention_core
from repro.kernels.ref import sla_attention_core_reference


def _inputs(seed, b, h, n, d, dtype, causal, block=16, kh=0.25, kl=0.25,
            phi_kind="softmax"):
    rs = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(r, (b, h, n, d), dtype) * 1.3 for r in rs)
    cfg = SLAConfig(block_q=block, block_kv=block, kh_frac=kh, kl_frac=kl,
                    causal=causal, phi=phi_kind)
    qp = phi(q, cfg.phi).astype(dtype)
    kp = phi(k, cfg.phi).astype(dtype)
    mc = compute_mask(q, k, cfg)
    return q, k, v, qp, kp, mc, cfg


SWEEP = [
    # (b, h, n, d, dtype, causal, block)
    (1, 1, 64, 16, jnp.float32, False, 16),
    (2, 2, 128, 32, jnp.float32, True, 16),
    (1, 2, 128, 64, jnp.float32, False, 32),
    (2, 1, 256, 16, jnp.bfloat16, False, 32),
    (1, 2, 128, 32, jnp.bfloat16, True, 16),
    (1, 4, 128, 8, jnp.float32, True, 32),  # tiny head dim
]


@pytest.mark.parametrize("b,h,n,d,dtype,causal,block", SWEEP[:4])
def test_bwd_matches_oracle(b, h, n, d, dtype, causal, block):
    q, k, v, qp, kp, mc, cfg = _inputs(1, b, h, n, d, dtype, causal, block)

    def loss_k(q, k, v, qp, kp):
        a, b_ = sla_attention_core(q, k, v, qp, kp, mc, cfg)
        return jnp.sum(jnp.sin(a.astype(jnp.float32))) + \
            jnp.sum(jnp.cos(b_.astype(jnp.float32)))

    def loss_r(q, k, v, qp, kp):
        a, b_ = sla_attention_core_reference(q, k, v, qp, kp, mc, cfg)
        return jnp.sum(jnp.sin(a.astype(jnp.float32))) + \
            jnp.sum(jnp.cos(b_.astype(jnp.float32)))

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3, 4))(q, k, v, qp, kp)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3, 4))(q, k, v, qp, kp)
    tol = 5e-4 if dtype == jnp.float32 else 0.12
    for name, a, b_ in zip("dq dk dv dqp dkp".split(), gk, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32),
            atol=tol, rtol=tol, err_msg=name)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000), kh=st.sampled_from([0.1, 0.25, 0.5]),
       kl=st.sampled_from([0.0, 0.25]), causal=st.booleans())
def test_property_kernel_equals_oracle(seed, kh, kl, causal):
    q, k, v, qp, kp, mc, cfg = _inputs(seed, 1, 2, 64, 16, jnp.float32,
                                       causal, 16, kh, kl)
    os_k, ol_k = sla_attention_core(q, k, v, qp, kp, mc, cfg)
    os_r, ol_r = sla_attention_core_reference(q, k, v, qp, kp, mc, cfg)
    np.testing.assert_allclose(np.asarray(os_k), np.asarray(os_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(ol_k), np.asarray(ol_r),
                               atol=1e-4, rtol=1e-4)


def test_kernel_under_jit_and_vmapless_batching():
    q, k, v, qp, kp, mc, cfg = _inputs(3, 2, 3, 128, 32, jnp.float32,
                                       False, 32)
    f = jax.jit(lambda *a: sla_attention_core(*a, mc, cfg))
    o1 = f(q, k, v, qp, kp)
    o2 = sla_attention_core(q, k, v, qp, kp, mc, cfg)
    np.testing.assert_allclose(np.asarray(o1[0]), np.asarray(o2[0]),
                               atol=1e-6)


# --- sla_fwd row streaming: one grid step per query row, the row's live
# critical blocks streamed through a two-slot VMEM buffer inside it ---

def _row_plan(seed, bh, tm, tn, k_sel, causal, base, min_count):
    """A LUT/counts pair as the plan lays it out: live entries first,
    padded slots repeating the row's first index. Causal rows see only
    blocks up to their own (absolute) block and always hold it. With
    `min_count` set, each row's count is drawn from [min_count, K_sel]."""
    r = np.random.RandomState(seed)
    lut = np.zeros((bh, tm, k_sel), np.int32)
    counts = np.zeros((bh, tm), np.int32)
    for h in range(bh):
        for i in range(tm):
            seen = min(base + i + 1, tn) if causal else tn
            c = min(k_sel, seen)
            if min_count is not None:
                c = r.randint(min_count, c + 1)
            sel = r.choice(seen, max(c, 1), replace=False)
            if causal and base + i not in sel:
                sel[0] = base + i
            lut[h, i, :c] = sel[:c]
            lut[h, i, c:] = sel[0]
            counts[h, i] = c
    return jnp.asarray(lut), jnp.asarray(counts)


def _fwd_operands(seed, bh, bh_kv, n_q, n_kv, d, block, k_sel, causal,
                  base, min_count, dtype):
    tm, tn = n_q // block, n_kv // block
    lut, counts = _row_plan(seed, bh, tm, tn, k_sel, causal, base,
                            min_count)
    rs = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(rs[0], (bh, n_q, d), dtype)
    k = jax.random.normal(rs[1], (bh_kv, n_kv, d), dtype)
    v = jax.random.normal(rs[2], (bh_kv, n_kv, d), dtype)
    qp = jax.nn.softplus(jax.random.normal(rs[3], (bh, n_q, d))).astype(dtype)
    hi = jax.random.uniform(rs[4], (bh, tm, d, d))
    zi = jax.random.uniform(rs[5], (bh, tm, d))
    return lut, counts, q, k, v, qp, hi, zi


def _fwd_oracle(lut, counts, q, k, v, qp, hi, zi, scale, causal, block,
                base):
    """Dense masked softmax over each row's live critical blocks, and the
    linear branch from (H_i, Z_i), in f32 at `highest` precision."""
    bh, n_q, d = q.shape
    group = bh // k.shape[0]
    tm, tn = n_q // block, k.shape[1] // block
    live = jnp.arange(lut.shape[-1]) < counts[..., None]
    crit = jnp.zeros((bh, tm, tn), bool).at[
        jnp.arange(bh)[:, None, None], jnp.arange(tm)[None, :, None],
        lut].max(live)
    mask = jnp.repeat(jnp.repeat(crit, block, 1), block, 2)
    if causal:
        rows = base * block + jnp.arange(n_q)
        mask &= rows[:, None] >= jnp.arange(k.shape[1])[None, :]
    f32 = lambda x: x.astype(jnp.float32)
    kk, vv = (jnp.repeat(f32(x), group, 0) for x in (k, v))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("hqd,hkd->hqk", f32(q), kk) * scale
        s = jnp.where(mask, s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)
        o_s = jnp.einsum("hqk,hkd->hqd", jnp.exp(s - lse[..., None]), vv)
        qpb = f32(qp).reshape(bh, tm, block, d)
        num = jnp.einsum("hmqd,hmde->hmqe", qpb, hi)
        den = jnp.einsum("hmqd,hmd->hmq", qpb, zi)[..., None]
    o_l = jnp.where(den > 1e-6, num / jnp.where(den > 1e-6, den, 1.0), 0.0)
    return o_s, o_l.reshape(bh, n_q, d), lse


@jax.jit
def _block_update(q, kk, vv, m, l, acc, scale, keep):
    """One critical block's online-softmax update, op for op as the
    kernel's loop body computes it (2-D dots, f32 accumulation)."""
    dims = lambda t: (((1,), (1 if t else 0,)), ((), ()))
    sij = jax.lax.dot_general(q, kk, dims(True),
                              preferred_element_type=jnp.float32) * scale
    sij = jnp.where(keep, sij, -1e30)
    m_new = jnp.maximum(m, jnp.max(sij, axis=-1))
    p = jnp.exp(sij - m_new[:, None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc = acc * alpha[:, None] + jax.lax.dot_general(
        p, vv, dims(False), preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def _blockwise_sparse(lut, counts, q, k, v, scale, causal, block, base):
    """The sparse output and lse of one block at a time in LUT order: the
    arithmetic the one-block-per-grid-step kernel ran."""
    bh, n_q, d = q.shape
    group = bh // k.shape[0]
    o_s, lse = np.zeros((bh, n_q, d), np.float32), np.zeros((bh, n_q))
    rows = jnp.arange(block)[:, None]
    for h in range(bh):
        for i in range(n_q // block):
            qi = q[h, i * block:(i + 1) * block].astype(jnp.float32)
            m = jnp.full((block,), -1e30, jnp.float32)
            l = jnp.zeros((block,), jnp.float32)
            acc = jnp.zeros((block, d), jnp.float32)
            for s in range(int(counts[h, i])):
                j = int(lut[h, i, s])
                kv = slice(j * block, (j + 1) * block)
                keep = ((base + i) * block + rows
                        >= j * block + rows.T) if causal else True
                m, l, acc = _block_update(
                    qi, k[h // group, kv].astype(jnp.float32),
                    v[h // group, kv].astype(jnp.float32), m, l, acc,
                    scale, keep)
            o_s[h, i * block:(i + 1) * block] = acc / l[:, None]
            lse[h, i * block:(i + 1) * block] = m + jnp.log(l)
    return o_s, lse.astype(np.float32)


# (bh, bh_kv, n_q, n_kv, d, block, k_sel, causal, base, min_count, dtype)
ROW_STREAM = {
    "causal_counts_below_ksel": (2, 2, 128, 128, 32, 16, 4, True, 0, None,
                                 jnp.float32),
    "uneven_counts": (2, 2, 128, 128, 32, 16, 5, False, 0, 1, jnp.float32),
    "empty_rows": (2, 2, 128, 128, 32, 16, 3, False, 0, 0, jnp.float32),
    "k_sel_1": (2, 2, 64, 64, 16, 16, 1, False, 0, None, jnp.float32),
    "odd_k_sel": (2, 2, 128, 128, 32, 16, 3, False, 0, None, jnp.float32),
    "gqa_group_2": (4, 2, 128, 128, 32, 16, 3, True, 0, 1, jnp.bfloat16),
    "traced_base": (2, 2, 64, 192, 32, 16, 4, True, 8, None, jnp.float32),
    "head_dim_108": (2, 2, 64, 64, 108, 16, 2, False, 0, None,
                     jnp.float32),
}


@pytest.mark.parametrize("case", list(ROW_STREAM))
def test_sla_fwd_row_streaming_matches_oracle_and_blockwise(case):
    """The row-streaming kernel equals the dense oracle on rows whose
    live counts fall short of K_sel (empty rows among them: NaN and -inf,
    as the oracle gives), K_sel 1 and odd K_sel (rows ending on each
    slot), GQA, a traced causal base and an unaligned head dim; and,
    consuming blocks singly in LUT order, it equals the one-block-at-a-
    time arithmetic bitwise."""
    from repro.kernels.sla_fwd import sla_fwd
    (bh, bh_kv, n_q, n_kv, d, block, k_sel, causal, base, min_count,
     dtype) = ROW_STREAM[case]
    ops = _fwd_operands(7, bh, bh_kv, n_q, n_kv, d, block, k_sel, causal,
                        base, min_count, dtype)
    lut, counts, q, k, v = ops[:5]
    if min_count is not None or (causal and base < k_sel):
        assert int(counts.min()) < k_sel  # padded LUT slots exist
    if min_count == 0:
        assert int(counts.min()) == 0
    scale = d ** -0.5
    o_s, o_l, lse = sla_fwd(*ops, scale=scale, causal=causal, block_q=block,
                            block_kv=block, interpret=True,
                            base=jnp.array([base], jnp.int32))
    want = _fwd_oracle(*ops, scale, causal, block, base)
    for name, got, ref in zip(("o_s", "o_l", "lse"), (o_s, o_l, lse), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4, err_msg=name)
    bo_s, blse = _blockwise_sparse(np.asarray(lut), np.asarray(counts), q,
                                   k, v, scale, causal, block, base)
    np.testing.assert_array_equal(np.asarray(o_s), bo_s)
    np.testing.assert_array_equal(np.asarray(lse), blse)


def _pallas_grids(jaxpr):
    """(name, grid) of every pallas_call in a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], eqn.params["grid_mapping"].grid
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_grids(inner)


# (BH, BH_kv, N, D, causal, K_sel, heads per launch): the compiled
# architectures of tests/test_tpu_compile.py::FWD
FWD_GRIDS = {
    "wan2_1_1_3b": (12, 12, 32768, 128, False, 26, 6),
    "qwen3-1.7b": (16, 8, 32768, 128, True, 26, 8),
    "lightningdit_1b": (16, 16, 1024, 108, False, 2, 16),
}


@pytest.mark.parametrize("arch", list(FWD_GRIDS))
def test_sla_fwd_grid_has_one_step_per_row(arch):
    """Each sla_fwd launch's grid is (heads in the run, T_m): no K_sel
    axis, so no grid step is spent on a single block."""
    from repro.kernels.sla_fwd import head_runs, sla_fwd
    bh, bh_kv, n, d, causal, k_sel, hr = FWD_GRIDS[arch]
    tm = n // 64
    assert head_runs(bh, bh // bh_kv, tm * k_sel)[0] == (0, hr)
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(functools.partial(
        sla_fwd, scale=d ** -0.5, causal=causal, block_q=64, block_kv=64,
        interpret=False))(
        S((bh, tm, k_sel), jnp.int32), S((bh, tm), jnp.int32),
        S((bh, n, d), jnp.bfloat16), S((bh_kv, n, d), jnp.bfloat16),
        S((bh_kv, n, d), jnp.bfloat16), S((bh, n, d), jnp.bfloat16),
        S((bh, tm, d, d), jnp.float32), S((bh, tm, d), jnp.float32))
    grids = list(_pallas_grids(jaxpr.jaxpr))
    assert grids == [("sla_fwd", (hr, tm))] * (bh // hr)
