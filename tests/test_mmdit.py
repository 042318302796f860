"""The dual-stream then single-stream MMDiT (models/mmdit.py, HunyuanVideo
HYVideo-T/2) at smoke widths: its 3-D RoPE against a per-axis
construction, the grouped execution of its heads against the whole, its
input checks, and the serving CLI end to end. The comparison with the
plain reference is bench/tests/test_bench_hunyuan.py; the scheduler's
bitwise parity with `sample` is tests/test_dit_serving.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import plan as plan_lib
from repro.models import dit, mmdit


@pytest.fixture(scope="module")
def cfg():
    return get_arch("hunyuanvideo-t2").smoke()


def _rotary_1d(x, pos, theta):
    """Plain 1-D rotary of x (N, D) at positions pos (N,): pair (2j,
    2j+1) turns by pos * theta^(-2j / D)."""
    d = x.shape[-1]
    out = np.empty_like(x)
    for j in range(d // 2):
        ang = pos * theta ** (-2.0 * j / d)
        c, s = np.cos(ang), np.sin(ang)
        a, b = x[:, 2 * j], x[:, 2 * j + 1]
        out[:, 2 * j], out[:, 2 * j + 1] = a * c - b * s, a * s + b * c
    return out


def test_rope_3d_matches_a_per_axis_construction(cfg):
    """Each axis's slice of the head dim is the 1-D rotary of that axis's
    coordinate (frame, row, column); the text rows are left as they are."""
    hh, ww = cfg.latent_hw
    frames, dh = 2, cfg.head_dim
    n = frames * hh * ww
    x = np.random.default_rng(0).standard_normal(
        (1, n + cfg.cond_len, 3, dh)).astype(np.float32)
    cos, sin = mmdit.rope_tables(cfg, n)
    got = np.asarray(mmdit.apply_rope(jnp.asarray(x), cos, sin))
    i = np.arange(n)
    coords = (i // (hh * ww), (i // ww) % hh, i % ww)
    off = 0
    for pos, d in zip(coords, cfg.rope_axes):
        for head in range(3):
            want = _rotary_1d(x[0, :n, head, off:off + d].astype(np.float64),
                              pos.astype(np.float64), cfg.rope_theta)
            np.testing.assert_allclose(got[0, :n, head, off:off + d], want,
                                       rtol=1e-5, atol=1e-5)
        off += d
    assert off == dh
    assert np.array_equal(got[0, n:], x[0, n:])


def test_rope_refuses_a_partial_frame(cfg):
    hh, ww = cfg.latent_hw
    with pytest.raises(ValueError, match="whole frames"):
        mmdit.rope_tables(cfg, hh * ww + 1)


@pytest.mark.parametrize("backend", ["gather", "kernel"])
def test_grouped_heads_equal_the_whole(cfg, backend):
    """Planned once over every head, then executed in groups one after
    the other: each head gets what the whole would give it."""
    b, h, n, dh = 1, cfg.num_heads, 64, cfg.head_dim
    r = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(ri, (b, h, n, dh)) for ri in r[:3])
    proj = {"sla_proj": 0.1 * jax.random.normal(r[3], (h, dh, dh))}
    sla = dataclasses.replace(cfg.sla, causal=False)
    plan = plan_lib.plan_attention(q, k, sla)

    def run(groups):
        attn = dit.SelfAttention.build(
            cfg, b, backend=backend, sla_mode=None, plans=None,
            drift_threshold=None, per_sample_refresh=False,
            return_plans=False, head_groups=groups)
        return np.asarray(attn(proj, q, k, v, plan, None)[0])

    whole, halves = run(1), run(2)
    np.testing.assert_allclose(halves, whole, rtol=1e-6, atol=1e-6)


def test_serve_cli_runs_the_mmdit(cfg):
    from repro.launch import serve

    done = serve.main(["--arch", "hunyuanvideo-t2", "--workload", "dit",
                       "--smoke", "--requests", "3", "--batch", "2",
                       "--num-steps", "2"])
    assert len(done) == 3
    assert all(r.result.shape == (2 * cfg.sla.block_q, cfg.patch_dim)
               and np.isfinite(r.result).all() for r in done)


def test_scheduler_equals_sample_with_grouped_heads(cfg, monkeypatch):
    """With the heads executed in groups (2 of 2 heads at smoke width),
    a mixed-timestep batch through the scheduler still gives each
    request bitwise what its own `dit.sample` run gives."""
    from repro.models import registry
    from repro.serving.diffusion import DenoiseParams, DiffusionScheduler

    monkeypatch.setattr(mmdit, "HEAD_GROUPS", 2)
    params = registry.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    n = cfg.latent_hw[0] * cfg.latent_hw[1]
    lat = [np.asarray(jax.random.normal(jax.random.PRNGKey(i), (n, 16)))
           for i in range(3)]
    trace = ((3, 1.0), (2, 0.5), (2, 1.0))
    sched = DiffusionScheduler(cfg, params, num_slots=2, seq_len=n,
                               backend="gather", compute_dtype=jnp.float32,
                               refresh_mode="adaptive", drift_threshold=0.2)
    for x, (steps, t0) in zip(lat, trace):
        sched.submit(x, DenoiseParams(num_steps=steps, t_start=t0))
    done = sched.drain()
    for x, (steps, t0), r in zip(lat, trace, done):
        ref = dit.sample(params, cfg, jnp.asarray(x[None]), num_steps=steps,
                         compute_dtype=jnp.float32, backend="gather",
                         refresh_mode="adaptive", drift_threshold=0.2,
                         t_start=t0)
        assert np.array_equal(np.asarray(ref[0]), r.result)
