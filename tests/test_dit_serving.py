"""Streaming DiT service tests (ISSUE 10).

Tiers:
  * plan-cache unit coverage: hit/miss/invalidation counters, the LRU
    eviction bound, serialization round-trip + compat-key discrimination;
  * per-sample refresh: `refresh_plan_per_sample` row-for-row bitwise
    equal to batch-1 `refresh_plan` (the lemma the scheduler's parity
    rests on), and scalar-vs-vector t bitwise in dit.forward;
  * the acceptance claim: a multi-user mixed-timestep
    DiffusionScheduler trace produces per-request final latents
    bitwise-equal to sequential per-request `dit.sample` runs (gather
    fast tier; reference + fixed-mode variants in the slow tier);
  * plan-cache drift parity: cached-plan outputs equal fresh-plan
    outputs within the conformance-matrix f32 tolerances;
  * registry smoke: wan2_1_1_3b, lightningdit_1b and hunyuanvideo-t2
    (the MMDiT) build, run one dit.sample step under SLA, and round-trip
    through the scheduler.

The parity and smoke tests run over both DiT models (dit, mmdit): the
scheduler and the sampler are shared, only the forward differs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import plan as plan_lib
from repro.models import dit, registry
from repro.serving.api import RequestState, stats_json_payload
from repro.serving.diffusion import (DenoiseParams, DenoiseRequest,
                                     DiffusionScheduler)
from repro.serving.plan_cache import PlanCache

TOL_F32 = dict(atol=5e-5, rtol=5e-5)  # tests/test_conformance.py TOL
SEQ = 32


@pytest.fixture(scope="module")
def lightning():
    cfg = get_arch("lightningdit_1b").smoke()
    return cfg, dit.init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module",
                params=["lightningdit_1b", "hunyuanvideo-t2"])
def dit_model(request):
    """Each DiT model the scheduler serves: dit, and the mmdit (at SEQ
    latent tokens, one 4 x 8 frame, joined by its 16 text tokens)."""
    cfg = get_arch(request.param).smoke()
    return cfg, registry.get_model(cfg).init(jax.random.PRNGKey(0), cfg)


def _latent(cfg, i):
    return np.asarray(jax.random.normal(
        jax.random.PRNGKey(i + 1), (SEQ, cfg.patch_dim), jnp.float32))


def _qk(cfg, seed, b=3):
    r = jax.random.split(jax.random.PRNGKey(seed), 2)
    h, dh = cfg.num_heads, cfg.head_dim
    q = jax.random.normal(r[0], (b, h, SEQ, dh), jnp.float32)
    k = jax.random.normal(r[1], (b, h, SEQ, dh), jnp.float32)
    return q, k


def _sla(cfg):
    return dataclasses.replace(cfg.sla, causal=False)


def _plan_stack(cfg, seed, layers=None):
    """Per-layer stacked batch-1 plans (leaves (L, 1, ...)) the way the
    scheduler stores them."""
    layers = cfg.num_layers if layers is None else layers
    sla = _sla(cfg)
    rows = []
    for l in range(layers):
        q, k = _qk(cfg, seed + 17 * l, b=1)
        rows.append(plan_lib.plan_attention(q, k, sla))
    return jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *rows)


# ---------------------------------------------------------------------------
# plan serialization + compat key
# ---------------------------------------------------------------------------
def test_plan_serialization_roundtrip(lightning):
    cfg, _ = lightning
    q, k = _qk(cfg, 0, b=2)
    plan = plan_lib.plan_attention(q, k, _sla(cfg))
    back = plan_lib.deserialize_plan(plan_lib.serialize_plan(plan))
    for name in ("mc", "lut", "counts", "col_lut", "col_counts",
                 "marginal"):
        a, b = getattr(plan, name), getattr(back, name)
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_plan_deserialize_rejects_wrong_version(lightning):
    cfg, _ = lightning
    q, k = _qk(cfg, 0, b=1)
    data = plan_lib.serialize_plan(plan_lib.plan_attention(q, k, _sla(cfg)))
    data["__version__"] = 99
    with pytest.raises(ValueError, match="wire version"):
        plan_lib.deserialize_plan(data)


def test_plan_compat_key_discriminates(lightning):
    cfg, _ = lightning
    sla = _sla(cfg)
    base = plan_lib.plan_compat_key(sla, 2, 4, 4)
    assert base == plan_lib.plan_compat_key(sla, 2, 4, 4)
    assert base != plan_lib.plan_compat_key(sla, 2, 8, 8)  # shape
    other = dataclasses.replace(sla, kh_frac=sla.kh_frac * 2)
    assert base != plan_lib.plan_compat_key(other, 2, 4, 4)  # config
    # execution-only knobs must NOT invalidate cached structure
    phi = dataclasses.replace(sla, phi="relu")
    assert base == plan_lib.plan_compat_key(phi, 2, 4, 4)


# ---------------------------------------------------------------------------
# PlanCache: counters, LRU bound
# ---------------------------------------------------------------------------
def test_plan_cache_hit_miss_invalidation_counters(lightning):
    cfg, _ = lightning
    cache = PlanCache(_sla(cfg), cfg.num_layers, t_buckets=4,
                      max_entries=64)
    assert cache.get(3) is None and cache.misses == 1
    stack = _plan_stack(cfg, 1)
    cache.put(3, stack)
    got = cache.get(3)
    assert got is not None and cache.hits == 1
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(stack)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # drift invalidation: layer 0 re-planned, layer 1 held
    stack2 = _plan_stack(cfg, 2)
    flags = np.zeros((cfg.num_layers,), bool)
    flags[0] = True
    assert cache.update(3, stack2, flags) == 1
    assert cache.invalidations == 1
    got2 = cache.get(3)
    assert np.array_equal(np.asarray(got2.mc[0]), np.asarray(stack2.mc[0]))
    assert np.array_equal(np.asarray(got2.mc[1]), np.asarray(stack.mc[1]))


def test_plan_cache_lru_eviction_bound(lightning):
    cfg, _ = lightning
    nl = cfg.num_layers
    cache = PlanCache(_sla(cfg), nl, t_buckets=8, max_entries=2 * nl)
    stack = _plan_stack(cfg, 1)
    for bucket in range(4):
        cache.put(bucket, stack)
        assert len(cache) <= 2 * nl  # the bound holds at every step
    assert cache.evictions == 2 * nl  # 4 buckets in, 2 evicted whole
    assert cache.get(0) is None  # oldest bucket gone
    assert cache.get(3) is not None  # newest retained
    # a hit refreshes recency: bucket 2 survives the next insertion
    assert cache.get(2) is not None
    cache.put(5, stack)
    assert cache.get(2) is not None
    assert cache.get(3) is None  # bucket 3 was the LRU, evicted


def test_plan_cache_bucket_of_t():
    cfg = get_arch("lightningdit_1b").smoke()
    cache = PlanCache(_sla(cfg), cfg.num_layers, t_buckets=8)
    assert cache.bucket(1.0) == 7  # t=1.0 clamps into the top bucket
    assert cache.bucket(0.999) == 7
    assert cache.bucket(0.5) == 4
    assert cache.bucket(1e-6) == 0
    assert cache.bucket(0.0) == 0


def test_plan_cache_rejects_incompatible_plan(lightning):
    cfg, _ = lightning
    cache = PlanCache(_sla(cfg), cfg.num_layers, t_buckets=4)
    cache.put(0, _plan_stack(cfg, 1))
    sla = _sla(cfg)
    q, k = _qk(cfg, 3, b=1)
    q2 = jnp.concatenate([q, q], axis=2)  # 2x seq -> 2x blocks
    k2 = jnp.concatenate([k, k], axis=2)
    rows = [plan_lib.plan_attention(q2, k2, sla)
            for _ in range(cfg.num_layers)]
    stack = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *rows)
    with pytest.raises(ValueError, match="incompatible"):
        cache.put(1, stack)


# ---------------------------------------------------------------------------
# per-sample refresh + vector-t lemmas
# ---------------------------------------------------------------------------
def test_refresh_plan_per_sample_matches_batch1(lightning):
    """Row r of the per-sample refresh over a batch == refresh_plan on
    row r alone — bitwise on every leaf, decision included."""
    cfg, _ = lightning
    sla = _sla(cfg)
    q0, k0 = _qk(cfg, 10, b=3)
    q1, k1 = _qk(cfg, 11, b=3)
    plan = plan_lib.plan_attention(q0, k0, sla)
    thr = np.array([0.0, 0.05, 1.0], np.float32)  # force / measure / pin
    new, ret, rep = plan_lib.refresh_plan_per_sample(
        plan, q1, k1, sla, thr)
    for r in range(3):
        row = lambda a: jax.tree_util.tree_map(
            lambda leaf: leaf[r:r + 1], a)
        ref_plan, ref_ret, ref_rep = plan_lib.refresh_plan(
            row(plan), q1[r:r + 1], k1[r:r + 1], sla, float(thr[r]))
        assert bool(rep[r]) == bool(ref_rep)
        assert np.float32(ret[r]) == np.float32(ref_ret)
        for a, b in zip(jax.tree_util.tree_leaves(row(new)),
                        jax.tree_util.tree_leaves(ref_plan)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert bool(rep[0]) and not bool(rep[2])  # 0.0 forces, 1.0 pins


def test_forward_scalar_vs_vector_t_bitwise(lightning):
    """Scalar t == uniform (B,) t, bitwise (the ISSUE contract)."""
    cfg, params = lightning
    lat = jnp.asarray(np.stack([_latent(cfg, i) for i in range(2)]))
    a = dit.forward(params, cfg, lat, 0.625, None, jnp.float32, "gather")
    b = dit.forward(params, cfg, lat, jnp.full((2,), 0.625, jnp.float32),
                    None, jnp.float32, "gather")
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the acceptance claim: batched-vs-sequential bitwise parity
# ---------------------------------------------------------------------------
MIXED_TRACE = ((4, 1.0), (3, 1.0), (5, 0.75), (2, 0.5))


def _parity_run(cfg, params, backend, mode, **kw):
    sched = DiffusionScheduler(
        cfg, params, num_slots=2, seq_len=SEQ, backend=backend,
        compute_dtype=jnp.float32, refresh_mode=mode,
        drift_threshold=0.2, **kw)
    for i, (steps, t0) in enumerate(MIXED_TRACE):
        sched.submit(_latent(cfg, i),
                     DenoiseParams(num_steps=steps, t_start=t0))
    mixed_ticks = 0
    while sched.has_work:
        sched.step()
        live = [t for t in sched.active_timesteps() if t is not None]
        if len(set(live)) >= 2:
            mixed_ticks += 1
    # the trace genuinely exercised mixed timesteps inside one batch
    assert mixed_ticks >= 1
    for i, (steps, t0) in enumerate(MIXED_TRACE):
        ref = dit.sample(params, cfg, jnp.asarray(_latent(cfg, i)[None]),
                         num_steps=steps, compute_dtype=jnp.float32,
                         backend=backend, refresh_mode=mode,
                         refresh_interval=2, drift_threshold=0.2,
                         t_start=t0)
        r = sched._requests[i]
        assert r.state == RequestState.FINISHED
        assert np.array_equal(np.asarray(ref[0]), r.result), \
            f"rid {i}: batched != sequential ({backend}, {mode})"
    return sched


def test_batched_vs_sequential_bitwise_gather_adaptive(dit_model):
    cfg, params = dit_model
    sched = _parity_run(cfg, params, "gather", "adaptive")
    assert sched.stats.admissions == len(MIXED_TRACE)
    assert sched.stats.denoise_steps == sum(s for s, _ in MIXED_TRACE)


@pytest.mark.slow
def test_batched_vs_sequential_bitwise_gather_fixed(dit_model):
    cfg, params = dit_model
    _parity_run(cfg, params, "gather", "fixed", refresh_interval=2)


@pytest.mark.slow
def test_batched_vs_sequential_bitwise_reference_adaptive(dit_model):
    cfg, params = dit_model
    _parity_run(cfg, params, "reference", "adaptive")


def test_parity_run_uses_fixed_interval(lightning):
    """fixed-mode scheduler forwards refresh_interval into the per-slot
    0/1 threshold schedule (replans exactly on multiples)."""
    cfg, params = lightning
    sched = DiffusionScheduler(
        cfg, params, num_slots=1, seq_len=SEQ, backend="gather",
        compute_dtype=jnp.float32, refresh_mode="fixed",
        refresh_interval=2)
    sched.submit(_latent(cfg, 0), DenoiseParams(num_steps=5))
    sched.drain()
    # steps 1..4; replans at steps 2 and 4 -> 2 * num_layers
    assert sched.stats.plan_replans == 2 * cfg.num_layers


# ---------------------------------------------------------------------------
# plan-cache drift parity (cached vs fresh within conformance tol)
# ---------------------------------------------------------------------------
def test_plan_cache_drift_parity_and_counters(lightning):
    cfg, params = lightning

    def run(cache):
        sched = DiffusionScheduler(
            cfg, params, num_slots=2, seq_len=SEQ, backend="gather",
            compute_dtype=jnp.float32, refresh_mode="adaptive",
            drift_threshold=0.3, plan_cache=cache)
        for i in range(5):
            sched.submit(_latent(cfg, i), DenoiseParams(num_steps=3))
        sched.drain()
        return sched

    off, on = run(False), run(True)
    # cached-plan outputs equal fresh-plan outputs within the
    # conformance-matrix f32 tolerances (drift below threshold means
    # the cached classification still captures the critical mass)
    for a, b in zip(off._requests, on._requests):
        np.testing.assert_allclose(a.result, b.result, **TOL_F32)
    st = on.stats
    # request 0 misses; the shared-config admissions behind it hit
    assert st.plan_cache_misses >= 1
    assert st.plan_cache_hits >= 1
    assert st.plan_cache_hits + st.plan_cache_misses == 5
    # reuse cut planning: only the miss paid full per-request builds
    assert st.plan_builds < off.stats.plan_builds
    assert off.stats.plan_cache_hits == 0  # cache-off runs no cache


# ---------------------------------------------------------------------------
# registry smoke: both paper DiT configs through sample + scheduler
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["wan2_1_1_3b", "lightningdit_1b",
                                  "hunyuanvideo-t2"])
def test_registry_dit_smoke_roundtrip(arch):
    cfg = get_arch(arch).smoke()
    assert cfg.is_dit
    params = registry.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    lat = _latent(cfg, 0)
    cond = (np.asarray(jax.random.normal(
        jax.random.PRNGKey(9), (cfg.cond_len, cfg.d_model), jnp.float32))
        if cfg.text_conditioned else None)
    # one dit.sample step under SLA
    one = dit.sample(params, cfg, jnp.asarray(lat[None]), num_steps=1,
                     cond=(jnp.asarray(cond[None])
                           if cond is not None else None),
                     compute_dtype=jnp.float32, backend="gather")
    assert one.shape == (1, SEQ, cfg.patch_dim)
    assert bool(jnp.isfinite(one).all())
    # round-trip through the scheduler: same single step, same result
    sched = DiffusionScheduler(cfg, params, num_slots=1, seq_len=SEQ,
                               backend="gather",
                               compute_dtype=jnp.float32)
    sched.submit(lat, DenoiseParams(num_steps=1), cond=cond)
    done = sched.drain()
    assert len(done) == 1 and done[0].state == RequestState.FINISHED
    assert np.array_equal(np.asarray(one[0]), done[0].result)


# ---------------------------------------------------------------------------
# request surface: metrics, events, validation, stats json
# ---------------------------------------------------------------------------
def test_metrics_none_safe_and_event_order(lightning):
    cfg, params = lightning
    sched = DiffusionScheduler(cfg, params, num_slots=1, seq_len=SEQ,
                               backend="gather",
                               compute_dtype=jnp.float32)
    sched.submit(_latent(cfg, 0), DenoiseParams(num_steps=3))
    sched.submit(_latent(cfg, 1), DenoiseParams(num_steps=2))
    r0, r1 = sched._requests
    # queued: every derived metric is None, never 0.0
    assert r1.metrics.queue_s is None
    assert r1.metrics.ttft_s is None
    assert r1.metrics.latency_s is None
    events = []
    while sched.has_work:
        events.extend(sched.step())
        if r0.state == RequestState.FINISHED and r1.slot is not None:
            # r1 admitted after r0 retired: in-flight metrics None-safe
            assert r1.metrics.latency_s is None
            assert r1.metrics.ttft_s is not None
    for r in (r0, r1):
        m = r.metrics
        assert m.queue_s is not None and m.queue_s >= 0
        assert m.ttft_s is not None and m.latency_s is not None
        assert m.decode_tokens == r.params.num_steps
        kinds = [e.kind for e in events if e.rid == r.rid]
        assert kinds[0] == "start" and kinds[-1] == "finish"
        assert kinds[1:-1] == ["step"] * r.params.num_steps
    # single slot: the second request queued behind the first
    assert r1.metrics.queue_s > 0


def test_submit_validation(lightning):
    cfg, params = lightning
    sched = DiffusionScheduler(cfg, params, num_slots=1, seq_len=SEQ,
                               backend="gather")
    with pytest.raises(ValueError, match="latent shape"):
        sched.submit(np.zeros((SEQ + 1, cfg.patch_dim), np.float32))
    with pytest.raises(ValueError, match="num_steps"):
        DenoiseParams(num_steps=0).validate()
    with pytest.raises(ValueError, match="t_start"):
        DenoiseParams(t_start=1.5).validate()
    with pytest.raises(ValueError, match="cross-attention"):
        sched.submit(np.zeros((SEQ, cfg.patch_dim), np.float32),
                     cond=np.zeros((4, cfg.d_model), np.float32))
    with pytest.raises(ValueError, match="dit family"):
        DiffusionScheduler(get_arch("qwen3-1.7b").smoke(), params)
    with pytest.raises(ValueError, match="multiple"):
        DiffusionScheduler(cfg, params, seq_len=SEQ + 1,
                           backend="gather")


def test_stats_json_payload_none_safe(lightning):
    """The --stats-json schema: in-flight requests dump null derived
    metrics (PR 7 convention), finished ones real numbers."""
    cfg, params = lightning
    sched = DiffusionScheduler(cfg, params, num_slots=1, seq_len=SEQ,
                               backend="gather",
                               compute_dtype=jnp.float32)
    sched.submit(_latent(cfg, 0), DenoiseParams(num_steps=2))
    sched.submit(_latent(cfg, 1), DenoiseParams(num_steps=2))
    import json
    payload = stats_json_payload("dit", sched.stats, sched._requests)
    json.dumps(payload)  # JSON-serializable as-is
    assert payload["mode"] == "dit"
    assert payload["requests"][1]["latency_s"] is None
    assert payload["requests"][1]["state"] == "queued"
    sched.drain()
    payload = stats_json_payload("dit", sched.stats, sched._requests)
    assert payload["stats"]["denoise_steps"] == 4
    for row in payload["requests"]:
        assert row["state"] == "finished"
        assert row["latency_s"] > 0
