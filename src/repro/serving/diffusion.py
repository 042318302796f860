"""Streaming DiT denoise service (DESIGN.md "Streaming DiT service").

The second served workload: many users submit latents to denoise, the
`DiffusionScheduler` continuously batches them into ONE forward launch
per tick, of the DiT model the configuration names (`dit` or `mmdit`,
through `models/registry.py`). Requests at *different* timesteps share the batch —
the timestep embedding, AdaLN modulation, and attention are all
row-independent, so a mixed-timestep batch computes each row exactly
what a batch-1 run at that row's t would (the bitwise
batched-vs-sequential parity pinned by tests/test_dit_serving.py).

Shape of the loop (mirrors the LM Scheduler's fixed-pool design):

  submit -> queue -> [admission: batch-1 step-0 forward plans the
  request's per-layer SLAPlans (or validates cached ones) and scatters
  (latent, plans) into a free slot] -> per tick, ONE batched forward +
  Euler update advances every active slot one denoising step at its own
  (t, dt) -> a slot that reaches its request's num_steps retires: the
  final latent is read out, the slot frees for the next admission.

Plan refresh inside the batched tick uses the per-sample drift path
(`plan_lib.refresh_plan_per_sample` via the model's `forward(...,
per_sample_refresh=True)`): each slot keeps/rebuilds its own plans on
its own schedule — "fixed" intervals become a per-slot 0/1 threshold
vector, "adaptive" measures real drift — so one slot's refresh never
couples to its neighbours', which is what makes the batched trajectory
bitwise-equal to `dit.sample` per request.

Cross-request plan cache (`serving/plan_cache.py`): admission looks up
the request's timestep bucket; on a hit the first forward *validates*
the cached per-layer stack through the drift machinery instead of
planning from scratch — layers whose structure still fits are planning
work saved fleet-wide (Sparse-vDiT: patterns repeat across requests),
layers that drifted re-plan and write back. Mid-flight, a slot crossing
into an unpopulated bucket donates its current plans, so the first few
requests populate the whole timestep axis for everyone behind them.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Deque, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import plan as plan_lib
from repro.models import dit, registry
from repro.serving.api import (RequestMetrics, RequestState, ServeStats,
                               StreamEvent, normalize_drift_threshold)
from repro.serving.plan_cache import PlanCache

__all__ = ["DenoiseParams", "DenoiseRequest", "DiffusionScheduler"]


@dataclasses.dataclass
class DenoiseParams:
    """Per-request denoise policy (the DiT analogue of SamplingParams).

    num_steps Euler steps from t_start down to 0 (dt = t_start /
    num_steps). t_start < 1.0 is SDEdit-style partial denoise — and the
    reason admissions land in different plan-cache buckets."""

    num_steps: int = 8
    t_start: float = 1.0

    def validate(self) -> "DenoiseParams":
        if self.num_steps < 1:
            raise ValueError(
                f"num_steps must be >= 1 (got {self.num_steps})")
        if not 0.0 < self.t_start <= 1.0:
            raise ValueError(
                f"t_start must be in (0, 1] (got {self.t_start})")
        return self


@dataclasses.dataclass
class DenoiseRequest:
    """A denoise request inside the scheduler (cf. api.ServedRequest)."""

    rid: int
    latent: np.ndarray  # (N, patch_dim) noise / partially-denoised input
    params: DenoiseParams
    cond: Optional[np.ndarray] = None  # (Lc, d_model) text embeddings
    state: RequestState = RequestState.QUEUED
    steps_done: int = 0
    metrics: RequestMetrics = dataclasses.field(
        default_factory=RequestMetrics)
    slot: Optional[int] = None
    result: Optional[np.ndarray] = None  # (N, patch_dim) final latent


class DiffusionScheduler:
    """Continuous batching for DiT denoising over a fixed slot pool.

    One jitted batched (forward + Euler) trace serves every tick; one
    jitted batch-1 admission trace plans (or validates) each incoming
    request's SLAPlans. Per-request trajectories are bitwise-equal to
    sequential `dit.sample(..., t_start=...)` runs when the plan cache
    is off; with the cache on, admissions reuse validated cross-request
    structure and outputs stay within the conformance-matrix tolerances
    (drift below threshold means the cached classification still
    captures the sample's critical mass).
    """

    def __init__(self, cfg: ArchConfig, params, *, num_slots: int = 4,
                 seq_len: int = 64, backend: str = "gather",
                 compute_dtype=jnp.float32,
                 refresh_mode: Optional[str] = None,
                 refresh_interval: Optional[int] = None,
                 drift_threshold=None,
                 plan_cache=None, t_buckets: int = 8,
                 cache_entries: int = 256):
        from repro.core import backends as backend_registry
        backend = backend_registry.resolve(backend)
        if not cfg.is_dit:
            raise ValueError(
                f"DiffusionScheduler serves the dit family only "
                f"(dit, mmdit; got family={cfg.family!r}; the LM families "
                f"go behind serving.Scheduler)")
        cfg.sla.validate()
        self.cfg = cfg
        self.params = params
        self.num_slots = int(num_slots)
        self.seq_len = int(seq_len)
        self.backend = backend
        self.compute_dtype = compute_dtype
        self.sla_cfg = dataclasses.replace(cfg.sla, causal=False)
        model = registry.get_model(cfg)
        self._forward = model.forward
        attn_len = model.attention_len(cfg, self.seq_len)
        if attn_len % self.sla_cfg.block_q or attn_len % self.sla_cfg.block_kv:
            raise ValueError(
                f"seq_len={seq_len} attends over {attn_len} tokens, which "
                f"must be a multiple of the SLA block sizes "
                f"({self.sla_cfg.block_q}, {self.sla_cfg.block_kv}) "
                "— the plan grid is block-aligned")
        mode = (cfg.sla.plan_refresh_mode if refresh_mode is None
                else refresh_mode)
        if mode not in ("fixed", "adaptive"):
            raise ValueError(f"unknown refresh_mode {mode!r}; "
                             "expected 'fixed' or 'adaptive'")
        self.refresh_mode = mode
        self.refresh_interval = max(1, int(
            cfg.sla.plan_refresh_interval if refresh_interval is None
            else refresh_interval))
        nl = cfg.num_layers
        thr = normalize_drift_threshold(cfg, drift_threshold)
        self._thr_layers = np.broadcast_to(
            np.asarray(thr, np.float32), (nl,)).copy()
        self.plan_needed = (cfg.attention_kind == "sla"
                            and self.sla_cfg.mode
                            not in ("full", "linear_only"))
        # cross-request plan cache: False/None = off, True = build one,
        # or pass a shared PlanCache instance (fleet-wide amortization)
        if plan_cache is True:
            plan_cache = PlanCache(self.sla_cfg, nl, t_buckets=t_buckets,
                                   max_entries=cache_entries)
        # identity checks, not truthiness: an empty PlanCache has
        # len() == 0 and must still count as "cache on"
        self.cache: Optional[PlanCache] = (
            plan_cache if (isinstance(plan_cache, PlanCache)
                           and self.plan_needed) else None)

        # live batched state: one latent row + one per-layer plan row
        # per slot; host-side f32 (t0, dt) bookkeeping per slot
        self._lat = jnp.zeros((num_slots, seq_len, cfg.patch_dim),
                              jnp.float32)
        self._cond = (jnp.zeros((num_slots, cfg.cond_len, cfg.d_model),
                                jnp.float32)
                      if cfg.text_conditioned else None)
        if self.plan_needed:
            tm = attn_len // self.sla_cfg.block_q
            tn = attn_len // self.sla_cfg.block_kv
            proto = plan_lib.empty_plan(self.sla_cfg, num_slots,
                                        cfg.num_heads, tm, tn)
            self._plans = jax.tree_util.tree_map(
                lambda leaf: jnp.stack([leaf] * nl), proto)
        else:
            self._plans = None
        self._t0 = np.zeros((num_slots,), np.float32)
        self._dt = np.zeros((num_slots,), np.float32)
        self._bucket = [None] * num_slots  # last plan-cache bucket seen

        self._queue: Deque[DenoiseRequest] = deque()
        self._requests: List[DenoiseRequest] = []
        self._slots: List[Optional[DenoiseRequest]] = [None] * num_slots
        self._next_rid = 0
        self.stats = ServeStats()
        self._build_jits()

    # -- jitted kernels --------------------------------------------------
    def _build_jits(self):
        cfg, dtype, backend = self.cfg, self.compute_dtype, self.backend
        plan_needed, cross = self.plan_needed, self.cfg.text_conditioned
        forward = self._forward

        def admit_fresh(params, lat1, t1, dt1, cond1):
            """Step 0 of the request's trajectory: plan + first Euler
            step, exactly `dit.sample`'s pre-loop head at batch 1."""
            out = forward(params, cfg, lat1, t1,
                              cond1 if cross else None, dtype, backend,
                              return_plans=plan_needed)
            vel, plans = out if plan_needed else (out, None)
            with jax.named_scope("dit.commit"):
                new = lat1 - dt1[:, None, None] * vel.astype(lat1.dtype)
            return new, plans

        def admit_cached(params, lat1, t1, dt1, cond1, plans, thr):
            """Step 0 against a cached plan stack: the drift machinery
            validates each layer's cached structure; `replanned` flags
            the invalidated layers (written back to the cache)."""
            vel, plans, info = forward(
                params, cfg, lat1, t1, cond1 if cross else None, dtype,
                backend, plans=plans, return_plans=True,
                drift_threshold=thr)
            with jax.named_scope("dit.commit"):
                new = lat1 - dt1[:, None, None] * vel.astype(lat1.dtype)
            return new, plans, info

        def tick(params, latents, tv, dtv, cond, plans, thr, mask):
            """ONE batched denoise step for every active slot: mixed
            per-slot (t, dt), per-sample plan refresh, masked commit so
            retired/free rows keep their state bitwise-untouched."""
            if plan_needed:
                vel, new_plans, info = forward(
                    params, cfg, latents, tv, cond if cross else None,
                    dtype, backend, plans=plans, return_plans=True,
                    drift_threshold=thr, per_sample_refresh=True)
            else:
                vel = forward(params, cfg, latents, tv,
                                  cond if cross else None, dtype, backend)
                new_plans, info = None, None
            with jax.named_scope("dit.commit"):
                new_lat = latents - dtv[:, None, None] * vel.astype(
                    latents.dtype)
                latents = jnp.where(mask[:, None, None], new_lat, latents)
                if plan_needed:
                    def sel(n, o):
                        m = mask.reshape((1, -1) + (1,) * (n.ndim - 2))
                        return jnp.where(m, n, o)
                    plans = jax.tree_util.tree_map(sel, new_plans, plans)
            return latents, plans, info

        self._admit_fresh_jit = jax.jit(admit_fresh)
        self._admit_cached_jit = jax.jit(admit_cached)
        self._tick_jit = jax.jit(tick)

    # -- request surface -------------------------------------------------
    def submit(self, latent, params: Optional[DenoiseParams] = None,
               cond=None) -> int:
        """Enqueue one denoise request; returns its rid. Never blocks."""
        params = (params or DenoiseParams()).validate()
        latent = np.asarray(latent, np.float32)
        if latent.shape != (self.seq_len, self.cfg.patch_dim):
            raise ValueError(
                f"latent shape {latent.shape} != scheduler's "
                f"({self.seq_len}, {self.cfg.patch_dim})")
        if cond is not None:
            if not self.cfg.text_conditioned:
                raise ValueError(
                    f"{self.cfg.name} has no cross-attention; cond must "
                    "be None")
            cond = np.asarray(cond, np.float32)
            want = (self.cfg.cond_len, self.cfg.d_model)
            if cond.shape != want:
                raise ValueError(f"cond shape {cond.shape} != {want}")
        r = DenoiseRequest(rid=self._next_rid, latent=latent,
                           params=params, cond=cond)
        r.metrics.submit_t = time.time()
        self._next_rid += 1
        self._queue.append(r)
        self._requests.append(r)
        return r.rid

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or any(r is not None for r in self._slots)

    def active_timesteps(self) -> List[Optional[float]]:
        """Current diffusion time per slot (None = free) — observability
        for the mixed-timestep claim; tests assert heterogeneity."""
        out: List[Optional[float]] = []
        for j, r in enumerate(self._slots):
            out.append(float(self._slot_t(j)) if r is not None else None)
        return out

    # -- host-side time bookkeeping ---------------------------------------
    def _slot_t(self, j: int) -> np.float32:
        """t for slot j's NEXT step, positionally (t0 - steps*dt in f32)
        — the same rounded value `dit.sample`'s tvec(step) computes, so
        host bookkeeping never drifts from the device trajectory."""
        r = self._slots[j]
        return np.float32(self._t0[j]
                          - np.float32(r.steps_done) * self._dt[j])

    # -- admission ---------------------------------------------------------
    @functools.partial(jax.profiler.annotate_function,
                       name="dit.sched.admit")
    def _admit_next(self, slot: int, events: List[StreamEvent]):
        r = self._queue.popleft()
        r.state = RequestState.PREFILLING
        r.slot = slot
        t0 = time.time()
        r.metrics.admit_t = t0
        t_start = np.float32(r.params.t_start)
        dt = np.float32(t_start / np.float32(r.params.num_steps))
        lat1 = jnp.asarray(r.latent[None])
        t1 = jnp.full((1,), t_start, jnp.float32)
        dt1 = jnp.full((1,), dt, jnp.float32)
        cond1 = (jnp.asarray(
            (r.cond if r.cond is not None
             else np.zeros((self.cfg.cond_len, self.cfg.d_model),
                           np.float32))[None])
            if self.cfg.text_conditioned else None)
        nl = self.cfg.num_layers
        cached = bucket = None
        if self.cache is not None:
            bucket = self.cache.bucket(float(t_start))
            cached = self.cache.get(bucket)
        if not self.plan_needed:
            new_lat, plan_row = self._admit_fresh_jit(
                self.params, lat1, t1, dt1, cond1)
        elif cached is None:
            new_lat, plan_row = self._admit_fresh_jit(
                self.params, lat1, t1, dt1, cond1)
            self.stats.plan_builds += nl
            if self.cache is not None:
                self.cache.put(bucket, plan_row)
        else:
            new_lat, plan_row, info = self._admit_cached_jit(
                self.params, lat1, t1, dt1, cond1, cached,
                jnp.asarray(self._thr_layers))
            replanned = np.asarray(info["replanned"]).reshape(nl)
            n_replan = int(replanned.sum())
            self.stats.plan_replans += n_replan
            self.stats.plan_reuses += nl - n_replan
            self.stats.last_retention = float(
                np.min(np.asarray(info["retention"])))
            if n_replan:
                self.cache.update(bucket, plan_row, replanned)
        self._lat, self._plans = dit.insert_denoise_slot(
            self._lat, self._plans, slot, new_lat, plan_row)
        if self._cond is not None:
            self._cond = self._cond.at[slot].set(
                cond1[0] if cond1 is not None else 0.0)
        self._t0[slot] = t_start
        self._dt[slot] = dt
        self._bucket[slot] = bucket
        self._slots[slot] = r
        r.steps_done = 1
        r.metrics.decode_tokens = 1
        r.state = RequestState.DECODING
        now = time.time()
        r.metrics.first_token_t = now
        self.stats.admissions += 1
        self.stats.denoise_steps += 1
        events.append(StreamEvent(rid=r.rid, kind="start", t=t0))
        events.append(StreamEvent(rid=r.rid, kind="step", t=now, index=0))
        if r.steps_done >= r.params.num_steps:
            self._finish(slot, events)
        self._sync_cache_stats()

    def _finish(self, slot: int, events: List[StreamEvent]):
        r = self._slots[slot]
        with jax.profiler.TraceAnnotation("dit.sched.retire"):
            r.result = np.asarray(dit.retire_denoise_slot(self._lat, slot))
        r.state = RequestState.FINISHED
        r.metrics.finish_t = time.time()
        r.slot = None
        self._slots[slot] = None
        self._bucket[slot] = None
        events.append(StreamEvent(rid=r.rid, kind="finish",
                                  t=r.metrics.finish_t))

    def _sync_cache_stats(self):
        if self.cache is None:
            return
        self.stats.plan_cache_hits = self.cache.hits
        self.stats.plan_cache_misses = self.cache.misses
        self.stats.plan_cache_invalidations = self.cache.invalidations
        self.stats.plan_cache_evictions = self.cache.evictions

    # -- the tick ----------------------------------------------------------
    @functools.partial(jax.profiler.annotate_function,
                       name="dit.sched.step")
    def step(self) -> List[StreamEvent]:
        """Admit queued requests into free slots, then run ONE batched
        denoise step over every active slot. Returns the events.

        Host spans (`jax.profiler.TraceAnnotation`, seen by any profiler
        trace around the call): `dit.sched.step` the whole call,
        `dit.sched.admit` each admission, `dit.sched.tick` the tick's
        inputs and dispatch, `dit.sched.readback` the reads of the
        tick's drift info (where the host waits for the tick),
        `dit.sched.retire` each finished slot's read-out."""
        events: List[StreamEvent] = []
        for slot in range(self.num_slots):
            if self._slots[slot] is None and self._queue:
                self._admit_next(slot, events)
        active = [j for j in range(self.num_slots)
                  if self._slots[j] is not None]
        if not active:
            return events
        info = self._dispatch_tick(active)
        if info is not None:
            nl = self.cfg.num_layers
            with jax.profiler.TraceAnnotation("dit.sched.readback"):
                rep = np.asarray(info["replanned"])[:, active]
                retention = np.asarray(info["retention"])[:, active]
            n_replan = int(rep.sum())
            self.stats.plan_replans += n_replan
            self.stats.plan_reuses += nl * len(active) - n_replan
            self.stats.last_retention = float(np.min(retention))
        self.stats.slot_steps_active += len(active)
        self.stats.slot_steps_total += self.num_slots
        self.stats.denoise_steps += len(active)
        now = time.time()
        for j in active:
            r = self._slots[j]
            r.steps_done += 1
            r.metrics.decode_tokens += 1
            events.append(StreamEvent(rid=r.rid, kind="step", t=now,
                                      index=r.steps_done - 1))
            if self.cache is not None and r.steps_done < r.params.num_steps:
                nb = self.cache.bucket(float(self._slot_t(j)))
                if nb != self._bucket[j]:
                    # crossing into a new timestep bucket: donate this
                    # slot's current plans if the bucket is unpopulated
                    self._bucket[j] = nb
                    self.cache.put_if_absent(
                        nb, dit.take_slot_plans(self._plans, j))
            if r.steps_done >= r.params.num_steps:
                self._finish(j, events)
        self._sync_cache_stats()
        return events

    @functools.partial(jax.profiler.annotate_function,
                       name="dit.sched.tick")
    def _dispatch_tick(self, active: List[int]):
        """Build the tick's per-slot inputs and dispatch it; returns the
        drift info (None without a plan) without waiting for it."""
        nl, ns = self.cfg.num_layers, self.num_slots
        tv = np.zeros((ns,), np.float32)
        mask = np.zeros((ns,), bool)
        thr = np.ones((nl, ns), np.float32)  # >= 1.0: inert rows
        for j in active:
            r = self._slots[j]
            tv[j] = self._slot_t(j)
            mask[j] = True
            if self.refresh_mode == "fixed":
                # the upcoming step index is steps_done; 0.0 forces the
                # row's re-plan, 1.0 pins reuse — dit.sample's static
                # schedule expressed per slot
                thr[:, j] = (0.0 if r.steps_done % self.refresh_interval
                             == 0 else 1.0)
            else:
                thr[:, j] = self._thr_layers
        self._lat, self._plans, info = self._tick_jit(
            self.params, self._lat, jnp.asarray(tv),
            jnp.asarray(self._dt), self._cond, self._plans,
            jnp.asarray(thr), jnp.asarray(mask))
        return info

    def drain(self) -> List[DenoiseRequest]:
        """Run until every submitted request has finished; returns all
        requests in submission order."""
        while self.has_work:
            self.step()
        return list(self._requests)

    def stream(self) -> Iterator[StreamEvent]:
        """Generator draining the scheduler one tick at a time, yielding
        events as they happen (cf. api.Scheduler.stream)."""
        while self.has_work:
            for ev in self.step():
                yield ev
