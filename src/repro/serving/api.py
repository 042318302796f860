"""Serving API v2: continuous-batching scheduler with streaming requests.

The v1 surface (`ServingEngine.run(List[Request])`) decodes fixed groups
in lockstep: finished slots keep computing frozen logits, queued
requests wait for the whole group, and per-request latency collapses to
cumulative engine time. This module is the request-level redesign
(DESIGN.md "Serving API v2"):

  * `SamplingParams` / `RequestState` / `StreamEvent` / `RequestMetrics`
    — the typed request surface (greedy or temperature sampling, stop
    tokens, QUEUED -> PREFILLING -> DECODING -> FINISHED lifecycle, and
    real per-request TTFT / queue-time / latency).
  * `Scheduler` — a fixed pool of decode slots over ONE live per-slot
    cache (`make_cache(per_slot=True)`). `submit()` enqueues;  `step()`
    admits queued requests into free slots (each prefilled in its own
    block-aligned `(1, bucket)` call, then scattered into the slot via
    `insert_slot`: KV rows, decode-SLA incremental plan rows, H/Z
    linear state, pooled q/k features) and runs one batched decode step
    with per-slot positions; `drain()` runs to completion; `stream()`
    yields `StreamEvent`s as they happen.

Admission happens at SLA block boundaries by construction: the prefill
bucket is a whole number of `block_q` blocks, so an admitted slot's
position starts block-aligned and the static-grid invariants of
`plan_extend` (rows appended monotonically, each exactly once) hold per
slot. Cross-request plan reuse (`plan_reuse="adaptive"`) and decode-time
SLA (`decode_sla=True`) both ride along — this is where they pay off
hardest, because slots turn over continuously instead of waiting for
the slowest group member.

Chunked admission prefill (DESIGN.md "Chunked admission prefill"): with
`prefill_chunk_blocks` set, a paged admission that misses the
full-prompt snapshot becomes a multi-tick `_PrefillJob` — the request
owns its slot in PREFILLING state (masked out of decode dispatch like a
finished-budget slot) and advances one block-aligned chunk per tick
through `transformer.prefill_chunk`, so other slots keep emitting
tokens while a long prompt prefills. Completion runs blocking
admission's tail verbatim (finalize -> page-table scatter -> snapshot),
which keeps chunked tokens and cache leaves bitwise equal to blocking's.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import math
import time
from typing import Deque, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import registry
from repro.models.common import logits_from_hidden


# ---------------------------------------------------------------------------
# typed request surface
# ---------------------------------------------------------------------------
class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILLING = "prefilling"
    DECODING = "decoding"
    FINISHED = "finished"


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling policy.

    temperature == 0.0 is greedy argmax (bit-reproducible against the
    static-batch engine); > 0 samples from softmax(logits / T) with a
    per-request deterministic host RNG (`seed`). Generation stops at
    `max_new_tokens` or on the first token in `stop_tokens` (the stop
    token itself is kept, matching the budget-truncation semantics of
    the v1 engine)."""

    max_new_tokens: int = 16
    temperature: float = 0.0
    stop_tokens: Tuple[int, ...] = ()
    seed: int = 0

    def validate(self) -> "SamplingParams":
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1 (got {self.max_new_tokens})")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0 (got {self.temperature})")
        return self


@dataclasses.dataclass
class RequestMetrics:
    """Wall-clock request accounting (absolute times from time.time()).

    queue_s / ttft_s / latency_s are derived and measured per request —
    the v1 engine assigned every request the engine's cumulative
    prefill+decode seconds instead. Each derived metric is None until
    the event it measures has actually happened (an unfinished request
    has no latency, a never-prefilled one no TTFT); clamping them to
    0.0 silently reported in-flight requests as instantaneous."""

    submit_t: float = 0.0
    admit_t: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0
    decode_tokens: int = 0  # total generated tokens (incl. the prefill one)

    @property
    def queue_s(self) -> Optional[float]:
        if self.admit_t == 0.0:
            return None
        return self.admit_t - self.submit_t

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t == 0.0:
            return None
        return self.first_token_t - self.submit_t

    @property
    def latency_s(self) -> Optional[float]:
        if self.finish_t == 0.0:
            return None
        return self.finish_t - self.submit_t


@dataclasses.dataclass
class StreamEvent:
    """One streaming output event.

    kind: "start" (request admitted to a slot), "token" (one generated
    token; `token`/`index` set), "finish" (request complete)."""

    rid: int
    kind: str
    t: float
    token: Optional[int] = None
    index: Optional[int] = None


@dataclasses.dataclass
class ServedRequest:
    """A request inside the scheduler (the v2 analogue of engine.Request)."""

    rid: int
    prompt: np.ndarray
    sampling: SamplingParams
    state: RequestState = RequestState.QUEUED
    tokens_out: List[int] = dataclasses.field(default_factory=list)
    metrics: RequestMetrics = dataclasses.field(
        default_factory=RequestMetrics)
    slot: Optional[int] = None


@dataclasses.dataclass
class ServeStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefill_s: float = 0.0
    # the LM engine's and scheduler's decode wall time; the DiT
    # scheduler leaves it 0 (its tick is timed by the profiler spans
    # `dit.sched.tick` and `dit.sched.readback`, serving/diffusion.py)
    decode_s: float = 0.0
    # plan-reuse accounting (layer granularity; DESIGN.md "Plan
    # lifetime & drift"): builds = first-chunk plans, replans =
    # drift-triggered rebuilds, reuses = layers served by a stale plan.
    plan_builds: int = 0
    plan_replans: int = 0
    plan_reuses: int = 0
    last_retention: float = 1.0
    # decode-plan accounting (layer granularity; DESIGN.md "Decode-time
    # SLA"): builds = decode plans seeded at prefill (one per layer per
    # chunk, covering all prompt rows), extends = completed rows
    # appended via plan_extend, replans = live rows re-classified at a
    # block boundary (drift over that layer's threshold), reuses = live
    # rows inheriting the previous row's structure.
    decode_plan_builds: int = 0
    decode_plan_extends: int = 0
    decode_plan_replans: int = 0
    decode_plan_reuses: int = 0
    decode_last_retention: float = 1.0
    # continuous-batching accounting (DESIGN.md "Serving API v2"):
    # admissions = requests scattered into a slot, slot_steps_active /
    # slot_steps_total = decode-slot occupancy (active slots vs pool
    # size, summed over decode steps; the static engine counts its
    # lockstep groups the same way, so the two paths are comparable).
    admissions: int = 0
    slot_steps_active: int = 0
    slot_steps_total: int = 0
    # paged-KV accounting (DESIGN.md "Paged KV & prefix caching"):
    # pages_in_use / pages_peak = referenced physical pages (current /
    # high-water), page_allocs = pool allocations, prefix_hits/misses =
    # per-page prefix-cache lookups at admission, prefix_full_hits =
    # whole-prompt snapshot hits (prefill compute skipped entirely),
    # cow_copies = copy-on-write duplications of a shared page.
    pages_in_use: int = 0
    pages_peak: int = 0
    page_allocs: int = 0
    prefix_hits: int = 0
    prefix_misses: int = 0
    prefix_full_hits: int = 0
    cow_copies: int = 0
    # chunked-admission accounting (DESIGN.md "Chunked admission
    # prefill"): chunked_admissions = requests admitted through the
    # multi-tick chunk machine, prefill_chunks = chunk dispatches that
    # actually ran (prefix-resumed chunks are skipped and never
    # counted), max_decode_gap_s = largest wall-clock gap between
    # consecutive token emissions — the decode-stall metric chunked
    # admission exists to shrink.
    chunked_admissions: int = 0
    prefill_chunks: int = 0
    max_decode_gap_s: float = 0.0
    # streaming-DiT accounting (DESIGN.md "Streaming DiT service"):
    # denoise_steps = per-request Euler steps executed (the DiT analogue
    # of decode_tokens); plan_cache_* mirror the cross-request
    # PlanCache's own counters at the scheduler level — hits/misses are
    # whole-bucket admission lookups, invalidations are cached layers
    # whose drift validation re-planned, evictions are LRU drops.
    denoise_steps: int = 0
    plan_cache_hits: int = 0
    plan_cache_misses: int = 0
    plan_cache_invalidations: int = 0
    plan_cache_evictions: int = 0

    def occupancy(self) -> float:
        """Decode-slot utilization in [0, 1]."""
        return self.slot_steps_active / max(1, self.slot_steps_total)


# ---------------------------------------------------------------------------
# shared serving helpers (engine + scheduler)
# ---------------------------------------------------------------------------
def block_bucket(length: int, block: int) -> int:
    """`length` rounded up to a whole number of SLA query blocks."""
    block = max(block, 1)
    return max(block, ((length + block - 1) // block) * block)


def normalize_drift_threshold(cfg: ArchConfig, drift_threshold):
    """CLI/user drift threshold -> scalar or per-layer tuple."""
    if drift_threshold is None:
        return cfg.sla.plan_drift_threshold
    if isinstance(drift_threshold, (tuple, list)):
        return tuple(float(t) for t in drift_threshold)
    return float(drift_threshold)


def percentile(xs, p: float) -> float:
    """Nearest-rank percentile (the serving-metrics convention used by
    both `launch/serve.py` and `benchmarks/fig_serving.py`): the
    smallest element with at least ceil(p * n) of the values at or
    below it, i.e. sorted(xs)[ceil(p * n) - 1]. The previous
    `int(p * n)` index sat one element HIGH of the nearest rank
    whenever p * n was not integral (p95 of 20 samples read xs[19],
    the max, instead of xs[18]) and only p == 1.0 was saved by the
    min clamp; tests/test_serving.py pins the exact ranks."""
    xs = sorted(xs)
    if not xs:
        raise ValueError("percentile() of an empty sequence")
    rank = min(len(xs), max(1, math.ceil(p * len(xs))))
    return xs[rank - 1]


def stats_json_payload(mode: str, stats, requests=()) -> dict:
    """JSON-ready dump of a stats dataclass + per-request metrics.

    Serves `launch/serve.py --stats-json` for every serving mode —
    `stats` is any stats dataclass (ServeStats, disagg.DisaggStats);
    `requests` any iterable of objects carrying `.rid`, `.state`, and
    `.metrics` (ServedRequest, engine.Request, DenoiseRequest). Derived
    metrics stay None (JSON null) for in-flight requests — the PR 7
    convention: an unfinished request has no latency, a never-admitted
    one no queue time; clamping to 0.0 would report them as
    instantaneous."""
    rows = []
    for r in requests:
        m = getattr(r, "metrics", None)
        state = getattr(r, "state", None)
        if state is None and m is not None:
            # v1 engine.Request carries no state enum; a finished
            # timestamp is the authoritative signal
            state = "finished" if m.finish_t else "in_flight"
        row = {"rid": getattr(r, "rid", None),
               "state": getattr(state, "value", state)}
        if m is not None:
            row.update(queue_s=m.queue_s, ttft_s=m.ttft_s,
                       latency_s=m.latency_s,
                       decode_tokens=m.decode_tokens)
        rows.append(row)
    return {"mode": mode, "stats": dataclasses.asdict(stats),
            "requests": rows}


def prefill_with_plan_reuse(prefill_plan, prefill_reuse, params, toks,
                            plans, stats: "ServeStats", num_layers: int):
    """Shared plan-reuse prefill step (DESIGN.md "Plan lifetime &
    drift"): build the per-layer plan stack on the first chunk, reuse
    it with drift-gated refresh afterwards, and account builds /
    replans / reuses / retention on `stats`. Returns
    (last_hidden, cache, plans)."""
    if plans is None:
        last_hidden, cache, plans = prefill_plan(params, toks)
        stats.plan_builds += num_layers
    else:
        last_hidden, cache, plans, info = prefill_reuse(params, toks,
                                                        plans)
        replans = int(np.sum(np.asarray(info["replanned"])))
        stats.plan_replans += replans
        stats.plan_reuses += num_layers - replans
        stats.last_retention = float(
            np.min(np.asarray(info["retention"])))
    return last_hidden, cache, plans


def check_serving_family(cfg: ArchConfig, mdl, plan_reuse: str,
                         decode_sla: bool, continuous: bool = False):
    """Loudly reject model families without the capabilities a serving
    mode needs (plan-aware prefill, decode-SLA prefill, slot caches)."""
    import inspect

    prefill_fn = getattr(mdl, "prefill", None)
    if plan_reuse != "off":
        if (prefill_fn is None
                or "plans" not in inspect.signature(prefill_fn).parameters):
            raise ValueError(
                f"plan_reuse={plan_reuse!r} requires a model family with "
                f"plan-aware prefill (got family {cfg.family!r})")
    if decode_sla:
        if (prefill_fn is None or "decode_max_len" not in
                inspect.signature(prefill_fn).parameters):
            raise ValueError(
                f"decode_sla requires a model family with decode-SLA "
                f"prefill (got family {cfg.family!r})")
    if continuous and getattr(mdl, "insert_slot", None) is None:
        raise ValueError(
            f"the continuous-batching scheduler requires a model family "
            f"with per-slot caches (make_cache(per_slot=True) + "
            f"insert_slot); family {cfg.family!r} has neither")


# ---------------------------------------------------------------------------
# the prefill engine (worker-reusable prefill compute)
# ---------------------------------------------------------------------------
class PrefillEngine:
    """The worker-reusable prefill half of the scheduler (DESIGN.md
    "Disaggregated serving"): the jitted (1, bucket) prefill closures
    (fresh / plan-build / drift-gated reuse), the chunked-prefill
    chunk / finalize dispatches, the zero-carry prototypes per bucket,
    and the LRU of chunk-boundary carry snapshots.

    The Scheduler owns one for in-process admissions; a disaggregated
    prefill worker pool (serving/disagg.py) shares ONE across workers,
    so jit caches and carry snapshots amortize across the pool and a
    requeued request re-prefills bitwise-identically on any worker —
    prefill here is a pure function of (padded prompt bytes, bucket)
    whenever plan_reuse is off."""

    def __init__(self, cfg: ArchConfig, params, mdl, *, backend: str,
                 compute_dtype, decode_sla: bool, max_len: int,
                 drift_threshold, plan_reuse: str = "off",
                 chunk_tokens: int = 0):
        self.cfg = cfg
        self.params = params
        self.mdl = mdl
        self.compute_dtype = compute_dtype
        self.decode_sla = decode_sla
        self.max_len = max_len
        self.plan_reuse = plan_reuse
        self.chunk_tokens = chunk_tokens
        dkw = {"decode_max_len": max_len} if decode_sla else {}
        thr = drift_threshold

        @jax.jit
        def _prefill(params, tokens):
            return mdl.prefill(params, cfg, tokens, backend=backend,
                               compute_dtype=compute_dtype, **dkw)

        @jax.jit
        def _prefill_plan(params, tokens):
            return mdl.prefill(params, cfg, tokens, backend=backend,
                               compute_dtype=compute_dtype,
                               return_plans=True, **dkw)

        @jax.jit
        def _prefill_reuse(params, tokens, plans):
            return mdl.prefill(params, cfg, tokens, backend=backend,
                               compute_dtype=compute_dtype, plans=plans,
                               drift_threshold=thr, return_plans=True,
                               **dkw)

        self._prefill = _prefill
        self._prefill_plan = _prefill_plan
        self._prefill_reuse = _prefill_reuse

        if chunk_tokens:
            dmx = max_len if decode_sla else None

            # `start` is a TRACED int32, so one compiled graph covers
            # every chunk index of a given (bucket, chunk) shape pair
            @jax.jit
            def _chunk(params, tokens, carry, start):
                return mdl.prefill_chunk(params, cfg, tokens, carry,
                                         start,
                                         compute_dtype=compute_dtype,
                                         backend=backend,
                                         decode_max_len=dmx)

            @jax.jit
            def _finalize(carry):
                return mdl.finalize_chunked_prefill(cfg, carry,
                                                    decode_max_len=dmx)

            self._chunk_jit = _chunk
            self._finalize_jit = _finalize

        self._carry_protos: dict = {}
        self._carry_snaps = collections.OrderedDict()
        self._carry_cap = 16

    def run(self, toks: jnp.ndarray, plans, stats: ServeStats,
            num_layers: int):
        """(1, bucket) prefill. With plan_reuse off, `plans` passes
        through untouched; otherwise the shared drift-gated reuse path
        runs and the updated plan stack comes back. Returns
        (last_hidden, cache, plans)."""
        if self.plan_reuse == "off":
            last_hidden, cache = self._prefill(self.params, toks)
            return last_hidden, cache, plans
        return prefill_with_plan_reuse(
            self._prefill_plan, self._prefill_reuse, self.params, toks,
            plans, stats, num_layers)

    def logits(self, last_hidden) -> np.ndarray:
        """(1, vocab) first-token logits row, on the host."""
        return np.asarray(logits_from_hidden(self.params, last_hidden))

    # -- chunked prefill (DESIGN.md "Chunked admission prefill") -----------
    def chunk(self, toks_span: jnp.ndarray, carry, start):
        """Run ONE prefill chunk; returns (carry, last_hidden)."""
        return self._chunk_jit(self.params, toks_span, carry, start)

    def finalize(self, carry):
        """Finalize a completed carry into blocking prefill's cache."""
        return self._finalize_jit(carry)

    def carry_proto(self, bucket: int):
        """Zero chunked-prefill carry for `bucket` (cached; the arrays
        are immutable, so every job can start from the same one)."""
        proto = self._carry_protos.get(bucket)
        if proto is None:
            proto = self.mdl.make_prefill_carry(
                self.cfg, bucket, compute_dtype=self.compute_dtype,
                decode_sla=self.decode_sla)
            self._carry_protos[bucket] = proto
        return proto

    def carry_get(self, key):
        """LRU lookup of a chunk-boundary carry snapshot (touches)."""
        snap = self._carry_snaps.get(key)
        if snap is not None:
            self._carry_snaps.move_to_end(key)
        return snap

    def carry_put(self, key, carry):
        self._carry_snaps[key] = carry
        self._carry_snaps.move_to_end(key)
        while len(self._carry_snaps) > self._carry_cap:
            self._carry_snaps.popitem(last=False)


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _PrefillJob:
    """One in-flight chunked admission (DESIGN.md "Chunked admission
    prefill"). The request owns `slot` in PREFILLING state while its
    prompt advances one chunk per tick; `carry` is the model-side
    chunked-prefill carry (KV written so far, pooled block features,
    decode-grid rows), `pids` the pool refs claimed page by page as
    chunks land (handed over to `_set_slot_pages` at completion), and
    `dispatched` the prompt tokens that actually ran (prefix-resumed
    chunks are skipped)."""

    r: ServedRequest
    slot: int
    toks: np.ndarray        # (1, bucket) left-padded prompt
    keys: List[bytes]       # page intern keys for every prompt page
    bucket: int             # admission-time bucket (survives later growth)
    carry: object
    num_chunks: int
    t0: float               # admission wall-clock (metrics.admit_t)
    next_chunk: int = 0
    dispatched: int = 0
    pids: List[int] = dataclasses.field(default_factory=list)
    last_hidden: object = None


class Scheduler:
    """Continuous-batching scheduler over a fixed pool of decode slots.

    One live per-slot cache holds `num_slots` independent sequences
    (per-slot positions, per-slot decode-SLA plan/state). Slots turn
    over continuously: the moment a request finishes, the next queued
    request is prefilled in its own `(1, bucket)` call and scattered
    into the freed slot — no request ever waits for a group.

    Greedy tokens are bit-identical to the static-batch engine's when
    the prefill bucket and slot count match (per-request numerics
    depend only on (prompt, bucket, batch width); verified by
    tests/test_serving.py).
    """

    def __init__(self, cfg: ArchConfig, params, num_slots: int = 4,
                 max_len: int = 512, backend: str = "gather",
                 decode_sla: Optional[bool] = None,
                 plan_reuse: str = "off", drift_threshold=None,
                 prefill_bucket: Optional[int] = None,
                 compute_dtype=jnp.bfloat16,
                 paged: Optional[bool] = None,
                 pool_pages: Optional[int] = None,
                 prefill_chunk_blocks: Optional[int] = None):
        from repro.core import backends as backend_registry

        backend = backend_registry.resolve(backend)
        cfg.sla.validate()
        if plan_reuse not in ("off", "adaptive"):
            raise ValueError(
                f"unknown plan_reuse mode {plan_reuse!r}; expected "
                "'off' or 'adaptive'")
        if decode_sla is None:
            decode_sla = cfg.sla.decode_mode == "sla"
        if paged is None:
            paged = cfg.sla.paged
        if paged and plan_reuse == "adaptive":
            # prefix pages are interned by prompt BYTES; adaptive plan
            # reuse makes a prefill depend on every earlier request's
            # plans, so identical bytes would no longer mean identical
            # page contents
            raise ValueError(
                "paged=True is incompatible with plan_reuse='adaptive': "
                "cross-request plan state breaks content-keyed prefix "
                "page interning (use plan_reuse='off')")
        if paged and cfg.sla.block_q != cfg.sla.block_kv:
            raise ValueError(
                f"paged KV pages are block_kv-sized and admission is "
                f"block_q-aligned; the grids must match (got block_q="
                f"{cfg.sla.block_q}, block_kv={cfg.sla.block_kv})")
        if prefill_chunk_blocks is None:
            prefill_chunk_blocks = cfg.sla.prefill_chunk_blocks
        if prefill_chunk_blocks is not None:
            if prefill_chunk_blocks < 1:
                raise ValueError(
                    f"prefill_chunk_blocks must be >= 1 (got "
                    f"{prefill_chunk_blocks})")
            if not paged:
                raise ValueError(
                    "prefill_chunk_blocks requires paged=True: chunked "
                    "admission lands its pages through the page-table "
                    "scatter and the prefix page cache")
        self.cfg = cfg
        self.params = params
        self.mdl = registry.get_model(cfg)
        check_serving_family(cfg, self.mdl, plan_reuse, decode_sla,
                             continuous=True)
        if prefill_chunk_blocks is not None:
            chk = getattr(self.mdl, "check_chunked_prefill", None)
            if chk is None:
                raise ValueError(
                    f"prefill_chunk_blocks requires a model family with "
                    f"chunked prefill (prefill_chunk / "
                    f"finalize_chunked_prefill); family {cfg.family!r} "
                    f"has none")
            chk(cfg, backend)  # loud eligibility (all-SLA, no col-cap, ...)
        self.num_slots = num_slots
        self.backend = backend
        self.decode_sla = decode_sla
        self.paged = paged
        self.plan_reuse = plan_reuse
        self.drift_threshold = normalize_drift_threshold(cfg,
                                                         drift_threshold)
        self.block = max(cfg.sla.block_q, 1)
        # admission at block boundaries: cache length and prefill
        # buckets are whole numbers of blocks, so every slot's position
        # starts block-aligned and plan_extend's static-grid invariants
        # hold per slot (paged mode block-aligns unconditionally — the
        # page pool is carved into block_kv-sized pages)
        self.max_len = block_bucket(max_len, self.block) \
            if (decode_sla or paged) else max_len
        self.compute_dtype = compute_dtype
        self.stats = ServeStats()

        self._queue: Deque[ServedRequest] = collections.deque()
        self._slots: List[Optional[ServedRequest]] = [None] * num_slots
        self._tokens = np.zeros((num_slots,), np.int32)
        self._next_rid = 0
        self._requests: List[ServedRequest] = []  # submission order
        self._bucket = (block_bucket(prefill_bucket, self.block)
                        if prefill_bucket else None)
        self._plans = None  # (1, bucket) plan stack for plan_reuse
        self._stat_base = [None] * num_slots  # decode-SLA counter bases
        # chunked-admission state (DESIGN.md "Chunked admission
        # prefill"): one optional in-flight _PrefillJob per slot; the
        # carry prototypes and boundary-snapshot LRU live on the
        # PrefillEngine below
        self.prefill_chunk_blocks = prefill_chunk_blocks
        self._chunk_tokens = ((prefill_chunk_blocks or 0) * self.block)
        self._job_by_slot: List[Optional[_PrefillJob]] = \
            [None] * num_slots
        self._last_token_t: Optional[float] = None

        if paged:
            from repro.serving.pages import PagePool, ZERO_PAGE

            if getattr(self.mdl, "make_paged_cache", None) is None:
                raise ValueError(
                    f"paged=True requires a model family with a paged "
                    f"decode cache (make_paged_cache / insert_slot_paged)"
                    f"; family {cfg.family!r} has none")
            tn = self.max_len // self.block
            # full per-slot backing + one pinned scratch page per slot +
            # the permanent zero page: exactly enough for zero sharing,
            # so any override below this trades capacity for the prefix
            # cache actually paying off
            default_pool = 1 + num_slots + num_slots * tn
            if pool_pages is None:
                pool_pages = (cfg.sla.page_pool_size
                              if cfg.sla.page_pool_size is not None
                              else default_pool)
            self.pool_pages = pool_pages
            self._pool = PagePool(pool_pages)
            self._zero_page = ZERO_PAGE
            # one pinned scratch page per slot: inactive slots keep
            # stepping through every batched dispatch, and their garbage
            # writes must land somewhere harmless
            self._scratch = [self._pool.alloc() for _ in range(num_slots)]
            self._pt_host = np.zeros((num_slots, tn), np.int32)
            for j in range(num_slots):
                self._pt_host[j, :] = self._scratch[j]
            self._slot_pids: List[List[int]] = [[] for _ in
                                                range(num_slots)]
            self._slot_base = [0] * num_slots  # prefill bucket at admit
            # full-prompt snapshots: (bucket, padded bytes) -> (per-slot
            # prefill state, first-token logits); exact hits skip the
            # prefill dispatch entirely
            self._snapshots = collections.OrderedDict()
            self._snapshot_cap = 32

        # the prefill half lives in a worker-reusable engine (also the
        # unit a disaggregated prefill pool shares; serving/disagg.py)
        self._pf = PrefillEngine(
            cfg, params, self.mdl, backend=backend,
            compute_dtype=compute_dtype, decode_sla=decode_sla,
            max_len=self.max_len, drift_threshold=self.drift_threshold,
            plan_reuse=plan_reuse, chunk_tokens=self._chunk_tokens)

        mdl, backend_, thr = self.mdl, backend, self.drift_threshold

        if decode_sla:
            def _one(params, token, cache):
                return mdl.decode_step(params, cfg, token, cache,
                                       compute_dtype=compute_dtype,
                                       backend=backend_,
                                       drift_threshold=thr)
        else:
            def _one(params, token, cache):
                return mdl.decode_step(params, cfg, token, cache,
                                       compute_dtype=compute_dtype)

        _decode = jax.jit(_one)
        max_len_ = self.max_len

        # rolled multi-step greedy decode (ISSUE 6): nsteps is a traced
        # scalar, so fori_loop lowers to while_loop and ONE trace covers
        # every segment length drain() ever requests
        @jax.jit
        def _decode_multi(params, token, cache, nsteps):
            buf = jnp.zeros((max_len_, token.shape[0]), jnp.int32)

            def body(i, carry):
                token, cache, buf = carry
                logits, cache = _one(params, token, cache)
                token = jnp.argmax(logits, -1).astype(jnp.int32)
                buf = jax.lax.dynamic_update_slice_in_dim(
                    buf, token[None], i, axis=0)
                return token, cache, buf

            return jax.lax.fori_loop(0, nsteps, body, (token, cache, buf))

        @jax.jit
        def _admit(live, single, slot):
            grow = max_len_ - single["k"].shape[-2]
            if grow > 0:  # dense prefill caches stop at the bucket
                pad = [(0, 0)] * 3 + [(0, grow), (0, 0)]
                single = dict(single, k=jnp.pad(single["k"], pad),
                              v=jnp.pad(single["v"], pad))
            return mdl.insert_slot(live, single, slot)

        # masked decode pair for MIXED drain ticks (some active slots
        # need per-token host control, the rest are pure-greedy): each
        # dispatch computes the full batch but commits cache/token
        # updates only where `mask` is set, so host-controlled slots
        # stay frozen through the greedy roll and vice versa. Per-slot
        # decode is batch-independent, so committed trajectories are
        # bitwise the ones per-token step() would have produced.
        nsl = num_slots

        def _mask_leaves(mask, new, old):
            def sel(n, o):
                if n.ndim == 1 and n.shape[0] == nsl:
                    return jnp.where(mask, n, o)
                if n.ndim >= 2 and n.shape[1] == nsl:
                    m = mask.reshape((1, -1) + (1,) * (n.ndim - 2))
                    return jnp.where(m, n, o)
                if n.ndim >= 2 and n.shape[0] == nsl:
                    m = mask.reshape((-1,) + (1,) * (n.ndim - 1))
                    return jnp.where(m, n, o)
                return n
            return jax.tree_util.tree_map(sel, new, old)

        @jax.jit
        def _decode_mask(params, token, cache, mask):
            logits, new_cache = _one(params, token, cache)
            return logits, _mask_leaves(mask, new_cache, cache)

        @jax.jit
        def _decode_multi_mask(params, token, cache, nsteps, mask):
            buf = jnp.zeros((max_len_, token.shape[0]), jnp.int32)

            def body(i, carry):
                token, cache, buf = carry
                logits, new_cache = _one(params, token, cache)
                cache = _mask_leaves(mask, new_cache, cache)
                new_tok = jnp.argmax(logits, -1).astype(jnp.int32)
                token = jnp.where(mask, new_tok, token)
                buf = jax.lax.dynamic_update_slice_in_dim(
                    buf, token[None], i, axis=0)
                return token, cache, buf

            return jax.lax.fori_loop(0, nsteps, body, (token, cache, buf))

        self._decode = _decode
        self._decode_multi = _decode_multi
        self._decode_mask = _decode_mask
        self._decode_multi_mask = _decode_multi_mask
        self._admit_jit = _admit
        if paged:
            self._admit_paged_jit = jax.jit(mdl.insert_slot_paged)
            self._admit_state_jit = jax.jit(mdl.insert_slot_state_paged)
            self._copy_page_jit = jax.jit(mdl.copy_page)
            self._live = mdl.make_paged_cache(cfg, num_slots, self.max_len,
                                              pool_pages,
                                              dtype=compute_dtype,
                                              decode_sla=decode_sla)
            self._push_pt()
        else:
            self._live = mdl.make_cache(cfg, num_slots, self.max_len,
                                        dtype=compute_dtype,
                                        decode_sla=decode_sla,
                                        per_slot=True)

    # -- public API --------------------------------------------------------
    def submit(self, prompt, sampling: Optional[SamplingParams] = None
               ) -> int:
        """Enqueue one request; returns its rid. O(1), never blocks."""
        sampling = (sampling or SamplingParams()).validate()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        # capacity check against the SHARED prefill bucket (every
        # admission pads to it, so a long earlier prompt raises the
        # floor for everyone); _admit_next re-checks after any growth
        # that happens while this request is queued
        bucket = max(block_bucket(len(prompt), self.block),
                     self._bucket or 0)
        need = bucket + sampling.max_new_tokens
        if need > self.max_len:
            raise ValueError(
                f"max_len={self.max_len} cannot hold a {len(prompt)}-token "
                f"prompt (shared prefill bucket {bucket}) plus "
                f"{sampling.max_new_tokens} new tokens; raise max_len "
                f"to >= {need}")
        r = ServedRequest(rid=self._next_rid, prompt=prompt,
                          sampling=sampling)
        r.metrics.submit_t = time.time()
        self._next_rid += 1
        self._queue.append(r)
        self._requests.append(r)
        return r.rid

    def free_slots(self) -> List[int]:
        """Slots with neither a resident request nor an in-flight
        chunked-prefill job — the ones an external admission may take."""
        return [j for j in range(self.num_slots)
                if self._slots[j] is None and self._job_by_slot[j] is None]

    def admit_external(self, r: ServedRequest, slot: int, cache, logits,
                       toks: np.ndarray, bucket: int, *, prefilled: int,
                       plan_built: bool = True,
                       start_emitted: bool = True) -> List[StreamEvent]:
        """Admit an externally-prefilled request into `slot` — the
        disaggregated handoff path (serving/disagg.py): a prefill
        worker ran the (1, bucket) prefill and hands over the bundle
        (the batch-1 prefill cache, the (1, vocab) first-token logits
        row, and the left-padded prompt). Runs blocking admission's
        tail verbatim — paged: page claim -> page-table scatter ->
        full-prompt snapshot; unpaged: `insert_slot` — so the slot's
        cache leaves and every subsequent greedy token are bitwise what
        self-admission of the same prompt at the same bucket would have
        produced. Re-admitting the SAME bundle after a worker loss
        replays the same trajectory (requeue parity).

        `prefilled` is the prompt-token count the prefill actually
        dispatched (0 for a replayed bundle — nothing was recomputed);
        `plan_built` gates decode-plan-build accounting the same way."""
        if self._slots[slot] is not None \
                or self._job_by_slot[slot] is not None:
            raise ValueError(
                f"slot {slot} is occupied; admit_external needs a slot "
                f"from free_slots()")
        r.state = RequestState.PREFILLING
        r.slot = slot
        t0 = time.time()
        if r.metrics.admit_t == 0.0:
            r.metrics.admit_t = t0
        # the handoff bucket raises this scheduler's shared floor
        # exactly like a self-admitted long prompt would
        if self._bucket is None or bucket > self._bucket:
            self._bucket = bucket
            self._plans = None
        if bucket + r.sampling.max_new_tokens > self.max_len:
            r.state = RequestState.QUEUED
            r.slot = None
            raise ValueError(
                f"max_len={self.max_len} cannot hold handoff request "
                f"{r.rid}: bucket {bucket} plus "
                f"{r.sampling.max_new_tokens} new tokens does not fit; "
                f"raise max_len to >= "
                f"{bucket + r.sampling.max_new_tokens}")
        if self.paged:
            padded = np.asarray(toks[0])
            keys = self._page_keys(padded)
            pids = [self._claim_page(key) for key in keys]
            self._live = self._admit_paged_jit(
                self._live, cache, slot, jnp.asarray(pids, jnp.int32))
            self._set_slot_pages(slot, pids, bucket=bucket)
            self._store_snapshot((bucket, padded.tobytes()), cache,
                                 logits)
            self._sync_page_stats()
        else:
            self._live = self._admit_jit(self._live, cache, slot)
        events: List[StreamEvent] = []
        self._finish_admission(r, slot, logits, t0, events,
                               prefilled=prefilled,
                               plan_built=plan_built,
                               start_emitted=start_emitted)
        return events

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or any(r is not None for r in self._slots)

    def step(self) -> List[StreamEvent]:
        """Advance in-flight chunked prefills by one chunk and admit
        queued requests into free slots, then run ONE batched decode
        step over the live cache. Returns the events produced."""
        events: List[StreamEvent] = []
        self._tick_admit(events)
        return events + self._decode_tick()

    def _tick_admit(self, events: List[StreamEvent]):
        """Shared tick head: every in-flight chunked-prefill job
        advances ONE chunk (a completion hands its slot to this very
        tick's decode), then queued requests fill free slots."""
        for slot in range(self.num_slots):
            if self._job_by_slot[slot] is not None:
                self._advance_job(slot, events)
        for slot in range(self.num_slots):
            if self._slots[slot] is None and self._queue:
                self._admit_next(slot, events)

    def _decoding(self) -> List[int]:
        """Slots eligible for decode dispatch: occupied AND past their
        prefill. PREFILLING job slots are masked out exactly like
        freed slots — their page-table rows still point at the pinned
        scratch page, so the batched dispatch's garbage writes land
        harmlessly until completion scatters the real pages in."""
        return [j for j in range(self.num_slots)
                if self._slots[j] is not None
                and self._slots[j].state is RequestState.DECODING]

    def _decode_tick(self) -> List[StreamEvent]:
        """ONE batched decode step over the live cache."""
        events: List[StreamEvent] = []
        active = self._decoding()
        if not active:
            return events
        if self.paged:
            for j in active:
                self._ensure_decode_pages(j, 1)
        t0 = time.time()
        logits, self._live = self._decode(
            self.params, jnp.asarray(self._tokens), self._live)
        # greedy slots argmax on device (a (B,) transfer); the full
        # (B, vocab) logits matrix only crosses to the host when some
        # active request actually samples
        greedy_toks = np.asarray(jnp.argmax(logits, -1))  # host sync
        larr = None
        if any(self._slots[j].sampling.temperature > 0.0 for j in active):
            larr = np.asarray(logits)
        now = time.time()
        self.stats.decode_s += now - t0
        self.stats.decode_tokens += len(active)
        self.stats.slot_steps_active += len(active)
        self.stats.slot_steps_total += self.num_slots
        self._note_gap(now)
        for j in active:
            r = self._slots[j]
            tok = int(greedy_toks[j]) if r.sampling.temperature <= 0.0 \
                else self._sample(r, larr[j])
            self._tokens[j] = tok
            r.tokens_out.append(tok)
            r.metrics.decode_tokens += 1
            events.append(StreamEvent(rid=r.rid, kind="token", t=now,
                                      token=tok,
                                      index=len(r.tokens_out) - 1))
            if self._is_done(r):
                self._finish(r, j, now, events)
        return events

    def drain(self) -> List[ServedRequest]:
        """Run the scheduler until every submitted request has finished;
        returns all requests in submission order.

        Greedy slots decode in ROLLED segments: one `_decode_multi`
        dispatch covers min-remaining-budget steps across the active
        slots, so host round-trips scale with the number of admission /
        finish boundaries, not the token horizon. Any active request
        that samples (temperature > 0) or watches stop tokens needs
        per-token host control, so those ticks fall back to `step()`."""
        while self.has_work:
            self._drain_tick()
        return list(self._requests)

    def _drain_tick(self) -> List[StreamEvent]:
        """One drain iteration: admit, then decode one rolled segment.

        Active slots are PARTITIONED: pure-greedy slots (no sampling, no
        stop tokens) always take a rolled multi-step dispatch, while
        host-controlled slots (temperature > 0 or stop tokens) take one
        masked single step. A tick where every active slot is greedy
        uses the original unmasked `_decode_multi` trace; a mixed tick
        uses the masked pair, so one sampling request no longer drags
        every greedy slot down to per-token host round-trips."""
        events: List[StreamEvent] = []
        self._tick_admit(events)
        active = self._decoding()
        if not active:
            return events
        ctl = [j for j in active
               if self._slots[j].sampling.temperature > 0.0
               or self._slots[j].sampling.stop_tokens]
        greedy = [j for j in active if j not in ctl]
        if ctl and self.paged:
            # page-pool leaves have no batch axis to mask on (distinct
            # slots write distinct pages inside ONE dispatch), so a
            # masked commit can't keep a slot's pool writes out —
            # per-token lockstep is the correct fallback
            return events + self._decode_tick()
        if ctl and greedy:
            events += self._masked_ctl_step(ctl)
            # a ctl slot may have finished and freed a slot; greedy
            # slots are untouched by the masked step
            return events + self._greedy_roll(greedy, masked=True)
        if ctl:
            return events + self._decode_tick()
        return events + self._greedy_roll(greedy, masked=False)

    def _masked_ctl_step(self, ctl: List[int]) -> List[StreamEvent]:
        """One decode step committed only for the host-controlled slots
        in `ctl` (sampling / stop-token requests)."""
        events: List[StreamEvent] = []
        mask = np.zeros((self.num_slots,), bool)
        mask[ctl] = True
        t0 = time.time()
        logits, self._live = self._decode_mask(
            self.params, jnp.asarray(self._tokens), self._live,
            jnp.asarray(mask))
        larr = np.asarray(logits)  # host sync; ctl slots sample anyway
        now = time.time()
        self.stats.decode_s += now - t0
        self.stats.decode_tokens += len(ctl)
        self.stats.slot_steps_active += len(ctl)
        self.stats.slot_steps_total += self.num_slots
        self._note_gap(now)
        for j in ctl:
            r = self._slots[j]
            tok = self._sample(r, larr[j])
            self._tokens[j] = tok
            r.tokens_out.append(tok)
            r.metrics.decode_tokens += 1
            events.append(StreamEvent(rid=r.rid, kind="token", t=now,
                                      token=tok,
                                      index=len(r.tokens_out) - 1))
            if self._is_done(r):
                self._finish(r, j, now, events)
        return events

    def _greedy_roll(self, greedy: List[int],
                     masked: bool) -> List[StreamEvent]:
        """Rolled multi-step greedy decode over the slots in `greedy`:
        nothing can finish before the smallest remaining budget, so run
        exactly that many steps in one traced-length dispatch (masked
        when host-controlled slots share the batch and must not move)."""
        events: List[StreamEvent] = []
        nsteps = min(self._slots[j].sampling.max_new_tokens
                     - len(self._slots[j].tokens_out) for j in greedy)
        if any(job is not None for job in self._job_by_slot):
            # a chunked prefill is in flight: cap the roll so its next
            # chunk interleaves at per-token granularity instead of
            # stalling behind a multi-step dispatch
            nsteps = 1
        if self.paged:
            for j in greedy:
                self._ensure_decode_pages(j, nsteps)
        t0 = time.time()
        if masked:
            mask = np.zeros((self.num_slots,), bool)
            mask[greedy] = True
            token, self._live, buf = self._decode_multi_mask(
                self.params, jnp.asarray(self._tokens), self._live,
                jnp.int32(nsteps), jnp.asarray(mask))
        else:
            token, self._live, buf = self._decode_multi(
                self.params, jnp.asarray(self._tokens), self._live,
                jnp.int32(nsteps))
        toks = np.asarray(buf)[:nsteps]  # host sync
        now = time.time()
        self.stats.decode_s += now - t0
        self.stats.decode_tokens += nsteps * len(greedy)
        self.stats.slot_steps_active += nsteps * len(greedy)
        self.stats.slot_steps_total += nsteps * self.num_slots
        self._note_gap(now)
        for j in greedy:
            r = self._slots[j]
            for i in range(nsteps):
                tok = int(toks[i][j])
                self._tokens[j] = tok
                r.tokens_out.append(tok)
                r.metrics.decode_tokens += 1
                events.append(StreamEvent(rid=r.rid, kind="token", t=now,
                                          token=tok,
                                          index=len(r.tokens_out) - 1))
            if self._is_done(r):
                self._finish(r, j, now, events)
        return events

    def stream(self) -> Iterator[StreamEvent]:
        """Yield StreamEvents as they are produced, until drained."""
        while self.has_work:
            yield from self.step()

    # -- internals ---------------------------------------------------------
    def _round_bucket(self, plen: int) -> int:
        return block_bucket(plen, self.block)

    def _admit_next(self, slot: int, events: List[StreamEvent]):
        r = self._queue.popleft()
        r.state = RequestState.PREFILLING
        r.slot = slot
        t0 = time.time()
        r.metrics.admit_t = t0
        plen = len(r.prompt)
        if self._bucket is None or plen > self._bucket:
            # a longer prompt grows the bucket; cached (1, bucket) plans
            # are for the old block grid, so they die with it
            self._bucket = self._round_bucket(plen)
            self._plans = None
        if self._bucket + r.sampling.max_new_tokens > self.max_len:
            # the shared bucket grew past this request's submit-time
            # check; past this point decode would write beyond the cache
            # and dynamic_update_slice would clamp onto the last slot —
            # silent token corruption, so fail loudly instead. The
            # request goes back to the queue head first, so a caller
            # that catches the error still sees it (and can cancel it)
            # rather than losing it in a half-admitted limbo state
            self._queue.appendleft(r)
            r.state = RequestState.QUEUED
            r.slot = None
            raise ValueError(
                f"max_len={self.max_len} cannot hold request {r.rid}: "
                f"the shared prefill bucket grew to {self._bucket} "
                f"(longest admitted prompt, block-aligned) and "
                f"{r.sampling.max_new_tokens} new tokens no longer fit; "
                f"raise max_len to >= "
                f"{self._bucket + r.sampling.max_new_tokens}")
        toks = np.zeros((1, self._bucket), np.int32)
        toks[0, self._bucket - plen:] = r.prompt  # left-pad
        if self.paged:
            padded = toks[0]
            keys = self._page_keys(padded)
            # precedence: full-prompt snapshot > chunked machine >
            # blocking dispatch (the snapshot fast path short-circuits
            # the whole chunk state machine)
            logits = self._try_snapshot(padded, keys, slot)
            if logits is not None:
                self._finish_admission(r, slot, logits, t0, events,
                                       prefilled=0, plan_built=False)
                return
            if self._chunk_tokens:
                self._start_job(r, slot, toks, keys, t0, events)
                return
            logits = self._dispatch_paged(toks, keys, slot)
        else:
            last_hidden, cache = self._run_prefill(jnp.asarray(toks))
            logits = np.asarray(
                logits_from_hidden(self.params, last_hidden))
            self._live = self._admit_jit(self._live, cache, slot)
        self._finish_admission(r, slot, logits, t0, events,
                               prefilled=self._bucket, plan_built=True)

    def _finish_admission(self, r: ServedRequest, slot: int, logits,
                          t0: float, events: List[StreamEvent], *,
                          prefilled: int, plan_built: bool,
                          start_emitted: bool = False):
        """Common admission tail (blocking, snapshot-hit and chunked
        completions): decode-SLA accounting — gated on whether a
        prefill actually dispatched, a snapshot fast-path hit builds no
        plans and prefills no tokens — then first-token sampling,
        events, and the slot hand-off to DECODING."""
        if self.decode_sla:
            if plan_built:
                self.stats.decode_plan_builds += self.cfg.num_layers
            self._stat_base[slot] = self._slot_counters(slot)
        tok = self._sample(r, logits[0])
        self._tokens[slot] = tok
        now = time.time()
        self.stats.admissions += 1
        self.stats.prefill_tokens += prefilled
        self.stats.prefill_s += now - t0
        r.metrics.first_token_t = now
        r.state = RequestState.DECODING
        r.tokens_out.append(tok)
        r.metrics.decode_tokens += 1
        if not start_emitted:
            events.append(StreamEvent(rid=r.rid, kind="start", t=t0))
        self._note_gap(now)
        events.append(StreamEvent(rid=r.rid, kind="token", t=now,
                                  token=tok, index=0))
        self._slots[slot] = r
        if self._is_done(r):
            self._finish(r, slot, now, events)

    def _note_gap(self, now: float):
        """Track the largest wall-clock gap between consecutive token
        emissions (`ServeStats.max_decode_gap_s`) — the decode-stall
        metric chunked admission exists to shrink: a blocking long
        prefill freezes every decoding slot for the whole dispatch,
        chunked admission bounds the freeze to one chunk."""
        if self._last_token_t is not None:
            gap = now - self._last_token_t
            if gap > self.stats.max_decode_gap_s:
                self.stats.max_decode_gap_s = gap
        self._last_token_t = now

    def _run_prefill(self, toks: jnp.ndarray):
        """(1, bucket) prefill, through the plan-reuse path if enabled."""
        last_hidden, cache, self._plans = self._pf.run(
            toks, self._plans, self.stats, self.cfg.num_layers)
        return last_hidden, cache

    # -- paged KV internals (DESIGN.md "Paged KV & prefix caching") --------
    def _page_keys(self, padded: np.ndarray) -> List[bytes]:
        """One intern key per prompt page: the raw bytes of the padded
        prompt up to that page's END. Causal attention over absolute
        positions makes page j's KV rows and h/z partials a pure
        function of the tokens below (j+1)*block_kv, so identical bytes
        mean bitwise-identical page contents — across requests and even
        across prefill buckets (the left-pad layout is part of the
        bytes, so differently-padded prompts simply never match)."""
        bkv = self.block
        return [padded[:(j + 1) * bkv].tobytes()
                for j in range(padded.size // bkv)]

    def _push_pt(self):
        """Publish the host-owned page table to the device cache. `pt`
        is read-only inside every jitted decode/admit dispatch; the
        scheduler owns it here and overwrites it between dispatches."""
        self._live = dict(self._live)
        self._live["pt"] = jnp.asarray(self._pt_host)

    def _sync_page_stats(self):
        ps, st = self._pool.stats, self.stats
        st.pages_in_use = self._pool.in_use()
        st.pages_peak = max(st.pages_peak, st.pages_in_use)
        st.page_allocs = ps.allocs
        st.prefix_hits = ps.prefix_hits
        st.prefix_misses = ps.prefix_misses
        st.cow_copies = ps.cow_copies

    def _set_slot_pages(self, slot: int, pids: List[int],
                        bucket: Optional[int] = None):
        """Point `slot`'s page-table row at its prompt pages (one
        pool ref each, already taken); the decode tail reads the
        permanent zero page until the CoW pass privatizes it. `bucket`
        defaults to the shared prefill bucket — a chunked completion
        passes its own admission-time bucket, which may predate a
        growth triggered by a later queued prompt."""
        npp = len(pids)
        self._pt_host[slot, :npp] = pids
        self._pt_host[slot, npp:] = self._zero_page
        self._slot_pids[slot] = list(pids)
        self._slot_base[slot] = self._bucket if bucket is None else bucket
        self._push_pt()

    def _try_snapshot(self, padded: np.ndarray, keys: List[bytes],
                      slot: int) -> Optional[np.ndarray]:
        """Full-prompt snapshot fast path: an exact (bucket,
        padded-prompt-bytes) snapshot hit whose prompt pages are all
        still interned skips the prefill dispatch entirely — the
        per-slot state and first-token logits were cached when the
        prompt was first seen, and the pages already hold its
        KV/partials. Returns the logits row, or None on a miss."""
        snap_key = (self._bucket, padded.tobytes())
        snap = self._snapshots.get(snap_key)
        if snap is None:
            return None
        pids, ok = [], True
        for key in keys:
            pid = self._pool.lookup(key)
            if pid is None:  # a page was evicted since the snapshot
                ok = False
                break
            pids.append(pid)
        if not ok:
            for pid in pids:  # partial hit: hand the taken refs back
                self._pool.release(pid)
            return None
        self._snapshots.move_to_end(snap_key)
        state, logits = snap
        self._live = self._admit_state_jit(self._live, state, slot)
        self._set_slot_pages(slot, pids)
        self.stats.prefix_full_hits += 1
        self._sync_page_stats()
        return logits

    def _claim_page(self, key: bytes) -> int:
        """Lookup-or-alloc one prompt page by its prefix-bytes intern
        key; the returned pool ref belongs to the caller."""
        pid = self._pool.lookup(key)
        if pid is None:
            pid = self._pool.alloc()
            self._pool.intern(key, pid)
        return pid

    def _store_snapshot(self, snap_key, cache, logits):
        self._snapshots[snap_key] = (
            self.mdl.slot_state_from_prefill(cache), logits)
        self._snapshots.move_to_end(snap_key)
        while len(self._snapshots) > self._snapshot_cap:
            self._snapshots.popitem(last=False)

    def _dispatch_paged(self, toks: np.ndarray, keys: List[bytes],
                        slot: int) -> np.ndarray:
        """Blocking page-granular admission: one (1, bucket) prefill,
        each prompt page interned by its prefix bytes; pages that hit
        are REWRITTEN with byte-identical contents, which keeps
        admission a single static-shape jit. Returns the first-token
        logits row."""
        last_hidden, cache = self._run_prefill(jnp.asarray(toks))
        logits = np.asarray(logits_from_hidden(self.params, last_hidden))
        pids = [self._claim_page(key) for key in keys]
        self._live = self._admit_paged_jit(
            self._live, cache, slot, jnp.asarray(pids, jnp.int32))
        self._set_slot_pages(slot, pids)
        self._store_snapshot((self._bucket, toks[0].tobytes()), cache,
                             logits)
        self._sync_page_stats()
        return logits

    # -- chunked admission (DESIGN.md "Chunked admission prefill") ---------
    def _claim_job_pages(self, job: _PrefillJob, lo: int, hi: int):
        """Intern-or-alloc the pages covering padded tokens [lo, hi) —
        one pool ref each, held by the job until `_set_slot_pages`
        takes them over at completion. Interned hits count prefix hits
        exactly once per page, as in blocking admission; page CONTENTS
        land at completion's full byte-identical rewrite, which is safe
        because nothing reads a slot's pages before its own completion
        scatter (snapshot fast-path hits require a stored snapshot, and
        snapshots are only stored after such a rewrite)."""
        bkv = self.block
        for j in range(lo // bkv, hi // bkv):
            job.pids.append(self._claim_page(job.keys[j]))
        self._sync_page_stats()

    def _start_job(self, r: ServedRequest, slot: int, toks: np.ndarray,
                   keys: List[bytes], t0: float,
                   events: List[StreamEvent]):
        """Claim `slot` for a multi-tick chunked admission. The request
        sits in PREFILLING state (masked out of decode dispatch) while
        `_tick_admit` advances it one chunk per tick; its first chunk
        runs within THIS tick. If a carry snapshot survives for a
        chunk-boundary prefix of the padded prompt, the job resumes
        past those chunks — a shared prefix skips its chunks, its pages
        claimed by intern lookup instead of recomputation."""
        bucket, ct = self._bucket, self._chunk_tokens
        job = _PrefillJob(r=r, slot=slot, toks=toks, keys=keys,
                          bucket=bucket,
                          carry=self._pf.carry_proto(bucket),
                          num_chunks=-(-bucket // ct), t0=t0)
        for c in range(job.num_chunks - 1, 0, -1):
            ckey = (bucket, toks[0, :c * ct].tobytes())
            snap = self._pf.carry_get(ckey)
            if snap is not None:
                job.carry = snap
                job.next_chunk = c
                self._claim_job_pages(job, 0, c * ct)
                break
        self.stats.chunked_admissions += 1
        self._job_by_slot[slot] = job
        self._slots[slot] = r  # owns the slot; PREFILLING masks decode
        events.append(StreamEvent(rid=r.rid, kind="start", t=t0))
        self._advance_job(slot, events)

    def _advance_job(self, slot: int, events: List[StreamEvent]):
        """Run ONE prefill chunk for the job occupying `slot`: the
        chunk's KV/pooled rows land in the carry, its pages are claimed
        from the pool, and the boundary carry is snapshotted for future
        prefix resumes. The final chunk hands the slot to decode."""
        job = self._job_by_slot[slot]
        ct = self._chunk_tokens
        lo = job.next_chunk * ct
        hi = min(lo + ct, job.bucket)
        t0 = time.time()
        carry, last_hidden = self._pf.chunk(
            jnp.asarray(job.toks[:, lo:hi]), job.carry, jnp.int32(lo))
        carry = jax.block_until_ready(carry)
        self.stats.prefill_s += time.time() - t0
        self.stats.prefill_chunks += 1
        job.carry = carry
        job.last_hidden = last_hidden
        job.dispatched += hi - lo
        self._claim_job_pages(job, lo, hi)
        if hi < job.bucket:  # full-prompt resume is the snapshot's job
            self._pf.carry_put((job.bucket, job.toks[0, :hi].tobytes()),
                               carry)
        job.next_chunk += 1
        if job.next_chunk >= job.num_chunks:
            self._complete_job(slot, job, events)

    def _complete_job(self, slot: int, job: _PrefillJob,
                      events: List[StreamEvent]):
        """Blocking admission's tail, verbatim: finalize the carry into
        the cache dict blocking prefill returns (decode state rebuilt
        with `_seed_decode_state`, so every leaf is bitwise blocking's),
        scatter it into `slot` through the page table, store the
        full-prompt snapshot, emit the first token."""
        t0 = time.time()
        cache = self._pf.finalize(job.carry)
        logits = np.asarray(
            logits_from_hidden(self.params, job.last_hidden))
        self._live = self._admit_paged_jit(
            self._live, cache, slot, jnp.asarray(job.pids, jnp.int32))
        self._set_slot_pages(slot, job.pids, bucket=job.bucket)
        self._store_snapshot((job.bucket, job.toks[0].tobytes()), cache,
                             logits)
        self._sync_page_stats()
        self._job_by_slot[slot] = None
        self._finish_admission(job.r, slot, logits, t0, events,
                               prefilled=job.dispatched, plan_built=True,
                               start_emitted=True)

    def _ensure_decode_pages(self, slot: int, nsteps: int):
        """Copy-on-write pass before a decode dispatch: every page in
        `slot`'s write range for the next `nsteps` tokens must be
        private (refcount 1, not the zero page) before the jitted step
        touches it. Fresh decode pages start as a copy of the permanent
        zero page — the h/z partials ACCUMULATE into them, so a
        recycled page must be cleaned; shared (prefix-interned or
        CoW-shared) pages are duplicated on first divergent write."""
        r = self._slots[slot]
        pos = self._slot_base[slot] + len(r.tokens_out) - 1
        bkv = self.block
        tn = self._pt_host.shape[1]
        first = min(pos // bkv, tn - 1)
        last = min((pos + nsteps - 1) // bkv, tn - 1)
        changed = False
        for blk in range(first, last + 1):
            pid = int(self._pt_host[slot, blk])
            if pid != self._zero_page and self._pool.refs(pid) == 1:
                continue  # already exclusively ours
            new, src = self._pool.ensure_private(pid)
            self._live = self._copy_page_jit(self._live, new, src)
            own = self._slot_pids[slot]
            if pid in own:
                own[own.index(pid)] = new
            else:
                own.append(new)  # the zero page was never slot-owned
            self._pt_host[slot, blk] = new
            changed = True
        if changed:
            self._push_pt()
            self._sync_page_stats()

    def _slot_counters(self, slot: int) -> dict:
        st = self._live["sla"]
        return {key: np.asarray(st[key][:, slot])
                for key in ("extends", "replans", "reuses")}

    def _sample(self, r: ServedRequest, logits_row: np.ndarray) -> int:
        if r.sampling.temperature <= 0.0:
            return int(np.argmax(logits_row))
        rng = np.random.default_rng(
            (r.sampling.seed, r.rid, len(r.tokens_out)))
        z = logits_row.astype(np.float64) / r.sampling.temperature
        z -= z.max()
        p = np.exp(z)
        return int(rng.choice(len(p), p=p / p.sum()))

    def _is_done(self, r: ServedRequest) -> bool:
        if len(r.tokens_out) >= r.sampling.max_new_tokens:
            return True
        return bool(r.tokens_out) and \
            r.tokens_out[-1] in r.sampling.stop_tokens

    def _finish(self, r: ServedRequest, slot: int, now: float,
                events: List[StreamEvent]):
        r.state = RequestState.FINISHED
        r.metrics.finish_t = now
        self._slots[slot] = None
        if self.paged:
            # drop this slot's page refs (interned prefix pages stay
            # resident under the index's own ref until LRU-evicted) and
            # point the row back at the pinned scratch page so the
            # now-idle slot's garbage writes land somewhere harmless
            for pid in self._slot_pids[slot]:
                self._pool.release(pid)
            self._slot_pids[slot] = []
            self._pt_host[slot, :] = self._scratch[slot]
            self._push_pt()
            self._sync_page_stats()
        if self.decode_sla and self._stat_base[slot] is not None:
            base, cur = self._stat_base[slot], self._slot_counters(slot)
            self.stats.decode_plan_extends += int(
                (cur["extends"] - base["extends"]).sum())
            self.stats.decode_plan_replans += int(
                (cur["replans"] - base["replans"]).sum())
            self.stats.decode_plan_reuses += int(
                (cur["reuses"] - base["reuses"]).sum())
            self.stats.decode_last_retention = float(
                np.min(np.asarray(self._live["sla"]["retention"][:, slot])))
            self._stat_base[slot] = None
        events.append(StreamEvent(rid=r.rid, kind="finish", t=now))
