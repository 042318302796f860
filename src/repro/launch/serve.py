"""Serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --scheduler continuous --stream

Flag reference (each flag's argparse help is authoritative; see
examples/serve_routing.py and examples/serve_stream.py for worked
end-to-end examples):

  --arch / --smoke          model selection (+ CPU-runnable reduction)
  --requests/--batch/--prompt-len/--max-new/--seed
                            synthetic request stream shape
  --backend                 SLA execution backend (core.backends registry)
  --scheduler               static lockstep groups vs the v2
                            continuous-batching slot pool
                            (DESIGN.md "Serving API v2")
  --stream                  print per-token StreamEvents (continuous only)
  --plan-reuse              reuse prefill block plans across request
                            chunks (DESIGN.md "Plan lifetime & drift")
  --drift-threshold         per-layer drift level that forces a re-plan
  --decode-sla              decode-time SLA (DESIGN.md "Decode-time SLA")
  --routing-mode            threshold vs learned block routing
                            (DESIGN.md "Learned routing")
  --paged / --pool-pages    paged KV cache + prefix page cache
                            (DESIGN.md "Paged KV & prefix caching")
  --prefill-chunk           chunked admission prefill: admit long
                            prompts one N-block chunk per tick so the
                            other slots keep decoding (DESIGN.md
                            "Chunked admission prefill"; requires
                            --paged, continuous scheduler)
  --disagg                  disaggregated prefill/decode worker pools
                            with handoff + fault-tolerant requeue
                            (DESIGN.md "Disaggregated serving")
  --prefill-workers/--decode-workers
                            pool sizes for --disagg
  --workload                'lm' (default) or 'dit': the streaming DiT
                            denoise service — continuous batching of
                            denoise requests with cross-request plan
                            caching (DESIGN.md "Streaming DiT service")
  --num-steps/--seq-len/--t-start
                            dit workload: Euler steps, latent tokens
                            per request, and trajectory start time
  --refresh-mode            dit workload: per-slot plan refresh policy
  --plan-cache/--t-buckets/--cache-entries
                            dit workload: cross-request SLA plan cache
  --stats-json PATH         dump ServeStats + per-request metrics as
                            JSON after the run (every serving mode;
                            in-flight metrics stay null, never 0.0)
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models import registry
from repro.serving.engine import Request, ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4,
                    help="static scheduler: decode group size; "
                         "continuous scheduler: number of decode slots")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="gather",
                    help="SLA execution backend from the core.backends "
                         "registry: 'gather' (LUT-gather XLA, true sparse "
                         "FLOPs — default), 'reference' (dense oracle), "
                         "'kernel' (fused Pallas; interpreted on the CPU). "
                         "Unknown names fail loudly at startup")
    ap.add_argument("--scheduler", default="static",
                    choices=["static", "continuous"],
                    help="'static' decodes fixed groups in lockstep (v1 "
                         "engine); 'continuous' runs the v2 continuous-"
                         "batching scheduler — a fixed pool of decode "
                         "slots that turn over the moment a request "
                         "finishes, with real per-request TTFT/latency "
                         "and slot-occupancy stats (DESIGN.md 'Serving "
                         "API v2'). Greedy tokens are identical across "
                         "both")
    ap.add_argument("--stream", action="store_true",
                    help="print per-token StreamEvents as they are "
                         "produced (continuous scheduler only)")
    ap.add_argument("--plan-reuse", default="off",
                    choices=["off", "adaptive"],
                    help="'adaptive' pads every prefill chunk to one "
                         "static block-aligned bucket, plans the per-layer "
                         "SLA block structure once, and reuses it across "
                         "chunks of the request stream — re-planning a "
                         "layer only when its measured plan drift reaches "
                         "--drift-threshold (DESIGN.md 'Plan lifetime & "
                         "drift'). 'off' plans every chunk from scratch")
    ap.add_argument("--drift-threshold", default=None,
                    help="re-plan a layer when its plan drift "
                         "(1 - retained critical mass, in [0, 1]) reaches "
                         "this; 0.0 re-plans every chunk, 1.0 never "
                         "re-plans after the first. A comma-separated "
                         "list gives one threshold PER LAYER (applied "
                         "layer-by-layer, never min-reduced). Also gates "
                         "the decode-SLA live-row refresh. Default: "
                         "cfg.sla.plan_drift_threshold")
    ap.add_argument("--decode-sla", action="store_true",
                    help="decode with incremental SLA block plans + the "
                         "O(1) linear running state instead of dense "
                         "masked attention over the full cache — per-token "
                         "attention cost becomes critical-blocks + O(1) "
                         "instead of O(context) (DESIGN.md 'Decode-time "
                         "SLA'). Requires block-aligned prompt/cache "
                         "lengths (the engine rounds max_len up)")
    ap.add_argument("--paged", action="store_true",
                    help="page the per-slot KV cache: block_kv-sized "
                         "physical pages in a refcounted global pool, "
                         "per-slot page tables, prefix-interned prompt "
                         "pages shared copy-on-write across requests "
                         "(DESIGN.md 'Paged KV & prefix caching'). "
                         "Greedy tokens are bitwise-identical to the "
                         "unpaged scheduler. Requires --scheduler "
                         "continuous")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="total physical pages in the paged KV pool "
                         "(incl. the zero page and one scratch page per "
                         "slot). Default: full per-slot backing — "
                         "1 + slots + slots * (max_len / block_kv); "
                         "smaller values bank on prefix sharing and "
                         "fail loudly (PagePoolExhausted) when the bet "
                         "doesn't pay")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    metavar="BLOCKS",
                    help="chunked admission prefill: a request that "
                         "misses the full-prompt snapshot owns its slot "
                         "in PREFILLING state and advances BLOCKS SLA "
                         "blocks of prompt per tick while other slots "
                         "keep decoding — bounding the decode stall a "
                         "long prompt inflicts to one chunk's dispatch. "
                         "Tokens and cache contents stay bitwise equal "
                         "to blocking admission (DESIGN.md 'Chunked "
                         "admission prefill'). Requires --paged and "
                         "--scheduler continuous; lifts "
                         "sla.col_capacity_factor to None (printed) — "
                         "chunk classification is row-decomposable "
                         "only uncapped")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: a prefill worker pool "
                         "runs admission, a decode worker pool runs "
                         "token generation, with explicit handoff "
                         "bundles (prefill cache + decode-SLA state) "
                         "routed to the least-loaded decode worker and "
                         "fault-tolerant requeue of a lost worker's "
                         "in-flight requests (DESIGN.md 'Disaggregated "
                         "serving'). Greedy tokens are bitwise equal to "
                         "the single-Scheduler run. --batch sets slots "
                         "PER decode worker; incompatible with --stream "
                         "and --plan-reuse adaptive")
    ap.add_argument("--prefill-workers", type=int, default=1,
                    help="prefill pool size for --disagg")
    ap.add_argument("--decode-workers", type=int, default=2,
                    help="decode pool size for --disagg")
    ap.add_argument("--workload", default="lm", choices=["lm", "dit"],
                    help="'lm' serves autoregressive token generation "
                         "(all flags above); 'dit' serves streaming "
                         "diffusion denoising: many users' denoise "
                         "requests continuously batched into one "
                         "dit.forward per tick, each slot at its own "
                         "timestep, with validated cross-request SLA "
                         "plan caching (DESIGN.md 'Streaming DiT "
                         "service'). Requires a dit-family --arch")
    ap.add_argument("--num-steps", type=int, default=8,
                    help="dit: Euler denoise steps per request")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="dit: latent tokens per request (block-"
                         "aligned). Default: 2 SLA query blocks")
    ap.add_argument("--t-start", type=float, default=1.0,
                    help="dit: trajectory start time in (0, 1]; < 1.0 "
                         "is SDEdit-style partial denoise")
    ap.add_argument("--refresh-mode", default=None,
                    choices=["fixed", "adaptive"],
                    help="dit: per-slot plan refresh policy — 'fixed' "
                         "re-plans every cfg.sla.plan_refresh_interval "
                         "steps, 'adaptive' re-plans a slot's layer "
                         "when its measured drift reaches "
                         "--drift-threshold. Default: "
                         "cfg.sla.plan_refresh_mode")
    ap.add_argument("--plan-cache", action="store_true",
                    help="dit: cross-request plan cache — admissions "
                         "look up per-(layer, timestep-bucket) SLAPlans "
                         "and validate them through the drift machinery "
                         "instead of planning from scratch "
                         "(serving/plan_cache.py)")
    ap.add_argument("--t-buckets", type=int, default=8,
                    help="dit: timestep buckets for --plan-cache keys")
    ap.add_argument("--cache-entries", type=int, default=256,
                    help="dit: LRU bound on --plan-cache entries "
                         "(per-layer, per-bucket)")
    ap.add_argument("--stats-json", default=None, metavar="PATH",
                    help="after the run, dump ServeStats + per-request "
                         "metrics as JSON to PATH (every serving mode). "
                         "Derived metrics of in-flight requests are "
                         "null, never 0.0")
    ap.add_argument("--routing-mode", default=None,
                    choices=["threshold", "learned"],
                    help="block-classification router: 'threshold' ranks "
                         "blocks by the paper's pooled P_c rule (Eq. 2-3); "
                         "'learned' ranks them with the trainable "
                         "SLA2-style per-head scorer (DESIGN.md 'Learned "
                         "routing'). Identity-initialized learned routing "
                         "reproduces threshold exactly, so fresh params "
                         "serve identically under either mode. Default: "
                         "cfg.sla.routing_mode")
    args = ap.parse_args(argv)
    if args.drift_threshold is not None:
        parts = [float(x) for x in str(args.drift_threshold).split(",")]
        args.drift_threshold = parts[0] if len(parts) == 1 else tuple(parts)
    if args.stream and args.scheduler != "continuous":
        ap.error("--stream requires --scheduler continuous")
    if args.paged and args.scheduler != "continuous" and not args.disagg:
        ap.error("--paged requires --scheduler continuous or --disagg")
    if args.prefill_chunk is not None and not args.paged \
            and not args.disagg:
        # in-process chunked admission lands through the page-table
        # scatter; the disaggregated prefill POOL chunks carry-side,
        # with no pages involved, so --disagg lifts the requirement
        ap.error("--prefill-chunk requires --paged (chunks land "
                 "through the page-table scatter) or --disagg")
    if args.disagg and args.stream:
        ap.error("--disagg prints pool stats, not a token stream; "
                 "drop --stream")
    if args.disagg and args.plan_reuse != "off":
        ap.error("--disagg requires --plan-reuse off: requeue replays "
                 "a lost worker's prefill, which must be a pure "
                 "function of the prompt")
    if args.workload == "dit" and (
            args.disagg or args.stream or args.paged
            or args.decode_sla or args.prefill_chunk is not None):
        ap.error("--workload dit serves denoise requests — "
                 "--disagg/--stream/--paged/--decode-sla/"
                 "--prefill-chunk are LM-serving flags")

    from repro.core import backends as backend_registry
    backend_registry.resolve(args.backend)  # unknown names fail here, loudly

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.routing_mode is not None:
        # before init: learned mode adds the routing head to the params
        cfg = dataclasses.replace(
            cfg, sla=cfg.sla.replace(routing_mode=args.routing_mode))
    if (args.prefill_chunk is not None
            and cfg.sla.col_capacity_factor is not None):
        # chunk plan rows are sliced from the full classification; the
        # column-capacity demotion pass couples rows, so chunked
        # admission requires the uncapped per-row regime. Lifting the
        # cap keeps strictly MORE critical columns — still a valid SLA
        # plan, applied to blocking admission identically.
        print("--prefill-chunk: lifting sla.col_capacity_factor "
              f"({cfg.sla.col_capacity_factor} -> None); chunked "
              "classification is row-decomposable only uncapped")
        cfg = dataclasses.replace(
            cfg, sla=cfg.sla.replace(col_capacity_factor=None))
    cfg.sla.validate()
    mdl = registry.get_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    if args.workload == "dit":
        # DiT serving stores its weights in bf16 (dit.forward casts each
        # one at use): at wan2_1_1_3b's width f32 weights plus a
        # 32k-token tick do not fit one 16 GB chip
        params = jax.jit(lambda k: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), mdl.init(k, cfg)))(key)
    else:
        params = mdl.init(key, cfg)
    rs = np.random.default_rng(args.seed)
    max_len = args.prompt_len + args.max_new + 8

    if args.workload == "dit":
        return _run_dit(args, cfg, params, rs)
    if cfg.family == "dit":
        ap.error(f"--arch {args.arch} is a DiT; serve it with "
                 "--workload dit")

    if args.disagg:
        from repro.serving.api import SamplingParams
        from repro.serving.disagg import DisaggScheduler

        ds = DisaggScheduler(cfg, params,
                             prefill_workers=args.prefill_workers,
                             decode_workers=args.decode_workers,
                             slots_per_worker=args.batch,
                             max_len=max_len, backend=args.backend,
                             decode_sla=args.decode_sla or None,
                             paged=args.paged or None,
                             pool_pages=args.pool_pages,
                             prefill_chunk_blocks=args.prefill_chunk)
        t0 = time.time()
        for i in range(args.requests):
            ds.submit(
                rs.integers(0, cfg.vocab_size,
                            size=args.prompt_len).astype(np.int32),
                SamplingParams(max_new_tokens=args.max_new))
        done = ds.drain()
        wall = time.time() - t0
        st = ds.stats
        print(f"{st.completed}/{st.submitted} requests in {wall:.1f}s "
              f"over {st.ticks} ticks | prefill pool "
              f"{args.prefill_workers}w occ "
              f"{st.prefill_occupancy():.2f} "
              f"({st.prefill_tokens} tok, {st.prefill_chunks} chunks) "
              f"| decode pool {args.decode_workers}w occ "
              f"{ds.decode_occupancy():.2f}")
        print(f"faults: {st.kills} kills, {st.requeues} requeues, "
              f"{st.straggler_drains} straggler drains, "
              f"{st.retries} retries | {st.handoffs} handoffs")
        for row in ds.pool_stats()["decode"]:
            print(f"  {row['worker']}: admitted {row['admitted']}, "
                  f"occupancy {row['occupancy']:.2f}, "
                  f"{row['decode_tokens']} decode tokens"
                  + (" [draining]" if row["draining"] else "")
                  + ("" if row["alive"] else " [dead]"))
        metrics = [r.metrics for r in done]
        from repro.serving.api import percentile as pct
        ttfts = [m.ttft_s for m in metrics if m.ttft_s is not None]
        if ttfts:
            print(f"per-request: TTFT p50 {pct(ttfts, 0.5)*1e3:.0f}ms "
                  f"/ p95 {pct(ttfts, 0.95)*1e3:.0f}ms")
        _maybe_stats_json(args, "disagg", st, done)
        return done

    if args.scheduler == "continuous" and args.stream:
        # drive the v2 API directly so events stream as they happen
        from repro.serving.api import SamplingParams, Scheduler

        sched = Scheduler(cfg, params, num_slots=args.batch,
                          max_len=max_len, backend=args.backend,
                          decode_sla=args.decode_sla or None,
                          plan_reuse=args.plan_reuse,
                          drift_threshold=args.drift_threshold,
                          paged=args.paged or None,
                          pool_pages=args.pool_pages,
                          prefill_chunk_blocks=args.prefill_chunk)
        t0 = time.time()
        for i in range(args.requests):
            sched.submit(
                rs.integers(0, cfg.vocab_size,
                            size=args.prompt_len).astype(np.int32),
                SamplingParams(max_new_tokens=args.max_new))
        for ev in sched.stream():
            if ev.kind == "token":
                print(f"  [{ev.t - t0:7.3f}s] req {ev.rid} "
                      f"token[{ev.index}] = {ev.token}")
            else:
                print(f"  [{ev.t - t0:7.3f}s] req {ev.rid} {ev.kind}")
        done = sched.drain()
        st = sched.stats
        _print_stats(args, st, len(done), time.time() - t0,
                     [r.metrics for r in done], sched.drift_threshold)
        _maybe_stats_json(args, "continuous", st, done)
        return done

    reqs = [Request(rid=i,
                    prompt=rs.integers(0, cfg.vocab_size,
                                       size=args.prompt_len)
                    .astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    engine = ServingEngine(cfg, params, batch_size=args.batch,
                           max_len=max_len,
                           backend=args.backend,
                           plan_reuse=args.plan_reuse,
                           drift_threshold=args.drift_threshold,
                           decode_sla=args.decode_sla,
                           scheduler=args.scheduler,
                           paged=args.paged or None,
                           pool_pages=args.pool_pages,
                           prefill_chunk_blocks=args.prefill_chunk)
    t0 = time.time()
    done = engine.run(reqs)
    _print_stats(args, engine.stats, len(done), time.time() - t0,
                 [r.metrics for r in done if r.metrics is not None],
                 engine.drift_threshold)
    _maybe_stats_json(args, args.scheduler, engine.stats, done)
    return done


def _run_dit(args, cfg, params, rs):
    """The dit workload: synthetic denoise requests through the
    DiffusionScheduler, mixed timesteps sharing every batched tick."""
    from repro.serving.api import percentile as pct
    from repro.serving.diffusion import DenoiseParams, DiffusionScheduler

    seq_len = (2 * cfg.sla.block_q if args.seq_len is None
               else args.seq_len)
    sched = DiffusionScheduler(
        cfg, params, num_slots=args.batch, seq_len=seq_len,
        backend=args.backend, refresh_mode=args.refresh_mode,
        drift_threshold=args.drift_threshold,
        plan_cache=args.plan_cache, t_buckets=args.t_buckets,
        cache_entries=args.cache_entries)
    t0 = time.time()
    for i in range(args.requests):
        sched.submit(
            rs.standard_normal((seq_len, cfg.patch_dim),
                               dtype=np.float32),
            DenoiseParams(num_steps=args.num_steps,
                          t_start=args.t_start))
    done = sched.drain()
    wall = time.time() - t0
    st = sched.stats
    print(f"{len(done)} denoise requests ({args.num_steps} steps, "
          f"{seq_len} latent tokens) in {wall:.1f}s | "
          f"{st.denoise_steps} denoise steps | slot occupancy "
          f"{st.occupancy():.2f} ({st.slot_steps_active}/"
          f"{st.slot_steps_total} slot-steps)")
    print(f"plans: {st.plan_builds} built, {st.plan_reuses} reused, "
          f"{st.plan_replans} re-plans | retention "
          f"{st.last_retention:.3f}")
    if sched.cache is not None:
        print(f"plan cache: {st.plan_cache_hits} hits / "
              f"{st.plan_cache_misses} misses, "
              f"{st.plan_cache_invalidations} drift invalidations, "
              f"{st.plan_cache_evictions} evictions "
              f"({len(sched.cache)} entries)")
    lats = [r.metrics.latency_s for r in done
            if r.metrics.latency_s is not None]
    if lats:
        print(f"per-request: latency p50 {pct(lats, 0.5)*1e3:.0f}ms / "
              f"p95 {pct(lats, 0.95)*1e3:.0f}ms")
    _maybe_stats_json(args, "dit", st, done)
    return done


def _maybe_stats_json(args, mode, st, requests):
    """--stats-json: one schema for every serving mode (satellite:
    None-safe — in-flight requests dump null derived metrics)."""
    if not args.stats_json:
        return
    from repro.serving.api import stats_json_payload

    payload = stats_json_payload(mode, st, requests)
    with open(args.stats_json, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"stats json -> {args.stats_json}")


def _print_stats(args, st, n_done, wall, metrics, drift_threshold):
    print(f"{n_done} requests in {wall:.1f}s | "
          f"prefill {st.prefill_tokens} tok / {st.prefill_s:.2f}s | "
          f"decode {st.decode_tokens} tok / {st.decode_s:.2f}s")
    if metrics:
        from repro.serving.api import percentile as pct

        # unfinished / never-prefilled requests report None, not 0.0
        ttfts = [m.ttft_s for m in metrics if m.ttft_s is not None]
        lats = [m.latency_s for m in metrics if m.latency_s is not None]
        if ttfts and lats:
            print(f"per-request: TTFT p50 {pct(ttfts, 0.5)*1e3:.0f}ms / "
                  f"p95 {pct(ttfts, 0.95)*1e3:.0f}ms | latency p50 "
                  f"{pct(lats, 0.5)*1e3:.0f}ms / p95 "
                  f"{pct(lats, 0.95)*1e3:.0f}ms")
    if st.slot_steps_total:
        print(f"scheduler: {st.admissions} admissions | decode-slot "
              f"occupancy {st.occupancy():.2f} "
              f"({st.slot_steps_active}/{st.slot_steps_total} slot-steps)")
    if getattr(args, "paged", False):
        print(f"paged KV: {st.pages_in_use} pages in use "
              f"(peak {st.pages_peak}) | {st.page_allocs} allocs, "
              f"{st.cow_copies} CoW copies | prefix cache "
              f"{st.prefix_hits} page hits / {st.prefix_misses} misses, "
              f"{st.prefix_full_hits} full-prompt hits")
    if getattr(args, "prefill_chunk", None):
        print(f"chunked admission: {st.chunked_admissions} requests in "
              f"{st.prefill_chunks} chunks | max inter-token gap "
              f"{st.max_decode_gap_s * 1e3:.0f}ms")
    if args.plan_reuse != "off":
        print(f"plan reuse: {st.plan_builds} built, {st.plan_reuses} "
              f"reused, {st.plan_replans} drift re-plans | retention "
              f"{st.last_retention:.3f} (threshold: drift >= "
              f"{drift_threshold})")
    if args.decode_sla:
        print(f"decode plans: {st.decode_plan_builds} layer plans built "
              f"at prefill, {st.decode_plan_extends} rows extended, "
              f"{st.decode_plan_reuses} live rows reused, "
              f"{st.decode_plan_replans} drift re-plans | retention "
              f"{st.decode_last_retention:.3f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    main()
