"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 20 --ckpt-dir /tmp/ckpt

Wires together: config -> mesh -> sharded params/opt -> deterministic data
pipeline -> jitted train_step (remat + SP context) -> atomic async
checkpoints with auto-resume -> straggler watchdog + NaN guard ->
optional error-feedback gradient compression on the (pod-)DP axis.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_arch, get_shape
from repro.data.pipeline import DataConfig, make_iterator
from repro.distributed import ctx as actx
from repro.distributed.fault_tolerance import NaNGuard, StragglerWatchdog
from repro.distributed.sharding import batch_shardings, param_shardings
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.optim import adamw
from repro.optim.compression import ef_compress_decompress, ef_init


ROUTING_WARM_EPS = 1e-3


def routing_warm_init(params):
    """Replace the zero-initialized per-head Proj merge (`sla_proj`)
    with an epsilon-scaled identity (`ROUTING_WARM_EPS * I`).

    Opt-in escape hatch for the learned-routing dead point (see
    `check_routing_dead_point`): a tiny but nonzero Proj lets the
    straight-through routing gradients through from step 0 while
    perturbing the model's output by only O(eps * ||o_l||)."""
    layers = dict(params["layers"])
    proj = layers["sla_proj"]
    eye = jnp.eye(proj.shape[-1], dtype=proj.dtype)
    layers["sla_proj"] = jnp.broadcast_to(eye, proj.shape) * ROUTING_WARM_EPS
    return dict(params, layers=layers)


def check_routing_dead_point(params, mask):
    """Warn loudly when a fine-tune is pinned at the learned-routing
    dead point: the routing head is trainable but every `sla_proj` is
    exactly zero. Routing parameters only receive gradients through the
    straight-through marginal gates of the LINEAR branch, and that
    branch's output is multiplied by `sla_proj` (Eq. 6) — so all-zero
    Proj multiplies every routing gradient by exact zero and
    `--train-only routing` silently flatlines. Returns True iff the
    warning fired (tests assert both paths)."""
    import warnings

    flat_m = jax.tree_util.tree_leaves_with_path(mask)
    trains_routing = any("routing" in jax.tree_util.keystr(path) and t
                         for path, t in flat_m)
    proj = params.get("layers", {}).get("sla_proj")
    if not trains_routing or proj is None:
        return False
    if bool(jnp.any(proj != 0)):
        return False
    warnings.warn(
        "learned-routing dead point: --train-only includes the routing "
        "head, but every sla_proj is exactly zero (the paper's init). "
        "Routing gradients flow only through the linear branch, whose "
        "output is multiplied by sla_proj — they are therefore all "
        "exactly zero and routing will never move. Pass "
        "--routing-warm-init to seed sla_proj with an epsilon identity, "
        "or include 'sla_proj' in --train-only and train the merge off "
        "zero first.")
    return True


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + shape (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--distill", action="store_true",
                    help="fine-tune against the model family's end-to-end "
                         "distillation loss (exact-attention teacher, SLA "
                         "student; paper Sec. 5) instead of the training "
                         "loss")
    ap.add_argument("--routing-mode", default=None,
                    choices=["threshold", "learned"],
                    help="override SLAConfig.routing_mode: 'learned' adds "
                         "the trainable SLA2-style routing head "
                         "(identity-initialized to reproduce 'threshold' "
                         "exactly; DESIGN.md 'Learned routing')")
    ap.add_argument("--train-only", default=None,
                    help="comma-separated parameter-name substrings to "
                         "train (e.g. 'routing,sla_proj'); everything "
                         "else is frozen — the fixed-FLOP-budget "
                         "fine-tuning recipe")
    ap.add_argument("--routing-warm-init", action="store_true",
                    help="seed every layer's sla_proj with a small "
                         "epsilon-scaled identity (1e-3) instead of the "
                         "paper's zero init. Breaks the learned-routing "
                         "dead point: routing gradients flow only "
                         "through the straight-through marginal gates "
                         "into the LINEAR branch, whose output is "
                         "multiplied by sla_proj — all-zero sla_proj "
                         "therefore multiplies every routing gradient "
                         "by exact zero, and '--train-only routing' "
                         "cannot move (a fresh checkpoint warns loudly "
                         "instead of silently flatlining)")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.routing_mode is not None:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, sla=cfg.sla.replace(routing_mode=args.routing_mode))
    shape = get_shape(args.shape, smoke=args.smoke)
    mdl = registry.get_model(cfg)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(args.steps // 10, 1))
    mesh = make_host_mesh(args.data_mesh, args.model_mesh)

    rng = jax.random.PRNGKey(args.seed)

    def init_state(rng):
        params = mdl.init(rng, cfg)
        if args.routing_warm_init:
            params = routing_warm_init(params)
        return params, adamw.init(params)

    # built straight into the mesh shardings: at published widths the
    # f32 params + AdamW moments never exist whole on one device
    from jax.sharding import NamedSharding, PartitionSpec as P
    p_shard = param_shardings(mesh, jax.eval_shape(init_state, rng)[0])
    o_shard = {"m": p_shard, "v": p_shard,
               "step": NamedSharding(mesh, P())}
    params, opt_state = jax.jit(
        init_state, out_shardings=(p_shard, o_shard))(rng)

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if mgr is not None and mgr.latest_step() is not None:
        start_step = mgr.latest_step()
        state = mgr.restore(start_step,
                            {"params": params, "opt": opt_state},
                            {"params": p_shard, "opt": o_shard})
        params, opt_state = state["params"], state["opt"]
        print(f"resumed from step {start_step}")

    data = make_iterator(cfg, shape, DataConfig(seed=args.seed),
                         start_step=start_step)
    ef_error = ef_init(params) if args.compress_grads else None

    loss_impl = mdl.loss_fn
    if args.distill:
        loss_impl = getattr(mdl, "distill_loss_fn", None)
        if loss_impl is None:
            raise ValueError(
                f"--distill: model family {cfg.family!r} has no "
                "distill_loss_fn")
    mask = None
    if args.train_only:
        mask = adamw.trainable_mask(
            params, tuple(s for s in args.train_only.split(",") if s))
        n_train = sum(p.size for p, t in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(mask)) if t)
        if n_train == 0:
            raise ValueError(
                f"--train-only {args.train_only!r} matches no parameters")
        print(f"training {n_train} of "
              f"{sum(p.size for p in jax.tree_util.tree_leaves(params))} "
              f"params ({args.train_only})")
        check_routing_dead_point(params, mask)

    def loss_of(p, batch):
        return loss_impl(p, cfg, batch)

    # gradients leave sharded like the params (reduce-scatter, not a
    # replicated all-reduce): whole f32 gradients do not fit one chip
    @functools.partial(jax.jit,
                       out_shardings=(NamedSharding(mesh, P()), p_shard))
    def grad_step(p, batch):
        return jax.value_and_grad(loss_of)(p, batch)

    # params and optimizer state are donated: the update writes in place
    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def apply_update(p, g, o):
        return adamw.update(p, g, o, opt_cfg, trainable=mask)

    if args.compress_grads:
        @jax.jit
        def compress(g, e):
            return ef_compress_decompress(g, e)

    watchdog = StragglerWatchdog()
    guard = NaNGuard()
    rspec = actx.default_residual_spec(mesh, shape.global_batch,
                                       shape.seq_len)
    losses = []
    with mesh, actx.activation_sharding(mesh, rspec, remat=True):
        for step in range(start_step, args.steps):
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            loss, grads = grad_step(params, batch)
            if not guard.check(loss):
                print(f"step {step}: non-finite loss, update skipped")
                continue
            if args.compress_grads:
                grads, ef_error, cstats = compress(grads, ef_error)
            params, opt_state, metrics = apply_update(params, grads,
                                                      opt_state)
            jax.block_until_ready(loss)
            dt = time.time() - t0
            slow = watchdog.record(dt)
            losses.append(float(loss))
            if step % args.log_every == 0 or step == args.steps - 1:
                extra = " STRAGGLER" if slow else ""
                print(f"step {step:5d} loss {float(loss):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt:.2f}s{extra}",
                      flush=True)
            if mgr is not None and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, {"params": params, "opt": opt_state})
        if mgr is not None:
            mgr.save(args.steps, {"params": params, "opt": opt_state},
                     blocking=True)
    if watchdog.flagged:
        print(f"stragglers flagged: {len(watchdog.flagged)}")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    main()
