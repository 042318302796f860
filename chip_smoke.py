"""Bring-up check: the SLA system's main paths on a TPU, in one process.

    python chip_smoke.py              # one chip: kernel, DiT and LM serving
    python chip_smoke.py --chips 4    # four chips: sharded Wan2.1 fine-tune

One chip (the default) runs three phases at published widths, with the
Pallas kernels compiled by Mosaic (never interpreted):

  kernel  one `core.backends.execute` call at Wan2.1 width (B=1, H=12,
          N=32768, D=128, bf16) on the `kernel` and `gather` backends
          over the same plan; they must agree within the conformance
          bf16 tolerance, and the kernel program must hold a
          `tpu_custom_call`.
  dit     `launch/serve.py --workload dit` on wan2_1_1_3b at 32768 latent
          tokens, backend kernel: every request completes with a finite
          latent.
  lm      `launch/serve.py` on qwen3-1.7b, continuous scheduler, paged KV,
          decode-time SLA, backend kernel (sla_fwd at prefill, the paged
          decode kernel per token): every request returns all its tokens.

`--chips 4` runs only the four-chip phase: `launch/train.py --distill` on
wan2_1_1_3b over a data=4 mesh (FSDP parameter shardings plus activation
sharding) at the reduced `dit_video_4k` shape, and compares its step-0
loss with the same loss computed on one device.

Each phase function also takes a small size (`smoke=True`, shorter
sequences), so a CPU run with interpreted kernels can rehearse the
control flow before a chip call.

Weights are random, made from seed 0. The script exits non-zero, and
prints no result, when JAX finds no TPU or when it is not run from a
checkout of the repository. Its last line of standard output is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
# conformance-matrix bf16 tolerance (tests/test_conformance.py)
BF16_ATOL = BF16_RTOL = 5e-2


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def memory_line(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    gib = lambda k: stats.get(k, 0) / 2**30  # noqa: E731
    return (f"device 0 memory: in use {gib('bytes_in_use'):.2f} GiB, "
            f"peak {gib('peak_bytes_in_use'):.2f} GiB "
            f"of {gib('bytes_limit'):.2f} GiB")


def device_check(jax, chips: int) -> dict:
    from repro.kernels.mode import default_interpret

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    check(d.platform == "tpu", f"JAX found no TPU (platform {d.platform})")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
          f"JAX found {len(devs)}")
    check(not default_interpret(), "Pallas kernels would run interpreted")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def kernel_phase(jax, jnp, *, n: int = 32768, heads: int = 12,
                 head_dim: int = 128) -> None:
    """kernel vs gather on one plan, at Wan2.1's SLAConfig."""
    from repro.configs import get_arch
    from repro.core import backends, plan_attention

    cfg = get_arch("wan2_1_1_3b").sla.replace(causal=False)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(r, (1, heads, n, head_dim), jnp.bfloat16)
               for r in ks)
    # identity Proj, so the output carries the linear branch as well
    params = {"proj": jnp.tile(jnp.eye(head_dim)[None], (heads, 1, 1))}
    plan = jax.jit(lambda q, k: plan_attention(q, k, cfg))(q, k)

    def run(backend):
        return jax.jit(lambda plan, q, k, v: backends.execute(
            plan, params, q, k, v, cfg, backend=backend))

    compiled = run("kernel").lower(plan, q, k, v).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "kernel backend compiled without a tpu_custom_call")
    out_k = compiled(plan, q, k, v).astype(jnp.float32)
    out_g = run("gather")(plan, q, k, v).astype(jnp.float32)
    err = jnp.abs(out_k - out_g)
    max_diff = float(jnp.max(err))
    excess = float(jnp.max(err - (BF16_ATOL + BF16_RTOL * jnp.abs(out_g))))
    finite = bool(jnp.all(jnp.isfinite(out_k)))
    log(f"kernel: B=1 H={heads} N={n} D={head_dim} bf16 block "
        f"{cfg.block_q} | kernel vs gather max |diff| {max_diff} "
        f"(bf16 tolerance atol {BF16_ATOL} + rtol {BF16_RTOL}) | "
        f"tpu_custom_call present")
    check(finite, "kernel output is not finite")
    check(excess <= 0.0, f"kernel differs from gather beyond the bf16 "
          f"tolerance (max |diff| {max_diff})")


def dit_phase(tmp: pathlib.Path, *, seq_len: int = 32768,
              smoke: bool = False) -> None:
    import numpy as np
    from repro.launch import serve

    requests = 3
    argv = ["--workload", "dit", "--arch", "wan2_1_1_3b",
            "--seq-len", str(seq_len), "--backend", "kernel",
            "--requests", str(requests), "--batch", "2",
            "--num-steps", "2", "--stats-json", str(tmp / "dit.json")]
    done = serve.main(argv + (["--smoke"] if smoke else []))
    check(len(done) == requests,
          f"dit: {len(done)} of {requests} requests returned")
    for r in done:
        check(r.result is not None and r.state.value == "finished",
              f"dit: request {r.rid} did not complete ({r.state})")
        check(r.result.shape[0] == seq_len,
              f"dit: request {r.rid} latent shape {r.result.shape}")
        check(bool(np.all(np.isfinite(r.result))),
              f"dit: request {r.rid} final latent is not finite")
    log(f"dit: {requests} requests x 2 denoise steps at {seq_len} tokens "
        f"completed, final latents finite")


def lm_phase(tmp: pathlib.Path, *, prompt_len: int = 2048,
             smoke: bool = False) -> None:
    from repro.launch import serve

    requests, max_new = 4, 16
    argv = ["--arch", "qwen3-1.7b", "--scheduler", "continuous", "--paged",
            "--decode-sla", "--backend", "kernel",
            "--requests", str(requests), "--batch", "2",
            "--prompt-len", str(prompt_len), "--max-new", str(max_new),
            "--stats-json", str(tmp / "lm.json")]
    done = serve.main(argv + (["--smoke"] if smoke else []))
    check(len(done) == requests,
          f"lm: {len(done)} of {requests} requests returned")
    for r in done:
        n_out = len(r.tokens_out or [])
        check(n_out == max_new,
              f"lm: request {r.rid} returned {n_out} of {max_new} tokens")
    log(f"lm: {requests} requests x {prompt_len}-token prompts returned "
        f"{max_new} tokens each")


@contextlib.contextmanager
def nonzero_output_head(jax):
    """Fresh DiTs zero-init `patch_out` and `sla_proj`, which makes the
    distillation target (and every gradient) exactly zero. Stand in for
    fine-tuning from trained weights: give both seeded random values."""
    from repro.models import dit

    init = dit.init

    def seeded_init(rng, cfg, *a, **kw):
        p = init(rng, cfg, *a, **kw)
        r1, r2 = jax.random.split(jax.random.fold_in(rng, 1))
        layers = dict(p["layers"])
        layers["sla_proj"] = 0.3 * jax.random.normal(
            r2, layers["sla_proj"].shape, layers["sla_proj"].dtype)
        return dict(p, layers=layers, patch_out=0.2 * jax.random.normal(
            r1, p["patch_out"].shape, p["patch_out"].dtype))

    dit.init = seeded_init
    try:
        yield
    finally:
        dit.init = init


def train_phase(jax, jnp, chips: int, *, shape: str = "dit_video_4k",
                smoke: bool = False) -> None:
    """Sharded distillation fine-tune; step-0 loss against one device."""
    from jax.sharding import SingleDeviceSharding
    from repro.configs import get_arch, get_shape
    from repro.data.pipeline import DataConfig, make_iterator
    from repro.launch import train
    from repro.models import registry

    cfg = get_arch("wan2_1_1_3b")
    cfg = cfg.smoke() if smoke else cfg
    shp = get_shape(shape, smoke=smoke)
    log(f"train: {cfg.name} width, shape cut to {shp.name}: global batch "
        f"{shp.global_batch} x {shp.seq_len} tokens, {chips}-way data mesh")
    with nonzero_output_head(jax):
        losses = train.main(["--arch", "wan2_1_1_3b", "--shape", shape,
                             "--data-mesh", str(chips), "--distill",
                             "--steps", "3", "--log-every", "1"]
                            + (["--smoke"] if smoke else []))
        gc.collect()
        mdl = registry.get_model(cfg)
        one = SingleDeviceSharding(jax.devices()[0])
        params = jax.jit(lambda k: mdl.init(k, cfg), out_shardings=one)(
            jax.random.PRNGKey(0))
    batch = next(make_iterator(cfg, shp, DataConfig(seed=0)))
    loss = jax.jit(lambda p, b: mdl.distill_loss_fn(p, cfg, b))
    # row-independent forward, equal-size rows: the batch loss is the
    # mean of per-sample losses, one sample at a time on one device
    per_sample = [float(loss(params, {k: jnp.asarray(v[i:i + 1])
                                      for k, v in batch.items()}))
                  for i in range(shp.global_batch)]
    ref = sum(per_sample) / len(per_sample)
    diff = abs(losses[0] - ref)
    log(f"train: {chips}-chip step-0 loss {losses[0]} vs one-device "
        f"{ref} | |diff| {diff} | losses {losses}")
    check(ref > 0.0, "one-device distillation loss is zero")
    # relative only: the loss has no natural scale for an absolute term
    check(diff <= BF16_RTOL * ref,
          f"sharded step-0 loss {losses[0]} != one-device {ref}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip sharded fine-tune")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: FAIL: {ROOT} is not a checkout of the repo "
              "(src/repro missing)", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache

    t_start = time.time()
    try:
        device = device_check(jax, args.chips)
        use_compile_cache()
        if args.chips == 4:
            phases = [("train", lambda tmp: train_phase(jax, jnp, 4))]
        else:
            phases = [("kernel", lambda tmp: kernel_phase(jax, jnp)),
                      ("dit", dit_phase), ("lm", lm_phase)]
        with tempfile.TemporaryDirectory() as tmp:
            for name, phase in phases:
                t0 = time.time()
                phase(pathlib.Path(tmp))
                gc.collect()
                log(f"phase {name}: {time.time() - t0:.1f} s wall | "
                    f"{memory_line(jax)}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"total: {time.time() - t_start:.1f} s wall")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
