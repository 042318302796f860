import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell
# against the production mesh with ShapeDtypeStruct inputs (zero
# allocation), and record memory / cost / collective statistics for the
# roofline analysis (EXPERIMENTS.md §Dry-run, §Roofline).
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
#       --mesh both --out artifacts/dryrun
import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ASSIGNED_ARCHS, PAPER_ARCHS, get_arch,
                           get_shape)  # noqa: E402
from repro.configs.base import DIT_SHAPES, SHAPES  # noqa: E402
from repro.distributed.sharding import (batch_shardings, cache_shardings,
                                        param_shardings)  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (abstract_state, make_prefill_step,
                                make_serve_step,
                                make_train_step)  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.roofline.analysis import (model_flops,
                                     roofline_terms)  # noqa: E402
from repro.roofline.hlo_cost import (analyze as hlo_analyze,  # noqa: E402
                                     xla_cost_analysis)

# Cells that are skipped by design (DESIGN.md §4 Arch-applicability).
SKIPS = {
    ("whisper-small", "long_500k"):
        "enc-dec: 500K-token decoder cache exceeds the model's structural "
        "audio context (1.5K frames); skipped per DESIGN.md",
}


def build_cell(cfg, shape, mesh, backend: str = "gather"):
    """Returns (fn, args, in_shardings, out_shardings)."""
    params, opt = abstract_state(cfg)
    p_shard = param_shardings(mesh, params)
    if shape.kind == "train":
        batch = registry.train_batch_specs(cfg, shape)
        batch = {k: v for k, v in batch.items() if v is not None}
        b_shard = batch_shardings(mesh, batch, shape.global_batch)
        opt_shard = {"m": p_shard, "v": p_shard,
                     "step": NamedSharding(mesh, P())}
        fn = make_train_step(cfg, AdamWConfig(), backend=backend)
        return (fn, (params, opt, batch),
                (p_shard, opt_shard, b_shard),
                (p_shard, opt_shard, NamedSharding(mesh, P()),
                 NamedSharding(mesh, P())))
    if shape.kind == "prefill":
        batch = registry.prefill_specs(cfg, shape)
        batch = {k: v for k, v in batch.items() if v is not None}
        b_shard = batch_shardings(mesh, batch, shape.global_batch)
        fn = make_prefill_step(cfg, backend=backend)
        return fn, (params, batch), (p_shard, b_shard), None
    # decode — the cache is donated (in-place update; see jit below)
    token, cache = registry.decode_specs(cfg, shape)
    t_shard = batch_shardings(mesh, token, shape.global_batch)
    c_shard = cache_shardings(mesh, cache, shape.global_batch)
    fn = make_serve_step(cfg)
    return (fn, (params, token, cache),
            (p_shard, t_shard, c_shard), (None, c_shard))


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: pathlib.Path, backend: str = "gather") -> dict:
    mesh_name = "multi" if multi_pod else "single"
    tag = f"{arch}__{shape_name}__{mesh_name}"
    out_path = out_dir / f"{tag}.json"
    if (arch, shape_name) in SKIPS:
        rec = {"cell": tag, "status": "skipped",
               "reason": SKIPS[(arch, shape_name)]}
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    cfg = get_arch(arch)
    shape = (DIT_SHAPES[arch] if arch in DIT_SHAPES
             else get_shape(shape_name))
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    try:
        from repro.distributed import ctx as actx
        fn, args, in_sh, out_sh = build_cell(cfg, shape, mesh)
        rspec = actx.default_residual_spec(mesh, shape.global_batch,
                                           shape.seq_len)
        # donation: decode donates its cache (arg 2); train donates params
        # + optimizer state (args 0, 1) — halves state memory via aliasing.
        donate = ((2,) if shape.kind == "decode"
                  else (0, 1) if shape.kind == "train" else ())
        with mesh, actx.activation_sharding(mesh, rspec, remat=True):
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = xla_cost_analysis(compiled)
            hlo_text = compiled.as_text()
        # loop-aware cost model (XLA cost_analysis counts scan bodies
        # ONCE — ~88x undercount on deep stacks; see roofline/hlo_cost.py)
        parsed = hlo_analyze(hlo_text)
        import gzip
        (out_dir / f"{tag}.hlo.txt.gz").write_bytes(
            gzip.compress(hlo_text.encode()))
        flops_dev = float(parsed["flops"])
        bytes_dev = float(parsed["bytes"])
        coll = {k.replace("coll_", ""): v for k, v in parsed.items()
                if k.startswith("coll_")}
        coll["total"] = parsed["collective_bytes"]
        terms = roofline_terms(flops_dev, bytes_dev, coll["total"], 1)
        mflops = model_flops(cfg, shape)
        rec = {
            "cell": tag,
            "status": "ok",
            "arch": arch, "shape": shape_name,
            "mesh": [int(mesh.shape[a]) for a in mesh.axis_names],
            "chips": chips,
            "seconds_lower": round(t_lower, 2),
            "seconds_compile": round(t_compile, 2),
            "memory": {
                "argument_bytes_per_device": mem.argument_size_in_bytes,
                "output_bytes_per_device": mem.output_size_in_bytes,
                "temp_bytes_per_device": mem.temp_size_in_bytes,
                "alias_bytes_per_device": mem.alias_size_in_bytes,
                "peak_estimate_gib": round(
                    (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes
                     - mem.alias_size_in_bytes) / 2**30, 3),
            },
            "cost": {"flops_per_device": flops_dev,
                     "bytes_per_device": bytes_dev,
                     "xla_flops_loopbody_once": float(
                         cost.get("flops", 0.0)),
                     "xla_bytes_loopbody_once": float(
                         cost.get("bytes accessed", 0.0))},
            "collectives": {k: v for k, v in coll.items()},
            "roofline": terms,
            "model_flops_total": mflops,
            "useful_flops_ratio": (
                mflops / (flops_dev * chips) if flops_dev else 0.0),
        }
    except Exception as e:  # a failing cell is a bug in the system
        rec = {"cell": tag, "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--include-paper-archs", action="store_true")
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    use_compile_cache()

    archs = (ASSIGNED_ARCHS if args.arch == "all" else [args.arch])
    if args.include_paper_archs and args.arch == "all":
        archs = archs + PAPER_ARCHS
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = 0
    for arch in archs:
        shapes = (["dit"] if arch in DIT_SHAPES else
                  (list(SHAPES) if args.shape == "all" else [args.shape]))
        for shape_name in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape_name, multi, out_dir)
                status = rec["status"]
                n_ok += status in ("ok", "skipped")
                n_fail += status == "error"
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']} "
                             f"bound={r['bound_s']:.4f}s "
                             f"mem={rec['memory']['peak_estimate_gib']}GiB "
                             f"[lower {rec['seconds_lower']}s, "
                             f"compile {rec['seconds_compile']}s]")
                elif status == "error":
                    extra = " " + rec["error"][:160]
                print(f"{rec['cell']:60s} {status}{extra}", flush=True)
    print(f"\n{n_ok} ok/skipped, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
