"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,table1]
"""
import argparse
import json
import sys
import traceback

MODULES = [
    "fig1_weight_distribution",
    "fig3_stable_rank",
    "table1_quality_efficiency",
    "table2_ablation",
    "table3_image",
    "fig6_kernel_speed",
    "fig_decode",
    "fig_routing",
    "fig_serving",
    "fig_dit_serving",
]


def decode_headlines() -> list:
    """Headline rows from BENCH_decode.json (written by fig_decode):
    the decode speedups at the largest measured context plus the
    acceptance booleans, so `-m benchmarks.run` surfaces the decode
    story without re-reading the raw cells."""
    from benchmarks.fig_decode import BENCH_PATH

    if not BENCH_PATH.exists():
        return []
    bench = json.loads(BENCH_PATH.read_text())
    rows = []
    n = str(max(int(k) for k in bench["cells"]))
    c = bench["cells"][n]
    rows.append((f"decode.headline.sla_vs_dense.n{n}", 0.0,
                 f"x{c['dense']['per_token_us'] / c['sla_gather']['per_token_us']:.1f}"))
    mn = str(max(int(k) for k in bench["model_cells"]))
    m = bench["model_cells"][mn]
    rows.append((f"decode.headline.chunk_vs_step.n{mn}", 0.0,
                 f"x{m['step_gather']['per_token_us'] / m['chunk_kernel']['per_token_us']:.1f}"))
    for key, ok in bench.get("acceptance", {}).items():
        rows.append((f"decode.accept.{key}", 0.0,
                     "PASS" if ok else "FAIL"))
    return rows


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substrings")
    args = ap.parse_args()
    use_compile_cache()
    selected = MODULES
    if args.only:
        keys = args.only.split(",")
        selected = [m for m in MODULES if any(k in m for k in keys)]

    print("name,us_per_call,derived")
    failed = 0
    for name in selected:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row in mod.run():
                print(",".join(str(x) for x in row), flush=True)
        except Exception:
            failed += 1
            print(f"{name},ERROR,0", flush=True)
            traceback.print_exc(file=sys.stderr)
    try:
        for row in decode_headlines():
            print(",".join(str(x) for x in row), flush=True)
    except Exception:
        traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"{failed} benchmark module(s) failed")


if __name__ == "__main__":
    main()
