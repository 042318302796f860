"""Readings that the correctness limits are set from: for each seed, the
numbers the program's run compares and the same numbers for the control,
the reference computed from float8 inputs (the next precision below the
bfloat16 inputs of the program's products) in the program's place, on
the same answers. Not part of a benchmark run; run on the chip when a
limit is set or checked:

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 30
    python3 bench/control.py --workload <name> --seeds 4 --fault unchanged

With --fault, the program's step is broken underneath the run (see
bench/faults.py) and the control is not read. One process reads every
seed, so set-up compiles once. Prints one JSON line per seed and a
summary line last.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--program-only", action="store_true")
    ap.add_argument("--fault", choices=("unchanged", "altered"))
    args = ap.parse_args(argv)
    from bench import faults, harness, spec

    cell = spec.load(args.workload)
    try:
        device = harness.device_check(cell.chips)
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    plant = (faults.FAULTS[cell.config["family"]][args.fault]
             if args.fault else None)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        kept = {}

        def after_setup(runner):
            kept.update(runner=runner)
            if plant is not None:
                plant(runner)

        res = harness.run(cell, seed, args.seconds, False,
                          time.perf_counter(), device,
                          after_setup=after_setup)
        row = {"seed": seed, "fault": args.fault,
               "correct": res["correct"], "program": res["readings"],
               "metrics": {k: m["value"] for k, m in res["metrics"].items()},
               "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        if not (args.program_only or args.fault):
            t = time.perf_counter()
            row["control"] = kept["runner"].check(control=True)
            row["control_s"] = time.perf_counter() - t
        kept.clear()
        gc.collect()
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "fault": args.fault}
    for side in ("program", "control"):
        vals = [r[side] for r in rows if side in r]
        if vals:
            summary[side] = {k: {"max": max(v[k] for v in vals),
                                 "min": min(v[k] for v in vals)}
                             for k in vals[0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
