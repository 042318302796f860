"""One run of one cell: device check, set-up, the measured window, the
per-layer readings of a traced run, then the correctness check against
the reference, and the result line.

Standard output ends with one JSON object; standard error ends with the
numbers compared against their limits. A run that finds no TPU (or fewer
chips than the cell asks for) exits with code 2 and prints no result.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import time

from bench import BENCH, ROOT, spec

TRACE_DIR = BENCH / ".trace"


class NoDevice(Exception):
    pass


def device_check(chips: int) -> dict:
    import jax
    from repro.kernels.mode import default_interpret

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {d.platform})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    if default_interpret():
        raise NoDevice("Pallas kernels would run interpreted")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def use_compile_cache():
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_cache

    program_cache()  # JAX_COMPILATION_CACHE_DIR if set, else <repo>/.jax_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def make_runner(cell, seed: int):
    family = cell.config["family"]
    if family == "dit":
        from bench.dit_runner import DiTRunner
        return DiTRunner(cell, seed)
    if family == "lm":
        from bench.lm_runner import LMRunner
        return LMRunner(cell, seed)
    raise ValueError(f"no runner for family {family!r}")


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


class WindowCompiles:
    """Counts the programs compiled or loaded from the compile cache while
    the window is open: set-up warms every shape, so it stays 0."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.open, self.count, self.seconds = False, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kwargs):
        if self.open and event == self.EVENT:
            self.count += 1
            self.seconds += duration


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        device: dict, after_setup=None) -> dict:
    """Everything after the device check. Returns the result object."""
    import jax

    runner = make_runner(cell, seed)
    # a traced run measures a shorter window of its own (the trace of a
    # whole window is too large to read back within the run's time)
    win = min(seconds, cell.settings["trace_seconds"]) if trace else seconds
    runner.setup(win)
    if after_setup is not None:
        after_setup(runner)
    setup_s = time.perf_counter() - t_start
    tdir = TRACE_DIR / cell.name
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    annotate = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())
    compiles = WindowCompiles()
    compiles.open = True
    with annotate("bench.window"):
        runner.run_window(win, annotate)
    compiles.open = False
    print(f"window compiles {compiles.count} ({compiles.seconds!r} s)",
          file=sys.stderr)
    if trace:
        jax.profiler.stop_trace()
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": dict(device)}
    result["attempted"], result["failed"] = runner.attempted()
    result["device"]["memory_peak_bytes"] = memory_peak_bytes()
    if trace:
        from bench import trace_reduce
        from bench.layer_context import LayerContext

        red = trace_reduce.reduce_dir(tdir)
        for name, secs, calls in red.module_summary()[:15]:
            print(f"trace module {name} {secs!r} s {calls} calls",
                  file=sys.stderr)
        ctx = LayerContext(cell, runner, red, device["kind"])
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        values = runner.end_to_end()
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    t_check = time.perf_counter()
    runner.release()
    got = runner.check()
    print(f"timing setup_s {setup_s!r} window_s {win!r} check_s "
          f"{time.perf_counter() - t_check!r}", file=sys.stderr)
    limits = cell.settings["limits"]
    compared = {k: {"value": got[k], "limit": lim} for k, lim in
                limits.items()}
    result["window_compiles"] = compiles.count
    result["correct"] = bool(result["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in compared.values()))
    result["readings"] = got
    result["compared"] = compared  # last: the numbers against their limits
    return result


def main(args, t_start: float) -> int:
    cell = spec.load(args.workload)
    try:
        device = device_check(cell.chips)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    use_compile_cache()
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start,
                 device)
    print(f"correct {result['correct']}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
