"""Per-layer metric readers on a made-up trace reduction: each reads a
share or a time from what the window recorded, and returns nothing (not
0) when there is nothing to read."""
import pytest

from bench import harness, spec
from bench.layer_context import LayerContext
from bench.trace_reduce import Op, Reduction
from bench.tests import small

MS = 1_000_000  # ns


class FakeRunner:
    ticks = 10
    admitted_prompt_tokens = 4000

    def __init__(self, counters):
        self.counters = counters


def _reduction(names):
    """1 s window; ops of 10 ms every 20 ms, cycling through `names`
    (op name, module name)."""
    ops, mods = [], []
    for i in range(50):
        name, module = names[i % len(names)]
        ops.append(Op(name, module, i * 20 * MS, 10 * MS, 10 * MS))
        mods.append(Op(module, module, i * 20 * MS, 10 * MS))
    return Reduction(window=(0, 1000 * MS), ops=ops, modules=mods,
                     spans=[("bench.window", 0, 1000 * MS)],
                     busy_ns_per_device=[500 * MS])


def _ctx(cell, counters, names):
    return LayerContext(cell, FakeRunner(counters), _reduction(names),
                        "TPU v5 lite")


def _read(name, ctx):
    return harness.load_reader(name)(ctx)


def test_dit_readers():
    cell = small.dit_cell()
    fwd = harness.load_reader("dit.sla_fwd_roofline").__globals__["KERNEL"]
    adm = harness.load_reader("dit.admit_share").__globals__["MODULE"]
    ctx = _ctx(cell, {"denoise_steps": 20, "admissions": 2},
               [(f"{fwd}.9", "jit_tick"), ("fusion", f"jit_{adm}")])
    assert _read("dit.idle_share", ctx) == pytest.approx(50.0)
    assert _read("dit.admit_share", ctx) == pytest.approx(50.0)
    assert 0 < _read("dit.mfu", ctx) < 100
    assert 0 < _read("dit.sla_fwd_roofline", ctx) < 100
    empty = _ctx(cell, {"denoise_steps": 0}, [("fusion", "jit_tick")])
    assert _read("dit.sla_fwd_roofline", empty) is None
    assert _read("dit.mfu", empty) is None
    assert _read("dit.admit_share", empty) is None


def test_lm_readers():
    cell = small.lm_cell()
    dec = harness.load_reader(
        "lm.sla_decode_roofline.backlog").__globals__["KERNEL"]
    pre = harness.load_reader("lm.prefill_ms.backlog").__globals__["MODULE"]
    one = harness.load_reader("lm.decode_step_ms.backlog").__globals__["MODULE"]
    counters = {"decode_tokens": 300, "admissions": 4,
                "slot_steps_active": 270, "slot_steps_total": 300}
    ctx = _ctx(cell, counters, [(f"{dec}.3", f"{one}(1)"),
                                ("fusion", f"{pre}(2)")])
    assert _read("lm.idle_share.backlog", ctx) == pytest.approx(50.0)
    assert _read("lm.slot_occupancy.backlog", ctx) == pytest.approx(90.0)
    assert 0 < _read("lm.mfu.backlog", ctx) < 100
    assert 0 < _read("lm.sla_decode_roofline.backlog", ctx) < 100
    assert _read("lm.prefill_ms.backlog", ctx) == pytest.approx(10.0)
    assert _read("lm.decode_step_ms.backlog", ctx) == pytest.approx(10.0)
    empty = _ctx(cell, {}, [("fusion", "other")])
    assert _read("lm.sla_decode_roofline.backlog", empty) is None
    assert _read("lm.slot_occupancy.backlog", empty) is None
    assert _read("lm.prefill_ms.backlog", empty) is None


def test_every_metric_of_the_benchmark_has_a_reader():
    import json
    from bench import ROOT
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for w in bench["workloads"]:
        cell = spec.load(w["name"])
        assert cell.per_layer and cell.end_to_end
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
