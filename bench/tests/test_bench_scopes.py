"""The layers the trace is read by.

The program side: every named scope the per-layer metrics and the
`trace scope` lines read appears in the op_name metadata of the
compiled tick and admission programs of a small DiffusionScheduler, on
the gather backend and on the fused kernel (interpreted on the CPU).

The reading side: `trace_scopes` on `data/scoped.xplane.pb`
(`data/make_scoped_xplane.py` writes it), whose ops carry their op_name
in the `tf_op` stat of their event metadata and whose host plane holds
the scheduler's `dit.sched.*` spans; and the three scope-share readers
on a made-up reduction."""
import pathlib
import re

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, program, trace_reduce, trace_scopes, weights
from bench.layer_context import LayerContext
from bench.tests import small

DATA = pathlib.Path(__file__).resolve().parent / "data"
US = 1e-6

LAYERS = {"dit.embed", "dit.adaln", "dit.qkv", "sla.plan", "sla.linear",
          "sla.sparse", "sla.merge", "dit.attn_out", "dit.cross_attn",
          "dit.mlp", "dit.head", "dit.commit"}
PLAN_STEPS = {"score", "classify", "lut", "col_lut"}


@pytest.fixture(scope="module", params=["gather", "kernel"])
def op_names(request):
    """{program: [op_name of every instruction]} of the compiled tick
    and fresh admission."""
    from repro.serving.diffusion import DiffusionScheduler

    cell = small.dit_cell(backend=request.param)
    arch = program.arch_config(cell.config)
    params = weights.make(
        lambda k: program.model_module(arch).init(k, arch), 0,
        jnp.bfloat16)
    s = DiffusionScheduler(
        arch, params, num_slots=int(cell.traffic["slots"]),
        seq_len=int(cell.traffic["seq_len"]), backend=request.param)
    ns, nl = s.num_slots, arch.num_layers
    one = jnp.ones((1,), jnp.float32)
    compiled = {
        "jit_tick": s._tick_jit.lower(
            s.params, s._lat, jnp.zeros((ns,)), jnp.asarray(s._dt),
            s._cond, s._plans, jnp.ones((nl, ns)),
            jnp.asarray(np.ones((ns,), bool))),
        "jit_admit_fresh": s._admit_fresh_jit.lower(
            s.params, s._lat[:1], one, one, s._cond[:1]),
    }
    return {name: re.findall(r'op_name="([^"]*)"',
                             low.compile().as_text())
            for name, low in compiled.items()}


def _components(names):
    return {c for n in names for c in n.split("/")}


@pytest.mark.parametrize("module", ["jit_tick", "jit_admit_fresh"])
def test_every_layer_scope_is_in_the_compiled_program(op_names, module):
    got = _components(op_names[module])
    assert LAYERS <= got, LAYERS - got
    assert PLAN_STEPS <= got, PLAN_STEPS - got


def test_the_tick_scopes_its_drift_select(op_names):
    assert "drift" in _components(op_names["jit_tick"])


def test_plan_steps_sit_inside_the_plan_scope(op_names):
    for names in op_names.values():
        for n in names:
            parts = n.split("/")
            inner = PLAN_STEPS.intersection(parts) | (
                {"drift"} & set(parts))
            if inner:
                assert "sla.plan" in parts[:parts.index(min(inner))], n


def test_top_level_scopes_are_layers(op_names):
    """The outermost scope of every op is one of the named layers (the
    parameters aside, whose op_name is the argument's path)."""
    tops = {trace_scopes.top_scope(n)
            for names in op_names.values() for n in names
            if "(" in n.split("/")[0]}
    assert tops <= LAYERS | {trace_scopes.UNSCOPED}, tops - LAYERS


class _Runner:
    counters = {}


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    """The fixture trace where a traced run leaves it, its reduction,
    and the context a reader is given."""
    cell = small.dit_cell()
    where = tmp_path / cell.name
    where.mkdir()
    (where / "scoped.xplane.pb").write_bytes(
        (DATA / "scoped.xplane.pb").read_bytes())
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    red = trace_reduce.reduce_file(where / "scoped.xplane.pb")
    return red, LayerContext(cell, _Runner(), red, "TPU v5 lite")


def test_op_names_come_from_the_metadata_stat(traced):
    red, ctx = traced
    by_name = dict(zip((op.name for op in red.ops),
                       trace_scopes.of(ctx).names))
    assert by_name["fusion.7"].endswith("/dit.mlp/dot_general:")
    assert "/score/" in by_name["fusion.20"]
    assert by_name["copy.1"] == by_name["while.3"] == ""


def test_scope_seconds_are_self_times(traced):
    red, ctx = traced
    sc = trace_scopes.of(ctx)
    assert sc.seconds("dit.mlp") == pytest.approx(3 * 150 * US)
    assert sc.seconds("sla.sparse") == pytest.approx(
        red.kernel_seconds("sla_fwd"))
    assert sc.seconds("sla.plan") == pytest.approx(3 * 110 * US)
    assert sc.seconds("score") == pytest.approx(3 * 60 * US)
    assert sc.seconds("classify") == pytest.approx(3 * 50 * US)
    assert sc.seconds("dit") == 0.0        # whole components only
    assert sc.seconds("no.such.scope") == 0.0


def test_scope_summary_covers_the_busy_time(traced, capsys):
    red, ctx = traced
    summary = dict(trace_scopes.of(ctx).summary())
    assert set(summary) == {"sla.sparse", "dit.mlp", "sla.plan",
                            "dit.commit", trace_scopes.UNSCOPED}
    # the while loop's own time and the copy carry no named scope
    assert summary[trace_scopes.UNSCOPED] == pytest.approx(3 * 60 * US)
    assert sum(summary.values()) == pytest.approx(red.busy_s)
    covered = 1 - summary[trace_scopes.UNSCOPED] / red.busy_s
    assert covered == pytest.approx(1 - 180 / 1650)
    lines = capsys.readouterr().err.splitlines()
    assert len(lines) == len(summary)
    assert all(ln.startswith("trace scope ") for ln in lines)
    trace_scopes.of(ctx)                   # read and printed once a run
    assert capsys.readouterr().err == ""


def test_idle_gaps_name_the_scheduler_span(traced):
    red, ctx = traced
    before = [g[0] for g in red.idle_gaps()]
    assert not any(b.startswith("dit.sched.") for b in before)
    trace_scopes.of(ctx)
    labels = [g[0] for g in red.idle_gaps()]
    assert labels.count("dit.sched.admit") == 3
    assert labels.count("dit.sched.readback") == 3
    assert "bench.step" in labels           # between two step() calls
    idle = sum(s for _, s in red.idle_gaps())
    assert idle == pytest.approx(red.window_s - red.busy_s)


def test_reading_scopes_leaves_the_other_readings(traced):
    red, ctx = traced
    before = (red.busy_s, red.op_seconds(), red.module_summary(),
              red.module_busy_seconds("admit_fresh"))
    trace_scopes.of(ctx)
    assert (red.busy_s, red.op_seconds(), red.module_summary(),
            red.module_busy_seconds("admit_fresh")) == before
    assert red.busy_s == pytest.approx(1650 * US)


def test_a_trace_without_the_stat_is_unscoped():
    path = DATA / "tiny.xplane.pb"
    tiny = trace_reduce.reduce_file(path)
    sc = trace_scopes.read(path, tiny)
    assert [n for n, _ in sc.summary()] == [trace_scopes.UNSCOPED]
    assert sc.seconds("dit.mlp") == 0.0
    assert sc.spans == []


def test_a_trace_of_other_ops_is_refused():
    tiny = trace_reduce.reduce_file(DATA / "tiny.xplane.pb")
    with pytest.raises(ValueError):
        trace_scopes.read(DATA / "scoped.xplane.pb", tiny)


def test_metadata_stats_reads_strings_and_references():
    """The wire reader against a trace written by the profiler's own
    text-to-proto converter: string and reference stats, device planes
    only."""
    from jax.profiler import ProfileData

    text = """planes { id: 1 name: "/device:TPU:0"
      event_metadata { key: 3 value { id: 3 name: "%fusion.7 = f32[2]"
        stats { metadata_id: 8 str_value: "jit(f)/dit.mlp/dot_general:" }
        stats { metadata_id: 9 ref_value: 10 }
        stats { metadata_id: 11 int64_value: 5 } } }
      event_metadata { key: 4 value { id: 4 name: "%while.1 = ()" } }
      stat_metadata { key: 8 value { id: 8 name: "tf_op" } }
      stat_metadata { key: 9 value { id: 9 name: "hlo_category" } }
      stat_metadata { key: 10 value { id: 10 name: "convolution fusion" } }
      stat_metadata { key: 11 value { id: 11 name: "flops" } } }
    planes { id: 2 name: "/host:CPU"
      event_metadata { key: 1 value { id: 1 name: "bench.step"
        stats { metadata_id: 1 str_value: "x" } } }
      stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }"""
    got = trace_scopes.metadata_stats(
        ProfileData.text_proto_to_serialized_xspace(text))
    assert got == {"/device:TPU:0": {"%fusion.7 = f32[2]": {
        "tf_op": "jit(f)/dit.mlp/dot_general:",
        "hlo_category": "convolution fusion"}}}


MS = 1_000_000  # ns


@pytest.mark.parametrize("name", ["dit.plan_share", "dit.linear_share",
                                  "dit.mlp_share"])
def test_scope_share_readers(name):
    """Each reads its scope's self time over the busy time, and nothing
    in a trace without its scope."""
    scope = harness.load_reader(name).__globals__["SCOPE"]
    ops = [trace_reduce.Op("fusion", "jit_tick", i * 20 * MS, 10 * MS,
                           10 * MS) for i in range(50)]
    red = trace_reduce.Reduction(window=(0, 1000 * MS), ops=ops,
                                 modules=[], spans=[],
                                 busy_ns_per_device=[500 * MS])
    ctx = LayerContext(small.dit_cell(), _Runner(), red, "TPU v5 lite")
    red.scopes = trace_scopes.Scopes(ops, [
        f"jit(tick)/while/body/{scope}/dot_general:" if i % 5 == 0
        else "jit(tick)/while/body/other.layer/add:" for i in range(50)])
    assert harness.load_reader(name)(ctx) == pytest.approx(20.0)
    red.scopes = trace_scopes.Scopes(
        ops, ["jit(tick)/while/body/other.layer/add:"] * 50)
    assert harness.load_reader(name)(ctx) is None
