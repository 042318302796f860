"""HunyuanVideo's MMDiT (HYVideo-T/2) against its plain reference at small
widths on the CPU: the program through the DiffusionScheduler against
`bench/reference/hunyuan.py` for an admission and a tick, each kind of
block alone, the correctness control and the faults; the cell's own
counts and per-layer readers."""
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import BENCH, faults, harness, program, spec, weights
from bench.counts import mmdit as counts
from bench.counts.sla import sla_flops
from bench.layer_context import LayerContext
from bench.reference import hunyuan
from bench.tests import small
from bench.trace_reduce import Op, Reduction
from bench.trace_scopes import Scopes

# products run in float32 on the CPU, so the program and the reference
# differ by float32 rounding alone: ~1e-7 of the update, read at these
# sizes; 1e-4 leaves room for the order of sums, and rounding any
# product's inputs to bfloat16 (~3e-3) or float8 (~5e-2) fails it
TOL = 1e-4
LIMIT = json.loads((BENCH / "cells" / "hunyuan-t2v-65k.json").read_text())[
    "limits"]["dit_step_excess_err"]

MODEL = {"num_layers": 3, "double_layers": 1, "d_model": 128,
         "num_heads": 4, "num_kv_heads": 4, "head_dim": 32, "d_ff": 256,
         "vocab_size": 0, "patch_dim": 16, "cross_attn": False,
         "cond_len": 16, "qk_norm": True, "rope_theta": 256.0,
         "rope_axes": [4, 14, 14], "latent_hw": [4, 8],
         "sla": {"block_q": 16, "block_kv": 16, "kh_frac": 0.25,
                 "kl_frac": 0.25, "phi": "softmax",
                 "col_capacity_factor": 2.0, "plan_refresh_mode": "fixed",
                 "plan_refresh_interval": 1}}
TRAFFIC = {"kind": "dit", "loop": "backlog", "seq_len": 64, "slots": 1,
           "num_steps": 6, "t_start": 1.0, "fill_steps": [3], "backlog": 2,
           "cond_pool": 2}


def _config(**model):
    return {"arch": "hunyuanvideo-t2", "family": "dit",
            "reference": "hunyuan", "weight_dtype": "bfloat16",
            "compute_dtype": "float32", "product_inputs": "bfloat16",
            "backend": "gather", "model": dict(MODEL, **model)}


def _cell(limit=TOL, **model):
    return spec.Cell("small-hunyuan", 1, _config(**model), dict(TRAFFIC),
                     {"trace_seconds": 2,
                      "limits": {"dit_step_excess_err": limit}},
                     [{"name": "dit_tokens_per_s", "unit": "tokens/s"},
                      {"name": "setup_s", "unit": "s"}], [])


def _run(cell, seed=2**33 + 17, patch=None):
    kept = {}

    def after_setup(runner):
        kept.update(runner=runner)
        if patch is not None:
            patch(runner)
    res = harness.run(cell, seed, 2.0, False, time.perf_counter(),
                      small.CPU, after_setup=after_setup)
    return res, kept["runner"]


@pytest.fixture(scope="module")
def served():
    return _run(_cell())


def test_scheduler_matches_the_reference(served):
    """Admission plus one tick of every request admitted in the window,
    through the DiffusionScheduler, against the reference."""
    res, runner = served
    assert res["correct"], res
    assert runner.samples
    assert 0.0 <= res["readings"]["dit_step_rel_err"] < TOL
    assert res["metrics"]["dit_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("inputs", ["bfloat16", "float8"])
def test_a_lower_precision_in_the_programs_place_fails(served, inputs):
    """The reference from rounded product inputs, put where the program's
    answer goes, misses the tolerance the program meets."""
    _, runner = served
    req, n, _ = runner.samples[0]
    args = (runner.params, runner.cell.config["model"], req.latent,
            req.t_start, req.num_steps, 0, n, runner.conds[req.cond])
    ref = np.asarray(hunyuan.euler_steps(*args))
    low = np.asarray(hunyuan.euler_steps(*args, inputs=inputs))
    rel = np.linalg.norm(low - ref) / np.linalg.norm(ref - req.latent)
    assert rel > 10 * TOL


def test_the_control_fails_the_cells_limit(served):
    _, runner = served
    got = runner.check(control=True)
    assert got["dit_step_excess_err"] > LIMIT


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_a_broken_step_is_not_correct(fault):
    res, _ = _run(_cell(limit=LIMIT), patch=faults.FAULTS["dit"][fault])
    assert not res["correct"]


@pytest.mark.parametrize("layers", [(1, 1), (0, 1)],
                         ids=["double", "single"])
def test_each_kind_of_block_matches_the_reference(layers):
    """One dual-stream block alone, then one single-stream block alone:
    the program's velocity against the reference's."""
    nd, nl = layers
    config = _config(double_layers=nd, num_layers=nl)
    arch = program.arch_config(config)
    mdl = program.model_module(arch)
    params = weights.make(lambda k: mdl.init(k, arch), 3, jnp.float32)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((64, 16), np.float32)
    cond = rng.standard_normal((16, 128), np.float32)
    got = mdl.forward(params, arch, jnp.asarray(x[None]), 0.7,
                      jnp.asarray(cond[None]), jnp.float32, "gather")[0]
    want = hunyuan.velocity(params, config["model"], jnp.asarray(x),
                            np.float32(0.7), jnp.asarray(cond))
    err = np.linalg.norm(np.asarray(got) - np.asarray(want))
    assert err < TOL * np.linalg.norm(np.asarray(want))


def test_slot_step_counts_by_hand():
    m = dict(MODEL)
    d, f, n, lc = 128, 256, 64, 16
    per_token = 2 * d * 3 * d + 2 * d * d + 2 * d * f + 2 * f * d
    sla = sla_flops(n + lc, 32, 4, m["sla"])
    layer = (n + lc) * per_token + sla["sparse"] + sla["linear"] \
        + sla["proj"]
    mods = 2 * (2 * d * 6 * d) + 2 * (2 * d * 3 * d) + 2 * d * 2 * d
    embed = 2 * n * 16 * d * 2 + 2 * 256 * d + 2 * d * d
    assert counts.slot_step(m, n) == 3 * layer + mods + embed
    # linear in the block count, at a cost per block that the joint
    # length sets
    more = dict(m, num_layers=5, double_layers=2)
    assert counts.slot_step(more, n) > counts.slot_step(m, n)


MS = 1_000_000  # ns


class _Runner:
    ticks = 10

    def __init__(self, counters):
        self.counters = counters


def _ctx(names, counters, scopes=None):
    ops = [Op(name, module, i * 20 * MS, 10 * MS, 10 * MS)
           for i, (name, module) in enumerate(names * (50 // len(names)))]
    red = Reduction(window=(0, 1000 * MS), ops=ops,
                    modules=[Op(o.module, o.module, o.start, o.dur)
                             for o in ops],
                    spans=[("bench.window", 0, 1000 * MS)],
                    busy_ns_per_device=[500 * MS])
    if scopes is not None:
        red.scopes = Scopes(ops, [scopes[i % len(scopes)]
                                  for i in range(len(ops))])
    cell = spec.load("hunyuan-t2v-65k")
    return LayerContext(cell, _Runner(counters), red, "TPU v5 lite")


def test_the_cells_readers_read_a_number():
    fwd = harness.load_reader("mmdit.sla_fwd_roofline").__globals__["KERNEL"]
    ctx = _ctx([(f"{fwd}.3", "jit_tick"), ("fusion", "jit_tick")],
               {"denoise_steps": 8, "admissions": 1},
               ["jit(tick)/while/body/cond/branch_0_fun/mmdit.double/"
                "dit.mlp/dot_general:",
                "jit(tick)/while/body/cond/branch_1_fun/mmdit.single/"
                "mmdit.rope/mul:",
                "jit(tick)/while/body/cond/branch_1_fun/mmdit.single/"
                "dit.mlp/dot_general:",
                "jit(tick)/while/body/cond/branch_1_fun/mmdit.single/"
                "sla.plan/sort:", "jit(tick)/dit.head/dot_general:"])
    for name in ("mmdit.mfu", "mmdit.sla_fwd_roofline",
                 "mmdit.double_share", "mmdit.rope_share",
                 "dit.mlp_share", "dit.plan_share", "dit.idle_share",
                 "dit.admit_share"):
        value = harness.load_reader(name)(ctx)
        if name == "dit.admit_share":      # no admission module here
            assert value is None
            continue
        assert value is not None and value > 0, (name, value)
    model = ctx.model
    assert harness.load_reader("mmdit.mfu")(ctx) == pytest.approx(
        100 * 8 * counts.slot_step(model, 65280) / 1.0
        / ctx.peaks["bf16_flops_per_s"])
    assert harness.load_reader("mmdit.double_share")(ctx) == \
        pytest.approx(20.0)
    assert harness.load_reader("mmdit.rope_share")(ctx) == \
        pytest.approx(20.0)
    empty = _ctx([("fusion", "jit_tick")], {"denoise_steps": 0},
                 ["jit(tick)/dit.head/dot_general:"])
    for name in ("mmdit.mfu", "mmdit.sla_fwd_roofline",
                 "mmdit.double_share", "mmdit.rope_share"):
        assert harness.load_reader(name)(empty) is None


def test_every_mmdit_scope_is_in_the_compiled_tick(served):
    """The named scopes the readers and the `trace scope` lines read are
    in the op_name metadata of the compiled tick."""
    _, runner = served
    cfg = program.arch_config(runner.cell.config)
    from repro.serving.diffusion import DiffusionScheduler

    s = DiffusionScheduler(cfg, runner.params, num_slots=1, seq_len=64,
                           backend="gather")
    text = s._tick_jit.lower(
        s.params, s._lat, jnp.zeros((1,)), jnp.asarray(s._dt), s._cond,
        s._plans, jnp.ones((cfg.num_layers, 1)),
        jnp.ones((1,), bool)).compile().as_text()
    got = {c for n in re.findall(r'op_name="([^"]*)"', text)
           for c in n.split("/")}
    want = {"mmdit.double", "mmdit.single", "mmdit.rope", "mmdit.joint",
            "dit.embed", "dit.adaln", "dit.qkv", "dit.attn_out", "dit.mlp",
            "dit.head", "dit.commit", "sla.plan", "sla.linear",
            "sla.sparse", "sla.merge"}
    assert want <= got, want - got


def test_the_cell_loads_with_its_metrics():
    cell = spec.load("hunyuan-t2v-65k")
    names = {m["name"] for m in cell.per_layer}
    assert {"mmdit.mfu", "mmdit.sla_fwd_roofline", "mmdit.double_share",
            "mmdit.rope_share", "dit.idle_share", "dit.admit_share",
            "dit.plan_share", "dit.linear_share", "dit.mlp_share"} == names
    assert {m["name"] for m in cell.end_to_end} == {"dit_tokens_per_s",
                                                    "setup_s"}
    model = cell.config["model"]
    joint = cell.traffic["seq_len"] + model["cond_len"]
    assert joint == 65536 and joint % model["sla"]["block_q"] == 0
    hh, ww = model["latent_hw"]
    assert cell.traffic["seq_len"] == 32 * hh * ww
    arch = program.arch_config(cell.config)
    shapes = jax.eval_shape(lambda: program.model_module(arch).init(
        jax.random.PRNGKey(0), arch))
    n_params = sum(np.prod(a.shape) for a in
                   jax.tree_util.tree_leaves(shapes))
    assert 2.5e9 < n_params < 2.55e9     # 4 + 8 blocks at full width
