"""Each configuration file names its plain reference, and the runner of
its family checks the run against that module and no other: a module
put in `dit`'s place is the one called, and a configuration whose
reference is missing or misnamed fails before set-up."""
import ast
import importlib
import importlib.util
import json
import pathlib
import sys
import time
import types

import pytest

from bench import BENCH, harness, program, reference, spec, weights
from bench.dit_runner import DiTRunner
from bench.lm_runner import LMRunner
from bench.reference import dit
from bench.tests import small

RUNNERS = {r.family: r for r in (DiTRunner, LMRunner)}
CONFIGS = sorted(p.name for p in (BENCH / "configs").glob("*.json"))
STUB = "bench.reference.stub_ref"


def _imports(name, path=None) -> set:
    """Every module that the source of `name` imports, followed through
    every module of `bench` that it reaches: the packages above each, the
    names of `from bench import x`, relative imports and imports inside
    functions."""
    parts = name.split(".")
    found = {".".join(parts[:i]) for i in range(1, len(parts))}
    todo, seen = [(name, path)], set()
    while todo:
        name, path = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        if path is None:
            try:
                found_spec = importlib.util.find_spec(name)
            except ModuleNotFoundError:     # a name inside a module
                continue
            if found_spec is None or not found_spec.origin:
                continue        # a name imported from a module, not one
            path = found_spec.origin
        package = (name if path.endswith("__init__.py")
                   else name.rpartition(".")[0])
        for node in ast.walk(ast.parse(pathlib.Path(path).read_text())):
            if isinstance(node, ast.Import):
                got = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                base = importlib.util.resolve_name(
                    "." * node.level + (node.module or ""), package)
                got = [base] + [f"{base}.{a.name}" for a in node.names]
            else:
                continue
            for g in got:
                parts = g.split(".")
                found.update(".".join(parts[:i + 1])
                             for i in range(len(parts)))
        todo += [(m, None) for m in found
                 if (m == "bench" or m.startswith("bench.")) and m not in seen]
    return found


def _program_imports(name, path=None) -> set:
    return {m for m in _imports(name, path)
            if m == "repro" or m.startswith("repro.")}


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_names_a_usable_reference(name):
    config = json.loads((BENCH / "configs" / name).read_text())
    function = RUNNERS[config["family"]].reference_function
    cell = types.SimpleNamespace(name=name, config=config)
    ref = reference.named(cell, function)
    assert ref.__name__ == f"bench.reference.{config['reference']}"
    assert callable(getattr(ref, function))
    program = _program_imports(ref.__name__)
    assert not program, f"{ref.__name__} imports the program: {program}"


@pytest.mark.parametrize("source", [
    "from bench import program",
    "import bench.program",
    "from bench.program import arch_config",
    "from .. import program",
    "from bench import dit_runner",
    "def euler_steps():\n    from bench import harness",
    "def euler_steps():\n    import repro.models.dit",
], ids=["from-bench", "import", "from-module", "relative", "through-runner",
        "through-harness", "in-function"])
def test_a_reference_that_reaches_the_program_is_found(tmp_path, source):
    """The scan follows every module of `bench` a reference reaches, so a
    reference that computes with the program's code by way of another
    benchmark module does not pass as independent."""
    path = tmp_path / "stub_leak.py"
    path.write_text(source + "\n")
    assert _program_imports("bench.reference.stub_leak", str(path))


def test_a_reference_on_the_benchmarks_own_modules_passes(tmp_path):
    path = tmp_path / "stub_clean.py"
    path.write_text("from bench import weights\n"
                    "from bench.reference import numerics\n"
                    "from .numerics import Numerics\n")
    found = _imports("bench.reference.stub_clean", str(path))
    assert {"bench.weights", "bench.reference.numerics"} <= found
    assert not _program_imports("bench.reference.stub_clean", str(path))


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_has_weights_for_each_leaf(name):
    """bench/weights.py draws a leaf by its name, and the references read
    it by that name: an architecture whose parameter leaves carry other
    names fails here, not on the chip."""
    import jax

    config = json.loads((BENCH / "configs" / name).read_text())
    arch = program.arch_config(config)
    mdl = program.model_module(arch)
    params = jax.eval_shape(lambda: weights.make(
        lambda k: mdl.init(k, arch), 0,
        program.DTYPES[config["weight_dtype"]]))
    assert jax.tree_util.tree_leaves(params)


def test_a_leaf_the_weights_do_not_know_fails():
    import jax
    import jax.numpy as jnp

    def init(key):
        return {"blocks": {"modulation": jnp.zeros((4, 8))}}
    with pytest.raises(ValueError, match="no rule for parameter leaf"):
        jax.eval_shape(lambda: weights.make(init, 0, jnp.bfloat16))


def _stub(monkeypatch, offset=0.0):
    """`bench.reference.stub_ref`: dit's euler_steps plus `offset`,
    recording the product precision of every call."""
    calls = []

    def euler_steps(*args, inputs="float32"):
        calls.append(inputs)
        return dit.euler_steps(*args, inputs=inputs) + offset
    stub = types.ModuleType(STUB)
    stub.euler_steps = euler_steps
    monkeypatch.setitem(sys.modules, STUB, stub)
    return calls


def _run(cell, seed):
    kept = {}
    res = harness.run(cell, seed, 2.0, False, time.perf_counter(),
                      small.CPU, after_setup=lambda r: kept.update(runner=r))
    return res, kept["runner"]


def test_the_reference_the_configuration_names_is_the_one_called(
        monkeypatch):
    calls = _stub(monkeypatch)
    res, runner = _run(small.dit_cell(limit=1e-4, reference="stub_ref"),
                       2**33 + 21)
    assert res["correct"], res
    assert runner.samples
    # one reference and one floor per request admitted in the window
    assert calls.count("float32") == len(runner.samples)
    assert calls.count("bfloat16") == len(runner.samples)
    runner.ref = dit
    assert runner.check() == res["readings"]


def test_a_reference_that_answers_otherwise_makes_the_run_incorrect(
        monkeypatch):
    limit = json.loads((BENCH / "cells" / "wan-t2v-32k.json").read_text())[
        "limits"]["dit_step_excess_err"]
    calls = _stub(monkeypatch, offset=0.5)
    res, _ = _run(small.dit_cell(limit=limit, reference="stub_ref"),
                  2**33 + 22)
    assert calls
    assert not res["correct"]
    assert res["compared"]["dit_step_excess_err"]["value"] > limit


def _committed_dit_cell(**config):
    cell = spec.load("wan-t2v-32k")
    cell.config.pop("reference")
    cell.config.update(config)
    return cell, "bench/configs/wan2_1_1_3b.json"


def _small_lm_cell(**config):
    cell = small.lm_cell()
    cell.config.pop("reference")
    cell.config.update(config)
    return cell, "small-lm"


@pytest.mark.parametrize("make", [_committed_dit_cell, _small_lm_cell],
                         ids=["dit", "lm"])
@pytest.mark.parametrize("config, why", [
    ({}, "must name a module"),
    ({"reference": None}, "must name a module"),
    ({"reference": "../dit"}, "must name a module"),
    ({"reference": "no_such_reference"}, "names no module"),
    ({"reference": "numerics"}, "has no function"),
], ids=["missing", "null", "path", "no-module", "no-function"])
def test_a_misnamed_reference_fails_before_setup(monkeypatch, make, config,
                                                 why):
    cell, where = make(**config)
    runner = RUNNERS[cell.config["family"]]
    monkeypatch.setattr(runner, "setup", lambda self, seconds: pytest.fail(
        "set-up ran with a misnamed reference"))
    with pytest.raises(ValueError, match=why) as err:
        harness.run(cell, 1, 1.0, False, time.perf_counter(), small.CPU)
    assert where in str(err.value)


def test_a_reference_of_the_other_family_fails():
    """`lm` has no euler_steps and `dit` no served_logits."""
    cell, _ = _committed_dit_cell(reference="lm")
    with pytest.raises(ValueError, match="has no function euler_steps"):
        DiTRunner(cell, 1)
    cell, _ = _small_lm_cell(reference="dit")
    with pytest.raises(ValueError, match="has no function served_logits"):
        LMRunner(cell, 1)
