"""Plain references of the configurations' mathematics, in straightforward
`jax.numpy` at float32 with every matrix product at `highest` precision.
They import nothing of the program and take nothing it made: the weights
they read are the benchmark's own (bench/weights.py). `Numerics(inputs)`
computes the same mathematics with every matrix-product input rounded to
a lower precision first: the configuration's stated one, or the one
below it (the control).

Each configuration file names its reference under the required key
"reference": the module `bench/reference/<reference>.py`, which the
runner of the file's family calls once the window has closed. A new
architecture brings its reference as a new module; no runner changes.
`arch` is the file's "model" group and `inputs` the precision to which
every product's inputs are rounded (`numerics.Numerics`):

- family "dit" calls `euler_steps(params, arch, x, t_start, num_steps,
  first_step, steps, cond, inputs="float32")`: the latent after `steps`
  Euler steps of a request (t_start, num_steps) from its step index
  `first_step`, starting at latent x under conditioning `cond`;
- family "lm" calls `served_logits(params, arch, tokens, prompt_len,
  inputs="float32")`: for a sequence of `max_len` tokens, the prompt
  left-padded to end at `prompt_len` and the served tokens after it,
  the logits of every position from `prompt_len - 1` on.

A reference reads its weights from the benchmark's `params` tree,
imports nothing from `repro`, and sets `highest` precision for every
float32 product. Those weights are drawn by `bench/weights.py`, which
knows a leaf by its name (the norm and matrix names it lists, `embed`,
`unembed`, `ada`, `sla_proj`) and refuses any other: a new architecture
joins as files only where the program's model names its parameter
leaves from that set.
"""
from __future__ import annotations

import importlib
import json


def named(cell, function: str):
    """The reference module that the cell's configuration names, checked
    to expose `function`. Fails, naming the configuration file and the
    module, where the key is missing, the module does not exist or it
    lacks the function."""
    name = cell.config.get("reference")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(
            f"{_config_file(cell)}: \"reference\" must name a module of "
            f"bench/reference/, found {name!r}")
    module = f"{__name__}.{name}"
    try:
        ref = importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"{_config_file(cell)}: reference {name!r} names "
                         f"no module bench/reference/{name}.py") from None
    if not callable(getattr(ref, function, None)):
        raise ValueError(f"{_config_file(cell)}: reference module {module} "
                         f"has no function {function}()")
    return ref


def _config_file(cell) -> str:
    """The configuration file BENCHMARK.json gives the cell, for messages."""
    from bench import ROOT

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        if w["name"] == cell.name:
            return files[w["config"]]
    return f"the configuration of cell {cell.name!r}"
