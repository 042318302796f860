"""The system under test, built from a configuration file: the only
module of the benchmark that turns the file's numbers into the
program's own configuration object."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def arch_config(config: dict):
    """The program's ArchConfig for a configuration file: the registry
    entry `config["arch"]` with every number of the file's "model" group
    (and its "sla" group) set as the file states it."""
    from repro.configs import get_arch

    model = dict(config["model"])
    sla = model.pop("sla")
    base = get_arch(config["arch"])
    return dataclasses.replace(base, **model, sla=base.sla.replace(**sla))


def model_module(arch):
    from repro.models import registry

    return registry.get_model(arch)
