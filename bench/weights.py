"""Seeded weights for the system under test, made by the benchmark.

The parameter tree's structure (leaf names and shapes) is read from the
program's own init with `jax.eval_shape`; every value is drawn here, from
the run's seed, in one jitted call on the device and in the dtype the
weights are served in. The references read the same leaves by name, so
a leaf this module does not know fails loudly instead of being guessed.
"""
from __future__ import annotations

import numpy as np

NORM_LEAVES = {"ln1", "ln2", "ln_x", "ln_f", "qnorm", "knorm"}
MATRIX_LEAVES = {"wq", "wk", "wv", "wo", "xq", "xk", "xv", "xo",
                 "mlp_wi", "mlp_wo", "patch_in", "patch_out", "t_embed"}


def seed_key(seed: int):
    """A JAX key from any non-negative integer seed (wider than 32 bits
    too): SeedSequence folds the whole integer into two words."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _leaf(key, name: str, shape, jnp, jax):
    """Values for one leaf, by the role its name gives it. Norm gains,
    the SLA Proj and the output head are random rather than zero (the
    program's init zeroes them), so that every branch the comparison
    covers carries signal."""
    normal = jax.random.normal(key, shape, jnp.float32)
    if name in NORM_LEAVES:
        return 0.1 * normal
    if name in ("embed", "unembed"):
        return normal * shape[-1] ** -0.5
    if name == "ada":
        return 0.01 * normal
    if name == "sla_proj":
        return normal * shape[-1] ** -0.5
    if name in MATRIX_LEAVES:
        fan_in, fan_out = shape[-2], shape[-1]
        return normal * (2.0 / (fan_in + fan_out)) ** 0.5
    raise ValueError(f"weights: no rule for parameter leaf {name!r} "
                     f"{tuple(shape)}; the reference does not know it")


def make(init_fn, seed: int, dtype):
    """Parameters shaped like `init_fn(key)`'s, drawn from `seed`."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    names = [str(path[-1].key) for path, _ in flat]

    @jax.jit
    def gen(key):
        leaves = [_leaf(jax.random.fold_in(key, i), name, sds.shape,
                        jnp, jax).astype(dtype)
                  for i, (name, (_, sds)) in enumerate(zip(names, flat))]
        return jax.tree_util.tree_unflatten(tree, leaves)

    return gen(seed_key(seed))
