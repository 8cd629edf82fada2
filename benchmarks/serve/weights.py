"""Weights made on the device from ``--seed`` in one jitted call.

Leaf shapes and types come from the program's public
``transformer.abstract_params``; the values follow the configuration file's
``init`` rule (a mean and a standard deviation for each leaf name), so no
PR can change the benchmark's weights by editing the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def weight_key(seed: int):
    """A JAX key from any whole-number seed (it may exceed 32 bits)."""
    return jax.random.PRNGKey(int(np.random.default_rng(seed).integers(2**31)))


def make_weights(abstract, init: dict, seed: int):
    """Every leaf ``mean + std * N(0, 1)`` with ``init["normal"][name]`` as
    ``[mean, std]``, in the leaf's dtype, in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [leaf_name(p) for p, _ in leaves]
    for n in names:
        if n not in init["normal"]:
            raise KeyError(f"the init rule does not cover leaf {n!r}")

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, name, (_, leaf) in zip(keys, names, leaves):
            mean, std = init["normal"][name]
            x = mean + std * jax.random.normal(k, leaf.shape, jnp.float32)
            out.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(build)(weight_key(seed)))


def plain(params) -> dict:
    """The weights as the reference reads them: a flat dict with each
    decoder layer's matrices stacked on a leading layer axis."""
    layer = params["g0"][0]
    flat = {"norm1": layer["norm1"], "norm2": layer["norm2"],
            **layer["mixer"], **layer["mlp"]}
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": params.get("lm_head"), "layers": flat}
