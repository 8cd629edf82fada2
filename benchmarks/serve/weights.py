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


def _flat(layer) -> dict:
    return {"norm1": layer["norm1"], "norm2": layer["norm2"],
            **layer["mixer"], **layer["mlp"]}


def plain(params) -> dict:
    """The weights as the reference reads them.  ``"groups"``: for each
    group of the program's stack (``g0``, ``g1``, ...), one flat dict per
    position of its period, each matrix stacked over the group's repeats on
    a leading axis.  A model of one group with a period of one also has
    that one dict as ``"layers"``."""
    groups = []
    while f"g{len(groups)}" in params:
        groups.append(tuple(_flat(x) for x in params[f"g{len(groups)}"]))
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "lm_head": params.get("lm_head"), "groups": groups}
    if len(groups) == 1 and len(groups[0]) == 1:
        out["layers"] = groups[0][0]
    return out
