"""Random weights of a configuration, made on the device from the seed.

The configuration's family writes out the parameter tree (its ``layout``);
here it is checked against the layout the program expects and drawn, so the
reference reads weights that the benchmark made, not the program.  Every
leaf is drawn in its own dtype in one jitted call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def check_layout(layout: dict, program_abstract: dict) -> None:
    """Raise unless the program expects exactly the ``layout`` tree."""
    mine = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), layout)
    theirs = jax.tree_util.tree_map(lambda a: (tuple(a.shape), str(a.dtype)),
                                    program_abstract)
    if mine != theirs:
        raise ValueError(f"the program's parameter layout differs from the "
                         f"benchmark's: {theirs} != {mine}")


def key_for(seed: int, stream: int = 0):
    """A PRNG key from a seed of any size (both 32-bit halves count)."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 32), stream)


def make(layout: dict, seed: int, std: float):
    """The ``layout`` tree with normal(0, std) leaves and unit norm scales
    (every leaf whose path names a norm), on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, a) in zip(keys, leaves):
            if "norm" in jax.tree_util.keystr(path):
                out.append(jnp.ones(a.shape, a.dtype))
            else:
                out.append((jax.random.normal(k, a.shape, a.dtype)
                            * jnp.asarray(std, a.dtype)).astype(a.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key_for(seed))
