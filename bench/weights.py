"""Random weights of a configuration, made on the device from the seed.

The layout is written out here from the configuration's published sizes and
checked against the layout the program expects, so the reference reads
weights that the benchmark made, not the program.  Every matrix is drawn
in the served dtype in one jitted call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def shape_of(config: dict, rehearse: bool = False) -> dict:
    """The sizes the harness, the reference and the weights use, read from
    the configuration's published keys (with its ``rehearse`` keys in their
    place when asked).  Raises for a decoder the reference does not
    describe."""
    c = dict(config, **config["rehearse"]) if rehearse else config
    if c["hidden_act"] != "silu" or c.get("rope_scaling") is not None:
        raise ValueError(f"{c['name']}: the reference describes a SwiGLU "
                         f"decoder with plain rotary positions")
    heads = c["num_attention_heads"]
    return {"layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "heads": heads, "kv_heads": c["num_key_value_heads"],
            "head_dim": c.get("head_dim") or c["hidden_size"] // heads,
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "tied": c["tie_word_embeddings"], "rope_theta": c["rope_theta"],
            "norm_eps": c["rms_norm_eps"], "dtype": c["torch_dtype"]}


def layout(shape: dict) -> dict:
    """The parameter tree's shapes and dtypes (ShapeDtypeStruct leaves)."""
    n, d, h, kv = shape["layers"], shape["d_model"], shape["heads"], \
        shape["kv_heads"]
    hd, ff, v = shape["head_dim"], shape["d_ff"], shape["vocab"]
    dt, f32 = jnp.dtype(shape["dtype"]), jnp.dtype("float32")

    def s(dims, dtype=dt):
        return jax.ShapeDtypeStruct(tuple(dims), dtype)

    embed = {"tok_embed": s((v, d))}
    if not shape["tied"]:
        embed["lm_head"] = s((d, v))
    layer = {"norm1": s((n, d), f32), "norm2": s((n, d), f32),
             "attn": {"w_q": s((n, d, h, hd)), "w_k": s((n, d, kv, hd)),
                      "w_v": s((n, d, kv, hd)), "w_o": s((n, h, hd, d))},
             "mlp": {"w_gate": s((n, d, ff)), "w_up": s((n, d, ff)),
                     "w_down": s((n, ff, d))}}
    return {"embed": embed, "blocks": {"layer0": layer},
            "final_norm": s((d,), f32)}


def check_layout(shape: dict, program_abstract: dict) -> None:
    """Raise unless the program expects exactly :func:`layout`'s tree."""
    mine = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                  layout(shape))
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


def make(shape: dict, seed: int, std: float):
    """Normal(0, std) matrices and unit norm scales, on the default device."""
    tree = layout(shape)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)

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
