"""The dense decoder family: the configurations in this directory whose
``family`` is ``decoder`` run it, and the harness learns everything it needs
of the model from this module.

- :func:`shape_of`: the sizes, read from the configuration's published keys;
- :func:`layout`: the parameter tree the weights are drawn in;
- :func:`program_config`: the program's ``ModelConfig`` at those sizes;
- :func:`served_request` and :func:`forward`: the model FLOPs of the work;
- :class:`Reference`: the plain reference the served tokens are judged by.

The decoder is pre-norm: token embedding; per layer RMSNorm, grouped-query
attention with rotary embeddings (the two halves of each head rotated) and a
causal mask, a residual add, RMSNorm, a SwiGLU MLP and a residual add; a
final RMSNorm and the LM head (the embedding's transpose when tied).  The
reference is written from that description in ``jax.numpy`` alone, layer by
layer in a Python loop, with every matrix product at ``HIGHEST`` precision
in float32.  The module imports nothing of the program under test.

``mode="fp8"`` is the control: the same computation with every operand of
every matrix product rounded to float8 e4m3 with a per-tensor scale, as a
lower-precision serving path would compute it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "fp8")


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


def program_config(base, shape: dict):
    """``base``, the program's ModelConfig of the configuration's
    architecture, at this configuration's sizes."""
    s = shape
    return dataclasses.replace(
        base, num_layers=s["layers"], d_model=s["d_model"],
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], vocab_size=s["vocab"],
        tie_embeddings=s["tied"], rope_theta=s["rope_theta"],
        norm_eps=s["norm_eps"], dtype=s["dtype"])


# -- model FLOPs --------------------------------------------------------------
# The multiply-adds the model needs (2 FLOPs each): the projections, the
# attention products over the keys a causal query really attends to, and the
# LM head at the positions whose logits are asked for.  Masked keys, padding
# rows and recomputation are not counted.

def _per_token_matmul(s: dict) -> int:
    d, h, kv, hd, ff = (s["d_model"], s["heads"], s["kv_heads"],
                        s["head_dim"], s["d_ff"])
    proj = d * h * hd * 2 + d * kv * hd * 2       # q, o and k, v
    return 2 * s["layers"] * (proj + 3 * d * ff)


def _attn(s: dict, keys_attended: int) -> int:
    """Scores and weighted values for ``keys_attended`` (query, key) pairs."""
    return 2 * 2 * s["layers"] * s["heads"] * s["head_dim"] * keys_attended


def _head(s: dict, positions: int) -> int:
    return 2 * s["d_model"] * s["vocab"] * positions


def forward(s: dict, batch: int, seq: int, logits_positions: int) -> int:
    """A causal forward pass over ``(batch, seq)`` tokens, with logits at
    ``logits_positions`` positions of each row."""
    pairs = seq * (seq + 1) // 2
    return batch * (seq * _per_token_matmul(s) + _attn(s, pairs)
                    + _head(s, logits_positions))


def served_request(s: dict, prompt_len: int, new_tokens: int) -> int:
    """One request: its prefill (logits at the last position), then one
    decode step for each generated token after the first."""
    total = forward(s, 1, prompt_len, 1)
    for j in range(1, new_tokens):
        ctx = prompt_len + j                       # keys incl. the new one
        total += _per_token_matmul(s) + _attn(s, ctx) + _head(s, 1)
    return total


# -- the reference ------------------------------------------------------------


def _q(x, mode: str):
    if mode == "f32":
        return x
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(eq: str, a, b, mode: str):
    return jnp.einsum(eq, _q(a, mode), _q(b, mode), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


class Reference:
    """The reference for one configuration's ``shape`` block."""

    def __init__(self, shape: dict, mode: str = "f32"):
        if mode not in MODES:
            raise ValueError(f"mode {mode!r} not in {MODES}")
        self.s, self.mode = shape, mode
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    def _project(self, a, h):
        """One layer's queries, keys and values (B, T, heads, head_dim) from
        its normed input ``h``, before the rotation."""
        return tuple(_mm("btd,dhk->bthk", h, a[w].astype(jnp.float32),
                         self.mode) for w in ("w_q", "w_k", "w_v"))

    def _layer_fn(self, lp, x):
        s, mode = self.s, self.mode
        f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
        n, t, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(t)[None], (n, t))
        h = _rms(x, lp["norm1"], s["norm_eps"])
        a = lp["attn"]
        q, k, v = self._project(a, h)
        q = _rope(q, pos, s["rope_theta"])
        k = _rope(k, pos, s["rope_theta"])
        g = s["heads"] // s["kv_heads"]
        k = jnp.repeat(k, g, axis=2)            # query head i reads kv i // g
        v = jnp.repeat(v, g, axis=2)
        scores = _mm("bqhk,bshk->bhqs", q, k, mode) / np.sqrt(s["head_dim"])
        causal = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = _mm("bhqs,bshk->bqhk", p, v, mode)
        x = x + _mm("bqhk,hkd->bqd", o, f32(a["w_o"]), mode)
        h = _rms(x, lp["norm2"], s["norm_eps"])
        m = lp["mlp"]
        gate = _mm("btd,df->btf", h, f32(m["w_gate"]), mode)
        up = _mm("btd,df->btf", h, f32(m["w_up"]), mode)
        return x + _mm("btf,fd->btd", jax.nn.silu(gate) * up,
                       f32(m["w_down"]), mode)

    def _head_fn(self, params, x):
        s, mode = self.s, self.mode
        h = _rms(x, params["final_norm"], s["norm_eps"])
        e = params["embed"]
        if s["tied"]:
            return _mm("btd,vd->btv", h, e["tok_embed"].astype(jnp.float32),
                       mode)
        return _mm("btd,dv->btv", h, e["lm_head"].astype(jnp.float32), mode)

    def logits(self, params, tokens, *, last_only: bool = False):
        """float32 logits (N, T, V) of ``tokens`` (N, T), or (N, 1, V) of the
        last position; a device array."""
        tokens = jnp.asarray(tokens, jnp.int32)
        x = jnp.take(params["embed"]["tok_embed"], tokens, axis=0
                     ).astype(jnp.float32)
        blocks = params["blocks"]["layer0"]
        for i in range(self.s["layers"]):
            lp = jax.tree_util.tree_map(lambda w: w[i], blocks)
            x = self._layer(lp, x)
        if last_only:
            x = x[:, -1:]
        return self._head(params, x)
