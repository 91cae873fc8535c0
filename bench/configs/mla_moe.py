"""The latent-attention mixture-of-experts family (DeepSeek-V2): the
configurations in this directory whose ``family`` is ``mla_moe`` run it.

- :func:`shape_of`: the sizes, read from the configuration's published keys;
- :func:`layout`: the parameter tree the weights are drawn in;
- :func:`program_config`: the program's ``ModelConfig`` at those sizes;
- :func:`served_request` and :func:`forward`: the model FLOPs of the work;
- :class:`Reference`: the plain reference the served tokens are judged by.

The model is pre-norm: token embedding; per layer RMSNorm, multi-head latent
attention, a residual add, RMSNorm, a feed-forward block and a residual add;
a final RMSNorm and an untied LM head.  The first ``first_k_dense_replace``
layers' feed-forward block is a SwiGLU MLP of width ``intermediate_size``;
every later one is a mixture of experts.

Latent attention: the query comes from a rank-``q_lora_rank`` latent
(``w_dq``, RMSNorm, ``w_uq``) and splits per head into a ``qk_nope_head_dim``
part and a ``qk_rope_head_dim`` part; keys and values come from one
rank-``kv_lora_rank`` latent of each position (``w_dkv``, RMSNorm), expanded
per head by ``w_uk`` and ``w_uv``, with one ``qk_rope_head_dim`` key part per
position (``w_kr``) shared by all heads.  The rope parts are rotated (the two
halves of each part) at YaRN's frequencies; the scores are scaled by
1/sqrt(nope + rope dims) times YaRN's mscale(factor, mscale_all_dim)**2;
causal softmax; per-head outputs through ``w_o``.

Experts: softmax scores over all ``published_routed_experts``; the experts
are ``n_group`` equal groups, each scored by its best expert; the experts of
all but the ``topk_group`` best groups read 0; the ``num_experts_per_tok``
best scores are taken (the lower index first on a tie), renormalised only if
``norm_topk_prob``, and multiplied by ``routed_scaling_factor``.  The chip
holds ``n_routed_experts`` of them from ``first_held_expert`` on and computes
their part of the result: each held expert's SwiGLU output times its weight,
for the tokens routed to it.  The ``n_shared_experts`` shared experts, one
SwiGLU of width ``n_shared_experts * moe_intermediate_size``, see every
token.

The reference is written from that description in ``jax.numpy`` alone,
layer by layer in a Python loop, with every matrix product at ``HIGHEST``
precision in float32, attention in blocks of query positions so that it
fits beside the weights.  The module imports nothing of the program under
test.  ``mode="fp8"`` is the control: every operand of every matrix product
rounded to float8 e4m3 with a per-tensor scale.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "fp8")
QUERY_BLOCK = 128          # query positions the reference attends at once


def shape_of(config: dict, rehearse: bool = False) -> dict:
    """The sizes the harness, the reference and the weights use, read from
    the configuration's published keys (with its ``rehearse`` keys in their
    place when asked).  Raises for a model the reference does not describe."""
    c = dict(config, **config["rehearse"]) if rehearse else config
    y = c["rope_scaling"]
    if (c["hidden_act"] != "silu" or c["scoring_func"] != "softmax"
            or c["topk_method"] not in ("greedy", "group_limited_greedy")
            or c["attention_bias"] or c["moe_layer_freq"] != 1
            or not c["q_lora_rank"] or y is None or y["type"] != "yarn"):
        raise ValueError(f"{c['name']}: the reference describes latent "
                         f"attention with a query latent and YaRN, and "
                         f"softmax-scored experts on every layer after the "
                         f"leading dense ones")
    groups = c["n_group"] if c["topk_method"] == "group_limited_greedy" \
        else 1
    return {"layers": c["num_hidden_layers"],
            "dense_layers": c["first_k_dense_replace"],
            "d_model": c["hidden_size"], "heads": c["num_attention_heads"],
            "q_lora": c["q_lora_rank"], "kv_lora": c["kv_lora_rank"],
            "nope_dim": c["qk_nope_head_dim"],
            "rope_dim": c["qk_rope_head_dim"], "v_dim": c["v_head_dim"],
            "d_ff": c["intermediate_size"],
            "expert_ff": c["moe_intermediate_size"],
            "experts": c["published_routed_experts"],
            "first_held": c["first_held_expert"],
            "held": c["n_routed_experts"], "top_k": c["num_experts_per_tok"],
            "groups": groups,
            "top_groups": c["topk_group"] if groups > 1 else 1,
            "norm_topk": c["norm_topk_prob"],
            "routed_scale": float(c["routed_scaling_factor"]),
            "shared": c["n_shared_experts"], "vocab": c["vocab_size"],
            "rope_theta": float(c["rope_theta"]),
            "yarn": {k: y[k] for k in ("factor", "beta_fast", "beta_slow",
                                       "mscale", "mscale_all_dim",
                                       "original_max_position_embeddings")},
            "norm_eps": c["rms_norm_eps"], "dtype": c["torch_dtype"]}


def layout(shape: dict) -> dict:
    """The parameter tree's shapes and dtypes (ShapeDtypeStruct leaves)."""
    s = shape
    d, h, v = s["d_model"], s["heads"], s["vocab"]
    dt, f32 = jnp.dtype(s["dtype"]), jnp.dtype("float32")

    def stack(n, tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((n,) + a[0], a[1]), tree,
            is_leaf=lambda a: isinstance(a, tuple) and len(a) == 2
            and isinstance(a[0], tuple))

    def mm(*dims):
        return (tuple(dims), dt)

    def norm(n):
        return ((n,), f32)

    attn = {"w_dkv": mm(d, s["kv_lora"]), "kv_norm": norm(s["kv_lora"]),
            "w_uk": mm(s["kv_lora"], h, s["nope_dim"]),
            "w_uv": mm(s["kv_lora"], h, s["v_dim"]),
            "w_kr": mm(d, s["rope_dim"]), "w_o": mm(h, s["v_dim"], d),
            "w_dq": mm(d, s["q_lora"]), "q_norm": norm(s["q_lora"]),
            "w_uq": mm(s["q_lora"], h, s["nope_dim"] + s["rope_dim"])}
    ff, ef, sf = s["d_ff"], s["expert_ff"], s["shared"] * s["expert_ff"]
    dense = {"norm1": norm(d), "attn": attn, "norm2": norm(d),
             "mlp": {"w_gate": mm(d, ff), "w_up": mm(d, ff),
                     "w_down": mm(ff, d)}}
    moe = {"norm1": norm(d), "attn": attn, "norm2": norm(d),
           "moe": {"router": ((d, s["experts"]), f32),
                   "w_gate": mm(s["held"], d, ef), "w_up": mm(s["held"], d, ef),
                   "w_down": mm(s["held"], ef, d),
                   "shared_w_gate": mm(d, sf), "shared_w_up": mm(d, sf),
                   "shared_w_down": mm(sf, d)}}
    tree = {"embed": {"tok_embed": jax.ShapeDtypeStruct((v, d), dt),
                      "lm_head": jax.ShapeDtypeStruct((d, v), dt)}}
    if s["dense_layers"]:
        tree["lead"] = {"layer0": stack(s["dense_layers"], dense)}
    tree["blocks"] = {"layer0": stack(s["layers"] - s["dense_layers"], moe)}
    tree["final_norm"] = jax.ShapeDtypeStruct((d,), f32)
    return tree


def program_config(base, shape: dict):
    """``base``, the program's ModelConfig of the configuration's
    architecture, at this configuration's sizes."""
    s, y = shape, shape["yarn"]
    return dataclasses.replace(
        base, num_layers=s["layers"], first_k_dense=s["dense_layers"],
        d_model=s["d_model"], num_heads=s["heads"], num_kv_heads=s["heads"],
        head_dim=s["nope_dim"], d_ff=s["d_ff"], vocab_size=s["vocab"],
        tie_embeddings=False, q_lora_rank=s["q_lora"],
        kv_lora_rank=s["kv_lora"], rope_head_dim=s["rope_dim"],
        v_head_dim=s["v_dim"], moe_num_experts=s["experts"],
        moe_top_k=s["top_k"], moe_num_shared=s["shared"],
        moe_d_ff=s["expert_ff"], moe_n_group=s["groups"],
        moe_topk_group=s["top_groups"], moe_norm_topk=s["norm_topk"],
        moe_routed_scale=s["routed_scale"],
        moe_experts_held=(s["first_held"], s["held"]),
        rope_theta=s["rope_theta"],
        rope_scaling=dataclasses.replace(
            base.rope_scaling, factor=y["factor"],
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
            mscale=y["mscale"], mscale_all_dim=y["mscale_all_dim"]),
        norm_eps=s["norm_eps"], dtype=s["dtype"])


# -- model FLOPs --------------------------------------------------------------
# The multiply-adds this chip's share of the model needs (2 FLOPs each): the
# projections, the attention products over the keys a causal query really
# attends to, the dense layers' MLP, the router, both shared experts, the
# held routed experts at their expected share (num_experts_per_tok * held /
# published experts per token) and the LM head at the positions whose logits
# are asked for.  Masked keys, padding rows, tokens routed elsewhere and
# recomputation are not counted.

def _attn_proj(s: dict) -> int:
    """Per token, every latent-attention projection but the per-key ones."""
    d, h, r = s["d_model"], s["heads"], s["rope_dim"]
    return (d * s["q_lora"] + s["q_lora"] * h * (s["nope_dim"] + r)
            + d * (s["kv_lora"] + r) + h * s["v_dim"] * d)


def _ffn(s: dict) -> int:
    """Per token, the feed-forward blocks of all the layers."""
    d, moe_layers = s["d_model"], s["layers"] - s["dense_layers"]
    routed = s["top_k"] * s["held"] / s["experts"]
    per_moe = (d * s["experts"] + 3 * d * s["expert_ff"]
               * (s["shared"] + routed))
    return int(s["dense_layers"] * 3 * d * s["d_ff"] + moe_layers * per_moe)


def _head(s: dict, positions: int) -> int:
    return 2 * s["d_model"] * s["vocab"] * positions


def forward(s: dict, batch: int, seq: int, logits_positions: int) -> int:
    """A causal forward pass over ``(batch, seq)`` tokens (keys and values
    expanded from each position's latent), with logits at
    ``logits_positions`` positions of each row."""
    h = s["heads"]
    pairs = seq * (seq + 1) // 2
    expand = s["kv_lora"] * h * (s["nope_dim"] + s["v_dim"])
    attn = pairs * h * (s["nope_dim"] + s["rope_dim"] + s["v_dim"])
    per_row = seq * (s["layers"] * (_attn_proj(s) + expand) + _ffn(s)) \
        + s["layers"] * attn
    return batch * (2 * per_row + _head(s, logits_positions))


def _decode(s: dict, keys: int) -> int:
    """One decode token over ``keys`` cached positions, the query absorbed
    into the latent space: q into the latent, scores over latent and rope
    parts, the weighted latents, their expansion to the values."""
    h, kvl = s["heads"], s["kv_lora"]
    absorbed = (h * s["nope_dim"] * kvl + keys * h * (kvl + s["rope_dim"])
                + keys * h * kvl + h * kvl * s["v_dim"])
    return 2 * (s["layers"] * (_attn_proj(s) + absorbed) + _ffn(s)) \
        + _head(s, 1)


def served_request(s: dict, prompt_len: int, new_tokens: int) -> int:
    """One request: its prefill (logits at the last position), then one
    decode step for each generated token after the first."""
    return forward(s, 1, prompt_len, 1) + sum(
        _decode(s, prompt_len + j) for j in range(1, new_tokens))


def weight_bytes(s: dict) -> int:
    """Bytes of the layout (the weights this chip holds)."""
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(layout(s)))


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


def _swiglu(h, w_gate, w_up, w_down, mode):
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    gate = _mm("td,df->tf", h, f32(w_gate), mode)
    up = _mm("td,df->tf", h, f32(w_up), mode)
    return _mm("tf,fd->td", jax.nn.silu(gate) * up, f32(w_down), mode)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, y: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies (its
    ``DeepseekV2YarnRotaryEmbedding``): the original frequencies where a
    dimension turns more than ``beta_fast`` times over the original context,
    ``factor``-times lower ones where it turns fewer than ``beta_slow``
    times, a linear ramp between."""
    pos_freqs = theta ** (np.arange(0, dim, 2) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (y["factor"]
                                                           * pos_freqs)
    ctx = y["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(ctx / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extrapolation_mask = 1.0 - ramp
    return (interpolation * (1 - extrapolation_mask)
            + extrapolation * extrapolation_mask)


class Reference:
    """The reference for one configuration's ``shape`` block."""

    def __init__(self, shape: dict, mode: str = "f32"):
        if mode not in MODES:
            raise ValueError(f"mode {mode!r} not in {MODES}")
        self.s, self.mode = shape, mode
        self._layer = jax.jit(self._layer_fn, static_argnames="moe")
        self._head = jax.jit(self._head_fn)

    # the parts a test may alter, to show the comparison sees them
    def _inv_freq(self):
        s = self.s
        return yarn_inv_freq(s["rope_dim"], s["rope_theta"], s["yarn"])

    def _softmax_scale(self):
        s, y = self.s, self.s["yarn"]
        m = yarn_mscale(y["factor"], y["mscale_all_dim"])
        return m * m / math.sqrt(s["nope_dim"] + s["rope_dim"])

    def _rope(self, x, t):
        """Rotate the two halves of the last axis of x (N, T, ..., rope) by
        position; YaRN's cos/sin factor mscale / mscale_all_dim is 1 for
        equal mscales, as published."""
        y = self.s["yarn"]
        gain = (yarn_mscale(y["factor"], y["mscale"])
                / yarn_mscale(y["factor"], y["mscale_all_dim"]))
        half = x.shape[-1] // 2
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
            * jnp.asarray(self._inv_freq(), jnp.float32)
        shape = (1, t) + (1,) * (x.ndim - 3) + (half,)
        cos = (jnp.cos(ang) * gain).reshape(shape)
        sin = (jnp.sin(ang) * gain).reshape(shape)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def _route(self, probs):
        """(weights (N, E) with the chosen experts' weights, 0 elsewhere)."""
        s = self.s
        n, e = probs.shape
        g = s["groups"]
        best = probs.reshape(n, g, e // g).max(-1)
        groups = jnp.argsort(-best, axis=-1, stable=True)[:, :s["top_groups"]]
        kept = (jnp.arange(g)[None, None] == groups[:, :, None]).any(1)
        scores = jnp.where(jnp.repeat(kept, e // g, axis=1), probs, 0.0)
        chosen = jnp.argsort(-scores, axis=-1, stable=True)[:, :s["top_k"]]
        mask = (jnp.arange(e)[None, None] == chosen[:, :, None]).any(1)
        w = jnp.where(mask, probs, 0.0)
        if s["norm_topk"]:
            w = w / w.sum(-1, keepdims=True)
        return w * s["routed_scale"]

    def _moe(self, m, h):
        s, mode = self.s, self.mode
        logits = _mm("td,de->te", h, m["router"].astype(jnp.float32), mode)
        w = self._route(jax.nn.softmax(logits, axis=-1))
        out = _swiglu(h, m["shared_w_gate"], m["shared_w_up"],
                      m["shared_w_down"], mode)
        for j in range(s["held"]):
            out = out + w[:, s["first_held"] + j, None] * _swiglu(
                h, m["w_gate"][j], m["w_up"][j], m["w_down"][j], mode)
        return out

    def _attention(self, a, h):
        s, mode = self.s, self.mode
        f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
        n, t, _ = h.shape
        nope = s["nope_dim"]
        cq = _rms(_mm("btd,dr->btr", h, f32(a["w_dq"]), mode), a["q_norm"],
                  s["norm_eps"])
        q = _mm("btr,rhk->bthk", cq, f32(a["w_uq"]), mode)
        q = jnp.concatenate([q[..., :nope], self._rope(q[..., nope:], t)],
                            -1)
        ckv = _rms(_mm("btd,dr->btr", h, f32(a["w_dkv"]), mode),
                   a["kv_norm"], s["norm_eps"])
        k_rope = self._rope(_mm("btd,dr->btr", h, f32(a["w_kr"]), mode), t)
        k_nope = _mm("btr,rhk->bthk", ckv, f32(a["w_uk"]), mode)
        v = _mm("btr,rhk->bthk", ckv, f32(a["w_uv"]), mode)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None], k_nope.shape[:3] + k_rope.shape[-1:])], -1)
        outs = []
        for q0 in range(0, t, QUERY_BLOCK):
            qb = q[:, q0:q0 + QUERY_BLOCK]
            scores = _mm("bqhk,bshk->bhqs", qb, k, mode) \
                * self._softmax_scale()
            causal = (jnp.arange(t)[None, :]
                      <= q0 + jnp.arange(qb.shape[1])[:, None])
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            outs.append(_mm("bhqs,bshk->bqhk", jax.nn.softmax(scores, -1),
                            v, mode))
        o = jnp.concatenate(outs, axis=1)
        return _mm("bqhk,hkd->bqd", o, f32(a["w_o"]), mode)

    def _layer_fn(self, lp, x, moe: bool):
        s = self.s
        x = x + self._attention(lp["attn"], _rms(x, lp["norm1"],
                                                 s["norm_eps"]))
        h = _rms(x, lp["norm2"], s["norm_eps"])
        n, t, d = h.shape
        flat = h.reshape(n * t, d)
        if moe:
            out = self._moe(lp["moe"], flat)
        else:
            m = lp["mlp"]
            out = _swiglu(flat, m["w_gate"], m["w_up"], m["w_down"],
                          self.mode)
        return x + out.reshape(n, t, d)

    def _head_fn(self, params, x):
        h = _rms(x, params["final_norm"], self.s["norm_eps"])
        return _mm("btd,dv->btv", h,
                   params["embed"]["lm_head"].astype(jnp.float32), self.mode)

    def layers(self, params):
        """(layer params, whether its feed-forward block is MoE), in order;
        each layer sliced out of its stack only when it is reached."""
        for seg, moe in (("lead", False), ("blocks", True)):
            if seg not in params:
                continue
            stacked = params[seg]["layer0"]
            n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
            for i in range(n):
                yield jax.tree_util.tree_map(lambda w: w[i], stacked), moe

    def logits(self, params, tokens, *, last_only: bool = False):
        """float32 logits (N, T, V) of ``tokens`` (N, T), or (N, 1, V) of the
        last position, as a host array: the head runs on
        :data:`QUERY_BLOCK` positions at a time, since the logits of a
        check sample over 102400 ids (2.8 GB at 6 x 1152) need not fit on
        the device beside the weights and a second reference's."""
        tokens = jnp.asarray(tokens, jnp.int32)
        x = jnp.take(params["embed"]["tok_embed"], tokens, axis=0
                     ).astype(jnp.float32)
        for lp, moe in self.layers(params):
            x = self._layer(lp, x, moe=moe)
            del lp                  # one layer's slice on the device at a time
        if last_only:
            x = x[:, -1:]
        return np.concatenate(
            [np.asarray(self._head(params, x[:, t:t + QUERY_BLOCK]))
             for t in range(0, x.shape[1], QUERY_BLOCK)], axis=1)
