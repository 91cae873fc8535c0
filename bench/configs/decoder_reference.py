"""Plain reference of the decoder the configurations in this directory run.

A pre-norm decoder: token embedding; per layer RMSNorm, grouped-query
attention with rotary embeddings (the two halves of each head rotated) and a
causal mask, a residual add, RMSNorm, a SwiGLU MLP and a residual add; a
final RMSNorm and the LM head (the embedding's transpose when tied).  It is
written from that description in ``jax.numpy`` alone, layer by layer in a
Python loop, with every matrix product at ``HIGHEST`` precision in float32,
and imports nothing of the program under test.

``mode="fp8"`` is the control: the same computation with every operand of
every matrix product rounded to float8 e4m3 with a per-tensor scale, as a
lower-precision serving path would compute it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "fp8")


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

    def _layer_fn(self, lp, x):
        s, mode = self.s, self.mode
        f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
        n, t, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(t)[None], (n, t))
        h = _rms(x, lp["norm1"], s["norm_eps"])
        a = lp["attn"]
        q = _rope(_mm("btd,dhk->bthk", h, f32(a["w_q"]), mode), pos,
                  s["rope_theta"])
        k = _rope(_mm("btd,dhk->bthk", h, f32(a["w_k"]), mode), pos,
                  s["rope_theta"])
        v = _mm("btd,dhk->bthk", h, f32(a["w_v"]), mode)
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
