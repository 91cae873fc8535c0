"""Attention variants: GQA (w/ qk_norm, bias), MLA, and gated cross-attention.

Each variant exposes a schema plus an apply function that covers both the
full-sequence path (train / prefill) and the single-token cached decode path.
GQA keys/values are *broadcast* over query groups via einsum — never
materialized with repeat (that wasteful twin is paper case c4 in the zoo).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.models.layers import (ParamSpec, ParamTree, rms_norm, rope,
                                 yarn_mscale)
from repro.sharding.rules import constrain

Cache = dict[str, jax.Array]

# attention implementations of the cached path (chunked prefill into a
# cache, and decode).  The Pallas kernel has no cached form: it takes no
# cache length mask, so a cached call with it is refused, never rerouted.
CACHED_ATTN_IMPLS = ("xla", "xla_bf16", "chunked")


# ---------------------------------------------------------------------------
# schemas
# ---------------------------------------------------------------------------

def attention_schema(cfg: ModelConfig) -> ParamTree:
    if cfg.use_mla:
        return _mla_schema(cfg)
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = cfg.dtype
    sch: ParamTree = {
        "w_q": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"), dtype=dt),
        "w_k": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "w_v": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "w_o": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"), dtype=dt,
                         scale=0.02 / np.sqrt(2.0)),
    }
    if cfg.qkv_bias:
        sch["b_q"] = ParamSpec((h, hd), ("heads", "head_dim"), init="zeros", dtype=dt)
        sch["b_k"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros", dtype=dt)
        sch["b_v"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros", dtype=dt)
    if cfg.qk_norm:
        sch["q_norm"] = ParamSpec((hd,), (None,), init="ones", dtype="float32")
        sch["k_norm"] = ParamSpec((hd,), (None,), init="ones", dtype="float32")
    return sch


def _mla_schema(cfg: ModelConfig) -> ParamTree:
    d, h = cfg.d_model, cfg.num_heads
    hd = cfg.resolved_head_dim          # qk_nope head dim
    vd = cfg.resolved_v_head_dim
    r = cfg.rope_head_dim
    kvl, ql = cfg.kv_lora_rank, cfg.q_lora_rank
    dt = cfg.dtype
    sch: ParamTree = {
        "w_dkv": ParamSpec((d, kvl), ("embed", None), dtype=dt),
        "kv_norm": ParamSpec((kvl,), (None,), init="ones", dtype="float32"),
        "w_uk": ParamSpec((kvl, h, hd), (None, "heads", "head_dim"), dtype=dt),
        "w_uv": ParamSpec((kvl, h, vd), (None, "heads", "head_dim"), dtype=dt),
        "w_kr": ParamSpec((d, r), ("embed", None), dtype=dt),
        "w_o": ParamSpec((h, vd, d), ("heads", "head_dim", "embed"), dtype=dt,
                         scale=0.02 / np.sqrt(2.0)),
    }
    if ql:
        sch["w_dq"] = ParamSpec((d, ql), ("embed", None), dtype=dt)
        sch["q_norm"] = ParamSpec((ql,), (None,), init="ones", dtype="float32")
        sch["w_uq"] = ParamSpec((ql, h, hd + r), (None, "heads", "head_dim"), dtype=dt)
    else:
        sch["w_q"] = ParamSpec((d, h, hd + r), ("embed", "heads", "head_dim"), dtype=dt)
    return sch


def cross_attention_schema(cfg: ModelConfig) -> ParamTree:
    sch = attention_schema(cfg)
    sch["attn_gate"] = ParamSpec((), (), init="zeros", dtype="float32")
    return sch


# ---------------------------------------------------------------------------
# core scaled-dot-product with GQA grouping (broadcast, not repeat)
# ---------------------------------------------------------------------------

def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array | None,
          *, scale: float, score_dtype=jnp.float32) -> jax.Array:
    """q: (B,S,H,D); k/v: (B,T,KV,D[v]); returns (B,S,H,Dv).

    score_dtype=bf16 halves the (S,T) matrix's HBM traffic (§Perf lever
    'xla_bf16'): scores and probabilities live at 2 bytes; numerical safety
    comes from the max-subtraction (exp <= 1) plus an f32 softmax
    denominator, so only the per-element probability quantization (~2^-8
    relative) remains — gradients are unaffected at bf16 training precision.
    """
    b, s, h, dq = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, dq)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                        k).astype(score_dtype) * score_dtype(scale)
    if mask is not None:
        scores = jnp.where(mask, scores, score_dtype(-1e30))
    m = jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m)
    denom = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
    w = (p / denom.astype(score_dtype)).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(b, s, h, v.shape[-1])


def _causal_mask(s: int, t: int, q_offset: jax.Array | int) -> jax.Array:
    qi = jnp.arange(s)[:, None] + q_offset
    kj = jnp.arange(t)[None, :]
    return (kj <= qi)[None, None, None, :, :]   # (1,1,1,S,T)


def _length_mask(t: int, length: jax.Array) -> jax.Array:
    kj = jnp.arange(t)
    return (kj < length)[None, None, None, None, :]   # (1,1,1,1,T)


def _chunked_sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
                  causal: bool, q_offset: jax.Array | int = 0,
                  valid_len: jax.Array | None = None,
                  num_chunks: int = 16) -> jax.Array:
    """Online-softmax attention over KV chunks — the flash-attention
    recurrence expressed in XLA (beyond-paper §Perf lever).

    Never materializes the full (S,T) score matrix: each chunk's scores are
    one (B,KV,G,S,T/chunks) tile, and XLA loop-fuses the mask/exp/rescale
    chain into ~2 HBM passes per tile instead of the naive path's ~12 over
    the full matrix.  The chunk loop is Python-unrolled so the dry-run's
    cost_analysis prices every chunk (an inner lax.scan body would be
    counted once).  The Pallas kernel (kernels/flash_attention.py) is the
    TPU-native version of the same recurrence with the tile kept in VMEM.

    q: (B,S,H,D); k/v: (B,T,KV,D).  valid_len masks a partially-filled
    decode cache; q_offset aligns causal positions for cached decode.
    """
    b, s, h, d = q.shape
    t_total, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, d)
    nc = num_chunks
    while t_total % nc != 0:
        nc //= 2
    bk = t_total // nc

    m = jnp.full((b, kvh, g, s, 1), -1e30, jnp.float32)
    l = jnp.zeros((b, kvh, g, s, 1), jnp.float32)
    acc = jnp.zeros((b, kvh, g, s, v.shape[-1]), jnp.float32)
    qi = jnp.arange(s)[:, None] + q_offset                    # (S,1)

    for c in range(nc):
        ks = k[:, c * bk:(c + 1) * bk]
        vs = v[:, c * bk:(c + 1) * bk]
        scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                            ks).astype(jnp.float32) * scale
        kj = c * bk + jnp.arange(bk)[None, :]                 # (1,bk)
        mask = None
        if causal:
            mask = kj <= qi
        if valid_len is not None:
            vm = kj < valid_len
            mask = vm if mask is None else jnp.logical_and(mask, vm)
        if mask is not None:
            scores = jnp.where(mask[None, None, None], scores, -1e30)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bkgst,btkd->bkgsd", p,
                                      vs.astype(jnp.float32))
        m = m_new

    out = acc / jnp.where(l == 0.0, 1.0, l)
    return (out.astype(v.dtype)
            .transpose(0, 3, 1, 2, 4).reshape(b, s, h, v.shape[-1]))


# ---------------------------------------------------------------------------
# the stacked KV cache of the cached path (GQA and MLA)
# ---------------------------------------------------------------------------

def _read_layout(ndim: int) -> Layout:
    """The layout the score and value dots read a stacked (L, B, T, ...,
    D) cache in: positions and the feature axis minor, so each (layer,
    batch, head) block of (T, D) is contiguous."""
    return Layout(major_to_minor=(0, 1) + tuple(range(3, ndim - 1))
                  + (2, ndim - 1))


def cache_write(cache: Cache, layer: jax.Array, cache_pos: jax.Array,
                mesh: Mesh | None,
                **new: jax.Array) -> tuple[Cache, list[jax.Array]]:
    """Write the new positions of one layer into the stacked cache.

    ``cache`` leaves are (L, B, T, ..., D), stacked over the layers; each
    ``new`` entry is (B, S, ..., D) and lands at ``(layer, :, cache_pos)``,
    so only S positions are written and the stack is updated in place when
    it is a scan carry of a donated buffer.  Copying the layer's whole (B,
    T, ...) slice out and back instead is zoo case c2-cache-copy.

    Unpartitioned (``mesh`` None), the stack is held in the layout the
    attention reads it in: left free, the TPU compiler lays the scan carry
    out for the one-position write, and then transposes the whole cache on
    entry and each layer's slice before its dots.  A partitioned cache is
    left to the compiler, since the SPMD partitioner replicates the operand
    of a layout constraint.  Returns the updated stack and, for each
    ``new`` entry in order, the layer's (B, T, ..., D) view of it to attend
    over.
    """
    out = dict(cache)
    for name, val in new.items():
        buf = cache[name]
        start = (layer, 0, cache_pos) + (0,) * (buf.ndim - 3)
        out[name] = jax.lax.dynamic_update_slice(
            buf, val[None].astype(buf.dtype), start)
        if mesh is None:
            out[name] = with_layout_constraint(out[name],
                                               _read_layout(buf.ndim))
    return out, [jax.lax.dynamic_index_in_dim(out[name], layer, 0,
                                              keepdims=False)
                 for name in new]


# ---------------------------------------------------------------------------
# GQA apply
# ---------------------------------------------------------------------------

def gqa_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Cache:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((batch, max_len, kv, hd), dt),
        "v": jnp.zeros((batch, max_len, kv, hd), dt),
    }


def gqa_apply(cfg: ModelConfig, params: ParamTree, x: jax.Array,
              positions: jax.Array, *, mesh: Mesh | None = None,
              cache: Cache | None = None, layer: jax.Array | None = None,
              cache_pos: jax.Array | None = None,
              causal: bool = True, attn_impl: str = "xla") -> tuple[jax.Array, Cache | None]:
    """x: (B,S,d).  With a cache, S is the new-token count (1 for decode):
    the stacked cache is updated at ``layer`` (see :func:`cache_write`) and
    returned."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["w_q"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["w_k"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["w_v"])
    if cfg.qkv_bias:
        q = q + params["b_q"]
        k = k + params["b_k"]
        v = v + params["b_v"]
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if cfg.is_causal or cfg.family != "audio":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    if mesh is not None:
        tp = int(mesh.shape.get("model", 1))
        if cfg.num_heads % tp == 0 or x.shape[1] == 1:
            q = constrain(q, mesh, ("batch", None, "heads", None))
            k = constrain(k, mesh, ("batch", None, "kv_heads", None))
            v = constrain(v, mesh, ("batch", None, "kv_heads", None))
        else:
            # Sequence-parallel attention (§Perf lever): when the head count
            # does not divide the TP axis, head-sharding falls back to full
            # replication — 16x redundant attention compute plus q/k/v
            # all-gathers.  Sharding the *query rows* over the model axis
            # instead keeps the S^2 score tile and its FLOPs 16-way sharded;
            # only the (much smaller) K/V heads are gathered.
            q = constrain(q, mesh, ("batch", "seq_sp", None, None))
            k = constrain(k, mesh, ("batch", None, None, None))
            v = constrain(v, mesh, ("batch", None, None, None))

    scale = 1.0 / float(np.sqrt(hd))
    new_cache = None
    if cache is not None:
        assert cache_pos is not None
        if attn_impl not in CACHED_ATTN_IMPLS:
            raise ValueError(
                f"attn_impl={attn_impl!r} has no cached attention path "
                f"(prefill into a cache, decode); use one of "
                f"{CACHED_ATTN_IMPLS}")
        new_cache, (k_all, v_all) = cache_write(cache, layer, cache_pos,
                                                mesh, k=k, v=v)
        t = k_all.shape[1]
        if attn_impl == "chunked":
            out = _chunked_sdpa(q, k_all, v_all, scale=scale, causal=causal,
                                q_offset=cache_pos,
                                valid_len=cache_pos + x.shape[1])
        else:
            mask = _length_mask(t, cache_pos + x.shape[1])
            if x.shape[1] > 1 and causal:   # chunked prefill into cache
                mask = jnp.logical_and(mask,
                                       _causal_mask(x.shape[1], t, cache_pos))
            out = _sdpa(q, k_all, v_all, mask, scale=scale,
                        score_dtype=(jnp.bfloat16 if attn_impl == "xla_bf16"
                                     else jnp.float32))
    else:
        if attn_impl == "pallas":
            from repro.kernels import ops as kops
            # kernel layout is (B,H,S,D); model layout is (B,S,H,D).
            out = kops.flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=causal,
                sm_scale=scale).transpose(0, 2, 1, 3)
        elif attn_impl == "chunked":
            out = _chunked_sdpa(q, k, v, scale=scale, causal=causal)
        else:
            mask = _causal_mask(x.shape[1], x.shape[1], 0) if causal else None
            out = _sdpa(q, k, v, mask, scale=scale,
                        score_dtype=(jnp.bfloat16 if attn_impl == "xla_bf16"
                                     else jnp.float32))
    y = jnp.einsum("bshk,hkd->bsd", out, params["w_o"])
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA apply (DeepSeek-V2): compressed latent cache + absorbed decode
# ---------------------------------------------------------------------------

def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Cache:
    dt = jnp.dtype(cfg.dtype)
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_len, cfg.rope_head_dim), dt),
    }


# rows of a wave prefilled at a time: the expanded keys and the float32
# (heads, S, T) scores of one row are large at 128 heads
MLA_PREFILL_ROWS = 4


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale(factor, mscale_all_dim)**2."""
    scale = 1.0 / float(np.sqrt(cfg.resolved_head_dim + cfg.rope_head_dim))
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_q(cfg: ModelConfig, params: ParamTree, x: jax.Array,
           positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    hd, r = cfg.resolved_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, params["w_dq"])
        cq = rms_norm(cq, params["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["w_q"])
    q_nope, q_rope = q[..., :hd], q[..., hd:hd + r]
    q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def _mla_expanded(cfg: ModelConfig, params: ParamTree, x: jax.Array,
                  positions: jax.Array, c_kv: jax.Array, k_rope: jax.Array,
                  mask: jax.Array | None) -> jax.Array:
    """Attention of the queries of ``x`` (B,S,d) with every head's keys and
    values expanded from the latent ``c_kv`` (B,T,kv_lora) and the shared
    ``k_rope`` (B,T,rope); returns the output projection (B,S,d)."""
    q_nope, q_rope = _mla_q(cfg, params, x, positions)
    k_nope = jnp.einsum("btr,rhk->bthk", c_kv, params["w_uk"])
    v = jnp.einsum("btr,rhk->bthk", c_kv, params["w_uv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  k_nope.shape[:3] + (cfg.rope_head_dim,))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = _sdpa(q, k, v, mask, scale=mla_softmax_scale(cfg))
    return jnp.einsum("bshk,hkd->bsd", out, params["w_o"])


def _mla_absorbed(cfg: ModelConfig, params: ParamTree, x: jax.Array,
                  positions: jax.Array, c_all: jax.Array, r_all: jax.Array,
                  mask: jax.Array) -> jax.Array:
    """Absorbed decode: project q into the latent space once, attend over the
    compressed cache, then expand through w_uv.  This is the energy win MLA
    exists for — the cache stays (T, kv_lora + rope) per token.  fp32
    contraction: the absorbed order reassociates the bf16 matmuls, and
    per-layer rounding would compound through deep stacks."""
    f32 = jnp.float32
    q_nope, q_rope = _mla_q(cfg, params, x, positions)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope.astype(f32),
                       params["w_uk"].astype(f32))
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_all.astype(f32))
              + jnp.einsum("bshk,btk->bhst", q_rope.astype(f32),
                           r_all.astype(f32))) * mla_softmax_scale(cfg)
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhst,btr->bshr", w, c_all.astype(f32))
    out = jnp.einsum("bshr,rhk->bshk", out_lat,
                     params["w_uv"].astype(f32)).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["w_o"])


def _in_row_blocks(fn, rows: int, *args: jax.Array) -> jax.Array:
    """``fn`` over blocks of ``rows`` batch rows of ``args`` (each (B, ...)),
    one block after another; the blocks' outputs joined back to (B, ...)."""
    b = args[0].shape[0]
    rows = math.gcd(b, rows)
    split = [a.reshape((b // rows, rows) + a.shape[1:]) for a in args]
    out = jax.lax.map(lambda xs: fn(*xs), split)
    return out.reshape((b,) + out.shape[2:])


def mla_apply(cfg: ModelConfig, params: ParamTree, x: jax.Array,
              positions: jax.Array, *, mesh: Mesh | None = None,
              cache: Cache | None = None, layer: jax.Array | None = None,
              cache_pos: jax.Array | None = None,
              causal: bool = True, attn_impl: str = "xla") -> tuple[jax.Array, Cache | None]:
    """x: (B,S,d).  Without a cache, keys and values are expanded from the
    latent of the S tokens.  With one, the new latents are written into the
    stacked cache at ``layer`` (see :func:`cache_write`); one new token per
    row (decode) attends absorbed over the latent cache, a prompt (S > 1)
    expanded over it, :data:`MLA_PREFILL_ROWS` rows at a time."""
    with jax.named_scope("mla"):
        s = x.shape[1]
        c_kv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"])
        c_kv = rms_norm(c_kv, params["kv_norm"], cfg.norm_eps)
        k_rope = rope(jnp.einsum("bsd,dr->bsr", x,
                                 params["w_kr"])[:, :, None, :],
                      positions, cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
        if cache is None:
            mask = _causal_mask(s, s, 0) if causal else None
            return _mla_expanded(cfg, params, x, positions, c_kv, k_rope,
                                 mask), None
        assert cache_pos is not None
        new_cache, (c_all, r_all) = cache_write(cache, layer, cache_pos,
                                                mesh, c_kv=c_kv,
                                                k_rope=k_rope)
        t = c_all.shape[1]
        mask = _length_mask(t, cache_pos + s)             # (1,1,1,1,T)
        if s > 1 and causal:
            mask = jnp.logical_and(mask, _causal_mask(s, t, cache_pos))
        if s == 1:
            y = _mla_absorbed(cfg, params, x, positions, c_all, r_all,
                              mask[:, :, 0])
        else:
            y = _in_row_blocks(
                lambda *a: _mla_expanded(cfg, params, *a, mask),
                MLA_PREFILL_ROWS, x, positions, c_all, r_all)
        return y, new_cache


# ---------------------------------------------------------------------------
# gated cross-attention (llama-3.2-vision)
# ---------------------------------------------------------------------------

def cross_init_cache(cfg: ModelConfig, batch: int, num_img: int) -> Cache:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "k_img": jnp.zeros((batch, num_img, kv, hd), dt),
        "v_img": jnp.zeros((batch, num_img, kv, hd), dt),
    }


def cross_apply(cfg: ModelConfig, params: ParamTree, x: jax.Array,
                image_embeds: jax.Array | None, *, mesh: Mesh | None = None,
                cache: Cache | None = None,
                attn_impl: str = "xla") -> tuple[jax.Array, Cache | None]:
    """Cross-attend x (B,S,d) to image patch embeddings (B,N,d)."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["w_q"])
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
    new_cache = None
    if image_embeds is not None:
        k = jnp.einsum("bnd,dhk->bnhk", image_embeds, params["w_k"])
        v = jnp.einsum("bnd,dhk->bnhk", image_embeds, params["w_v"])
        if cfg.qk_norm:
            k = rms_norm(k, params["k_norm"], cfg.norm_eps)
        if cache is not None:
            new_cache = {"k_img": k, "v_img": v}
    else:
        assert cache is not None, "decode needs a prefilled image-KV cache"
        k, v = cache["k_img"], cache["v_img"]
        new_cache = cache
    out = _sdpa(q, k, v, None, scale=1.0 / float(np.sqrt(hd)))
    y = jnp.einsum("bshk,hkd->bsd", out, params["w_o"])
    gate = jnp.tanh(params["attn_gate"]).astype(y.dtype)
    return y * gate, new_cache
