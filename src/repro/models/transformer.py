"""Model assembly: superblock schemas, scan-stacked forward, prefill/decode.

The layer stack is ``cfg.num_superblocks`` repetitions of the
``cfg.block_pattern`` superblock, scanned with jax.lax.scan (HLO size O(1) in
depth) and rematerialized per superblock, after ``cfg.first_k_dense``
leading dense layers scanned the same way (:func:`segments`).  Params and
caches hold one pytree per segment, whose leaves carry a leading stack
dimension.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (ParamSpec, ParamTree, abstract_params,
                                 cross_entropy, embed_tokens, embedding_schema,
                                 init_params, lm_head, mlp_apply, mlp_schema,
                                 param_shardings, rms_norm, stack_schema)
from repro.sharding.rules import constrain

# ---------------------------------------------------------------------------
# schemas
# ---------------------------------------------------------------------------

def layer_schema(cfg: ModelConfig, spec: LayerSpec) -> ParamTree:
    d, dt = cfg.d_model, cfg.dtype
    sch: ParamTree = {
        "norm1": ParamSpec((d,), ("embed_act",), init="ones", dtype="float32"),
    }
    if spec.mixer == "attn":
        sch["attn"] = attn_mod.attention_schema(cfg)
    elif spec.mixer == "cross_attn":
        sch["attn"] = attn_mod.cross_attention_schema(cfg)
    elif spec.mixer == "mamba":
        sch["mamba"] = ssm_mod.mamba_schema(cfg)
    elif spec.mixer == "mlstm":
        sch["mlstm"] = xlstm_mod.mlstm_schema(cfg)
    elif spec.mixer == "slstm":
        sch["slstm"] = xlstm_mod.slstm_schema(cfg)
    else:
        raise ValueError(f"unknown mixer {spec.mixer}")
    if spec.ffn == "dense":
        sch["norm2"] = ParamSpec((d,), ("embed_act",), init="ones", dtype="float32")
        sch["mlp"] = mlp_schema(d, cfg.d_ff, dt)
    elif spec.ffn == "moe":
        sch["norm2"] = ParamSpec((d,), ("embed_act",), init="ones", dtype="float32")
        sch["moe"] = moe_mod.moe_schema(cfg)
    return sch


LEAD = (LayerSpec("attn", "dense"),)


def segments(cfg: ModelConfig) -> list[tuple[str, tuple[LayerSpec, ...], int]]:
    """The stack's scanned segments in order, as (name, pattern, count): the
    leading dense layers (``lead``) if any, then the superblocks
    (``blocks``)."""
    lead = [("lead", LEAD, cfg.first_k_dense)] if cfg.first_k_dense else []
    return lead + [("blocks", cfg.block_pattern, cfg.num_superblocks)]


def superblock_schema(cfg: ModelConfig,
                      pattern: tuple[LayerSpec, ...] | None = None) -> ParamTree:
    return {f"layer{i}": layer_schema(cfg, spec)
            for i, spec in enumerate(pattern or cfg.block_pattern)}


def model_schema(cfg: ModelConfig) -> ParamTree:
    sch: ParamTree = {
        "embed": embedding_schema(cfg.vocab_size, cfg.d_model, cfg.dtype,
                                  cfg.tie_embeddings),
        **{name: stack_schema(superblock_schema(cfg, pattern), n)
           for name, pattern, n in segments(cfg)},
        "final_norm": ParamSpec((cfg.d_model,), ("embed_act",), init="ones",
                                dtype="float32"),
    }
    return sch


def model_abstract_params(cfg: ModelConfig) -> ParamTree:
    return abstract_params(model_schema(cfg))


def model_param_shardings(cfg: ModelConfig, mesh: Mesh) -> ParamTree:
    return param_shardings(model_schema(cfg), mesh)


def model_init(cfg: ModelConfig, key) -> ParamTree:
    return init_params(model_schema(cfg), key)


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    from repro.models.layers import count_schema_params
    total = count_schema_params(model_schema(cfg))
    if active_only and cfg.moe_num_experts:
        # a token meets k of the E experts, k * held / E of those held here
        e, k = cfg.moe_num_experts, cfg.moe_top_k
        routed = 0
        for spec in cfg.block_pattern:
            if spec.ffn == "moe":
                routed += 3 * cfg.d_model * cfg.resolved_moe_d_ff \
                    * cfg.held_experts[1]
        routed *= cfg.num_superblocks
        total -= int(routed * (1.0 - k / e))
    return total


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                max_len: int) -> dict | None:
    if spec.mixer == "attn":
        if cfg.use_mla:
            return attn_mod.mla_init_cache(cfg, batch, max_len)
        return attn_mod.gqa_init_cache(cfg, batch, max_len)
    if spec.mixer == "cross_attn":
        return attn_mod.cross_init_cache(cfg, batch, cfg.num_image_tokens)
    if spec.mixer == "mamba":
        return ssm_mod.mamba_init_cache(cfg, batch)
    if spec.mixer == "mlstm":
        return xlstm_mod.mlstm_init_cache(cfg, batch)
    if spec.mixer == "slstm":
        return xlstm_mod.slstm_init_cache(cfg, batch)
    raise ValueError(spec.mixer)


def _segment_caches(cfg: ModelConfig, batch: int, max_len: int, stacked):
    """One cache pytree per segment; ``stacked(leaf, n)`` stacks a layer's
    leaf n deep."""
    out = {}
    for name, pattern, n in segments(cfg):
        one = {f"layer{i}": layer_cache(cfg, spec, batch, max_len)
               for i, spec in enumerate(pattern)}
        out[name] = jax.tree_util.tree_map(lambda a: stacked(a, n), one)
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> ParamTree:
    """Decode cache pytree: per segment, leaves with a leading stack dim."""
    return _segment_caches(
        cfg, batch, max_len,
        lambda a, n: jnp.broadcast_to(a[None], (n,) + a.shape).copy())


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int) -> ParamTree:
    return _segment_caches(
        cfg, batch, max_len,
        lambda a, n: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype))


def cache_logical_axes(cfg: ModelConfig) -> ParamTree:
    """Logical sharding axes for every cache leaf (for in/out_shardings)."""
    def axes_for(name: str, leaf_shape_len: int, mixer: str):
        if mixer in ("attn",):
            if cfg.use_mla:
                return ("stack", "batch", "kv_seq", None)
            return ("stack", "batch", "kv_seq", "kv_heads", None)
        if mixer == "cross_attn":
            return ("stack", "batch", None, "kv_heads", None)
        if mixer == "mamba":
            return {"conv": ("stack", "batch", None, "ssm_inner"),
                    "h": ("stack", "batch", "ssm_inner", None)}[name]
        if mixer == "mlstm":
            return {"C": ("stack", "batch", "heads", None, None),
                    "n": ("stack", "batch", "heads", None),
                    "m": ("stack", "batch", "heads"),
                    "conv": ("stack", "batch", None, "ssm_inner")}[name]
        if mixer == "slstm":
            return ("stack", "batch", None)
        raise ValueError(mixer)

    out: dict = {}
    for seg, pattern, _ in segments(cfg):
        out[seg] = {}
        for i, spec in enumerate(pattern):
            lc = layer_cache(cfg, spec, 1, 8)
            out[seg][f"layer{i}"] = {k: axes_for(k, v.ndim + 1, spec.mixer)
                                     for k, v in lc.items()}
    return out


# ---------------------------------------------------------------------------
# layer / superblock application
# ---------------------------------------------------------------------------

def layer_apply(cfg: ModelConfig, spec: LayerSpec, params: ParamTree,
                x: jax.Array, positions: jax.Array, *,
                mesh: Mesh | None = None, cache: dict | None = None,
                layer=None, cache_pos=None,
                image_embeds: jax.Array | None = None,
                decode: bool = False, attn_impl: str = "xla"):
    """One layer.  ``cache`` is this layer's entry of the stacked cache
    (leaves (L, ...)), updated at index ``layer`` and returned: an attention
    cache gets only its new positions written, a recurrent state or the
    image K/V its whole (small) slice."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    new_cache = None
    if spec.mixer == "attn":
        apply = attn_mod.mla_apply if cfg.use_mla else attn_mod.gqa_apply
        out, new_cache = apply(cfg, params["attn"], h, positions, mesh=mesh,
                               cache=cache, layer=layer, cache_pos=cache_pos,
                               causal=cfg.is_causal, attn_impl=attn_impl)
    else:
        own = None if cache is None else jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0,
                                                   keepdims=False), cache)
        if spec.mixer == "cross_attn":
            out, nc = attn_mod.cross_apply(cfg, params["attn"], h,
                                           image_embeds, mesh=mesh,
                                           cache=own, attn_impl=attn_impl)
        elif spec.mixer == "mamba":
            out, nc = ssm_mod.mamba_apply(cfg, params["mamba"], h, mesh=mesh,
                                          cache=own, decode=decode)
        elif spec.mixer == "mlstm":
            out, nc = xlstm_mod.mlstm_apply(cfg, params["mlstm"], h,
                                            mesh=mesh, cache=own,
                                            decode=decode)
        elif spec.mixer == "slstm":
            out, nc = xlstm_mod.slstm_apply(cfg, params["slstm"], h,
                                            mesh=mesh, cache=own,
                                            decode=decode)
        else:
            raise ValueError(spec.mixer)
        if cache is not None:
            new_cache = jax.tree_util.tree_map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), layer, 0), cache, nc)
    x = x + out
    if mesh is not None:
        x = constrain(x, mesh, ("batch", "seq_sp", None))

    if spec.ffn != "none":
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        if spec.ffn == "dense":
            out2 = mlp_apply(params["mlp"], h2, mesh=mesh)
        else:
            out2, aux = moe_mod.moe_apply(cfg, params["moe"], h2, mesh=mesh,
                                          layer=layer)
        x = x + out2
        if mesh is not None:
            x = constrain(x, mesh, ("batch", "seq_sp", None))
    return x, new_cache, aux


def superblock_apply(cfg: ModelConfig, params: ParamTree, x: jax.Array,
                     positions: jax.Array, *, mesh=None, cache=None,
                     layer=None, cache_pos=None, image_embeds=None,
                     decode=False, attn_impl="xla", pattern=None):
    """One superblock of ``pattern`` (default ``cfg.block_pattern``).
    ``cache`` is its segment's whole stacked cache (leaves with a leading
    superblock axis) and ``layer`` the superblock's index into it."""
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    for i, spec in enumerate(pattern or cfg.block_pattern):
        lc = cache[f"layer{i}"] if cache is not None else None
        x, nc, aux = layer_apply(cfg, spec, params[f"layer{i}"], x, positions,
                                 mesh=mesh, cache=lc, layer=layer,
                                 cache_pos=cache_pos,
                                 image_embeds=image_embeds, decode=decode,
                                 attn_impl=attn_impl)
        aux_total = aux_total + aux
        if nc is not None:
            new_cache[f"layer{i}"] = nc
    return x, (new_cache or None), aux_total


def stack_apply(cfg: ModelConfig, params: ParamTree, x: jax.Array,
                positions: jax.Array, *, mesh=None, caches=None,
                cache_pos=None, image_embeds=None, decode=False,
                remat: bool | str = True, attn_impl: str = "xla"):
    """Scan each segment's superblock over its stacked params/caches, the
    segments in order (``params`` and ``caches`` hold one entry per
    segment).

    The stacked caches ride in the scan's carry with the superblock index,
    so each layer writes only its new entries into the one buffer (in place
    when the caller donates it) instead of the scan slicing every layer's
    whole cache out and stacking it back.  Likewise, in the served steps
    (with caches), the held experts' weights stay whole
    (:func:`moe.split_expert_stacks`): each layer's grouped matmuls read its
    experts in the stack.  Without caches the scan slices them per layer, as
    a gradient through the whole stack would give every layer's backward a
    gradient the size of the whole stack.

    remat: False | True ("full": recompute everything in bwd) | "dots"
    (save matmul outputs: no fwd recompute of dots in bwd, so parameter
    all-gathers and the S^2 attention scores are not re-paid — §Perf lever;
    costs peak activation memory).
    """
    use_cache = caches is not None
    new_caches = {} if use_cache else None
    aux_total = jnp.zeros((), jnp.float32)
    for name, pattern, n in segments(cfg):
        scanned, whole = (moe_mod.split_expert_stacks(cfg, params[name], mesh)
                          if use_cache else (params[name], {}))
        indexed = use_cache or bool(whole)

        def body(carry, inp, pattern=pattern, whole=whole):
            xc, cc = carry if use_cache else (carry, None)
            i, p_i = inp if indexed else (None, inp)
            for lname, experts in whole.items():
                p_i = dict(p_i, **{lname: dict(
                    p_i[lname], moe=dict(p_i[lname]["moe"], **experts))})
            out, nc, aux = superblock_apply(cfg, p_i, xc, positions,
                                            mesh=mesh, cache=cc, layer=i,
                                            cache_pos=cache_pos,
                                            image_embeds=image_embeds,
                                            decode=decode,
                                            attn_impl=attn_impl,
                                            pattern=pattern)
            return ((out, nc) if use_cache else out), aux

        if remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_saveable)
        elif remat:
            body = jax.checkpoint(body)

        xs = (jnp.arange(n, dtype=jnp.int32), scanned) if indexed \
            else scanned
        if use_cache:
            (x, new_caches[name]), auxs = jax.lax.scan(
                body, (x, caches[name]), xs)
        else:
            x, auxs = jax.lax.scan(body, x, xs)
        aux_total = aux_total + jnp.sum(auxs)
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# public model functions
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: ParamTree, tokens: jax.Array | None,
            *, mesh: Mesh | None = None, inputs_embeds: jax.Array | None = None,
            image_embeds: jax.Array | None = None, remat: bool = True,
            attn_impl: str = "xla", logits_mode: str = "all"):
    """Full-sequence forward. Returns (logits, aux_loss)."""
    if inputs_embeds is not None:
        x = inputs_embeds.astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params["embed"], tokens)
    if mesh is not None:
        x = constrain(x, mesh, ("batch", "seq_sp", None))
    s = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s)[None], x.shape[:2])
    x, _, aux = stack_apply(cfg, params, x, positions, mesh=mesh,
                            image_embeds=image_embeds, remat=remat,
                            attn_impl=attn_impl)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if logits_mode == "last":
        x = x[:, -1:, :]
    logits = lm_head(params["embed"], x)
    if mesh is not None:
        logits = constrain(logits, mesh, ("batch", None, "vocab"))
    return logits, aux


def prefill(cfg: ModelConfig, params: ParamTree, tokens: jax.Array, *,
            mesh=None, max_len: int, image_embeds=None, remat: bool = True,
            attn_impl: str = "xla"):
    """Process the prompt, build the decode cache, return last-token logits.

    Only the final position's logits are computed (the efficient LMHead
    path; computing all-position logits during prefill is zoo case
    'lmhead-redundant' / hf-38977).
    """
    x = embed_tokens(params["embed"], tokens)
    if mesh is not None:
        x = constrain(x, mesh, ("batch", "seq_sp", None))
    s = x.shape[1]
    caches = init_cache(cfg, tokens.shape[0], max_len)
    positions = jnp.broadcast_to(jnp.arange(s)[None], x.shape[:2])
    x, new_caches, aux = stack_apply(cfg, params, x, positions,
                                     mesh=mesh, caches=caches,
                                     cache_pos=jnp.int32(0),
                                     image_embeds=image_embeds, remat=remat,
                                     attn_impl=attn_impl)
    x = rms_norm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    logits = lm_head(params["embed"], x)
    return logits, new_caches


def decode_step(cfg: ModelConfig, params: ParamTree, caches: ParamTree,
                tokens: jax.Array, pos, *, mesh=None,
                attn_impl: str = "xla"):
    """One decode step. tokens: (B,1); pos: scalar current length (or (B,))."""
    x = embed_tokens(params["embed"], tokens)
    if jnp.ndim(pos) == 0:
        pos_bc = pos[None, None]
    elif jnp.ndim(pos) == 1:
        pos_bc = pos[:, None]
    else:
        pos_bc = pos
    positions = jnp.broadcast_to(pos_bc, tokens.shape)
    x, new_caches, _ = stack_apply(cfg, params, x, positions,
                                   mesh=mesh, caches=caches, cache_pos=pos,
                                   decode=True, remat=False,
                                   attn_impl=attn_impl)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(params["embed"], x)
    return logits, new_caches
