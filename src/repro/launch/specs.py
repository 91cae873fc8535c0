"""Abstract input specs and sharded step builders for the dry-run.

Everything here operates on ShapeDtypeStructs — weak-type-correct, shardable,
zero device allocation — so the full production configs (up to 398B params,
512k contexts) lower and compile on the CPU container.

This module must stay importable WITHOUT the 512-device XLA flag; only
launch/dryrun.py sets that, as its first two lines, per the deployment
contract.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, get_config
from repro.models import transformer as tf
from repro.sharding.rules import GLOBAL_RULES
from repro.train.optimizer import (OptimizerConfig, abstract_opt_state,
                                   opt_state_shardings)
from repro.train.train_step import TrainConfig, make_train_step


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# input specs (deliverable: ShapeDtypeStruct stand-ins for every model input)
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    """Training-batch ShapeDtypeStructs for one arch x shape."""
    B, S = shape.global_batch, shape.seq_len
    out: dict[str, Any] = {}
    if cfg.family == "audio":
        out["frames"] = _sds((B, S, cfg.d_model), cfg.dtype)
    else:
        out["tokens"] = _sds((B, S), jnp.int32)
    out["labels"] = _sds((B, S), jnp.int32)
    if cfg.family == "vlm":
        out["image_embeds"] = _sds((B, cfg.num_image_tokens, cfg.d_model),
                                   cfg.dtype)
    return out


def input_specs(arch: str, shape_name: str = "train_4k") -> dict[str, Any]:
    """Public API per the deliverable: all model inputs as abstract specs."""
    cfg = get_config(arch)
    return batch_specs(cfg, SHAPES[shape_name])


def batch_shardings(mesh: Mesh, specs: dict[str, Any]) -> dict[str, Any]:
    out = {}
    for k, v in specs.items():
        axes: tuple = ("batch",) + (None,) * (len(v.shape) - 1)
        if k in ("tokens", "labels", "frames") and len(v.shape) >= 2:
            axes = ("batch", "seq") + (None,) * (len(v.shape) - 2)
        out[k] = GLOBAL_RULES.sharding(mesh, axes, v.shape)
    return out


# ---------------------------------------------------------------------------
# step builders (train / prefill / decode), with production shardings
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StepBundle:
    """Everything needed to lower one cell: fn, abstract args, shardings."""

    fn: Callable
    args: tuple
    in_shardings: tuple
    donate_argnums: tuple = ()
    meta: dict = dataclasses.field(default_factory=dict)


def train_bundle(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                 tcfg: TrainConfig = TrainConfig(),
                 opt_cfg: OptimizerConfig = OptimizerConfig()) -> StepBundle:
    params = tf.model_abstract_params(cfg)
    pshard = tf.model_param_shardings(cfg, mesh)
    opt = abstract_opt_state(params, opt_cfg)
    oshard = opt_state_shardings(pshard, opt_cfg, mesh)
    batch = batch_specs(cfg, shape)
    bshard = batch_shardings(mesh, batch)
    step = make_train_step(cfg, mesh, opt_cfg, tcfg)
    return StepBundle(fn=step, args=(params, opt, batch),
                      in_shardings=(pshard, oshard, bshard),
                      donate_argnums=(0, 1),
                      meta={"kind": "train"})


def _cache_shardings(cfg: ModelConfig, mesh: Mesh, caches) -> Any:
    axes = tf.cache_logical_axes(cfg)
    return jax.tree_util.tree_map(
        lambda leaf, ax: GLOBAL_RULES.sharding(mesh, ax, leaf.shape),
        caches, axes)


def prefill_bundle(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                   attn_impl: str = "xla") -> StepBundle:
    B, S = shape.global_batch, shape.seq_len
    params = tf.model_abstract_params(cfg)
    pshard = tf.model_param_shardings(cfg, mesh)

    if cfg.family == "audio":
        frames = _sds((B, S, cfg.d_model), cfg.dtype)
        fshard = GLOBAL_RULES.sharding(mesh, ("batch", "seq_sp", None),
                                       frames.shape)

        def fn(params, frames):
            logits, _ = tf.forward(cfg, params, None, inputs_embeds=frames,
                                   mesh=mesh, remat=True, attn_impl=attn_impl,
                                   logits_mode="last")
            return logits
        return StepBundle(fn=fn, args=(params, frames),
                          in_shardings=(pshard, fshard),
                          meta={"kind": "prefill"})

    tokens = _sds((B, S), jnp.int32)
    tshard = GLOBAL_RULES.sharding(mesh, ("batch", "seq"), tokens.shape)
    extra_args: tuple = ()
    extra_shard: tuple = ()
    if cfg.family == "vlm":
        img = _sds((B, cfg.num_image_tokens, cfg.d_model), cfg.dtype)
        extra_args = (img,)
        extra_shard = (GLOBAL_RULES.sharding(mesh, ("batch", None, None),
                                             img.shape),)

    def fn(params, tokens, *extra):
        img = extra[0] if extra else None
        logits, caches = tf.prefill(cfg, params, tokens, mesh=mesh,
                                    max_len=S, image_embeds=img,
                                    attn_impl=attn_impl)
        return logits, caches
    return StepBundle(fn=fn, args=(params, tokens) + extra_args,
                      in_shardings=(pshard, tshard) + extra_shard,
                      meta={"kind": "prefill"})


def decode_bundle(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                  attn_impl: str = "xla") -> StepBundle:
    """One decode step with a seq_len-deep cache (the assigned decode_* /
    long_* cells lower serve_step, not train_step)."""
    B, S = shape.global_batch, shape.seq_len
    params = tf.model_abstract_params(cfg)
    pshard = tf.model_param_shardings(cfg, mesh)
    caches = tf.abstract_cache(cfg, B, S)
    cshard = _cache_shardings(cfg, mesh, caches)
    tokens = _sds((B, 1), jnp.int32)
    tshard = GLOBAL_RULES.sharding(mesh, ("batch", None), tokens.shape)
    pos = _sds((), jnp.int32)
    posshard = NamedSharding(mesh, P())
    extra_args: tuple = ()
    extra_shard: tuple = ()

    def fn(params, caches, tokens, pos, *extra):
        logits, new_caches = tf.decode_step(cfg, params, caches, tokens, pos,
                                            mesh=mesh, attn_impl=attn_impl)
        return logits, new_caches

    return StepBundle(fn=fn, args=(params, caches, tokens, pos) + extra_args,
                      in_shardings=(pshard, cshard, tshard, posshard)
                      + extra_shard,
                      donate_argnums=(1,),
                      meta={"kind": "decode"})


def make_bundle(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                **kw) -> StepBundle:
    if shape.kind == "train":
        return train_bundle(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return prefill_bundle(cfg, shape, mesh, **kw)
    if shape.kind == "decode":
        return decode_bundle(cfg, shape, mesh, **kw)
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# segment bundles — one superblock, for the scan-trip-count cost correction
# ---------------------------------------------------------------------------

def superblock_segment(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                       train: bool, attn_impl: str = "xla",
                       remat: bool | str = True) -> StepBundle:
    """fwd(+bwd if train) of ONE superblock under production shardings.

    compiled.cost_analysis() does not multiply while-body costs by the trip
    count, so the roofline total is  full + (num_superblocks-1) * segment.
    """
    B = shape.global_batch
    S = shape.seq_len if shape.kind != "decode" else 1
    params_one = jax.tree_util.tree_map(
        lambda s: _sds(s.shape[1:], s.dtype),
        tf.model_abstract_params(cfg)["blocks"])
    pshard_one = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P(*tuple(s.spec)[1:])),
        tf.model_param_shardings(cfg, mesh)["blocks"])
    x = _sds((B, S, cfg.d_model), cfg.dtype)
    xshard = GLOBAL_RULES.sharding(mesh, ("batch", "seq_sp", None), x.shape)
    positions = _sds((B, S), jnp.int32)
    posshard = GLOBAL_RULES.sharding(mesh, ("batch", "seq"), positions.shape)
    img = None
    imgshard: tuple = ()
    if cfg.family == "vlm":
        img = _sds((B, cfg.num_image_tokens, cfg.d_model), cfg.dtype)
        imgshard = (GLOBAL_RULES.sharding(mesh, ("batch", None, None),
                                          img.shape),)

    cache_args: tuple = ()
    cache_shard: tuple = ()
    if shape.kind in ("decode", "prefill"):
        # a stack of one superblock: the layer writes into it at index 0
        cache_one = jax.tree_util.tree_map(
            lambda s: _sds((1,) + s.shape[1:], s.dtype),
            tf.abstract_cache(cfg, B, shape.seq_len))
        cshard_one = jax.tree_util.tree_map(
            lambda leaf, ax: GLOBAL_RULES.sharding(mesh, ax, leaf.shape),
            cache_one, tf.cache_logical_axes(cfg))
        cache_args = (cache_one,)
        cache_shard = (cshard_one,)

    if train:
        def fn(p, x, positions, *extra):
            image = extra[0] if (cfg.family == "vlm" and extra) else None

            def f(p_, x_):
                out, _, aux = tf.superblock_apply(cfg, p_, x_, positions,
                                                  mesh=mesh,
                                                  image_embeds=image,
                                                  attn_impl=attn_impl)
                return out, aux
            # match the train pipeline's remat policy
            if remat == "dots":
                f = jax.checkpoint(
                    f, policy=jax.checkpoint_policies.dots_saveable)
            elif remat:
                f = jax.checkpoint(f)
            (out, aux), vjp = jax.vjp(f, p, x)
            gp, gx = vjp((out, aux))
            return out, gp, gx
        args = (params_one, x, positions) + ((img,) if img is not None else ())
        shards = (pshard_one, xshard, posshard) + imgshard
    else:
        def fn(p, x, positions, *extra):
            idx = 0
            cache = None
            if shape.kind in ("decode", "prefill"):
                cache = extra[idx]
                idx += 1
            image = extra[idx] if (cfg.family == "vlm"
                                   and len(extra) > idx) else None
            out, nc, aux = tf.superblock_apply(
                cfg, p, x, positions, mesh=mesh, cache=cache,
                layer=jnp.int32(0),
                cache_pos=(positions[0, 0] if shape.kind == "decode"
                           else jnp.int32(0)),
                image_embeds=image, decode=(shape.kind == "decode"),
                attn_impl=attn_impl)
            return out, nc
        args = ((params_one, x, positions) + cache_args
                + ((img,) if img is not None else ()))
        shards = (pshard_one, xshard, posshard) + cache_shard + imgshard

    return StepBundle(fn=fn, args=args, in_shardings=shards,
                      meta={"kind": "segment",
                            "trips": cfg.num_superblocks})
