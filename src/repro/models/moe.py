"""Mixture-of-Experts with expert parallelism.

Two dispatch implementations of the SAME function:

  * ``moe_apply`` (production path): sort/gather-based dispatch — tokens are
    sorted by expert id, scattered into (E, capacity, d) buffers with
    byte-cost O(E*C*d), exchanged across the "model" mesh axis with
    all_to_all (EP), and combined back.  Dispatch costs *bytes*, not FLOPs.

  * ``moe_apply_einsum`` (GShard-style baseline): one-hot dispatch einsums
    costing 2*N*E*C*d FLOPs — for fine-grained-expert models (deepseek-v2:
    160 experts) this is orders of magnitude more compute than the experts
    themselves.  Kept as a first-class energy-waste case for the
    differential debugger (zoo case 'moe-dispatch').

``moe_reference`` is the dropless dense oracle used by tests.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import ParamSpec, ParamTree


def moe_schema(cfg: ModelConfig) -> ParamTree:
    d, e, f = cfg.d_model, cfg.moe_num_experts, cfg.resolved_moe_d_ff
    dt = cfg.dtype
    sch: ParamTree = {
        "router": ParamSpec((d, e), ("embed", None), dtype="float32", scale=0.01),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed", "expert_ffn"), dtype=dt),
        "w_up": ParamSpec((e, d, f), ("experts", "embed", "expert_ffn"), dtype=dt),
        "w_down": ParamSpec((e, f, d), ("experts", "expert_ffn", "embed"), dtype=dt,
                            scale=0.02 / np.sqrt(2.0)),
    }
    if cfg.moe_num_shared:
        s = cfg.moe_num_shared
        sch["shared_w_gate"] = ParamSpec((d, s * f), ("embed", "ffn"), dtype=dt)
        sch["shared_w_up"] = ParamSpec((d, s * f), ("embed", "ffn"), dtype=dt)
        sch["shared_w_down"] = ParamSpec((s * f, d), ("ffn", "embed"), dtype=dt,
                                         scale=0.02 / np.sqrt(2.0))
    return sch


def _capacity(num_tokens: int, k: int, e: int, factor: float) -> int:
    c = int(np.ceil(num_tokens * k / e * factor))
    return max(4, -(-c // 4) * 4)


def _router(cfg: ModelConfig, params: ParamTree, x_flat: jax.Array):
    """top-k routing probabilities. x_flat: (N, d) -> ids (N,k), w (N,k), probs."""
    logits = jnp.einsum("nd,de->ne", x_flat.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, cfg.moe_top_k)
    w = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_ids, w.astype(x_flat.dtype), probs


def _aux_loss(cfg: ModelConfig, probs: jax.Array, top_ids: jax.Array) -> jax.Array:
    """Switch-style load-balancing loss (fp32)."""
    e = cfg.moe_num_experts
    me = jnp.mean(probs, axis=0)                               # (E,)
    counts = jnp.zeros((e,), jnp.float32).at[top_ids.reshape(-1)].add(1.0)
    ce = counts / jnp.maximum(1.0, top_ids.size)
    return e * jnp.sum(me * ce)


def _expert_ffn(buf: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """buf: (E_loc, C, d)."""
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _dispatch_local(cfg: ModelConfig, x_flat: jax.Array, top_ids, top_w,
                    capacity: int):
    """Sort-based dispatch. Returns (buffers (E,C,d), slot, tok_idx, keep)."""
    n, d = x_flat.shape
    k, e = cfg.moe_top_k, cfg.moe_num_experts
    flat_e = top_ids.reshape(-1)                       # (N*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    pos_global = jnp.arange(n * k)
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos_in_expert = pos_global - starts[sorted_e]
    keep = pos_in_expert < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos_in_expert, e * capacity)
    tok_idx = order // k
    x_sorted = jnp.take(x_flat, tok_idx, axis=0)       # (N*k, d)
    buf = jnp.zeros((e * capacity, d), x_flat.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], x_sorted, 0), mode="drop")
    return buf.reshape(e, capacity, d), slot, tok_idx, order, keep


def _combine_local(cfg: ModelConfig, out_buf: jax.Array, slot, tok_idx, order,
                   keep, top_w, n: int) -> jax.Array:
    e, c, d = out_buf.shape
    flat_out = out_buf.reshape(e * c, d)
    y_sorted = jnp.take(flat_out, jnp.minimum(slot, e * c - 1), axis=0)
    y_sorted = jnp.where(keep[:, None], y_sorted, 0)
    w_sorted = top_w.reshape(-1)[order][:, None].astype(y_sorted.dtype)
    y = jnp.zeros((n, d), y_sorted.dtype)
    return y.at[tok_idx].add(y_sorted * w_sorted)


def _moe_local(cfg: ModelConfig, params: ParamTree, x: jax.Array,
               *, ep_axis: str | None) -> tuple[jax.Array, jax.Array]:
    """Per-shard MoE body (runs inside shard_map when ep_axis is set)."""
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    n = b * s
    top_ids, top_w, probs = _router(cfg, params, x_flat)
    aux = _aux_loss(cfg, probs, top_ids)
    cap = _capacity(n, cfg.moe_top_k, cfg.moe_num_experts, cfg.capacity_factor)
    buf, slot, tok_idx, order, keep = _dispatch_local(cfg, x_flat, top_ids,
                                                      top_w, cap)
    if ep_axis is not None:
        ep = jax.lax.axis_size(ep_axis)
        # (E, C, d) -> (E/ep, C*ep, d): each shard keeps its local experts'
        # slots from every peer.
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
        out = _expert_ffn(buf, params["w_gate"], params["w_up"], params["w_down"])
        out = jax.lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                                 tiled=True)
        del ep
    else:
        out = _expert_ffn(buf, params["w_gate"], params["w_up"], params["w_down"])
    y = _combine_local(cfg, out, slot, tok_idx, order, keep, top_w, n)
    if cfg.moe_num_shared:
        g = jnp.einsum("nd,df->nf", x_flat, params["shared_w_gate"])
        u = jnp.einsum("nd,df->nf", x_flat, params["shared_w_up"])
        y = y + jnp.einsum("nf,fd->nd", jax.nn.silu(g) * u,
                           params["shared_w_down"])
    return y.reshape(b, s, d), aux


def moe_apply(cfg: ModelConfig, params: ParamTree, x: jax.Array,
              *, mesh: Mesh | None = None) -> tuple[jax.Array, jax.Array]:
    """Production MoE layer. x: (B,S,d) -> (y, aux_loss)."""
    use_ep = (mesh is not None and "model" in mesh.axis_names
              and mesh.shape["model"] > 1
              and cfg.moe_num_experts % mesh.shape["model"] == 0)
    if not use_ep:
        return _moe_local(cfg, params, x, ep_axis=None)

    # Tokens split across BOTH the data axes (batch dim) and the EP axis
    # (sequence dim): each EP rank dispatches a DISTINCT token slice, and the
    # all_to_all exchanges slices for experts.  Replicating tokens over the
    # EP axis instead (the pre-fix behaviour) made every rank process every
    # token — ep-fold redundant expert FLOPs, flagged by our own
    # differential debugger as redundant compute (EXPERIMENTS.md §Perf B).
    # Divisibility-aware: falls back to replication when a dim can't split
    # (e.g. long_500k decode B=1, S=1).
    from repro.sharding.rules import GLOBAL_RULES
    xs = GLOBAL_RULES.spec(mesh, ("batch", "seq_sp", None), x.shape)
    xs = P(*(tuple(xs) + (None,) * (3 - len(tuple(xs)))))
    ps: dict[str, P] = {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    # Routed experts go through shard_map EP; the shared-expert MLP (if any)
    # stays outside under plain GSPMD TP sharding (it is a dense MLP).
    params_routed = {k: v for k, v in params.items() if not k.startswith("shared")}
    cfg_routed = _without_shared(cfg)
    y, aux = jax.shard_map(lambda p, xl: _shardmap_body(cfg_routed, p, xl, mesh),
                       mesh=mesh, in_specs=(ps, xs), out_specs=(xs, P()),
                       check_vma=False)(params_routed, x)
    if cfg.moe_num_shared:
        g = jnp.einsum("bsd,df->bsf", x, params["shared_w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, params["shared_w_up"])
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                           params["shared_w_down"])
    return y, aux


def _without_shared(cfg: ModelConfig) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(cfg, moe_num_shared=0)


def _shardmap_body(cfg: ModelConfig, params_l, x_l, mesh):
    y, aux = _moe_local(cfg, params_l, x_l, ep_axis="model")
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    return y, jax.lax.pmean(aux, axes)


# ---------------------------------------------------------------------------
# GShard one-hot dispatch (the wasteful twin — zoo case 'moe-dispatch')
# ---------------------------------------------------------------------------

def moe_apply_einsum(cfg: ModelConfig, params: ParamTree,
                     x: jax.Array) -> tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    n = b * s
    x_flat = x.reshape(n, d)
    top_ids, top_w, probs = _router(cfg, params, x_flat)
    aux = _aux_loss(cfg, probs, top_ids)
    e = cfg.moe_num_experts
    cap = _capacity(n, cfg.moe_top_k, e, cfg.capacity_factor)
    # position of each assignment within its expert, via one-hot cumsum
    oh = jax.nn.one_hot(top_ids, e, dtype=jnp.float32)          # (N,k,E)
    pos = jnp.cumsum(oh.reshape(n * cfg.moe_top_k, e), axis=0) - 1.0
    pos = pos.reshape(n, cfg.moe_top_k, e)
    pos = jnp.sum(pos * oh, axis=-1)                            # (N,k)
    keep = pos < cap
    cap_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    disp = jnp.einsum("nke,nkc->nec", oh * keep[..., None], cap_oh)
    comb = jnp.einsum("nec,nke->nec", disp,
                      oh * (top_w.astype(jnp.float32))[..., None])
    buf = jnp.einsum("nd,nec->ecd", x_flat.astype(jnp.float32), disp)
    out = _expert_ffn(buf.astype(x.dtype), params["w_gate"], params["w_up"],
                      params["w_down"])
    y = jnp.einsum("ecd,nec->nd", out.astype(jnp.float32), comb).astype(x.dtype)
    if cfg.moe_num_shared:
        g = jnp.einsum("nd,df->nf", x_flat, params["shared_w_gate"])
        u = jnp.einsum("nd,df->nf", x_flat, params["shared_w_up"])
        y = y + jnp.einsum("nf,fd->nd", jax.nn.silu(g) * u,
                           params["shared_w_down"])
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# dropless dense oracle (tests)
# ---------------------------------------------------------------------------

def moe_reference(cfg: ModelConfig, params: ParamTree,
                  x: jax.Array) -> tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    n = b * s
    x_flat = x.reshape(n, d)
    top_ids, top_w, probs = _router(cfg, params, x_flat)
    aux = _aux_loss(cfg, probs, top_ids)
    # run every expert on every token, combine by routing weight (no drops)
    g = jnp.einsum("nd,edf->enf", x_flat, params["w_gate"])
    u = jnp.einsum("nd,edf->enf", x_flat, params["w_up"])
    h = jax.nn.silu(g) * u
    full = jnp.einsum("enf,efd->end", h, params["w_down"])       # (E,N,d)
    w_full = jnp.zeros((n, cfg.moe_num_experts), x.dtype)
    w_full = w_full.at[jnp.arange(n)[:, None], top_ids].set(top_w)
    y = jnp.einsum("end,ne->nd", full, w_full)
    if cfg.moe_num_shared:
        gg = jnp.einsum("nd,df->nf", x_flat, params["shared_w_gate"])
        uu = jnp.einsum("nd,df->nf", x_flat, params["shared_w_up"])
        y = y + jnp.einsum("nf,fd->nd", jax.nn.silu(gg) * uu,
                           params["shared_w_down"])
    return y.reshape(b, s, d), aux
