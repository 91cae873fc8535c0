"""Mixture-of-Experts with expert parallelism.

Every path routes the same way (:func:`_router`): softmax over all the
experts, group-limited greedy top-k, weights renormalised or not and
scaled.  Three dispatch implementations of the SAME function:

  * ``moe_apply`` without an expert-parallel mesh (the served path): the
    layer computes the part of the result that the experts this device
    holds give (``cfg.held_experts``), with no token dropped — the
    assignments are sorted by expert and go through ragged (grouped)
    matmuls, in blocks of at most :data:`TOKENS_PER_BLOCK` tokens.  As one
    rank of expert parallelism, it leaves out what the experts it does not
    hold would add; the shared experts are added once.

  * ``moe_apply`` on a mesh whose "model" axis splits the experts: sort/
    gather-based dispatch — tokens are sorted by expert id, scattered into
    (E, capacity, d) buffers with byte-cost O(E*C*d), exchanged across the
    "model" mesh axis with all_to_all (EP), and combined back; tokens past
    an expert's capacity are dropped.  Dispatch costs *bytes*, not FLOPs.

  * ``moe_apply_einsum`` (GShard-style baseline): one-hot dispatch einsums
    costing 2*N*E*C*d FLOPs — for fine-grained-expert models (deepseek-v2:
    160 experts) this is orders of magnitude more compute than the experts
    themselves.  Kept as a first-class energy-waste case for the
    differential debugger (zoo case 'moe-dispatch').

``moe_reference`` is the dropless dense oracle used by tests: every held
expert on every token.
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


# tokens whose held-expert assignments are gathered and multiplied at once
TOKENS_PER_BLOCK = 4096
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_schema(cfg: ModelConfig) -> ParamTree:
    """The router over every expert; the held experts' weights."""
    d, f = cfg.d_model, cfg.resolved_moe_d_ff
    e, held = cfg.moe_num_experts, cfg.held_experts[1]
    dt = cfg.dtype
    sch: ParamTree = {
        "router": ParamSpec((d, e), ("embed", None), dtype="float32", scale=0.01),
        "w_gate": ParamSpec((held, d, f), ("experts", "embed", "expert_ffn"), dtype=dt),
        "w_up": ParamSpec((held, d, f), ("experts", "embed", "expert_ffn"), dtype=dt),
        "w_down": ParamSpec((held, f, d), ("experts", "expert_ffn", "embed"), dtype=dt,
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
    """Group-limited greedy routing (DeepSeek-V2).  x_flat: (N, d) -> expert
    ids (N,k) over all experts, float32 weights (N,k), probs (N,E).

    Softmax scores over all E experts; the experts are ``moe_n_group``
    equal groups, each scored by its best expert, and only the
    ``moe_topk_group`` best groups keep their scores (the rest read 0); the
    top ``moe_top_k`` scores are taken (the lower id first on a tie), then
    renormalised if ``moe_norm_topk`` and scaled by ``moe_routed_scale``."""
    logits = jnp.einsum("nd,de->ne", x_flat.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    scores = probs
    g = cfg.moe_n_group
    if cfg.moe_topk_group < g:
        n, e = probs.shape
        best = jnp.max(probs.reshape(n, g, e // g), axis=-1)     # (N, G)
        _, top_groups = jax.lax.top_k(best, cfg.moe_topk_group)
        keep = jnp.zeros((n, g), bool).at[
            jnp.arange(n)[:, None], top_groups].set(True)
        scores = jnp.where(jnp.repeat(keep, e // g, axis=1), probs, 0.0)
    top_p, top_ids = jax.lax.top_k(scores, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_ids, top_p * cfg.moe_routed_scale, probs


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
               *, ep_axis: str) -> tuple[jax.Array, jax.Array]:
    """Per-shard body of the mesh EP layer (inside shard_map): route, sort
    into (E, capacity, d) buffers, exchange over ``ep_axis``, run the local
    experts, exchange back and combine.  The shared experts are added
    outside, by :func:`moe_apply`."""
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    n = b * s
    top_ids, top_w, probs = _router(cfg, params, x_flat)
    aux = _aux_loss(cfg, probs, top_ids)
    cap = _capacity(n, cfg.moe_top_k, cfg.moe_num_experts, cfg.capacity_factor)
    buf, slot, tok_idx, order, keep = _dispatch_local(cfg, x_flat, top_ids,
                                                      top_w, cap)
    # (E, C, d) -> (E/ep, C*ep, d): each shard keeps its local experts'
    # slots from every peer.
    buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                             tiled=True)
    out = _expert_ffn(buf, params["w_gate"], params["w_up"], params["w_down"])
    out = jax.lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                             tiled=True)
    y = _combine_local(cfg, out, slot, tok_idx, order, keep, top_w, n)
    return y.reshape(b, s, d), aux


def _shared(params: ParamTree, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, params["shared_w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["shared_w_up"])
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u,
                      params["shared_w_down"])


def _held_block(cfg: ModelConfig, params: ParamTree, x: jax.Array,
                top_ids: jax.Array, top_w: jax.Array,
                layer: jax.Array | int) -> jax.Array:
    """The held experts' part of the layer for one block of tokens: x (n,d),
    top_ids / top_w (n,k) -> (n,d), summed in float32.  Each assignment to a
    held expert is one row of a ragged matmul, grouped by expert;
    assignments to experts held elsewhere sort last and give nothing.

    The expert weights may be a whole stack (L, held, ...) of which this is
    layer ``layer``: the stack is one (L * held)-group matmul operand whose
    groups of other layers get no rows, so the kernel reads this layer's
    experts where they lie, instead of a copy sliced out of the stack."""
    n, k = top_ids.shape
    first, count = cfg.held_experts
    ws = [params[w] for w in EXPERT_WEIGHTS]
    if ws[0].ndim == 3:
        ws, layer = [w[None] for w in ws], 0
    groups = ws[0].shape[0] * count
    ws = [w.reshape((groups,) + w.shape[2:]) for w in ws]
    local = top_ids.reshape(-1) - first
    held = (local >= 0) & (local < count)
    group = jnp.where(held, local + layer * count, groups)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=groups + 1)[:groups].astype(jnp.int32)
    rows = order // k
    xs = jnp.take(x, rows, axis=0)                          # (n*k, d)
    g = jax.lax.ragged_dot(xs, ws[0], sizes)
    u = jax.lax.ragged_dot(xs, ws[1], sizes)
    ys = jax.lax.ragged_dot(jax.nn.silu(g) * u, ws[2], sizes)
    # rows past the held groups are in no group: selected away, not
    # multiplied by 0, as ragged_dot does not promise what it leaves there
    kept = held[order]
    w = jnp.where(kept, top_w.reshape(-1)[order], 0.0)
    part = jnp.where(kept[:, None], ys.astype(jnp.float32) * w[:, None], 0.0)
    return jnp.zeros((n, x.shape[1]), jnp.float32).at[rows].add(
        part).astype(x.dtype)


def _moe_held(cfg: ModelConfig, params: ParamTree, x: jax.Array,
              layer: jax.Array | int) -> tuple[jax.Array, jax.Array]:
    """The layer as one rank of expert parallelism computes it, without the
    exchange: routing over every expert, the held experts' part (dropless,
    :data:`TOKENS_PER_BLOCK` tokens at a time) and the shared experts."""
    b, s, d = x.shape
    n = b * s
    x_flat = x.reshape(n, d)
    with jax.named_scope("route"):
        top_ids, top_w, probs = _router(cfg, params, x_flat)
        aux = _aux_loss(cfg, probs, top_ids)
    with jax.named_scope("experts"):
        blk = min(n, TOKENS_PER_BLOCK)
        nb = -(-n // blk)
        pad = nb * blk - n          # padding rows go to no expert
        xb = jnp.pad(x_flat, ((0, pad), (0, 0))).reshape(nb, blk, d)
        ib = jnp.pad(top_ids, ((0, pad), (0, 0)),
                     constant_values=cfg.moe_num_experts)
        wb = jnp.pad(top_w, ((0, pad), (0, 0)))
        k = top_ids.shape[1]
        y = jax.lax.map(lambda a: _held_block(cfg, params, *a, layer),
                        (xb, ib.reshape(nb, blk, k), wb.reshape(nb, blk, k)))
        y = y.reshape(nb * blk, d)[:n]
    if cfg.moe_num_shared:
        with jax.named_scope("shared"):
            y = (y.astype(jnp.float32)
                 + _shared(params, x_flat).astype(jnp.float32)).astype(x.dtype)
    return y.reshape(b, s, d), aux


def _uses_ep(cfg: ModelConfig, mesh: Mesh | None) -> bool:
    return (mesh is not None and "model" in mesh.axis_names
            and mesh.shape["model"] > 1
            and cfg.moe_num_experts % mesh.shape["model"] == 0)


def split_expert_stacks(cfg: ModelConfig, stacked: ParamTree,
                        mesh: Mesh | None) -> tuple[ParamTree, ParamTree]:
    """A stacked superblock's params (leaves (L, ...)) as (what a layer scan
    slices per layer, the held experts' weights of each MoE layer left
    whole, by layer name).  A layer's slice of a stack is copied out before
    a kernel reads it; the held-experts layer reads its layer's experts in
    the whole stack (:func:`_held_block`).  The mesh EP layer shards the
    experts itself and takes its slices."""
    if _uses_ep(cfg, mesh):
        return stacked, {}
    scanned, whole = {}, {}
    for name, lp in stacked.items():
        if "moe" in lp:
            scanned[name] = dict(lp, moe={k: v for k, v in lp["moe"].items()
                                          if k not in EXPERT_WEIGHTS})
            whole[name] = {k: lp["moe"][k] for k in EXPERT_WEIGHTS}
        else:
            scanned[name] = lp
    return scanned, whole


def moe_apply(cfg: ModelConfig, params: ParamTree, x: jax.Array,
              *, mesh: Mesh | None = None,
              layer: jax.Array | int = 0) -> tuple[jax.Array, jax.Array]:
    """Production MoE layer. x: (B,S,d) -> (y, aux_loss).  Without an EP
    mesh the held experts' weights may be their whole stack, of which this
    is layer ``layer`` (:func:`split_expert_stacks`)."""
    if not _uses_ep(cfg, mesh):
        with jax.named_scope("moe"):
            return _moe_held(cfg, params, x, layer)
    if cfg.held_experts[1] != cfg.moe_num_experts:
        raise ValueError(f"{cfg.name}: the mesh EP layer divides all "
                         f"{cfg.moe_num_experts} experts itself; this "
                         f"config holds {cfg.held_experts[1]}")

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
        y = y + _shared(params, x)
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
        y = y + _shared(params, x_flat)
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
    # run every held expert on every token, combine by routing weight (no
    # drops); an expert held elsewhere contributes nothing
    g = jnp.einsum("nd,edf->enf", x_flat, params["w_gate"])
    u = jnp.einsum("nd,edf->enf", x_flat, params["w_up"])
    h = jax.nn.silu(g) * u
    full = jnp.einsum("enf,efd->end", h, params["w_down"])       # (E,N,d)
    first, count = cfg.held_experts
    w_full = jnp.zeros((n, cfg.moe_num_experts), x.dtype)
    w_full = w_full.at[jnp.arange(n)[:, None], top_ids].set(top_w)
    y = jnp.einsum("end,ne->nd", full, w_full[:, first:first + count])
    if cfg.moe_num_shared:
        y = y + _shared(params, x_flat)
    return y.reshape(b, s, d), aux
