"""Shared model substrate: param schemas, norms, RoPE, MLPs, embeddings.

Parameters are described by a *schema* (nested dict of ParamSpec) before they
are materialized.  The schema carries logical sharding axes, so the same
definition serves three consumers:
  * init_params      — materialize real arrays (smoke tests, training),
  * abstract_params  — ShapeDtypeStructs (the multi-pod dry-run; no allocation),
  * param_shardings  — NamedShardings for pjit in/out_shardings.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.sharding.rules import GLOBAL_RULES, ShardingRules, constrain


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    logical: tuple[Any, ...]
    init: str = "normal"          # normal | zeros | ones | ssm_a_log | ssm_dt_bias
    scale: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


ParamTree = dict  # nested dict of ParamSpec / arrays


def _init_leaf(spec: ParamSpec, key) -> jax.Array:
    dt = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    if spec.init == "ssm_a_log":
        # S4/Mamba init: A = -(1..d_state) broadcast; store log(-A)
        d_state = spec.shape[-1]
        a = jnp.broadcast_to(jnp.arange(1, d_state + 1, dtype=jnp.float32),
                             spec.shape)
        return jnp.log(a).astype(dt)
    if spec.init == "ssm_dt_bias":
        # softplus^-1 of dt in [1e-3, 1e-1]
        u = jax.random.uniform(key, spec.shape, jnp.float32,
                               minval=np.log(1e-3), maxval=np.log(1e-1))
        dt = jnp.exp(u)
        inv = dt + jnp.log(-jnp.expm1(-dt))
        return inv.astype(spec.dtype)
    return (jax.random.normal(key, spec.shape, jnp.float32) * spec.scale
            ).astype(dt)


def _is_leaf(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(schema: ParamTree, key) -> ParamTree:
    leaves, treedef = jax.tree_util.tree_flatten(schema, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(s, k) for s, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_params(schema: ParamTree) -> ParamTree:
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        schema, is_leaf=_is_leaf)


def param_shardings(schema: ParamTree, mesh: Mesh,
                    rules: ShardingRules = GLOBAL_RULES) -> ParamTree:
    return jax.tree_util.tree_map(
        lambda s: rules.sharding(mesh, s.logical, s.shape),
        schema, is_leaf=_is_leaf)


def stack_schema(schema: ParamTree, n: int) -> ParamTree:
    """Prepend a scan-stack dimension to every leaf in the schema."""
    return jax.tree_util.tree_map(
        lambda s: ParamSpec(shape=(n,) + s.shape, logical=("stack",) + s.logical,
                            init=s.init, scale=s.scale, dtype=s.dtype),
        schema, is_leaf=_is_leaf)


def count_schema_params(schema: ParamTree) -> int:
    leaves = jax.tree_util.tree_leaves(schema, is_leaf=_is_leaf)
    return int(sum(np.prod(s.shape, dtype=np.int64) for s in leaves))


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with fp32 internals (the production-default path)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(scale) + 1."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """(dim/2,) float32 inverse frequencies under YaRN (``YarnScaling``), as
    DeepSeek-V2 computes them: dimensions that turn more than ``beta_fast``
    times over the original context keep their frequency, those that turn
    fewer than ``beta_slow`` times are interpolated by ``factor``, and a
    linear ramp blends the ones between."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / yarn.factor

    def dim_of(rotations):      # the dimension that turns this many times
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling=None) -> jax.Array:
    """Rotary embedding; x: (..., seq, heads, head_dim), positions: (..., seq).
    ``scaling`` (a ``YarnScaling``) gives YaRN's frequencies and scales the
    rotation by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        gain = 1.0
    else:
        freqs = jnp.asarray(yarn_inv_freq(head_dim, theta, scaling))
        gain = (yarn_mscale(scaling.factor, scaling.mscale)
                / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., :, None, :]   # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    if gain != 1.0:
        cos, sin = cos * gain, sin * gain
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_schema(d_model: int, d_ff: int, dtype: str) -> ParamTree:
    return {
        "w_gate": ParamSpec((d_model, d_ff), ("embed", "ffn"), dtype=dtype),
        "w_up": ParamSpec((d_model, d_ff), ("embed", "ffn"), dtype=dtype),
        "w_down": ParamSpec((d_ff, d_model), ("ffn", "embed"), dtype=dtype,
                            scale=0.02 / np.sqrt(2.0)),
    }


def mlp_apply(params: ParamTree, x: jax.Array, *, mesh: Mesh | None = None,
              fused_activation: Callable | None = None) -> jax.Array:
    gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    if mesh is not None:
        gate = constrain(gate, mesh, ("batch", None, "ffn"))
        up = constrain(up, mesh, ("batch", None, "ffn"))
    act = (fused_activation or _swiglu)(gate, up)
    return jnp.einsum("...f,fd->...d", act, params["w_down"])


def _swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def embedding_schema(vocab: int, d_model: int, dtype: str,
                     tie: bool) -> ParamTree:
    sch: ParamTree = {
        "tok_embed": ParamSpec((vocab, d_model), ("vocab", "embed"), dtype=dtype),
    }
    if not tie:
        sch["lm_head"] = ParamSpec((d_model, vocab), ("embed", "vocab"),
                                   dtype=dtype)
    return sch


def embed_tokens(params: ParamTree, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["tok_embed"], tokens, axis=0)


def lm_head(params: ParamTree, x: jax.Array) -> jax.Array:
    if "lm_head" in params:
        return jnp.einsum("...d,dv->...v", x, params["lm_head"])
    return jnp.einsum("...d,vd->...v", x, params["tok_embed"])


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  *, z_loss: float = 0.0) -> jax.Array:
    """Efficient CE: log_softmax + take_along_axis (no one-hot materialized).

    The wasteful twin (one-hot einsum over the full vocab) lives in
    zoo/cases.py as paper case c13.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1, keepdims=True)
    logp = lf - lse
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(lse**2)
    return loss
