"""Public jit'd wrappers for the Pallas kernels.

Every wrapper: (1) normalizes model-layout tensors into the kernel layout,
(2) picks block sizes the TPU compiler accepts (``_pick_block``) and pads
only where none divides the problem, (3) runs the kernel in interpret mode
automatically when no TPU is present, and (4) is shape-polymorphic enough
for every assigned architecture's head_dim / d_ff / state size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as fa
from repro.kernels import fused_act, rmsnorm, ssm_scan


def _auto_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _sublanes(dtype) -> int:
    """Rows of one (sublane x 128-lane) VMEM tile: 8 for 32-bit, 16 bf16."""
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def _pick_block(size: int, target: int, align: int) -> tuple[int, int]:
    """(block, padded size) for a second-minor block dimension.

    The TPU compiler takes a block that spans the whole dimension or is a
    multiple of ``align`` rows.  Take the whole dimension when it fits
    ``target``; else split it into the fewest blocks of about ``target``
    rows, each rounded up to ``align``, and pad the dimension to a whole
    number of blocks (less than ``align`` rows per block).
    """
    if size <= target:
        return size, size
    n = -(-size // target)
    block = -(-size // (n * align)) * align
    return block, n * block


def _pow2_divisor(size: int, target: int) -> int:
    """Largest power-of-two divisor of ``size`` that is <= target (the
    selective scan's blocks; see ROADMAP B4 for its TPU compile status)."""
    b = 1
    while b * 2 <= min(size, target) and size % (b * 2) == 0:
        b *= 2
    return b


def _pad_rows(x: jax.Array, rows: int, axis: int = 0) -> jax.Array:
    if x.shape[axis] == rows:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rows - x.shape[axis])
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    """Differentiable core on flat same-head-count tensors (B,H,S,D)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, sq_pad = _pick_block(Sq, block_q, _sublanes(q.dtype))
    bk, sk_pad = _pick_block(Sk, block_k, _sublanes(k.dtype))
    o = fa.flash_attention_bh(
        _pad_rows(q.reshape(B * H, Sq, D), sq_pad, axis=1),
        _pad_rows(k.reshape(B * H, Sk, D), sk_pad, axis=1),
        _pad_rows(v.reshape(B * H, Sk, D), sk_pad, axis=1),
        causal=causal, sm_scale=sm_scale, block_q=bq, block_k=bk,
        q_offset=Sk - Sq, kv_len=Sk if sk_pad > Sk else None,
        interpret=interpret)
    return o[:, :Sq].reshape(B, H, Sq, D)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    return _flash_core(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret), (q, k, v)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    # Recompute-based backward: re-derives the attention probabilities via
    # the reference path (fp32) and differentiates through it.  Keeps the
    # fused forward (the paper's energy win is in inference/prefill); a
    # dedicated dq/dk/dv flash backward kernel is a recorded §Perf lever.
    from repro.kernels import ref
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention(q_, k_, v_, causal=causal,
                                         sm_scale=sm_scale), q, k, v)
    return vjp(g)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B,H,Sq,D); k,v: (B,KV,Sk,D); returns (B,H,Sq,D).

    GQA: kv heads are index-expanded to q heads (no HBM materialization —
    XLA turns the gather of contiguous repeats into an access pattern);
    gradients scatter-add back onto the KV heads automatically.
    """
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0
    interp = _auto_interpret(interpret)
    if H != KV:
        reps = H // KV
        head_map = jnp.arange(H, dtype=jnp.int32) // reps
        k = jnp.take(k, head_map, axis=1)
        v = jnp.take(v, head_map, axis=1)
    scale = sm_scale if sm_scale is not None else float(1.0 / np.sqrt(D))
    return _flash_core(q, k, v, causal, scale, block_q, block_k, interp)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def fused_rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-5,
                  block_rows: int = 256,
                  interpret: bool | None = None) -> jax.Array:
    """x: (..., d); w: (d,)."""
    shape = x.shape
    d = shape[-1]
    rows = int(np.prod(shape[:-1], dtype=np.int64))
    br, padded = _pick_block(rows, block_rows, _sublanes(x.dtype))
    out = rmsnorm.rmsnorm_2d(_pad_rows(x.reshape(rows, d), padded), w,
                             eps=eps, block_rows=br,
                             interpret=_auto_interpret(interpret))
    return out[:rows].reshape(shape)


# ---------------------------------------------------------------------------
# fused activations
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_swiglu(g: jax.Array, u: jax.Array, *, block_rows: int = 256,
                 interpret: bool | None = None) -> jax.Array:
    shape = g.shape
    d = shape[-1]
    rows = int(np.prod(shape[:-1], dtype=np.int64))
    br, padded = _pick_block(rows, block_rows, _sublanes(g.dtype))
    out = fused_act.swiglu_2d(_pad_rows(g.reshape(rows, d), padded),
                              _pad_rows(u.reshape(rows, d), padded),
                              block_rows=br,
                              interpret=_auto_interpret(interpret))
    return out[:rows].reshape(shape)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_gelu(x: jax.Array, *, block_rows: int = 256,
               interpret: bool | None = None) -> jax.Array:
    shape = x.shape
    d = shape[-1]
    rows = int(np.prod(shape[:-1], dtype=np.int64))
    br, padded = _pick_block(rows, block_rows, _sublanes(x.dtype))
    out = fused_act.gelu_2d(_pad_rows(x.reshape(rows, d), padded),
                            block_rows=br,
                            interpret=_auto_interpret(interpret))
    return out[:rows].reshape(shape)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def fused_ssm_scan(a: jax.Array, b: jax.Array, c: jax.Array, h0: jax.Array,
                   *, chunk: int = 64, block_d: int = 128,
                   interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """a,b: (B,S,di,n); c: (B,S,n); h0: (B,di,n) — all fp32."""
    B, S, di, n = a.shape
    ck = _pow2_divisor(S, chunk)
    bd = _pow2_divisor(di, block_d)
    return ssm_scan.ssm_scan_fused(a, b, c, h0, chunk=ck, block_d=bd,
                                   interpret=_auto_interpret(interpret))
