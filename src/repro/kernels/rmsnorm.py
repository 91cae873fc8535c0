"""Fused RMSNorm Pallas kernel: one HBM pass, fp32 statistics in VMEM.

The unfused XLA form (square, mean, rsqrt, mul, mul — and a transpose when
the reduction axis is not minormost) is the wasteful twin in the zoo
(pytorch-76012 class).  The kernel reads each (block_rows x d) tile once,
computes the row statistic in registers/VMEM, and writes the tile once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (block_rows, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    w = w_ref[...].astype(jnp.float32)                 # (1, d)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w).astype(o_ref.dtype)


def rmsnorm_2d(x: jax.Array, w: jax.Array, *, eps: float = 1e-5,
               block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: (rows, d); w: (d,).  rows must divide by block_rows (wrapper pads)."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, (rows, block_rows)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w.reshape(1, d))
