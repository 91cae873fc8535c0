"""Chunked selective-scan Pallas kernel (Mamba recurrence, state in VMEM).

Solves h_t = a_t * h_{t-1} + b_t and fuses the output projection
y_t = <h_t, c_t> over the state dim.  The XLA oracle (ref.ssm_scan and the
model path in models/ssm.py) must materialize every per-step state
h_all (B,S,di,n) in HBM — n x more traffic than the inputs — because the
projection is a separate einsum.  The kernel keeps the running state in a
(block_d x n) VMEM scratch, writes only y (B,S,di), and carries the state
across sequence chunks through the sequential minormost grid dimension.

HBM traffic: oracle O(S*di*n) state writes + reads; kernel O(S*di) outputs.
With n=16 that is a ~16x reduction on the scan stage — the same
"keep-it-in-SRAM" insight as the paper's fused-GELU finding, applied to the
SSM mixer that three of our assigned architectures (xlstm, jamba) depend on.

TPU layout note: blocks arrive as (chunk, block_d, n) with the state dim n
minormost to match the model's (B,S,di,n) layout.  A production v5e kernel
would transpose di into the lane dimension (n=16 < 128 lanes); we keep the
model layout here and record the lever in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(a_ref, b_ref, c_ref, h0_ref, y_ref, hlast_ref, h_scr, *,
                chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    def step(t, h):
        h = a_ref[0, t] * h + b_ref[0, t]              # (block_d, n)
        y_ref[0, t] = jnp.sum(h * c_ref[0, t][None, :], axis=1)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ci == num_chunks - 1)
    def _finalize():
        hlast_ref[0] = h_scr[...]


def ssm_scan_fused(a: jax.Array, b: jax.Array, c: jax.Array, h0: jax.Array,
                   *, chunk: int = 64, block_d: int = 128,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """a, b: (B,S,di,n) f32; c: (B,S,n) f32; h0: (B,di,n) f32.

    Returns (y (B,S,di) f32, h_last (B,di,n) f32).
    """
    B, S, di, n = a.shape
    chunk = min(chunk, S)
    block_d = min(block_d, di)
    assert S % chunk == 0, (S, chunk)
    assert di % block_d == 0, (di, block_d)
    num_chunks = S // chunk
    d_blocks = di // block_d

    kernel = functools.partial(_ssm_kernel, chunk=chunk,
                               num_chunks=num_chunks)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, d_blocks, num_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d, n),
                         lambda bi, di_, ci: (bi, ci, di_, 0)),
            pl.BlockSpec((1, chunk, block_d, n),
                         lambda bi, di_, ci: (bi, ci, di_, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, di_, ci: (bi, ci, 0)),
            pl.BlockSpec((1, block_d, n), lambda bi, di_, ci: (bi, di_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d),
                         lambda bi, di_, ci: (bi, ci, di_)),
            pl.BlockSpec((1, block_d, n), lambda bi, di_, ci: (bi, di_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, c, h0)
    return y, h_last
