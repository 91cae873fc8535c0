"""The paper's energy-waste case catalog, adapted to JAX/TPU (DESIGN.md §6).

Each case is a pair of JAX callables computing the same function — the
inefficient twin reproduces the reported waste pattern, the efficient twin is
the developer fix.  The differential debugger (core/diff.py) must detect the
wasteful region and diagnose its root cause; benchmarks/table2_detection.py
replays the paper's Table 2 over this catalog.

Input sizes are chosen so every case runs in seconds on the CPU container
while keeping the energy asymmetry structurally forced (the Δ sign on TPU is
determined by FLOP/byte counts, not wall-clock noise).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_KEY = jax.random.key(1234)


def _keys(n: int) -> list[jax.Array]:
    return list(jax.random.split(_KEY, n))


# Accepted spellings for the two sides of a case (CLI specs, artifact
# provenance metadata, baseline tooling) -> canonical attribute name.
SIDE_ALIASES: Mapping[str, str] = {
    "ineff": "inefficient", "inefficient": "inefficient", "a": "inefficient",
    "eff": "efficient", "efficient": "efficient", "b": "efficient",
}


@dataclasses.dataclass(frozen=True)
class Case:
    id: str                       # our id
    paper_id: str                 # the paper's Table 1/3 id
    category: str                 # misconfiguration | api_misuse | redundant
    description: str
    inefficient: Callable
    efficient: Callable
    make_args: Callable[[], tuple]
    config_a: Mapping[str, Any] | None = None   # config snapshot, wasteful side
    config_b: Mapping[str, Any] | None = None
    expect_detect: bool = True    # c11 is the documented miss (CPU-side waste)
    known: bool = True            # Table 1 (known) vs Table 3 (new)
    output_rtol: float = 1e-2
    match_rtol: float = 1e-3
    notes: str = ""

    def side(self, which: str) -> tuple[Callable, Mapping[str, Any] | None]:
        """``(fn, config)`` for one side, accepting any SIDE_ALIASES
        spelling (``ineff``/``a``/``efficient``/...)."""
        canon = SIDE_ALIASES.get(which)
        if canon is None:
            raise KeyError(f"unknown case side {which!r}; expected one of "
                           f"{sorted(SIDE_ALIASES)}")
        fn = getattr(self, canon)
        cfg = self.config_a if canon == "inefficient" else self.config_b
        return fn, cfg


# Registry: case id -> Case, insertion-ordered.  ``CASES`` is kept as the
# live list view for back-compat (existing callers iterate it directly).
_REGISTRY: dict[str, Case] = {}
CASES: list[Case] = []


def register_case(case):
    """Register a :class:`Case` in the zoo registry.

    Usable directly (``register_case(Case(...))``) or as a decorator over a
    zero-argument factory returning a Case::

        @register_case
        def _my_case() -> Case:
            return Case(id="c99-...", ...)

    The CLI (``python -m repro.cli cases``) and the Table-2 harness iterate
    :func:`list_cases` instead of hand-maintained lists, so registering here
    is all it takes to make a new case addressable everywhere.
    """
    made = case() if callable(case) and not isinstance(case, Case) else case
    if not isinstance(made, Case):
        raise TypeError(f"register_case expects a Case or a zero-arg factory "
                        f"returning one, got {type(made).__name__}")
    if made.id in _REGISTRY:
        raise ValueError(f"duplicate case id {made.id!r}")
    _REGISTRY[made.id] = made
    CASES.append(made)
    return case


def list_cases(*, category: str | None = None,
               known: bool | None = None) -> list[Case]:
    """All registered cases, optionally filtered by category / known flag."""
    out = list(_REGISTRY.values())
    if category is not None:
        out = [c for c in out if c.category == category]
    if known is not None:
        out = [c for c in out if c.known == known]
    return out


def get_case(name: str) -> Case:
    """Look up a case by our id or the paper's issue id."""
    c = _REGISTRY.get(name)
    if c is not None:
        return c
    for c in _REGISTRY.values():
        if c.paper_id == name:
            return c
    raise KeyError(f"unknown case {name!r}; known ids: "
                   f"{', '.join(sorted(_REGISTRY))}")


def _case(**kw):
    register_case(Case(**kw))


# ===========================================================================
# c1 / c8 — misconfiguration: matmul precision (tensor cores / TF32 analogue)
# TPU adaptation: precision=HIGHEST forces a 3-pass bf16-emulated fp32 matmul
# on the MXU; DEFAULT uses the native single-pass mode.  Same API, one flag.
# ===========================================================================

def _mk_matmul_args():
    k1, k2 = _keys(2)
    x = jax.random.normal(k1, (256, 512), jnp.bfloat16)
    w = jax.random.normal(k2, (512, 512), jnp.bfloat16)
    return x, w


def _matmul_highest(x, w):
    return jax.lax.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _matmul_default(x, w):
    return jax.lax.dot(x, w, precision=jax.lax.Precision.DEFAULT)


_case(id="c1-precision-prefill", paper_id="vllm-9471",
      category="misconfiguration",
      description="Prefill matmul runs with MXU fast path disabled "
                  "(precision=HIGHEST => 3-pass bf16 emulation).",
      inefficient=_matmul_highest, efficient=_matmul_default,
      make_args=_mk_matmul_args,
      config_a={"matmul_precision": "HIGHEST"},
      config_b={"matmul_precision": "DEFAULT"},
      output_rtol=3e-2,
      notes="c8/sd-279 is the same root cause at the application layer.")

_case(id="c8-tf32-linear", paper_id="sd-279", category="misconfiguration",
      description="Linear layers fail to use the energy-efficient MXU mode "
                  "(allow_tf32 analogue: precision flag).",
      inefficient=_matmul_highest, efficient=_matmul_default,
      make_args=_mk_matmul_args,
      config_a={"allow_fast_matmul": False},
      config_b={"allow_fast_matmul": True},
      output_rtol=3e-2)


# ===========================================================================
# c2 — redundant: decode-attention cache update via full copy
# ===========================================================================

_C2_LEN = 1024


def _mk_cache_args():
    k1, k2 = _keys(2)
    cache = jax.random.normal(k1, (4, _C2_LEN, 8, 64), jnp.bfloat16)
    new = jax.random.normal(k2, (4, 1, 8, 64), jnp.bfloat16)
    return cache, new


def _cache_update_copy(cache, new):
    # copies the whole cache through HBM to append one token
    pos = _C2_LEN // 2
    return jnp.concatenate(
        [cache[:, :pos], new, cache[:, pos + 1:]], axis=1)


def _cache_update_inplace(cache, new):
    pos = _C2_LEN // 2
    return jax.lax.dynamic_update_slice_in_dim(cache, new, pos, axis=1)


_case(id="c2-cache-copy", paper_id="vllm-10811", category="redundant",
      description="Decode attention appends to the KV cache via whole-cache "
                  "copy instead of an in-place slice update.",
      inefficient=_cache_update_copy, efficient=_cache_update_inplace,
      make_args=_mk_cache_args)


# ===========================================================================
# c3 — API misuse: top-k via full sort
# ===========================================================================

def _mk_topk_args():
    (k1,) = _keys(1)
    return (jax.random.normal(k1, (64, 32000), jnp.float32),)


def _topk_sort(logits):
    # two full O(V log V) passes (values + indices), like the reported issue;
    # outputs compared on values (index tie-breaks are implementation-defined)
    vals = jnp.sort(logits, axis=-1)[:, -8:]
    idx = jnp.argsort(logits, axis=-1)[:, -8:]
    return vals[:, ::-1] + 0.0 * idx.astype(logits.dtype)


def _topk_lax(logits):
    v, _ = jax.lax.top_k(logits, 8)
    return v


_case(id="c3-topk-sort", paper_id="sglang-5128", category="api_misuse",
      description="Sampler top-k implemented with two full O(V log V) sorts "
                  "instead of lax.top_k.",
      inefficient=_topk_sort, efficient=_topk_lax, make_args=_mk_topk_args,
      match_rtol=1e-5)


# ===========================================================================
# c4 — redundant: GQA repeat_interleave materialization
# ===========================================================================

def _mk_gqa_args():
    # 16x head-group ratio (H=32, KV=2), short sequence: the repeated K/V
    # materialization dominates HBM traffic, as in the Megatron report.
    k1, k2, k3 = _keys(3)
    q = jax.random.normal(k1, (2, 32, 128, 64), jnp.float32)   # (B,H,S,D)
    k = jax.random.normal(k2, (2, 2, 128, 64), jnp.float32)    # (B,KV,S,D)
    v = jax.random.normal(k3, (2, 2, 128, 64), jnp.float32)
    return q, k, v


def _gqa_repeat(q, k, v):
    g = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, g, axis=1)          # materializes H-sized K/V in HBM
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhtd->bhqt", q, k) / np.sqrt(q.shape[-1])
    return jnp.einsum("bhqt,bhtd->bhqd", jax.nn.softmax(s, -1), v)


def _gqa_grouped(q, k, v):
    B, H, S, D = q.shape
    KV = k.shape[1]
    qg = q.reshape(B, KV, H // KV, S, D)
    s = jnp.einsum("bkgqd,bktd->bkgqt", qg, k) / np.sqrt(D)
    o = jnp.einsum("bkgqt,bktd->bkgqd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, H, S, D)


_case(id="c4-gqa-repeat", paper_id="megatron-543", category="redundant",
      description="GQA K/V heads materialized with repeat_interleave instead "
                  "of group-broadcast einsum.",
      inefficient=_gqa_repeat, efficient=_gqa_grouped, make_args=_mk_gqa_args)


# ===========================================================================
# c5 — misconfiguration: layout transformations around attention
# ===========================================================================

def _mk_layout_args():
    k1, k2 = _keys(2)
    x = jax.random.normal(k1, (4, 512, 16, 64), jnp.float32)   # (B,S,H,D)
    w = jax.random.normal(k2, (16 * 64, 1024), jnp.float32)
    return x, w


def _layout_thrash(x, w):
    # HND storage forces transposes before and after the projection
    xt = jnp.transpose(x, (0, 2, 1, 3))                # to (B,H,S,D)
    xt = jnp.transpose(xt, (0, 2, 1, 3))               # back to (B,S,H,D)
    flat = xt.reshape(x.shape[0], x.shape[1], -1)
    return jnp.einsum("bsf,fo->bso", flat, w)


def _layout_clean(x, w):
    flat = x.reshape(x.shape[0], x.shape[1], -1)
    return jnp.einsum("bsf,fo->bso", flat, w)


_case(id="c5-layout", paper_id="hf-14450", category="misconfiguration",
      description="Default tensor format triggers energy-intensive layout "
                  "transformations (transpose round-trip) around attention.",
      inefficient=_layout_thrash, efficient=_layout_clean,
      make_args=_mk_layout_args)


# ===========================================================================
# c6 — API misuse: algorithm selection (matrix power)
# ===========================================================================

def _mk_matpow_args():
    (k1,) = _keys(1)
    a = jax.random.normal(k1, (256, 256), jnp.float32) / 16.0
    return (a,)


def _matpow_naive(a):
    out = a
    for _ in range(7):          # a^8 with 7 multiplies
        out = out @ a
    return out


def _matpow_binary(a):
    a2 = a @ a
    a4 = a2 @ a2
    return a4 @ a4              # 3 multiplies


_case(id="c6-matpow", paper_id="hf-34570", category="api_misuse",
      description="Repeated-multiplication matrix power instead of binary "
                  "exponentiation (kernel/algorithm selection class).",
      inefficient=_matpow_naive, efficient=_matpow_binary,
      make_args=_mk_matpow_args, output_rtol=5e-2, match_rtol=1e-2)


# ===========================================================================
# c7 — API misuse: unnecessary concat/split round-trip
# ===========================================================================

def _mk_qkv_args():
    k1, k2, k3, k4 = _keys(4)
    x = jax.random.normal(k1, (8, 512, 768), jnp.float32)
    wq = jax.random.normal(k2, (768, 768), jnp.float32) * 0.02
    wk = jax.random.normal(k3, (768, 768), jnp.float32) * 0.02
    wv = jax.random.normal(k4, (768, 768), jnp.float32) * 0.02
    return x, wq, wk, wv


def _qkv_concat_split(x, wq, wk, wv):
    w = jnp.concatenate([wq, wk, wv], axis=1)          # extra HBM writes
    qkv = jnp.einsum("bsd,df->bsf", x, w)
    q, k, v = jnp.split(qkv, 3, axis=-1)               # extra HBM reads
    return q + k + v


def _qkv_direct(x, wq, wk, wv):
    q = jnp.einsum("bsd,df->bsf", x, wq)
    k = jnp.einsum("bsd,df->bsf", x, wk)
    v = jnp.einsum("bsd,df->bsf", x, wv)
    return q + k + v


_case(id="c7-concat-split", paper_id="diffusers-12131", category="api_misuse",
      description="QKV projection concat->matmul->split round-trip pays "
                  "extra memory-access energy vs direct projections.",
      inefficient=_qkv_concat_split, efficient=_qkv_direct,
      make_args=_mk_qkv_args)


# ===========================================================================
# c9 — redundant: per-microbatch gradient all-reduce (dist.Join analogue)
# ===========================================================================

_C9_MB = 8


def _mk_grad_args():
    k1, k2 = _keys(2)
    grads = jax.random.normal(k1, (_C9_MB, 64, 1024), jnp.float32)
    w = jax.random.normal(k2, (1024, 1024), jnp.float32) * 0.02
    return grads, w


def _psum_per_microbatch(grads, w):
    def body(acc, g):
        gw = jnp.einsum("bd,df->df", g, w) / _C9_MB
        # all-reduce every microbatch: collective energy x microbatches
        gw = _fake_all_reduce(gw)
        return acc + gw, None
    out, _ = jax.lax.scan(body, jnp.zeros_like(w), grads)
    return out


def _psum_accumulated(grads, w):
    def body(acc, g):
        return acc + jnp.einsum("bd,df->df", g, w) / _C9_MB, None
    acc, _ = jax.lax.scan(body, jnp.zeros_like(w), grads)
    return _fake_all_reduce(acc)          # single all-reduce at the end


def _fake_all_reduce(x):
    """Stands in for psum on the data axis.

    Traced single-host: shard_map(psum) over a 1-device mesh produces the
    real psum eqn; costs.py prices its ici_bytes.  We use the shard_map form
    so the jaxpr carries a genuine collective.
    """
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    return jax.shard_map(lambda y: jax.lax.psum(y, "dp"), mesh=mesh,
                     in_specs=P(), out_specs=P())(x)


_case(id="c9-join-psum", paper_id="pytorch-181115", category="redundant",
      description="dist.Join analogue: gradient all-reduce fired per "
                  "microbatch keeps the interconnect busy; accumulate-then-"
                  "reduce frees it (GPU can idle).",
      inefficient=_psum_per_microbatch, efficient=_psum_accumulated,
      make_args=_mk_grad_args)


# ===========================================================================
# c10 — API misuse: addmm kernel selection (fp32-accumulated fused form)
# ===========================================================================

def _mk_addmm_args():
    k1, k2, k3 = _keys(3)
    x = jax.random.normal(k1, (2048, 1024), jnp.bfloat16)
    w = jax.random.normal(k2, (1024, 1024), jnp.bfloat16)
    b = jax.random.normal(k3, (1024,), jnp.bfloat16)
    return x, w, b


def _addmm_fused_f32(x, w, b):
    # addmm-analogue: materializes a double-width fp32 logits buffer in HBM,
    # adds the bias in fp32, then downcasts — 2x the HBM write traffic.
    out = jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    return (out + b.astype(jnp.float32)).astype(jnp.bfloat16)


def _add_mm_native(x, w, b):
    # same fp32 MXU accumulation, but the result is written back at native
    # width and the bias added in bf16: half the HBM bytes on the hot buffer.
    out = jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    return out.astype(jnp.bfloat16) + b


_case(id="c10-addmm", paper_id="pytorch-141210", category="api_misuse",
      description="addmm analogue selects an fp32-accumulating kernel with "
                  "double-width HBM writes at large batch; add+mm in native "
                  "width is cheaper.",
      inefficient=_addmm_fused_f32, efficient=_add_mm_native,
      make_args=_mk_addmm_args, output_rtol=2e-2)


# ===========================================================================
# c11 — misconfiguration: CPU busy-waiting (DOCUMENTED MISS)
# The paper's Magneton also fails on c11: the waste is host-side polling,
# invisible at operator granularity.  On TPU/XLA there is no user-level
# busy-wait knob at all; we keep the case as the structural miss.  Both
# sides are the identical computation.
# ===========================================================================

def _mk_c11_args():
    (k1,) = _keys(1)
    return (jax.random.normal(k1, (512, 512), jnp.float32),)


def _c11_same(x):
    return jnp.tanh(x @ x)


_case(id="c11-busywait", paper_id="pytorch-28224", category="misconfiguration",
      description="CPU busy-wait (host-side polling): no operator-level "
                  "signature; documented miss mirroring the paper.",
      inefficient=_c11_same, efficient=_c11_same, make_args=_mk_c11_args,
      expect_detect=False,
      notes="host-side waste is invisible in the op graph; paper misses it too")


# ===========================================================================
# c12 — API misuse: non-contiguous LayerNorm (reduction over non-minor axis)
# ===========================================================================

def _mk_ln_args():
    k1, k2 = _keys(2)
    x = jax.random.normal(k1, (2048, 1024), jnp.float32)
    w = jax.random.normal(k2, (1024,), jnp.float32)
    return x, w


def _ln_nonminor(x, w):
    # stats over the non-minor axis: forces a transpose round-trip
    xt = x.T                                           # (d, rows)
    mu = jnp.mean(xt, axis=0, keepdims=True)
    var = jnp.mean((xt - mu) ** 2, axis=0, keepdims=True)
    return (((xt - mu) / jnp.sqrt(var + 1e-5)).T * w)


def _ln_minor(x, w):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * w


_case(id="c12-ln-layout", paper_id="pytorch-76012", category="api_misuse",
      description="LayerNorm on non-contiguous input: reduction over the "
                  "non-minor axis triggers transposes / inefficient access.",
      inefficient=_ln_nonminor, efficient=_ln_minor, make_args=_mk_ln_args)


# ===========================================================================
# c13 — API misuse: cross-entropy with materialized one-hot
# ===========================================================================

_C13_V = 8192


def _mk_ce_args():
    k1, k2 = _keys(2)
    logits = jax.random.normal(k1, (16, 128, _C13_V), jnp.float32)
    labels = jax.random.randint(k2, (16, 128), 0, _C13_V)
    return logits, labels


def _ce_onehot(logits, labels):
    oh = jax.nn.one_hot(labels, _C13_V, dtype=logits.dtype)   # B*S*V bytes!
    return -jnp.sum(oh * jax.nn.log_softmax(logits, -1), axis=-1).mean()


def _ce_gather(logits, labels):
    ls = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(ls, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()


_case(id="c13-ce-onehot", paper_id="pytorch-141822", category="api_misuse",
      description="cross_entropy materializes a (B,S,V) one-hot and reduces "
                  "it; gather of the target logit avoids vocab-sized HBM "
                  "traffic.",
      inefficient=_ce_onehot, efficient=_ce_gather, make_args=_mk_ce_args)


# ===========================================================================
# c14 — API misuse: STFT via dense DFT matmul
# ===========================================================================

_C14_NFFT = 256
_C14_HOP = 128


def _mk_stft_args():
    (k1,) = _keys(1)
    return (jax.random.normal(k1, (8, 4096), jnp.float32),)


def _frame(x):
    n_frames = (x.shape[-1] - _C14_NFFT) // _C14_HOP + 1
    idx = (jnp.arange(n_frames)[:, None] * _C14_HOP
           + jnp.arange(_C14_NFFT)[None, :])
    return x[..., idx]                                  # (B, frames, nfft)


def _stft_dense(x):
    frames = _frame(x)
    n = _C14_NFFT
    t = jnp.arange(n)
    ang = -2.0 * np.pi * t[:, None] * t[None, :] / n
    # dense (n x n) DFT matrices: O(n^2) flops per frame
    re = jnp.einsum("bfn,nk->bfk", frames, jnp.cos(ang))[..., :n // 2 + 1]
    im = jnp.einsum("bfn,nk->bfk", frames, jnp.sin(ang))[..., :n // 2 + 1]
    return re * re + im * im


def _stft_fft(x):
    frames = _frame(x)
    spec = jnp.fft.rfft(frames, axis=-1)               # O(n log n)
    return jnp.real(spec) ** 2 + jnp.imag(spec) ** 2


_case(id="c14-stft", paper_id="jax-28614", category="api_misuse",
      description="STFT computed with dense DFT matmuls instead of an FFT "
                  "kernel (O(n^2) vs O(n log n)).",
      inefficient=_stft_dense, efficient=_stft_fft, make_args=_mk_stft_args,
      output_rtol=2e-2, match_rtol=1e-2)


# ===========================================================================
# c15 — redundant: expm recomputing matrix powers
# ===========================================================================

def _mk_expm_args():
    (k1,) = _keys(1)
    return (jax.random.normal(k1, (192, 192), jnp.float32) / 32.0,)


def _expm_redundant(a):
    # Taylor-6 with every power recomputed from scratch
    out = jnp.eye(a.shape[0], dtype=a.dtype)
    for k in range(1, 7):
        p = a
        for _ in range(k - 1):       # recompute a^k each term: O(k) matmuls
            p = p @ a
        out = out + p / float(math.factorial(k))
    return out


def _expm_shared(a):
    out = jnp.eye(a.shape[0], dtype=a.dtype)
    p = jnp.eye(a.shape[0], dtype=a.dtype)
    for k in range(1, 7):
        p = p @ a                    # share powers: 1 matmul per term
        out = out + p / float(math.factorial(k))
    return out


_case(id="c15-expm", paper_id="jax-9239", category="redundant",
      description="Matrix exponential recomputes A^k for every Taylor term "
                  "instead of sharing the running power.",
      inefficient=_expm_redundant, efficient=_expm_shared,
      make_args=_mk_expm_args, output_rtol=2e-2, match_rtol=1e-2)


# ===========================================================================
# c16 — API misuse: count_nonzero via materialized int copy
# ===========================================================================

def _mk_cnz_args():
    (k1,) = _keys(1)
    return (jax.random.normal(k1, (4096, 4096), jnp.float32),)


def _cnz_copy(x):
    # materializes a full-width f32 indicator copy (64 MiB) and reduces it
    ones = jnp.where(x != 0.0, jnp.ones_like(x), jnp.zeros_like(x))
    return ones.sum().astype(jnp.int32)


def _cnz_direct(x):
    return jnp.count_nonzero(x).astype(jnp.int32)   # 1-byte bool reduce


_case(id="c16-count-nonzero", paper_id="tf-60772", category="api_misuse",
      description="count_nonzero materializes an int32 copy of the operand "
                  "before reducing (implicit data-copy energy).",
      inefficient=_cnz_copy, efficient=_cnz_direct, make_args=_mk_cnz_args,
      match_rtol=1e-5)


# ===========================================================================
# NEW ISSUES (paper Table 3) — the ones our framework's design adopts
# ===========================================================================

def _mk_gelu_args():
    (k1,) = _keys(1)
    return (jax.random.normal(k1, (512, 2048), jnp.float32),)


def _gelu_unfused(x):
    # HuggingFace's 5-op tanh GELU: five HBM round-trips
    c = float(np.sqrt(2.0 / np.pi))
    inner = c * (x + 0.044715 * x * x * x)
    return 0.5 * x * (1.0 + jnp.tanh(inner))


def _gelu_fused(x):
    from repro.kernels import ops as kops
    return kops.fused_gelu(x)


@register_case
def _n1_gelu_backend() -> Case:
    return Case(id="n1-gelu-backend", paper_id="hf-39073",
                category="misconfiguration",
                description="Default GELU backend launches 5 unfused kernels; "
                            "the fused Pallas kernel is one HBM pass (paper: "
                            "-77.4% op energy, -12% end-to-end).",
                inefficient=_gelu_unfused, efficient=_gelu_fused,
                make_args=_mk_gelu_args, known=False)


_N2_V = 32000


def _mk_lmhead_args():
    k1, k2 = _keys(2)
    h = jax.random.normal(k1, (4, 512, 1024), jnp.float32)
    w = jax.random.normal(k2, (1024, _N2_V), jnp.float32) * 0.02
    return h, w


def _lmhead_all(h, w):
    logits = jnp.einsum("bsd,dv->bsv", h, w)   # logits for every position
    return logits[:, -1, :]


def _lmhead_last(h, w):
    return jnp.einsum("bd,dv->bv", h[:, -1, :], w)


_case(id="n2-lmhead-redundant", paper_id="hf-38977", category="redundant",
      description="LM head computes logits for all S positions during "
                  "single-token generation; only the last is needed.",
      inefficient=_lmhead_all, efficient=_lmhead_last,
      make_args=_mk_lmhead_args, known=False)


def _mk_prefill_attn_args():
    k1, k2, k3 = _keys(3)
    q = jax.random.normal(k1, (1, 8, 1024, 64), jnp.float32)
    k = jax.random.normal(k2, (1, 8, 1024, 64), jnp.float32)
    v = jax.random.normal(k3, (1, 8, 1024, 64), jnp.float32)
    return q, k, v


def _prefill_naive(q, k, v):
    from repro.kernels import ref
    return ref.attention(q, k, v, causal=True)


def _prefill_flash(q, k, v):
    from repro.kernels import ops as kops
    return kops.flash_attention(q, k, v, causal=True)


_case(id="n3-prefill-attn", paper_id="vllm-20174", category="api_misuse",
      description="Default prefill attention materializes the (S,S) score "
                  "matrix; the flash kernel streams it through VMEM.",
      inefficient=_prefill_naive, efficient=_prefill_flash,
      make_args=_mk_prefill_attn_args, known=False, output_rtol=2e-2)


_N4_T = 512
_N4_E, _N4_CAP = 8, _N4_T   # capacity == tokens: no drops, outputs identical


def _mk_moe_args():
    k1, k2 = _keys(2)
    x = jax.random.normal(k1, (_N4_T, 256), jnp.float32)
    router = jax.random.normal(k2, (256, _N4_E), jnp.float32) * 0.1
    return x, router


def _moe_onehot_dispatch(x, router):
    # GShard-style dense dispatch: tokens x experts x capacity einsum
    T = x.shape[0]
    logits = x @ router
    top = jnp.argmax(logits, axis=-1)
    onehot = jax.nn.one_hot(top, _N4_E, dtype=x.dtype)           # (T,E)
    pos = jnp.cumsum(onehot, axis=0) * onehot                    # (T,E)
    cap_oh = jax.nn.one_hot(pos.astype(jnp.int32) - 1, _N4_CAP,
                            dtype=x.dtype)                       # (T,E,C)
    dispatch = onehot[..., None] * cap_oh                        # (T,E,C)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)           # dense!
    return expert_in.sum(axis=(0, 1)), top.astype(jnp.int32)


def _moe_gather_dispatch(x, router):
    logits = x @ router
    top = jnp.argmax(logits, axis=-1)
    order = jnp.argsort(top)
    sorted_x = jnp.take(x, order, axis=0)        # gather, no (T,E,C) tensor
    return sorted_x.sum(axis=0), top.astype(jnp.int32)


_case(id="n4-moe-dispatch", paper_id="ours-moe", category="api_misuse",
      description="MoE dispatch via dense one-hot (tokens x experts x "
                  "capacity einsum) vs sort/gather-based routing.",
      inefficient=_moe_onehot_dispatch, efficient=_moe_gather_dispatch,
      make_args=_mk_moe_args, known=False, output_rtol=2e-2,
      match_rtol=1e-4)


# ===========================================================================
# registry helpers
# ===========================================================================

def by_id(case_id: str) -> Case:
    """Back-compat alias for :func:`get_case`."""
    return get_case(case_id)


def known_cases() -> list[Case]:
    return list_cases(known=True)


def new_cases() -> list[Case]:
    return list_cases(known=False)
