"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes and dtypes.

Every Pallas kernel runs in interpret mode (CPU container); the oracle in
kernels/ref.py is ground truth.  Property tests assert the kernels'
numerical invariants on hypothesis-generated shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st  # skips property tests w/o hypothesis

from repro.kernels import ops, ref

KEYS = jax.random.split(jax.random.key(42), 8)


def _tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 2e-5


def _rand(key, shape, dt):
    return jax.random.normal(key, shape, jnp.float32).astype(dt)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,S,D,dtype,causal", [
    (1, 4, 4, 64, 32, jnp.float32, True),       # MHA causal
    (1, 4, 4, 64, 32, jnp.float32, False),      # MHA full
    (2, 8, 2, 128, 64, jnp.bfloat16, True),     # GQA 4:1 bf16
    (1, 8, 1, 256, 16, jnp.float32, True),      # MQA long
    (2, 4, 4, 96, 48, jnp.float32, True),       # non-pow2 seq (block fallback)
    (1, 4, 2, 64, 32, jnp.bfloat16, False),     # GQA bf16 full
    (1, 2, 2, 12, 64, jnp.bfloat16, True),      # short prompt: whole-seq block
    (1, 2, 2, 130, 32, jnp.float32, True),      # no aligned divisor: padded
    (1, 2, 2, 130, 32, jnp.float32, False),     # padded keys masked
])
def test_flash_attention_matches_oracle(B, H, KV, S, D, dtype, causal):
    q = _rand(KEYS[0], (B, H, S, D), dtype)
    k = _rand(KEYS[1], (B, KV, S, D), dtype)
    v = _rand(KEYS[2], (B, KV, S, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_attention_cached_decode_shape():
    """Sq=1 against a longer KV cache (causal offset path)."""
    q = _rand(KEYS[0], (2, 4, 1, 32), jnp.float32)
    k = _rand(KEYS[1], (2, 2, 128, 32), jnp.float32)
    v = _rand(KEYS[2], (2, 2, 128, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_grads_match_reference():
    q = _rand(KEYS[0], (1, 2, 64, 16), jnp.float32)
    k = _rand(KEYS[1], (1, 2, 64, 16), jnp.float32)
    v = _rand(KEYS[2], (1, 2, 64, 16), jnp.float32)
    g1 = jax.grad(lambda q, k, v: ops.flash_attention(q, k, v).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: ref.attention(q, k, v).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@settings(max_examples=5, deadline=None)
@given(st.integers(1, 2), st.integers(0, 2), st.integers(4, 6),
       st.booleans())
def test_flash_attention_property(b, kv_pow, s_pow, causal):
    """Softmax rows sum to 1 => output is a convex combination of V rows:
    max|out| <= max|v| for every hypothesis-generated shape."""
    H = 4
    KV = 2 ** kv_pow
    if H % KV:
        KV = 1
    S = 2 ** s_pow
    D = 16
    q = _rand(KEYS[3], (b, H, S, D), jnp.float32)
    k = _rand(KEYS[4], (b, KV, S, D), jnp.float32)
    v = _rand(KEYS[5], (b, KV, S, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-4
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size,target,align", [
    (12, 128, 8), (12, 256, 16), (96, 64, 8), (1024, 128, 16), (130, 128, 8),
    (303, 256, 16), (24, 16, 16), (1032, 128, 8)])
def test_pick_block_is_legal_on_tpu(size, target, align):
    """A block spans the whole dimension or is a multiple of the sublane
    tile; a dimension longer than the target splits into the fewest
    blocks of about target rows, padded by less than a tile per block."""
    block, padded = ops._pick_block(size, target, align)
    assert block == size or (block % align == 0 and block <= target)
    assert padded % block == 0 and padded >= size
    blocks = padded // block
    assert blocks == -(-size // target)
    assert padded - size < blocks * align


@pytest.mark.parametrize("shape", [(8, 64), (3, 5, 128), (2, 7, 9, 256),
                                   (3, 101, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_oracle(shape, dtype):
    x = _rand(KEYS[0], shape, dtype)
    w = _rand(KEYS[1], shape[-1:], jnp.float32)
    out = ops.fused_rmsnorm(x, w)
    want = ref.rmsnorm(x, w)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 64), st.sampled_from([32, 64, 128]))
def test_rmsnorm_property_unit_rms(rows, d):
    """With w=1, output rows have RMS ~ 1."""
    x = _rand(KEYS[2], (rows, d), jnp.float32) * 3.0 + 1.0
    out = ops.fused_rmsnorm(x, jnp.ones((d,)))
    rms = jnp.sqrt(jnp.mean(out.astype(jnp.float32) ** 2, axis=-1))
    np.testing.assert_allclose(rms, np.ones(rows), atol=1e-3)


# ---------------------------------------------------------------------------
# fused activations
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(16, 64), (4, 100, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu_matches_oracle(shape, dtype):
    g = _rand(KEYS[0], shape, dtype)
    u = _rand(KEYS[1], shape, dtype)
    np.testing.assert_allclose(
        ops.fused_swiglu(g, u).astype(jnp.float32),
        ref.swiglu(g, u).astype(jnp.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("shape", [(16, 64), (4, 100, 256), (1, 1, 8),
                                   (3, 101, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gelu_matches_oracle(shape, dtype):
    x = _rand(KEYS[2], shape, dtype)
    np.testing.assert_allclose(
        ops.fused_gelu(x).astype(jnp.float32),
        ref.gelu_tanh(x).astype(jnp.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


@settings(max_examples=8, deadline=None)
@given(st.floats(-10, 10))
def test_gelu_property_bounds(v):
    """GELU(x) in [min(0,x)-0.2, max(0,x)] and monotone asymptotics."""
    x = jnp.full((8, 128), v, jnp.float32)
    out = float(ops.fused_gelu(x)[0, 0])
    assert out <= max(0.0, v) + 1e-4
    assert out >= min(0.0, v) - 0.2


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,di,n,chunk", [
    (1, 64, 32, 8, 16),
    (2, 128, 64, 16, 64),
    (1, 32, 128, 4, 32),     # chunk == S
])
def test_ssm_scan_matches_oracle(B, S, di, n, chunk):
    a = jax.nn.sigmoid(_rand(KEYS[0], (B, S, di, n), jnp.float32)) * 0.95
    b = _rand(KEYS[1], (B, S, di, n), jnp.float32) * 0.1
    c = _rand(KEYS[2], (B, S, n), jnp.float32)
    h0 = _rand(KEYS[3], (B, di, n), jnp.float32) * 0.1
    y1, h1 = ops.fused_ssm_scan(a, b, c, h0, chunk=chunk)
    y2, h2 = ref.ssm_scan(a, b, c, h0, chunk=chunk)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h1, h2, atol=1e-5, rtol=1e-4)


def test_ssm_scan_state_carrying_across_chunks():
    """Splitting the sequence in two and chaining h must equal one pass."""
    B, S, di, n = 1, 64, 32, 8
    a = jax.nn.sigmoid(_rand(KEYS[4], (B, S, di, n), jnp.float32)) * 0.9
    b = _rand(KEYS[5], (B, S, di, n), jnp.float32) * 0.1
    c = _rand(KEYS[6], (B, S, n), jnp.float32)
    h0 = jnp.zeros((B, di, n))
    y_full, h_full = ops.fused_ssm_scan(a, b, c, h0)
    half = S // 2
    y1, h_mid = ops.fused_ssm_scan(a[:, :half], b[:, :half], c[:, :half], h0)
    y2, h_end = ops.fused_ssm_scan(a[:, half:], b[:, half:], c[:, half:], h_mid)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], axis=1), y_full,
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h_end, h_full, atol=1e-5, rtol=1e-4)


@settings(max_examples=5, deadline=None)
@given(st.integers(1, 2), st.sampled_from([16, 32, 64]),
       st.sampled_from([8, 16]))
def test_ssm_scan_property_decay_bound(B, S, n):
    """With |a|<1 and b=0, the state can only shrink."""
    di = 16
    a = jnp.full((B, S, di, n), 0.5, jnp.float32)
    b = jnp.zeros((B, S, di, n), jnp.float32)
    c = jnp.ones((B, S, n), jnp.float32)
    h0 = jnp.ones((B, di, n), jnp.float32)
    _, h_last = ops.fused_ssm_scan(a, b, c, h0)
    assert float(jnp.max(jnp.abs(h_last))) <= 1.0
