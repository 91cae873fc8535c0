"""Per-architecture smoke tests: reduced config, one forward pass and one
prefill/decode step on CPU, asserting output shapes and finiteness
(deliverable f).  The train step is in test_archs_train.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _archs import ARCHS, arch_state, make_batch  # noqa: F401 (fixture)
from repro import configs
from repro.models import transformer as T


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, arch_state):
    cfg, params = arch_state(arch)
    key = jax.random.key(1)
    B, S = 2, 16
    batch = make_batch(cfg, key, B, S)
    logits, aux = T.forward(cfg, params, batch.get("tokens"),
                            inputs_embeds=batch.get("frames"),
                            image_embeds=batch.get("image_embeds"))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if configs.get_config(a).is_causal])
def test_prefill_decode_parity(arch, arch_state):
    """decode_step(prefill(prompt)) logits == forward(prompt+token) logits."""
    cfg, params = arch_state(arch)
    key = jax.random.key(3)
    B, S = 1, 8
    tokens = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    img = None
    if cfg.family == "vlm":
        img = jax.random.normal(key, (B, cfg.num_image_tokens,
                                      cfg.d_model)).astype(cfg.dtype)
    # full forward over S+1 tokens
    full_logits, _ = T.forward(cfg, params, tokens, image_embeds=img,
                               remat=False)
    # prefill on S tokens, decode 1
    _, caches = T.prefill(cfg, params, tokens[:, :S], max_len=S + 1,
                          image_embeds=img)
    dec_logits, _ = T.decode_step(cfg, params, caches, tokens[:, S:S + 1],
                                  jnp.int32(S))
    a = np.asarray(full_logits[:, -1, :], np.float32)
    b = np.asarray(dec_logits[:, -1, :], np.float32)
    # bf16 accumulation differences across the two paths
    np.testing.assert_allclose(a, b, atol=0.15, rtol=0.15)
    assert np.argmax(a) == np.argmax(b)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_schema(arch):
    cfg = configs.get_config(arch)
    n = cfg.param_count()
    assert n > 0
    na = cfg.active_param_count()
    assert 0 < na <= n
    if cfg.moe_num_experts:
        assert na < n


def test_supported_shapes_skips():
    """DESIGN.md §4 skip table: encoder-only has no decode; full-attention
    archs skip long_500k; SSM/hybrid run it."""
    assert "decode_32k" not in configs.supported_shapes(
        configs.get_config("hubert-xlarge"))
    assert "long_500k" not in configs.supported_shapes(
        configs.get_config("qwen1.5-110b"))
    assert "long_500k" in configs.supported_shapes(
        configs.get_config("xlstm-1.3b"))
    assert "long_500k" in configs.supported_shapes(
        configs.get_config("jamba-1.5-large-398b"))
