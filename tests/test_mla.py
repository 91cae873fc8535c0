"""DeepSeek-V2's attention pieces: YaRN's frequencies and softmax scale
against the published formulas, the corrected registry config, and the
latent attention's prefill in row blocks against the cache-free forward."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention
from repro.models import transformer as T
from repro.models.layers import rope, yarn_inv_freq, yarn_mscale

DSV2 = configs.get_config("deepseek-v2-236b")


def _published_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies, step by step as
    the published modelling code computes them."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask, \
        (low, high)


def test_yarn_frequencies_and_mscale_match_the_published_formulas():
    y = DSV2.rope_scaling
    got = yarn_inv_freq(DSV2.rope_head_dim, DSV2.rope_theta, y)
    want, (low, high) = _published_inv_freq(
        64, 10000.0, 40.0, 4096, 32.0, 1.0)
    # float32 (published) against float64 then rounded
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert (low, high) == (10, 23)
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:low], plain[:low], rtol=1e-6)
    np.testing.assert_allclose(got[high:], plain[high:] / 40, rtol=1e-6)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(
        0.1 * 0.707 * math.log(40) + 1)
    assert yarn_mscale(1.0, 0.707) == 1.0
    # softmax scale: 1/sqrt(128 + 64) times mscale(40, 0.707)**2
    assert attention.mla_softmax_scale(DSV2) == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2 / math.sqrt(192))


def test_rope_with_yarn_rotates_at_yarn_frequencies():
    y = DSV2.rope_scaling
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None]
    got = rope(x, pos, 10000.0, y)
    f = yarn_inv_freq(64, 10000.0, y)
    ang = np.arange(5)[:, None] * f
    a, b = np.asarray(x[..., :32]), np.asarray(x[..., 32:])
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    # without scaling the rotation is the plain one
    assert not np.allclose(np.asarray(rope(x, pos, 10000.0)), want,
                           atol=1e-3)


def test_deepseek_v2_is_the_published_model():
    c = DSV2
    assert (c.num_layers, c.first_k_dense, c.num_superblocks, c.d_model,
            c.num_heads, c.vocab_size, c.d_ff) == \
        (60, 1, 59, 5120, 128, 102400, 12288)
    assert (c.q_lora_rank, c.kv_lora_rank, c.head_dim, c.rope_head_dim,
            c.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (c.moe_num_experts, c.moe_top_k, c.moe_num_shared, c.moe_d_ff,
            c.moe_n_group, c.moe_topk_group, c.moe_norm_topk,
            c.moe_routed_scale) == (160, 6, 2, 1536, 8, 3, False, 16.0)
    y = c.rope_scaling
    assert (y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
            y.mscale, y.mscale_all_dim) == (40, 4096, 32, 1, 0.707, 0.707)
    assert (c.rope_theta, c.norm_eps, c.tie_embeddings) == \
        (10000.0, 1e-6, False)
    assert c.held_experts == (0, 160)
    # one leading dense layer, then the MoE superblocks
    assert [name for name, _, _ in T.segments(c)] == ["lead", "blocks"]


def test_reduced_keeps_groups_consistent_with_four_experts():
    small = DSV2.reduced()
    e, g, tg = small.moe_num_experts, small.moe_n_group, small.moe_topk_group
    assert e == 4 and e % g == 0 and 1 <= tg < g
    assert small.moe_top_k <= tg * (e // g)
    assert (small.first_k_dense, small.num_superblocks) == (1, 2)
    with pytest.raises(ValueError):
        configs.ModelConfig(**{**small.__dict__, "moe_n_group": 3})
    with pytest.raises(ValueError):
        configs.ModelConfig(**{**small.__dict__,
                               "moe_experts_held": (3, 2)})


@pytest.mark.parametrize("batch", [1, 6])
def test_mla_prefill_in_row_blocks_matches_the_forward_pass(batch):
    """A wave prefilled into the cache, its latent attention expanded over
    the cache a few rows at a time (6 rows: three blocks of two), gives the
    last logits of the cache-free forward over every row."""
    cfg = DSV2.reduced()
    params = T.model_init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (batch, 10), 0,
                                cfg.vocab_size)
    full, _ = T.forward(cfg, params, tokens, remat=False)
    last, caches = T.prefill(cfg, params, tokens, max_len=16)
    # bf16 accumulation differences across the two paths
    np.testing.assert_allclose(np.asarray(last[:, -1], np.float32),
                               np.asarray(full[:, -1], np.float32),
                               atol=0.15, rtol=0.15)
    assert set(caches) == {"lead", "blocks"}
    assert caches["lead"]["layer0"]["c_kv"].shape == (1, batch, 16, 32)
