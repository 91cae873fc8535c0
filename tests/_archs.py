"""Shared by the per-architecture smoke tests (test_archs*.py): the
architecture list, one batch for each model family, and a module-scoped
cache of each reduced config with its initialised parameters."""

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import transformer as T

ARCHS = configs.list_archs()


def make_batch(cfg, key, B=2, S=16):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.family == "audio":
        batch = {"frames": jax.random.normal(key, (B, S, cfg.d_model),
                                             jnp.float32).astype(cfg.dtype),
                 "labels": tokens}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            key, (B, cfg.num_image_tokens, cfg.d_model)).astype(cfg.dtype)
    return batch


@pytest.fixture(scope="module")
def arch_state():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = configs.get_config(arch).reduced()
            params = T.model_init(cfg, jax.random.key(0))
            cache[arch] = (cfg, params)
        return cache[arch]
    return get
