"""Compiles for a described TPU v5e: the main-path kernels at gpt2-small
widths (including a length that is not a multiple of 8), the jitted
gpt2-small decode step, and the served decode step at phi3-medium's
published widths.

Nothing runs: the TPU compiler compiles for a chip that is described, not
attached, and refuses what the chip would refuse (block shapes off the
tiling, more VMEM than a kernel may use, a program larger than the device).
The topology is described inside a fixture, never while a module is
imported, so every test worker collects the same tests.  The persistent
compilation cache is off around these compiles: an entry written for a
described chip cannot be read back without one.
"""

import dataclasses
import os
import re
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import ops
from repro.models import transformer as tf
from repro.serve.serve_step import make_decode_step

GPT2 = get_config("gpt2-small")
D, HEADS, HEAD_DIM, FF = (GPT2.d_model, GPT2.num_heads,
                          GPT2.resolved_head_dim, GPT2.d_ff)
BATCH = 4


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU compiler's own logs, which it would otherwise write to /tmp
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu-logs")))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # loading the TPU compiler installs a handler that prints a stack trace
    # into the shared test output when the process is terminated; restore
    # the default so a worker ended by the run's time limit exits quietly
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_in_module(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_described_chip_is_a_v5e(one_chip):
    from repro.hw.specs import TPU_V5E, spec_for_device_kind
    (dev,) = one_chip.device_set
    assert spec_for_device_kind(dev.device_kind) is TPU_V5E


@pytest.mark.parametrize("seq", [12, 16, 130, 1024])
def test_flash_attention_compiles(one_chip, seq):
    x = _shape(one_chip, (BATCH, HEADS, seq, HEAD_DIM))
    f = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                    interpret=False))
    assert _kernel_in_module(f.lower(x, x, x).compile())


@pytest.mark.parametrize("rows", [12, BATCH * 16, 300])
@pytest.mark.parametrize("kernel", ["gelu", "swiglu", "rmsnorm"])
def test_elementwise_kernels_compile(one_chip, kernel, rows):
    if kernel == "gelu":
        fn = lambda x: ops.fused_gelu(x, interpret=False)          # noqa: E731
        args = (_shape(one_chip, (rows, FF)),)
    elif kernel == "swiglu":
        fn = lambda g, u: ops.fused_swiglu(g, u, interpret=False)  # noqa: E731
        args = (_shape(one_chip, (rows, FF)),) * 2
    else:
        fn = lambda x, w: ops.fused_rmsnorm(x, w, interpret=False)  # noqa: E731
        args = (_shape(one_chip, (rows, D)),
                _shape(one_chip, (D,), jnp.float32))
    assert _kernel_in_module(jax.jit(fn).lower(*args).compile())


def test_gpt2_decode_step_fits_one_chip(one_chip):
    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _shape(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: tf.model_init(GPT2, jax.random.key(0))))
    caches = on_chip(tf.abstract_cache(GPT2, BATCH, 1024))
    step = jax.jit(make_decode_step(GPT2, None))
    compiled = step.lower(params, caches,
                          _shape(one_chip, (BATCH, 1), jnp.int32),
                          _shape(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < 16 * 2**30


def test_phi3_medium_decode_updates_its_cache_in_place(one_chip):
    """At phi3-medium's published widths and the benchmark's batch and
    cache (2 of its layers), the engine's decode step aliases the donated
    cache and neither copies nor transposes it: its temporaries stay below
    one layer's K, which slicing a layer's cache out would take."""
    from repro.serve.engine import EngineConfig, ServeEngine
    cfg = dataclasses.replace(get_config("phi3-medium-14b"), num_layers=2,
                              vocab_size=32064, tie_embeddings=False)
    batch, max_len = 32, 768

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _shape(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: tf.model_init(cfg, jax.random.key(0))))
    engine = ServeEngine(cfg, params, ecfg=EngineConfig(batch_size=batch,
                                                        max_len=max_len))
    caches = on_chip(tf.abstract_cache(cfg, batch, max_len))
    compiled = engine._decode.lower(
        params, caches, _shape(one_chip, (batch, 1), jnp.int32),
        _shape(one_chip, (), jnp.int32)).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(caches))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // (2 * cfg.num_superblocks)
    layer = f"{batch},{max_len},{cfg.num_kv_heads},{cfg.resolved_head_dim}]"
    moved = re.findall(r"= bf16\[([0-9,]+\])\S* (?:copy|transpose)\(",
                       compiled.as_text())
    assert not [m for m in moved if m.endswith(layer)]
