"""Compile the main path for one described TPU v5e chip, at published widths.

Nothing runs: the TPU compiler lowers each program against shapes placed
on a described (not attached) chip and refuses what the chip would refuse —
misaligned kernel blocks, too much VMEM, a step that does not fit HBM.
The topology is described inside a fixture, never at import, so that every
test worker collects the same tests and only the worker given this file
loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.rwkv6_scan.kernel import wkv6_scan
from repro.models import build_model

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def qwen():
    return build_model(get_config("qwen2.5-3b"))


def test_qwen_decode_step_fits_one_chip(one_chip, qwen):
    """The engine's decode step at 8 slots x 4,096 tokens."""
    slots, max_len = 8, 4096
    params = _on(one_chip, qwen.shapes())
    cache = _on(one_chip, qwen.cache_shape_fn(slots, max_len))
    tokens = _spec(one_chip, (slots,), jnp.int32)
    pos = _spec(one_chip, (slots,), jnp.int32)
    compiled = jax.jit(qwen.decode_step).lower(params, cache, tokens,
                                               pos).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def test_qwen_prefill_2k(one_chip, qwen):
    params = _on(one_chip, qwen.shapes())
    tokens = _spec(one_chip, (1, 2048), jnp.int32)
    compiled = jax.jit(
        lambda p, t: qwen.prefill(p, {"tokens": t})).lower(params,
                                                           tokens).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_decode_attention_kernel(one_chip):
    B, KV, G, S, D = 8, 2, 8, 4096, 128
    q = _spec(one_chip, (B, KV, G, D), jnp.bfloat16)
    kv = _spec(one_chip, (B, KV, S, D), jnp.bfloat16)
    cache_len = _spec(one_chip, (), jnp.int32)
    compiled = decode_attention.lower(q, kv, kv, cache_len,
                                      interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_gqa(one_chip):
    B, H, KV, S, D = 1, 16, 2, 2048, 128
    q = _spec(one_chip, (B, H, S, D), jnp.bfloat16)
    kv = _spec(one_chip, (B, KV, S, D), jnp.bfloat16)
    compiled = flash_attention.lower(q, kv, kv, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv6_scan_kernel(one_chip):
    cfg = get_config("rwkv6-7b")
    H, D, S = cfg.num_heads, cfg.head_dim, 2048
    x = _spec(one_chip, (H, S, D), jnp.bfloat16)
    u = _spec(one_chip, (H, D), jnp.float32)
    state = _spec(one_chip, (H, D, D), jnp.float32)
    compiled = wkv6_scan.lower(x, x, x, x, u, state, num_heads=H,
                               interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
