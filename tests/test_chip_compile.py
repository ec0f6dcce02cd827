"""Compile the Pallas kernels for a described (not attached) TPU v5e.

Interpret mode on CPU runs the kernel bodies but skips the chip
compiler's checks: block-shape tiling, VMEM limits, layouts. Here each
kernel is lowered and compiled by the TPU compiler at the widths it
serves — qwen2-1.5b's attention heads, recurrentgemma-9b's RG-LRU width
— from shapes alone, so no chip is needed. Each compile takes about a
second.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas

# qwen2-1.5b attention: 12 query heads over 2 kv heads of width 128,
# an 8-slot batch at 2048 rows per slot in 16-row blocks
B, H, HK, D = 8, 12, 2, 128
MAX_LEN, BLOCK = 2048, 16
PREFILL = 512
# recurrentgemma-9b RG-LRU width
SCAN_B, SCAN_LEN, SCAN_WIDTH = 2, 512, 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache here, so keep it out of the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def spec(one_chip):
    """Shape of an argument placed on one described chip."""
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_paged_decode_attention_compiles_for_v5e(spec):
    n_blocks = B * (MAX_LEN // BLOCK) + 1
    text = _compile_text(
        paged_decode_attention_pallas,
        spec((B, H, D)), spec((n_blocks, BLOCK, HK, D)),
        spec((n_blocks, BLOCK, HK, D)),
        spec((B, MAX_LEN // BLOCK), jnp.int32), spec((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_decode_attention_compiles_for_v5e(spec):
    text = _compile_text(
        decode_attention_pallas, spec((B, H, D)),
        spec((B, MAX_LEN, HK, D)), spec((B, MAX_LEN, HK, D)),
        spec((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(spec):
    text = _compile_text(
        flash_attention_pallas, spec((1, PREFILL, H, D)),
        spec((1, PREFILL, HK, D)), spec((1, PREFILL, HK, D)))
    assert "tpu_custom_call" in text


def test_rglru_scan_compiles_for_v5e(spec):
    f32 = jnp.float32
    text = _compile_text(
        rglru_scan_pallas, spec((SCAN_B, SCAN_LEN, SCAN_WIDTH), f32),
        spec((SCAN_B, SCAN_LEN, SCAN_WIDTH), f32),
        spec((SCAN_B, SCAN_WIDTH), f32))
    assert "tpu_custom_call" in text
