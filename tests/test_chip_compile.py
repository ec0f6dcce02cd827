"""Compile the Pallas kernels, and the serving expert layer's grouped
matmul (on one chip and sharded over four), for a described (not
attached) TPU v5e.

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
# an 8-slot batch at 2048 rows per slot; paged K/V in 16-row blocks
B, H, HK, D = 8, 12, 2, 128
MAX_LEN, BLOCK = 2048, 16
PREFILL = 512
# recurrentgemma-9b RG-LRU width
SCAN_B, SCAN_LEN, SCAN_WIDTH = 2, 512, 4096
# qwen3-moe-235b-a22b: one expert rank's 8 of 128 experts of width 1536
# at d_model 4096, top-8
MOE_D, MOE_F, MOE_E, MOE_HELD, MOE_K = 4096, 1536, 128, 8, 8


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2."""
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
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    """Shape of an argument placed on one described chip."""
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


# the paged decode kernel at each cell's serving shape: (slots, pages
# per slot, query heads, kv heads) of qwen2-1.5b, chatglm3-6b (4096 rows)
# and qwen3-moe-235b-a22b (128 slots), 16-row blocks of width 128
PAGED_SHAPES = {"qwen2-1.5b": (32, 128, 12, 2), "chatglm3-6b": (32, 256, 32, 2),
                "qwen3-moe": (128, 128, 64, 4)}


@pytest.mark.parametrize("cell", sorted(PAGED_SHAPES))
def test_paged_decode_attention_compiles_for_v5e(spec, cell):
    b, nb, h, hk = PAGED_SHAPES[cell]
    n_blocks = b * nb + 1
    text = _compile_text(
        paged_decode_attention_pallas,
        spec((b, h, D)), spec((n_blocks, BLOCK, hk, D)),
        spec((n_blocks, BLOCK, hk, D)),
        spec((b, nb), jnp.int32), spec((b,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_decode_step_runs_the_kernel_on_a_tpu(spec, monkeypatch):
    """A whole paged decode step of a small dense model, compiled for the
    described chip, takes the paged kernel (``layers.paged_attention``
    picks it by the lowering platform) and gathers no slot's whole table
    into a (B, nb * bs, Hk, D) buffer; the same step with the gather in
    its place does."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import layers as L
    from repro.models import transformer as T
    cfg = dataclasses.replace(get_config("qwen2_1_5b").smoke(),
                              head_dim=D, dtype="bfloat16")
    b, nb = 8, 4
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: T.init_paged_cache(
        cfg, b * nb + 1, BLOCK, b, max_len=nb * BLOCK))

    def placed(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)
    vec = spec((b,), jnp.int32)
    args = (placed(params), vec, placed(cache), vec,
            spec((b, nb), jnp.int32))

    def step(p, tok, c, clen, tbl):
        return T.decode_step(p, cfg, tok, c, clen, block_tables=tbl)
    hk = cfg.n_kv_heads
    whole = re.compile(rf"\[{b},({nb},{BLOCK}|{nb * BLOCK}),{hk},{D}\]")
    text = _compile_text(step, *args)
    assert "tpu_custom_call" in text
    assert not whole.search(text)
    monkeypatch.setattr(L, "paged_attention", L._paged_attention_xla)
    gathered = _compile_text(lambda *a: step(*a), *args)  # a fresh trace
    assert whole.search(gathered)
    assert "tpu_custom_call" not in gathered


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


@pytest.mark.parametrize("b,s", [(128, 1), (1, 256)],
                         ids=["decode-128", "chunk-256"])
def test_dropless_expert_layer_compiles_for_v5e(spec, b, s):
    """The serving expert layer at qwen3-moe widths, for a decode step
    of 128 slots and a 256-token prefill chunk: its grouped matmul lowers
    to the TPU's ragged-dot kernels (``ragged-dot-none*``, the names its
    ops carry in a profiler trace)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import moe as M
    cfg = get_config("qwen3_moe_235b_a22b")
    cfg = dataclasses.replace(cfg, dtype="bfloat16", moe=dataclasses.replace(
        cfg.moe, held=(0, MOE_HELD)))
    assert (cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.n_experts,
            cfg.moe.top_k) == (MOE_D, MOE_F, MOE_E, MOE_K)
    p = {"ln": spec((MOE_D,)), "router": spec((MOE_D, MOE_E)),
         "w_gate": spec((MOE_HELD, MOE_D, MOE_F)),
         "w_up": spec((MOE_HELD, MOE_D, MOE_F)),
         "w_down": spec((MOE_HELD, MOE_F, MOE_D))}
    text = _compile_text(lambda p, x: M.moe_serve(p, x, cfg), p,
                         spec((b, s, MOE_D)))
    assert "ragged-dot-none" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_sharded_expert_serving_keeps_expert_parallelism(v5e_2x2, step):
    """The mesh-sharded serving steps of qwen3-moe's smoke config (8
    experts) on a (data 2, model 2) mesh of the described v5e:2x2: each
    grouped matmul reads its "model" shard's 4 experts, no program
    gathers a whole expert bank, and the shards' parts are summed
    (``moe._sharded_part``)."""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime.trainstep import make_prefill_step, make_serve_step
    from repro.sharding.rules import cache_shardings, params_shardings

    mesh = Mesh(np.array(v5e_2x2).reshape(2, 2), ("data", "model"))
    cfg = get_config("qwen3_moe_235b_a22b").smoke()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           n_experts=8))
    e, d, f = cfg.moe.n_experts, cfg.d_model, cfg.moe.d_ff_expert

    def placed(tree, shardings):
        return jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sh), tree, shardings)

    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    params = placed(params, params_shardings(params, mesh))
    rows = NamedSharding(mesh, P("data"))
    with mesh:
        if step == "prefill":
            tokens = jax.ShapeDtypeStruct((8, 16), jnp.int32, sharding=rows)
            lowered = jax.jit(make_prefill_step(cfg, max_len=32, mesh=mesh)
                              ).lower(params, {"tokens": tokens})
        else:
            cache = jax.eval_shape(lambda: T.init_cache(cfg, 8, 32))
            cache = placed(cache, cache_shardings(cache, mesh))
            vec = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=rows)
            lowered = jax.jit(make_serve_step(cfg, mesh=mesh)).lower(
                params, cache, vec, vec)
        text = lowered.compile().as_text()
    assert "ragged-dot-none" in text and "psum" in text

    def bank(n):
        return re.compile(rf"\[(1,)?{n},({d},{f}|{f},{d})\]")
    assert bank(e // 2).search(text)
    assert not bank(e).search(text)
