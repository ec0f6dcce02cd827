"""The served weight tree: ``Engine`` casts the fp32 masters to the
compute dtype once, at construction, instead of inside every program.

* every serving path (paged with chunked prefill and the prefix cache,
  paged alone, slotted, static buckets) gives the greedy
  tokens of ``T.prefill`` / ``T.decode_step`` run on the raw fp32 tree,
  and logits bit-identical to the same engine serving the fp32 masters
  with the per-program cast;
* the held tree is in the compute dtype wherever
  ``cast_params_for_compute`` puts it there, so the programs' own cast
  is the identity; the caller's tree is left untouched; the
  ``repro_weight_bytes`` gauge reads the held tree's bytes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.runtime import engine as engine_mod
from repro.runtime.engine import Engine, EngineConfig
from repro.runtime.observability import parse_prometheus
from repro.runtime.policies import Sampler
from repro.runtime.scheduler import Request

MAX_LEN = 64
# (prompt length, new tokens); requests 0, 2 and 4 share a 16-token
# preamble, so the prefix cache serves part of their prompts
SPECS = [(20, 6), (9, 5), (24, 7), (13, 4), (18, 6)]


@pytest.fixture(scope="module")
def setup():
    """qwen2's smoke shape (q/k/v bias, tied head) with bf16 compute
    over fp32 masters, as the repo serves it on the chip; four layers so
    that two decode stages each hold some."""
    cfg = dataclasses.replace(
        get_config("qwen2_1_5b").smoke(), n_layers=4, dtype="bfloat16",
        param_dtype="float32")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(3))


def _requests(cfg):
    rng = np.random.RandomState(7)
    pre = rng.randint(0, cfg.vocab_size, 16)
    out = []
    for i, (plen, mnew) in enumerate(SPECS):
        toks = rng.randint(0, cfg.vocab_size, plen)
        if i % 2 == 0:
            toks[:16] = pre
        out.append(Request(i, toks.astype(np.int32), max_new_tokens=mnew))
    return out


def _reference_tokens(cfg, params, req):
    """Greedy decode of one request on the raw fp32 tree."""
    prefill = jax.jit(lambda p, t: T.prefill(p, cfg, {"tokens": t},
                                             max_len=MAX_LEN))
    decode = jax.jit(lambda p, t, c, n: T.decode_step(p, cfg, t, c, n))
    logits, cache, clen = prefill(params, jnp.asarray(req.prompt[None]))
    toks = []
    for _ in range(req.max_new_tokens):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(int(tok[0]))
        logits, cache, clen = decode(params, tok, cache, clen)
    return toks


CASES = {
    "paged-chunked-prefix": dict(kv_layout="paged", block_size=8,
                                 prefill_chunk=8, prefix_cache=True),
    "slotted": dict(kv_layout="slotted"),
    "batch": dict(admission="batch"),
    "paged": dict(kv_layout="paged", block_size=8),
}


def _serve(cfg, params, kw, monkeypatch):
    """Serve the requests; returns (tokens by id, every logits array the
    sampler was handed, in order)."""
    seen = []
    call = Sampler.__call__

    def record(self, logits):
        seen.append(np.asarray(logits))
        return call(self, logits)
    with monkeypatch.context() as m:
        m.setattr(Sampler, "__call__", record)
        eng = Engine(cfg, params, EngineConfig(max_slots=3, max_len=MAX_LEN,
                                               **kw))
        done = eng.generate(_requests(cfg))
    return {c.id: c.tokens for c in done}, seen


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_tokens_and_logits_match_fp32_masters(setup, case,
                                                     monkeypatch):
    cfg, params = setup
    toks, logits = _serve(cfg, params, CASES[case], monkeypatch)
    # the same engine serving the fp32 masters, each program casting
    # them on entry: what Engine did before it held the bf16 copy
    with monkeypatch.context() as m:
        m.setattr(engine_mod, "_cast_for_compute", lambda p, c: p)
        toks_f32, logits_f32 = _serve(cfg, params, CASES[case], monkeypatch)
    assert toks == toks_f32
    assert len(logits) == len(logits_f32) > 0
    for a, b in zip(logits, logits_f32):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(a, b)
    for req in _requests(cfg):
        assert toks[req.id] == _reference_tokens(cfg, params, req), req.id


@pytest.mark.parametrize("arch,dtype", [
    ("qwen2_1_5b", "bfloat16"), ("qwen2_1_5b", "float32"),
    # its expert router is one of the leaves kept in fp32
    ("qwen2_moe_a2_7b", "bfloat16")])
def test_engine_holds_compute_dtype_tree(arch, dtype):
    cfg = dataclasses.replace(get_config(arch).smoke(), dtype=dtype,
                              param_dtype="float32")
    masters = T.init_params(cfg, jax.random.PRNGKey(4))
    before = jax.tree_util.tree_map(np.asarray, masters)
    eng = Engine(cfg, masters, EngineConfig(max_slots=2, max_len=MAX_LEN))
    held = eng.params
    assert eng.scheduler.params is held
    flat, _ = jax.tree_util.tree_flatten_with_path(held)
    kept = []
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))
        if name in T._KEEP_F32:
            kept.append(name)
            assert leaf.dtype == jnp.float32, name
        else:
            assert leaf.dtype == jnp.dtype(dtype), name
    assert bool(kept) == (arch == "qwen2_moe_a2_7b")

    # the programs' own cast meets the held tree and changes nothing
    again = T.cast_params_for_compute(held, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(held)):
        assert a is b

    # the caller's masters are untouched: still fp32, same values, alive
    for m, b in zip(jax.tree_util.tree_leaves(masters),
                    jax.tree_util.tree_leaves(before)):
        assert not m.is_deleted() and m.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(m), b)

    held_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(held))
    master_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(masters))
    if dtype == "float32":
        assert held_bytes == master_bytes
    else:
        assert (held_bytes > master_bytes / 2) == bool(kept)
        assert master_bytes / 2 <= held_bytes < master_bytes * 0.6
    # set at construction; rendered with observability off
    assert not eng.config.observability
    gauges = parse_prometheus(eng.metrics_text())["gauges"]
    assert gauges["repro_weight_bytes"] == held_bytes


@pytest.mark.parametrize("param_dtype", ["bfloat16", "float32"])
def test_engine_keeps_leaves_already_in_compute_dtype(param_dtype):
    """A leaf already in its compute dtype (every leaf of a bf16 tree;
    the fp32 router of an fp32 one) is served from the caller's own
    device buffer: construction makes no second copy of it. The other
    leaves are cast, each into a buffer of its own."""
    cfg = dataclasses.replace(get_config("qwen3_moe_235b_a22b").smoke(),
                              dtype="bfloat16", param_dtype=param_dtype)
    params = T.init_params(cfg, jax.random.PRNGKey(4))
    eng = Engine(cfg, params, EngineConfig(max_slots=2, max_len=MAX_LEN))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    held = jax.tree_util.tree_leaves(eng.params)
    same = []
    for (path, mine), theirs in zip(flat, held):
        name = str(getattr(path[-1], "key", ""))
        kept = mine.dtype == theirs.dtype
        assert kept == (param_dtype == "bfloat16" or name == "router"), name
        assert (mine.unsafe_buffer_pointer()
                == theirs.unsafe_buffer_pointer()) == kept, name
        same.append(kept)
    assert any(same) and (all(same) == (param_dtype == "bfloat16"))
    out = eng.generate([Request(0, np.arange(9, dtype=np.int32),
                                max_new_tokens=3)])
    assert len(out[0].tokens) == 3
