"""Paged decode attention through ``Engine``: the paged Pallas kernel
(the path a TPU compile takes) serves the same greedy tokens as the XLA
gather of the whole block table (the path every other platform takes),
with logits equal to float32 rounding.

Off the TPU ``layers.paged_attention`` lowers the gather; each test
forces the kernel, in interpret mode, by replacing that function for the
engine it builds. One case has prefix-shared slots, whose tables name
the same physical blocks."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import (
    paged_decode_attention_pallas)
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.engine import Engine, EngineConfig
from repro.runtime.policies import Sampler
from repro.runtime.scheduler import Request

CFG = ModelConfig(
    name="paged-kernel", arch_type="dense", n_layers=3, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=96, dtype="float32",
    param_dtype="float32", attn_chunk=16, remat=False,
    layer_pattern=("attn", "attn"))
MAX_LEN = 48
# (prompt length, new tokens); with ``shared`` requests 0, 2 and 4 open
# with one 16-token preamble that the prefix cache maps into each table
SPECS = [(20, 9), (9, 12), (24, 7), (5, 14), (18, 10)]
CASES = {
    "paged": dict(kv_layout="paged", block_size=4),
    "paged-prefix": dict(kv_layout="paged", block_size=4, prefill_chunk=8,
                         prefix_cache=True),
}


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(5))


def _requests(shared: bool):
    rng = np.random.RandomState(11)
    pre = rng.randint(0, CFG.vocab_size, 16)
    out = []
    for i, (plen, mnew) in enumerate(SPECS):
        toks = rng.randint(0, CFG.vocab_size, plen)
        if shared and i % 2 == 0:
            toks[:16] = pre
        out.append(Request(i, toks.astype(np.int32), max_new_tokens=mnew))
    return out


def _serve(params, case, monkeypatch, kernel: bool):
    """Tokens by request id and every logits array the sampler saw, with
    the paged kernel or the gather behind ``layers.paged_attention``."""
    seen, traced = [], []
    call = Sampler.__call__

    def paged_kernel(*args):
        traced.append(args[0].shape)
        return paged_decode_attention_pallas(*args, interpret=True)

    def record(self, logits):
        seen.append(np.asarray(logits))
        return call(self, logits)
    with monkeypatch.context() as m:
        m.setattr(Sampler, "__call__", record)
        if kernel:
            m.setattr(L, "paged_attention", paged_kernel)
        eng = Engine(CFG, params, EngineConfig(max_slots=3, max_len=MAX_LEN,
                                               **CASES[case]))
        done = eng.generate(_requests(shared=case == "paged-prefix"))
        if case == "paged-prefix":
            assert eng.scheduler.prefill_tokens_saved > 0
    assert bool(traced) == kernel
    return {c.id: c.tokens for c in done}, seen


@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_kernel_serves_the_gather_tokens(params, case, monkeypatch):
    toks, logits = _serve(params, case, monkeypatch, kernel=True)
    toks_x, logits_x = _serve(params, case, monkeypatch, kernel=False)
    assert toks == toks_x
    assert len(logits) == len(logits_x) > 0
    for a, b in zip(logits, logits_x):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_paged_reads_are_counted_per_step(params):
    """With observability on, each decode step adds every slot's live
    pages, ceil((cache_len + 1) / block_size), to the pages read, and
    the whole table to the table pages; the slotted layout counts
    neither."""
    bs, slots = 4, 3
    eng = Engine(CFG, params, EngineConfig(
        max_slots=slots, max_len=MAX_LEN, kv_layout="paged", block_size=bs,
        observability=True))
    lay = eng.scheduler.layout
    expect = []
    decode = lay.decode

    def spy(p, tokens, cache_len):
        expect.append(int(((cache_len + bs) // bs).sum()))
        return decode(p, tokens, cache_len)
    lay.decode = spy
    eng.generate(_requests(shared=False))
    got = eng.snapshot()["metrics"]["counters"]
    assert len(expect) == eng.scheduler.step_count > 0
    assert got["repro_paged_kv_pages_read_total"] == sum(expect)
    assert got["repro_paged_kv_table_pages_total"] == \
        len(expect) * slots * (MAX_LEN // bs)
    assert slots * len(expect) <= sum(expect) < \
        got["repro_paged_kv_table_pages_total"]
    slotted = Engine(CFG, params, EngineConfig(
        max_slots=slots, max_len=MAX_LEN, kv_layout="slotted",
        observability=True))
    slotted.generate(_requests(shared=False))
    assert "repro_paged_kv_pages_read_total" not in \
        slotted.snapshot()["metrics"]["counters"]
