"""Greedy-identity conformance matrix for the serving stack.

One differential suite pins the stack's core contract in one place
(consolidating the ad-hoc identity checks that used to be scattered
through test_scheduler.py and test_engine_lifecycle.py): under greedy
sampling, EVERY serving configuration —

    {slotted, slotted+chunked-prefill, paged, paged+watermark,
     paged+chunked-prefill, paged+prefix-cache, paged+chunked+prefix,
     paged+prefix+victim, paged+chunked+prefix+victim}
  x {fifo, priority, deadline-EDF, batch}
  x {evict-latest, lowest-priority}
  x 3 model configs (scan-only depth, scan+remainder depth, and a
    sparse-expert config on {fifo, EDF, batch} x evict-latest)

— must emit tokens (and finish reasons) bit-identical to the
static-bucket oracle. Policies move waiting time, never content; cache
layouts move memory, never content; prefix sharing moves *prefill work*,
never content. The workload is adversarial on purpose: overlapping
prompt prefixes (so prefix-cache cells actually share blocks), an eos
stop, single-token budgets, scrambled priorities and deadlines, and a
pool tight enough to force growth preemption in paged cells (so the
preemption policy axis is actually exercised).

The full matrix is heavy (every cell builds and drains an engine), so
only a representative diagonal runs in the fast CI lane; the rest is
``slow`` and runs nightly.
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.engine import Engine, EngineConfig
from repro.runtime.scheduler import Request

from test_moe import _tiny_qwen3

KEY = jax.random.PRNGKey(0)

CONFIGS = {
    # scan-only depth: 2 layers = 2 periods of ("attn",)
    "scan": ModelConfig(
        name="cm-scan", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64, dtype="float32",
        param_dtype="float32", attn_chunk=16, remat=False),
    # scan + remainder depth: 3 layers over a period of 2 leaves one
    # unrolled remainder layer — the cache pytree's "rem" half
    "rem": ModelConfig(
        name="cm-rem", arch_type="dense", n_layers=3, d_model=48,
        n_heads=4, n_kv_heads=2, d_ff=96, vocab_size=96, dtype="float32",
        param_dtype="float32", attn_chunk=16, remat=False,
        layer_pattern=("attn", "attn"), tie_embeddings=True),
    # sparse experts shaped like the benchmark's moe_gqa family: q/k-
    # normed GQA, 8 experts top-2, this device holding experts 2-5
    "moe": dataclasses.replace(_tiny_qwen3(held=(2, 4)), attn_chunk=16,
                               remat=False),
}

LAYOUTS = {
    "slotted": dict(kv_layout="slotted"),
    "slotted-chunked": dict(kv_layout="slotted", prefill_chunk=4),
    "paged": dict(kv_layout="paged", block_size=8, num_blocks=18),
    # admission keeps 2 blocks free beyond the prompt's need
    "paged-watermark": dict(kv_layout="paged", block_size=8, num_blocks=18,
                            watermark=2),
    "paged-chunked": dict(kv_layout="paged", block_size=8, num_blocks=18,
                          prefill_chunk=4),
    "paged-prefix": dict(kv_layout="paged", block_size=8, num_blocks=18,
                         prefix_cache=True),
    "paged-chunked-prefix": dict(kv_layout="paged", block_size=8,
                                 num_blocks=18, prefill_chunk=4,
                                 prefix_cache=True),
    # victim cache on top of prefix sharing: completed chains park in a
    # reclaimable pool instead of freeing. Retention moves prefill work
    # only — tokens must stay oracle-identical, and at drain the books
    # balance against the parked population instead of zero.
    "paged-prefix-victim": dict(kv_layout="paged", block_size=8,
                                num_blocks=18, prefix_cache=True,
                                victim_cache=True),
    # chunked prefill over the victim cache
    "paged-chunked-prefix-victim": dict(kv_layout="paged", block_size=8,
                                        num_blocks=18, prefill_chunk=4,
                                        prefix_cache=True,
                                        victim_cache=True),
}

ADMISSIONS = ("fifo", "priority", "edf", "batch")
PREEMPTIONS = ("evict-latest", "lowest-priority")

# the fast-lane diagonal: every layout, every admission and both
# preemption policies appear at least once on each dense model config
FAST = {
    ("scan", "slotted", "batch", "evict-latest"),
    ("scan", "slotted", "fifo", "evict-latest"),
    ("scan", "slotted-chunked", "fifo", "evict-latest"),
    ("rem", "slotted-chunked", "edf", "evict-latest"),
    ("scan", "paged", "priority", "lowest-priority"),
    ("scan", "paged-chunked", "edf", "evict-latest"),
    ("scan", "paged-prefix", "fifo", "evict-latest"),
    ("scan", "paged-prefix", "priority", "lowest-priority"),
    ("scan", "paged-chunked-prefix", "edf", "lowest-priority"),
    ("rem", "slotted", "batch", "evict-latest"),
    ("rem", "slotted", "priority", "evict-latest"),
    ("rem", "paged", "fifo", "evict-latest"),
    ("rem", "paged-chunked", "priority", "lowest-priority"),
    ("rem", "paged-prefix", "edf", "lowest-priority"),
    ("rem", "paged-chunked-prefix", "fifo", "evict-latest"),
    ("scan", "paged-prefix-victim", "fifo", "evict-latest"),
    ("rem", "paged-prefix-victim", "priority", "lowest-priority"),
    ("scan", "paged-watermark", "priority", "lowest-priority"),
    ("rem", "paged-chunked-prefix-victim", "edf", "evict-latest"),
    ("moe", "slotted-chunked", "edf", "evict-latest"),
    ("moe", "paged-chunked-prefix-victim", "fifo", "evict-latest"),
}


def _cells():
    for cfg, lay, adm, pre in itertools.product(CONFIGS, LAYOUTS,
                                                ADMISSIONS, PREEMPTIONS):
        if adm == "batch" and lay != "slotted":
            continue        # rejected combination (engine raises; see below)
        if LAYOUTS[lay].get("kv_layout") == "slotted" \
                and pre != "evict-latest":
            continue        # no pool -> preemption never engages; one
            #                 representative per slotted cell is enough
        if cfg == "moe" and (adm == "priority" or pre != "evict-latest"):
            continue        # the dense configs cover those axes
        marks = () if (cfg, lay, adm, pre) in FAST else (pytest.mark.slow,)
        yield pytest.param(cfg, lay, adm, pre,
                           id=f"{cfg}-{lay}-{adm}-{pre}", marks=marks)


@pytest.fixture(scope="module")
def zoo():
    """Params and the static-bucket oracle tokens, once per config."""
    out = {}
    for name, cfg in CONFIGS.items():
        params = T.init_params(cfg, KEY)
        oracle = Engine(cfg, params, EngineConfig(
            max_len=48, admission="batch")).generate(_workload(cfg))
        out[name] = (cfg, params, oracle)
    return out


def _workload(cfg: ModelConfig):
    """Mixed prompts with a shared 12-token preamble on most requests
    (prefix cells must share), one eos stop, one single-token budget,
    scrambled priorities/deadlines. Worst case 4 blocks of 8 rows, so a
    tight 17-block pool forces growth preemption with 3+ slots busy."""
    rng = np.random.RandomState(7)
    shared = rng.randint(0, cfg.vocab_size, 12).astype(np.int32)
    specs = [(5, 6), (12, 4), (8, 9), (16, 5), (7, 1), (9, 8), (12, 7),
             (16, 2), (8, 6), (14, 5)]
    reqs = []
    for i, (plen, mnew) in enumerate(specs):
        if i % 3 == 0:      # unrelated prompt: must never falsely match
            prompt = rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
        else:               # shared preamble + private tail
            tail = rng.randint(0, cfg.vocab_size,
                               max(plen - 12, 1)).astype(np.int32)
            prompt = np.concatenate([shared, tail])
        reqs.append(Request(i, prompt, max_new_tokens=mnew,
                            priority=(i * 7) % 3,
                            deadline_s=None if i % 4 == 0
                            else 0.01 * ((i * 5) % 4)))
    # an exact duplicate of a shared-preamble prompt: the whole-prompt
    # (partial tail block) match, boundary copy-on-write at insert
    reqs.append(Request(len(specs), reqs[1].prompt.copy(),
                        max_new_tokens=3))
    # an eos that fires mid-stream for request 2 (probed from the oracle
    # by the fixture consumer; here just reserve the slot)
    return reqs


@pytest.mark.parametrize("cfg_name,layout,admission,preemption", _cells())
def test_matrix_cell_matches_static_oracle(zoo, cfg_name, layout, admission,
                                           preemption):
    cfg, params, oracle = zoo[cfg_name]
    reqs = _workload(cfg)
    kw = dict(LAYOUTS[layout])
    if admission == "batch":
        eng = Engine(cfg, params, EngineConfig(
            max_len=48, admission="batch", **kw))
    else:
        eng = Engine(cfg, params, EngineConfig(
            max_len=48, max_slots=3, admission=admission,
            preemption=preemption, debug=True, **kw))
    outs = eng.generate(reqs)
    assert [c.id for c in outs] == [c.id for c in oracle]
    for ref, got in zip(oracle, outs):
        assert got.tokens == ref.tokens, \
            f"request {ref.id} diverged in cell {cfg_name}-{layout}-" \
            f"{admission}-{preemption}"
        assert got.finish_reason == ref.finish_reason
    sched = eng.scheduler
    if sched is None:
        return
    st = sched.stats()
    assert st["admissions"] >= len(reqs)
    if kw.get("kv_layout") == "paged":
        # the pool comes home whole: no leaked or double-freed blocks.
        # With the victim cache on, "home" is the parked population —
        # every in-use block is accounted for by the victim pool.
        parked = len(sched.layout.victim) if kw.get("victim_cache") else 0
        assert sched.alloc.in_use == parked
        assert sched.alloc.available == sched.alloc.capacity - parked
        assert not sched.block_tables.any()
        assert not sched.cache_len.any() and not sched.tokens.any()
        if kw.get("victim_cache"):
            assert parked > 0, "no chain survived the drain"
            sched.layout.check(set(), 3)
    if kw.get("prefix_cache"):
        assert st["prefix_hits"] > 0, "shared-prefix workload never shared"
        assert st["prefill_tokens_saved"] > 0


def test_matrix_cell_with_eos(zoo):
    """Eos stops agree across the matrix's most feature-loaded cell: the
    token streams truncate at the same point with the same reason."""
    cfg, params, _ = zoo["scan"]
    reqs = _workload(cfg)
    probe = Engine(cfg, params, EngineConfig(
        max_len=48, admission="batch")).generate(reqs)
    eos = probe[2].tokens[3]            # occurs mid-stream for request 2
    ref = Engine(cfg, params, EngineConfig(
        max_len=48, admission="batch")).generate(_with_eos(_workload(cfg),
                                                           eos))
    eng = Engine(cfg, params, EngineConfig(
        max_len=48, max_slots=3, kv_layout="paged", block_size=8,
        num_blocks=18, prefill_chunk=4, prefix_cache=True,
        admission="priority", preemption="lowest-priority", debug=True))
    outs = eng.generate(_with_eos(_workload(cfg), eos))
    assert [c.tokens for c in outs] == [c.tokens for c in ref]
    assert [c.finish_reason for c in outs] == [c.finish_reason for c in ref]
    assert "eos" in {c.finish_reason for c in ref}


def _with_eos(reqs, eos):
    for r in reqs:
        r.eos = eos
    return reqs


def test_invalid_cells_are_rejected(zoo):
    """The matrix's structural holes are loud, not silent: batch
    admission refuses paged layouts / chunked prefill, and prefix
    sharing refuses the slotted layout."""
    cfg, params, _ = zoo["scan"]
    with pytest.raises(ValueError, match="batch admission"):
        Engine(cfg, params, EngineConfig(admission="batch",
                                         kv_layout="paged"))
    with pytest.raises(ValueError, match="batch admission"):
        Engine(cfg, params, EngineConfig(admission="batch",
                                         prefill_chunk=4))
    with pytest.raises(ValueError, match="prefix_cache"):
        Engine(cfg, params, EngineConfig(kv_layout="slotted",
                                         prefix_cache=True))
    with pytest.raises(ValueError, match="prefix_cache"):
        Engine(cfg, params, EngineConfig(kv_layout="paged",
                                         victim_cache=True))
