"""Continuous-batching scheduler + pipelined execution tests:

* admission/eviction ordering — FIFO admission, slots freed on eviction
  and reused by later requests;
* paged KV mechanics — admission waits instead of over-committing the
  pool, growth can preempt an in-flight chunked prefill, block
  accounting balances across evict/fail/preempt (token identity against
  the static oracle for every layout/policy combination lives in
  tests/test_conformance_matrix.py);
* prefix sharing — admissions with a common prompt prefix map the same
  physical blocks (observable refcounts), eviction releases references
  rather than freeing shared blocks, the prefix index dies with its
  blocks, and the copy-on-write growth guard gives a writer a private
  copy;
* pipelined modeled clocks — per-unit start times are monotone, every
  firing respects data availability, and the pipelined makespan beats
  sequential execution of the same stages while staying >= the bottleneck
  bound.
"""
from __future__ import annotations

from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.core import (Link, Mapping, PlatformGraph, PlatformModel,
                        ProcessingUnit, Simulator)
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.scheduler import (ContinuousScheduler, SchedulerConfig,
                                     SlotFailure)
from repro.runtime.serving import Request, ServeEngine

KEY = jax.random.PRNGKey(0)


def _tiny_cfg(n_layers: int = 2) -> ModelConfig:
    return ModelConfig(
        name="tiny", arch_type="dense", n_layers=n_layers, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64, dtype="float32",
        param_dtype="float32", attn_chunk=16, remat=False)


@pytest.fixture(scope="module")
def setup():
    cfg = _tiny_cfg()
    return cfg, T.init_params(cfg, KEY)


def _mixed_requests(cfg, specs, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(i, rng.randint(0, cfg.vocab_size, plen).astype(np.int32),
                    max_new_tokens=mnew)
            for i, (plen, mnew) in enumerate(specs)]


# ---------------------------------------------------------------------------
# paged KV cache + chunked prefill
# (greedy-identity cells live in tests/test_conformance_matrix.py)
# ---------------------------------------------------------------------------

MIXED_SPECS = [(8, 6), (12, 4), (8, 9), (5, 1), (12, 7),
               (16, 5), (7, 3), (9, 8), (8, 2), (16, 6)]


def test_paged_admission_waits_when_pool_exhausted(setup):
    """A pool that fits ~one request's worst case at a time must
    serialize admissions (no over-commit) and still serve everything:
    the set of concurrently admitted requests never needs more blocks
    than the pool holds."""
    cfg, params = setup
    specs = [(8, 4)] * 5                 # worst case 11 rows -> 3 blocks
    sched = ContinuousScheduler(
        cfg, params, SchedulerConfig(max_slots=4, max_len=32, paged=True,
                                     block_size=4, num_blocks=5, debug=True))
    for r in _mixed_requests(cfg, specs):
        sched.submit(r)
    outs = sched.run()
    assert [len(o.tokens) for o in outs] == [m for _, m in specs]
    live = set()
    peak = 0
    for e in sched.events:
        if e.kind == "admit":
            live.add(e.request_id)
        elif e.kind in ("evict", "fail", "preempt"):
            live.discard(e.request_id)
        peak = max(peak, len(live))
    assert peak <= 2, f"over-committed pool: {peak} concurrent requests"
    assert sched.alloc.in_use == 0


def test_growth_can_preempt_inflight_chunked_prefill(setup):
    """A pool dried out partly by a half-prefilled prompt's blocks must
    still let an older request's decode growth make progress: the
    in-flight chunked prefill is a preemption candidate like any active
    slot, not an invisible block holder that crashes run()."""
    cfg, params = setup
    # capacity 7 blocks of 2 rows. Request 0 (2-row prompt, 12 new
    # tokens, worst case 7 blocks) is decoding and growing a block every
    # 2 steps while request 1's 10-row prompt (5 blocks, admitted
    # upfront) spends 5 iterations in 2-token prefill chunks — the pool
    # runs dry at request 0's second growth, mid-prefill.
    sched = ContinuousScheduler(
        cfg, params, SchedulerConfig(max_slots=2, max_len=14, paged=True,
                                     block_size=2, num_blocks=8,
                                     prefill_chunk=2, debug=True))
    rng = np.random.RandomState(0)
    sched.submit(Request(0, rng.randint(0, cfg.vocab_size, 2)
                         .astype(np.int32), max_new_tokens=12))
    sched.submit(Request(1, rng.randint(0, cfg.vocab_size, 10)
                         .astype(np.int32), max_new_tokens=2))
    outs = sched.run()
    assert [len(o.tokens) for o in outs] == [12, 2]
    preempted = [e for e in sched.events if e.kind == "preempt"]
    assert preempted and preempted[0].request_id == 1
    assert sched.alloc.in_use == 0


@pytest.mark.parametrize("preemption,priorities,prefill", [
    ("evict-latest", (0, 0), {}),
    # one chunk per prompt: smaller chunks serialize the two
    # admissions, so the pool never runs dry
    ("evict-latest", (0, 0), dict(prefill_chunk=4)),
    ("lowest-priority", (0, 1), {}),
], ids=["one-shot", "chunked", "lowest-priority"])
def test_growth_preemption_drain_ends(setup, preemption, priorities,
                                      prefill):
    """The smallest drain in which growth preemption can ping-pong: two
    4-row prompts fill one page each of a 3-block pool, and both need a
    second page on the same step. A grower may only evict a holder its
    policy ranks below it, and otherwise yields, so the top-ranked
    request finishes and the drain ends — rather than the two evicting
    each other in turn for ever. Driven by step_once() under a step cap,
    so a regression fails in seconds instead of hanging."""
    cfg, params = setup
    rng = np.random.RandomState(0)
    reqs = [Request(i, rng.randint(0, cfg.vocab_size, 4).astype(np.int32),
                    max_new_tokens=3, priority=p)
            for i, p in enumerate(priorities)]
    sched = ContinuousScheduler(
        cfg, params, SchedulerConfig(max_slots=2, max_len=8, paged=True,
                                     block_size=4, num_blocks=4, debug=True,
                                     **prefill),
        preemption=preemption)
    for r in reqs:
        sched.submit(r)
    outs = []
    cap = (len(reqs) + 1) * sum(r.max_new_tokens for r in reqs)
    for _ in range(cap):
        if sched.done:
            break
        outs.extend(sched.step_once())
    assert sched.done, f"drain still running after {cap} steps: " \
        f"{sched.stats()}"
    assert sched.stats()["preemptions"] > 0, "growth never ran dry"
    sched.alloc.check()
    assert sched.alloc.in_use == 0
    assert {o.id: len(o.tokens) for o in outs} == {0: 3, 1: 3}
    # the grower's yield moves time only: tokens match a roomy pool
    ref = ContinuousScheduler(
        cfg, params, SchedulerConfig(max_slots=2, max_len=8, paged=True,
                                     block_size=4, num_blocks=8))
    for r in reqs:
        ref.submit(r)
    assert {o.id: o.tokens for o in outs} == \
        {o.id: o.tokens for o in ref.run()}


def test_paged_rejects_configs_with_no_global_attention(setup):
    """Subquadratic configs are exempt from the max_len rows bound, so
    paged growth could index past the block table; they also have no
    global-attn K/V to page. The combination is rejected up front."""
    cfg, params = setup
    import dataclasses
    local = dataclasses.replace(cfg, layer_pattern=("attn_local",), window=8)
    with pytest.raises(ValueError, match="paged KV cache pages"):
        ContinuousScheduler(local, params,
                            SchedulerConfig(max_slots=2, paged=True))


def test_evicted_slot_state_is_zeroed(setup):
    """No stale host-side mirrors after a drain: cache_len, last-token
    and block-table rows of freed slots are all zero (the invariant that
    used to rot silently when only cache_len was reset)."""
    cfg, params = setup
    sched = ContinuousScheduler(
        cfg, params, SchedulerConfig(max_slots=2, max_len=64, paged=True,
                                     block_size=8, debug=True),
        failures=[SlotFailure(step=2, slots=(1,))])
    for r in _mixed_requests(cfg, MIXED_SPECS[:5]):
        sched.submit(r)
    sched.run()
    assert not sched.cache_len.any()
    assert not sched.tokens.any()
    assert not sched.block_tables.any()
    assert sched.alloc.in_use == 0


def test_run_is_reentrant_and_keeps_pending_failures(setup):
    """A failure scheduled past the first drain's final step must fire in
    a later run() — the injected list is tracked with a cursor, not
    consumed destructively — and both drains stay bit-identical to the
    static path."""
    cfg, params = setup
    specs_a, specs_b = MIXED_SPECS[:3], MIXED_SPECS[3:6]
    static = ServeEngine(cfg, params, max_len=64).generate(
        _mixed_requests(cfg, specs_a + specs_b))
    sched = ContinuousScheduler(
        cfg, params, SchedulerConfig(max_slots=4, max_len=64, debug=True),
        failures=[SlotFailure(step=10 ** 6),    # never due: must survive
                  SlotFailure(step=12, slots=(0,))])
    reqs = _mixed_requests(cfg, specs_a + specs_b)
    for r in reqs[:3]:
        sched.submit(r)
    first = sched.run()
    steps_after_first = sched.step_count
    for r in reqs[3:]:
        sched.submit(r)
    second = sched.run()
    outs = sorted(first + second, key=lambda c: c.id)
    assert [c.tokens for c in outs] == [s.tokens for s in static]
    # the step-12 failure was consumed by whichever drain reached step 12
    # (the second, unless the first ran long), and the far-future one is
    # still pending — not dropped with the first drain's state
    assert steps_after_first < sched.step_count >= 12
    assert sched._failure_pos == 1
    assert sched.failures[sched._failure_pos].step == 10 ** 6


# ---------------------------------------------------------------------------
# admission / eviction ordering
# ---------------------------------------------------------------------------

def test_admission_is_fifo_and_eviction_frees_slots(setup):
    cfg, params = setup
    sched = ContinuousScheduler(cfg, params,
                                SchedulerConfig(max_slots=2, max_len=64))
    reqs = _mixed_requests(cfg, [(8, 2), (8, 6), (8, 3), (8, 4), (8, 1)])
    for r in reqs:
        sched.submit(r)
    outs = sched.run()
    assert [o.id for o in outs] == [0, 1, 2, 3, 4]
    admits = [e for e in sched.events if e.kind == "admit"]
    evicts = [e for e in sched.events if e.kind == "evict"]
    # FIFO: admission order == submission order even with slot contention
    assert [e.request_id for e in admits] == [0, 1, 2, 3, 4]
    assert len(evicts) == len(reqs)
    # every late admission reuses a slot somebody vacated first
    assert {e.slot for e in admits} == {0, 1}
    for a in admits[2:]:
        freed = [e for e in evicts if e.slot == a.slot and e.t_s <= a.t_s]
        assert freed, f"admission of {a.request_id} into occupied slot"
    # eviction happens exactly when the request's budget is spent
    for o in outs:
        assert len(o.tokens) == reqs[o.id].max_new_tokens


def test_overflowing_request_rejected(setup):
    cfg, params = setup
    sched = ContinuousScheduler(cfg, params,
                                SchedulerConfig(max_slots=2, max_len=16))
    with pytest.raises(ValueError, match="exceeds max_len"):
        sched.submit(Request(0, np.zeros(32, np.int32)))
    # prompt fits but prompt + decode budget would wrap the KV ring
    with pytest.raises(ValueError, match="exceeding max_len"):
        sched.submit(Request(1, np.zeros(14, np.int32), max_new_tokens=8))
    # exactly at capacity is fine: 14 + 3 - 1 == 16
    sched.submit(Request(2, np.zeros(14, np.int32), max_new_tokens=3))
    (out,) = sched.run()
    assert len(out.tokens) == 3


def test_static_path_rejects_overflow_identically(setup):
    """Both modes must agree on admission: a request the continuous
    scheduler rejects for KV-ring overflow can't silently wrap (and
    corrupt) on the static path either."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_len=16)
    with pytest.raises(ValueError, match="exceeding max_len"):
        eng.generate([Request(0, np.zeros(14, np.int32), max_new_tokens=8)])


def test_capped_cache_exempt_from_overflow_guard(setup):
    """max_cache_len caps the global-attention ring on purpose — the
    guard must not reject generations that slide past it."""
    cfg, params = setup
    import dataclasses
    capped = dataclasses.replace(cfg, max_cache_len=8)
    eng = ServeEngine(capped, params, max_len=16)
    outs = eng.generate([Request(0, np.zeros(8, np.int32),
                                 max_new_tokens=12)])
    assert len(outs[0].tokens) == 12


def test_arrivals_length_mismatch_rejected(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_len=64, mode="continuous",
                      max_slots=2)
    reqs = _mixed_requests(cfg, [(8, 2), (8, 2)])
    with pytest.raises(ValueError, match="arrivals"):
        eng.generate(reqs, arrivals=[0.0])


def test_arrival_times_produce_waiting(setup):
    """A request arriving later must not be admitted before its arrival
    instant (open-loop Poisson workloads rely on this)."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_len=64, mode="continuous",
                      max_slots=4)
    reqs = _mixed_requests(cfg, [(8, 4), (8, 4)])
    outs = eng.generate(reqs, arrivals=[0.0, 0.05])
    byid = {o.id: o for o in outs}
    assert byid[1].first_token_s >= 0.05
    assert byid[1].ttft_s >= 0.0


# ---------------------------------------------------------------------------
# failure injection: requests on failed slots are re-queued, not dropped
# ---------------------------------------------------------------------------

def test_slot_failure_requeues_not_drops(setup):
    """Mid-decode slot loss: affected requests go back to the head of the
    admission queue and re-prefill; every request (affected or not) must
    emit greedy tokens bit-identical to the failure-free run."""
    cfg, params = setup
    specs = [(8, 6), (12, 4), (8, 9), (5, 5), (12, 7)]
    ref_sched = ContinuousScheduler(cfg, params,
                                    SchedulerConfig(max_slots=2, max_len=64))
    for r in _mixed_requests(cfg, specs):
        ref_sched.submit(r)
    ref = ref_sched.run()

    sched = ContinuousScheduler(cfg, params,
                                SchedulerConfig(max_slots=2, max_len=64),
                                failures=[SlotFailure(step=3, slots=(0,))])
    for r in _mixed_requests(cfg, specs):
        sched.submit(r)
    out = sched.run()

    fails = [e for e in sched.events if e.kind == "fail"]
    assert fails, "injected failure never applied"
    assert [c.id for c in out] == [c.id for c in ref], "requests dropped"
    for a, b in zip(ref, out):
        assert a.tokens == b.tokens, f"request {a.id} diverged after requeue"
    # the victim was re-admitted (two admits), budget fully served
    victim = fails[0].request_id
    admits = [e.request_id for e in sched.events if e.kind == "admit"]
    assert admits.count(victim) == 2
    assert len(out[victim].tokens) == specs[victim][1]


def test_whole_unit_failure_requeues_every_active_request(setup):
    """slots=None models whole-unit loss: every active request re-queues
    in FIFO (arrival) order and the stream still completes bit-exactly."""
    cfg, params = setup
    specs = [(8, 5), (12, 5), (8, 5), (16, 5)]
    ref = ServeEngine(cfg, params, max_len=64).generate(
        _mixed_requests(cfg, specs))
    sched = ContinuousScheduler(cfg, params,
                                SchedulerConfig(max_slots=4, max_len=64),
                                failures=[SlotFailure(step=1)])
    for r in _mixed_requests(cfg, specs):
        sched.submit(r)
    out = sched.run()
    fails = [e for e in sched.events if e.kind == "fail"]
    assert len(fails) == 4
    assert [c.tokens for c in out] == [c.tokens for c in ref]


# ---------------------------------------------------------------------------
# prefix sharing (paged copy-on-write)
# ---------------------------------------------------------------------------

def _prefix_sched(cfg, params, **kw):
    base = dict(max_slots=2, max_len=32, paged=True, block_size=4,
                prefix_cache=True, debug=True)
    base.update(kw)
    return ContinuousScheduler(cfg, params, SchedulerConfig(**base))


def test_prefix_sharing_maps_same_blocks(setup):
    """Two concurrently-admitted requests with a common prompt prefix
    share physical blocks: identical table entries for the matched
    pages, refcount 2 on each, and the matched rows never re-prefill."""
    cfg, params = setup
    rng = np.random.RandomState(0)
    head = rng.randint(0, cfg.vocab_size, 14).astype(np.int32)
    tail = rng.randint(0, cfg.vocab_size, 6).astype(np.int32)
    sched = _prefix_sched(cfg, params)
    sched.submit(Request(0, head, max_new_tokens=6))
    sched.submit(Request(1, np.concatenate([head, tail]), max_new_tokens=6))
    sched.step_once()                   # one admission pass: 0 then 1
    s0, s1 = sched.block_tables[0], sched.block_tables[1]
    # request 1 matched request 0's whole prompt (14 rows: 3 full pages
    # shared, the partial tail seeded through the scratch)
    assert (s0[:3] == s1[:3]).all() and s0[:3].all(), (s0, s1)
    for blk in s1[:3]:
        assert sched.alloc.refcount(int(blk)) == 2
    assert s0[3] != s1[3], "partial tail block must be private (COW)"
    st = sched.stats()
    assert st["prefix_hits"] == 1
    assert st["prefill_tokens_saved"] == 14
    sched.run()
    assert sched.alloc.in_use == 0
    assert not sched.layout._prefix_full and not sched.layout._prefix_partial
    assert not sched.layout._block_keys, "index outlived its blocks"


def test_prefix_match_variants(setup):
    """The index matches what it may and nothing more: block-aligned
    chains, whole-prompt partial tails (capped at len-1 so admission
    still has logits to sample from), and no false positives on
    divergent or too-short prompts."""
    cfg, params = setup
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, cfg.vocab_size, 14).astype(np.int32)
    aligned = rng.randint(0, cfg.vocab_size, 16).astype(np.int32)
    sched = _prefix_sched(cfg, params)
    sched.submit(Request(0, prompt, max_new_tokens=8))
    sched.submit(Request(1, aligned, max_new_tokens=8))
    sched.step_once()                   # both admitted, still decoding
    lay = sched.layout
    # exact duplicate of the 14-token prompt: the partial tail entry is
    # as long as the whole prompt, so only the full chain matches (the
    # last token is always recomputed for its logits)
    src, matched = lay.match_prefix(prompt.copy())
    assert matched == 12 and len(src) == 3
    # exact duplicate of the block-aligned prompt: the final full block
    # covers the whole prompt, so the match caps at len - 1 and the
    # boundary block is seeded-from, never table-shared
    src, matched = lay.match_prefix(aligned.copy())
    assert matched == 15 and len(src) == 4
    # same aligned prefix, divergent tail: full blocks only
    div = np.concatenate([prompt[:12], (prompt[12:14] + 1) % cfg.vocab_size])
    src, matched = lay.match_prefix(div)
    assert matched == 12 and len(src) == 3
    # longer prompt continuing the resident one: chain + partial tail
    longer = np.concatenate([prompt, prompt[:5]])
    src, matched = lay.match_prefix(longer)
    assert matched == 14 and len(src) == 4
    # divergence inside the first block: no match
    bad = prompt.copy()
    bad[0] = (bad[0] + 1) % cfg.vocab_size
    assert lay.match_prefix(bad) == ([], 0)
    # a strict prefix of the resident prompt: full blocks only (partial
    # tails are keyed by the whole resident prompt)
    src, matched = lay.match_prefix(prompt[:13].copy())
    assert matched == 12 and len(src) == 3
    sched.run()


def test_eviction_releases_references_not_shared_blocks(setup):
    """Cancelling the request that *created* a shared chain must not
    free the blocks out from under the survivor: references release one
    by one, the block comes home only at refcount 0, and the survivor's
    tokens stay bit-identical to the static path."""
    cfg, params = setup
    rng = np.random.RandomState(2)
    head = rng.randint(0, cfg.vocab_size, 12).astype(np.int32)
    tail = rng.randint(0, cfg.vocab_size, 5).astype(np.int32)
    reqs = [Request(0, head, max_new_tokens=12),
            Request(1, np.concatenate([head, tail]), max_new_tokens=8)]
    static = ServeEngine(cfg, params, max_len=32).generate(
        [Request(r.id, r.prompt, max_new_tokens=r.max_new_tokens)
         for r in reqs])
    sched = _prefix_sched(cfg, params)
    t0 = sched.submit(reqs[0])
    sched.submit(reqs[1])
    sched.step_once()
    shared = [int(b) for b in sched.block_tables[1][:3]]
    assert all(sched.alloc.refcount(b) == 2 for b in shared)
    sched.request_cancel(t0)            # creator goes away mid-decode
    sched.step_once()
    assert all(sched.alloc.refcount(b) == 1 for b in shared), \
        "survivor lost its shared blocks"
    outs = {c.id: c for c in sched.run()}
    assert outs[1].tokens == static[1].tokens
    assert sched.alloc.in_use == 0


def test_grow_one_copy_on_write_gives_private_copy(setup):
    """The defensive COW guard on decode growth: a write targeting a
    block with refcount > 1 allocates a fresh block, copies the rows,
    swaps the table entry and drops one reference — the original block
    and its other reader are untouched."""
    cfg, params = setup
    rng = np.random.RandomState(3)
    sched = _prefix_sched(cfg, params)
    sched.submit(Request(0, rng.randint(0, cfg.vocab_size, 8)
                         .astype(np.int32), max_new_tokens=4))
    sched.step_once()
    lay = sched.layout
    old = int(sched.block_tables[0][1])
    lay.alloc.share([old])              # simulate a second reader
    assert lay.needs_block(0, 5)        # pos 5 -> page 1, shared
    assert lay.grow_one(0, 5)
    new = int(sched.block_tables[0][1])
    assert new != old
    assert lay.alloc.refcount(old) == 1 and lay.alloc.refcount(new) == 1
    k = np.asarray(lay.cache["scan"][0]["k"])
    assert np.array_equal(k[:, old], k[:, new]), "COW did not copy rows"
    assert old not in lay._slot_blocks[0] and new in lay._slot_blocks[0]
    lay._unregister(lay.alloc.release([old]))   # drop the simulated reader
    outs = sched.run()
    assert len(outs[0].tokens) == 4
    assert sched.alloc.in_use == 0


def test_prefix_seed_with_non_block_multiple_max_len(setup):
    """max_len is rounded up to a whole number of blocks in paged mode,
    so seeding a matched prefix whole-pages-at-a-time always fits the
    scratch cache — even when the configured max_len isn't a block
    multiple and the match ends mid-page (the near-miss shape: pages *
    block_size > configured max_len)."""
    cfg, params = setup
    rng = np.random.RandomState(5)
    head = rng.randint(0, cfg.vocab_size, 18).astype(np.int32)
    sched = _prefix_sched(cfg, params, max_len=20, block_size=8,
                          max_slots=2)
    assert sched.max_len == 24          # rounded up from 20
    static = ServeEngine(cfg, params, max_len=24).generate(
        [Request(0, head, max_new_tokens=2),
         Request(1, np.concatenate([head, head[:1]]), max_new_tokens=2)])
    sched.submit(Request(0, head, max_new_tokens=2))
    # 19-token prompt matching all 18 resident rows: seeds ceil(18/8)=3
    # whole pages = 24 rows, exactly the rounded scratch length
    sched.submit(Request(1, np.concatenate([head, head[:1]]),
                         max_new_tokens=2))
    outs = sched.run()
    assert [c.tokens for c in outs] == [s.tokens for s in static]
    assert sched.stats()["prefix_hits"] == 1
    assert sched.alloc.in_use == 0


def test_prefix_cache_silently_disabled_without_extend_support(setup):
    """Configs outside supports_chunked_prefill can't resume mid-prompt;
    prefix_cache degrades to plain paged serving instead of erroring
    (mirroring prefill_chunk's fallback)."""
    cfg, params = setup
    import dataclasses
    mixed = dataclasses.replace(cfg, layer_pattern=("attn", "attn_local"),
                                window=8)
    mixed_params = T.init_params(mixed, KEY)
    sched = ContinuousScheduler(
        mixed, mixed_params,
        SchedulerConfig(max_slots=2, max_len=32, paged=True, block_size=4,
                        prefix_cache=True, debug=True))
    assert not sched.layout.prefix_cache
    rng = np.random.RandomState(4)
    head = rng.randint(0, mixed.vocab_size, 8).astype(np.int32)
    for i in range(2):
        sched.submit(Request(i, head.copy(), max_new_tokens=3))
    outs = sched.run()
    assert [len(o.tokens) for o in outs] == [3, 3]
    assert sched.stats()["prefix_hits"] == 0


# ---------------------------------------------------------------------------
# pipelined modeled clocks
# ---------------------------------------------------------------------------

def _two_unit_platform(overlap: bool = False,
                       tx_cost: float = 0.0) -> PlatformModel:
    pg = PlatformGraph("test-2u")
    pg.add_unit(ProcessingUnit("endpoint", "cpu", flops=1e9,
                               mem_bandwidth=1e9, tx_cost_per_byte=tx_cost))
    pg.add_unit(ProcessingUnit("server", "cpu", flops=4e9,
                               mem_bandwidth=4e9))
    pg.add_link(Link("endpoint", "server", bandwidth=100e6, latency_s=1e-4,
                     overlap=overlap))
    return PlatformModel(pg)


@pytest.fixture(scope="module")
def staged():
    cfg = _tiny_cfg(n_layers=4)
    params = T.init_params(cfg, KEY)
    g = T.to_actor_graph(cfg, params, batch=1, seq=8, group_size=2)
    names = list(g.actors)
    mapping = Mapping("half", {n: ("endpoint" if i < len(names) // 2
                                   else "server")
                               for i, n in enumerate(names)})
    return cfg, params, g, mapping


def test_pipelined_makespan_beats_sequential(staged):
    from repro.core import synthesize
    cfg, params, g, mapping = staged
    prog = synthesize(g, mapping)
    pm = _two_unit_platform(overlap=True)
    rng = np.random.RandomState(0)
    frames = [{"Input": jax.numpy.asarray(
        rng.randint(0, cfg.vocab_size, (1, 8)).astype(np.int32))}
        for _ in range(6)]
    sinks, sched = prog.run_pipelined(frames, platform=pm)
    assert len(sinks) == len(frames)
    # outputs identical to the non-pipelined staged execution
    ref = prog.run_local(frames[0])
    assert np.array_equal(np.asarray(sinks[0]["Head"]),
                          np.asarray(ref["Head"]))
    assert sched.makespan_s < sched.sequential_s
    # bottleneck lower bound: no schedule finishes before the busiest
    # unit has done all its frames
    assert sched.makespan_s >= max(sched.unit_busy_s.values()) - 1e-12
    # per-unit modeled clocks are monotone and causally consistent
    last = defaultdict(float)
    for e in sched.entries:
        assert e.finish_s >= e.start_s
        assert e.start_s >= last[e.unit] - 1e-12
        last[e.unit] = e.finish_s


@pytest.mark.parametrize("tx_cost", [0.0, 56e-9])
def test_simulator_concurrent_clocks_monotone(staged, tx_cost):
    """tx_cost > 0 covers the sender-side TX CPU charge: the sequential
    reference must include it or pipeline_speedup drops below 1."""
    cfg, params, g, mapping = staged
    pm = _two_unit_platform(overlap=False, tx_cost=tx_cost)
    rng = np.random.RandomState(0)
    feed = [jax.numpy.asarray(rng.randint(0, cfg.vocab_size, (1, 8))
                              .astype(np.int32)) for _ in range(5)]
    res = Simulator(g, mapping=mapping, platform=pm).run(
        len(feed), source_inputs={"Input": feed})
    assert res.modeled_makespan_s > 0
    # concurrency can only help: makespan within [bottleneck, sequential]
    assert res.modeled_makespan_s <= res.modeled_total_s() + 1e-12
    assert res.modeled_makespan_s >= max(res.unit_busy_s.values()) - 1e-12
    assert res.pipeline_speedup >= 1.0
    last = defaultdict(float)
    for f in res.firings:
        assert f.finish_s >= f.start_s - 1e-12
        assert f.start_s >= last[f.unit] - 1e-12
        last[f.unit] = f.finish_s


def test_simulator_single_unit_makespan_is_sequential():
    """Without a second unit there is nothing to overlap: the concurrent
    clocks must degenerate to the summed busy time."""
    from repro.models.cnn import vehicle_graph
    g = vehicle_graph()
    pg = PlatformGraph("one")
    pg.add_unit(ProcessingUnit("endpoint", "cpu", flops=1e9,
                               mem_bandwidth=1e9))
    mapping = Mapping("all-local", {n: "endpoint" for n in g.actors})
    res = Simulator(g, mapping=mapping,
                    platform=PlatformModel(pg)).run(3)
    assert res.modeled_makespan_s == pytest.approx(res.modeled_total_s())
