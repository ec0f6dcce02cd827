"""Property-based prefill→decode handoff accounting (hypothesis).

* the prefill→decode **handoff is zero-copy bookkeeping**: across ANY
  interleaving of admissions, chunked prefills, growth preemptions
  (tight pool), and ``SlotFailure`` injections, the
  ``BlockAllocator``'s books still balance — no block leaks or
  double-frees when a slot's K/V joins the decode batch, every request
  gets its full token budget, and the drained pool is whole;
* preemption and failure re-queues move **time only**: the token
  streams are bit-identical to a clean, roomy, failure-free run of the
  same requests.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.scheduler import (ContinuousScheduler, Request,
                                     SchedulerConfig, SlotFailure)

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (see "
    "requirements-dev.txt); the fast lane skips them")
from hypothesis import given, settings, strategies as st  # noqa: E402

CFG = ModelConfig(
    name="handoff-props", arch_type="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64, dtype="float32",
    param_dtype="float32", attn_chunk=16, remat=False)
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
# few distinct prompt lengths => the one-shot prefill compiles stay cached
PROMPT_LENS = (4, 6, 8, 12)


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_property_handoff_books_balance(data):
    """Random workloads + random ``SlotFailure`` injections over a tight
    paged pool: the allocator's books balance at drain, and tokens match
    a clean, roomy run bit for bit."""
    rng = np.random.RandomState(data.draw(st.integers(0, 2 ** 16),
                                          label="seed"))
    n_req = data.draw(st.integers(2, 6), label="n_req")
    chunk = data.draw(st.sampled_from([0, 4]), label="prefill_chunk")
    # worst case: 12 prompt + 6 new tokens - 1 -> 17 rows -> 5 blocks of
    # 4; a tight pool forces growth preemption with 2-3 slots busy
    num_blocks = data.draw(st.integers(6, 14), label="num_blocks")
    reqs = [Request(i, rng.randint(0, CFG.vocab_size,
                                   PROMPT_LENS[i % len(PROMPT_LENS)]
                                   ).astype(np.int32),
                    max_new_tokens=int(rng.randint(1, 7)))
            for i in range(n_req)]
    n_fail = data.draw(st.integers(0, 3), label="n_fail")
    failures = [SlotFailure(step=data.draw(st.integers(0, 20),
                                           label=f"fail_step{i}"),
                            slots=data.draw(st.sampled_from(
                                [None, (0,), (0, 1)]), label=f"fail_slots{i}"))
                for i in range(n_fail)]
    sched = ContinuousScheduler(
        CFG, PARAMS, SchedulerConfig(max_slots=3, max_len=24, paged=True,
                                     block_size=4, num_blocks=num_blocks,
                                     prefill_chunk=chunk, debug=True),
        failures=failures)
    for r in reqs:
        sched.submit(r)
    outs = sched.run()
    assert [o.id for o in outs] == list(range(n_req)), "request dropped"
    for o, r in zip(outs, reqs):
        assert len(o.tokens) == r.max_new_tokens
    # the pool comes home whole through every handoff
    sched.alloc.check()
    assert sched.alloc.in_use == 0, "leaked blocks across the handoff"
    assert sched.alloc.available == sched.alloc.capacity
    assert not sched.block_tables.any()
    # bit-identical to a roomy, failure-free drain of the same requests
    ref = ContinuousScheduler(
        CFG, PARAMS, SchedulerConfig(max_slots=3, max_len=24, paged=True,
                                     block_size=4, num_blocks=32))
    for r in reqs:
        ref.submit(Request(r.id, r.prompt.copy(),
                           max_new_tokens=r.max_new_tokens))
    ref_outs = ref.run()
    assert {o.id: o.tokens for o in outs} == \
        {o.id: o.tokens for o in ref_outs}
