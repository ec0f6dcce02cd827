"""Prompt prefill paths: one-shot, prefix-resume, and chunked admission.

Free functions over the ``ContinuousScheduler`` (they are the prefill
half of its admission machinery, split out so the core loop module stays
within the runtime module-size budget).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.runtime.scheduler.types import _Ticket

__all__ = ["admit_one_shot", "admit_prefix_resume", "advance_chunked"]


def admit_one_shot(sched, ticket: _Ticket, slot: int, t0: float) -> None:
    """Whole-prompt prefill at admission: compute, insert into the
    shared cache, register the prefix, sample the first token."""
    r = ticket.req
    batch = {"tokens": jnp.asarray(r.prompt[None])}
    if r.embeds is not None:
        batch["embeds"] = jnp.asarray(r.embeds[None])
    tp = time.perf_counter()
    logits, req_cache, clen = jax.block_until_ready(
        sched._prefill_fn(sched.params, batch))
    sched.layout.insert(req_cache, slot)
    if sched._prefix and r.embeds is None:
        sched.layout.register_prefix(slot, r.prompt)
    dt = time.perf_counter() - tp
    ticket.prefill_s += dt
    if sched.obs is not None:
        sched._obs_prefill(slot, "prefill", tp, dt, len(r.prompt))
    first = int(sched.sampler(logits)[0])
    sched._activate(ticket, slot, first, int(clen[0]), t0)


def admit_prefix_resume(sched, ticket: _Ticket, slot: int, res,
                        matched: int, t0: float) -> None:
    """Prefix-cache hit on the one-shot path: the matched prompt rows'
    K/V already sit in resident pool blocks (now mapped into this slot's
    table), so prefill runs only over the unmatched tail — a scratch
    cache is seeded with the matched rows and one ``prefill_extend``
    resumes mid-prompt. The insert then writes only the private pages
    (shared pages keep the resident blocks). Greedy tokens are
    bit-identical to a full prefill: the seeded rows are exactly what
    this prompt's prefill would recompute."""
    r = ticket.req
    tp = time.perf_counter()
    scratch = T.init_cache(sched.cfg, 1, sched._scratch_len)
    scratch = sched.layout.seed_scratch(scratch, res, matched)
    tail = jnp.asarray(np.ascontiguousarray(r.prompt[matched:],
                                            np.int32)[None])
    logits, scratch, _ = jax.block_until_ready(sched._extend_fn(
        sched.params, tail, scratch,
        jnp.full((1,), matched, jnp.int32)))
    sched.layout.insert_scratch(scratch, slot)
    sched.layout.register_prefix(slot, r.prompt)
    dt = time.perf_counter() - tp
    ticket.prefill_s += dt
    if sched.obs is not None:
        sched._obs_prefill(slot, "prefill (prefix resume)", tp, dt,
                           len(r.prompt) - matched)
    sched.prefill_tokens_saved += matched
    first = int(sched.sampler(logits[:, -1])[0])
    sched._activate(ticket, slot, first, len(r.prompt), t0)


def advance_chunked(sched, t0: float) -> None:
    """Run ONE prefill chunk of the in-flight chunked admission, so
    prefill work interleaves with decode steps instead of stalling
    them. On the last chunk the scratch K/V is inserted into the
    shared cache and the request joins the decode batch."""
    st = sched._chunking
    if st is None:
        return
    r = st.ticket.req
    c = sched._chunk
    real = min(c, len(r.prompt) - st.pos)
    chunk = np.zeros((c,), np.int32)
    chunk[:real] = r.prompt[st.pos:st.pos + real]
    tp = time.perf_counter()
    logits, st.cache, _ = jax.block_until_ready(sched._extend_fn(
        sched.params, jnp.asarray(chunk[None]), st.cache,
        jnp.full((1,), st.pos, jnp.int32)))
    dt = time.perf_counter() - tp
    st.ticket.prefill_s += dt
    if sched.obs is not None:
        sched._obs_prefill(st.slot, "prefill chunk", tp, dt, real)
    st.pos += real
    if st.pos < len(r.prompt):
        return
    sched.layout.insert_scratch(st.cache, st.slot)
    if sched._prefix and r.embeds is None:
        sched.layout.register_prefix(st.slot, r.prompt)
    first = int(sched.sampler(logits[:, real - 1])[0])
    sched._chunking = None
    sched._activate(st.ticket, st.slot, first, len(r.prompt), t0)
