"""Continuous-batching core loop: mechanism under pluggable policies.

The scheduler is the *mechanism* half of the serving stack (the policy
half lives in ``runtime.policies``; the user-facing facade is
``runtime.engine.Engine``). The package splits it by concern —
``types`` (request/config dataclasses), ``allocator`` (the refcounted
block pool), ``layouts`` (slotted / paged KV surgery), ``prefill``
(one-shot / prefix-resume / chunked admission compute), ``units`` (the
multi-unit execution core) — and this module owns the loop that ties
them together:

* the decode loop — one decode function compiled ONCE at a fixed slot
  count ``max_slots``; requests join and leave the running batch between
  steps without recompiling;
* the waiting set — *which* request is admitted next, *who* is
  preempted under pool pressure, *how* logits become tokens, and
  *where* prompt bursts land are the injected policies' calls;
* the request lifecycle — per-token streaming to a ``RequestHandle``,
  cancellation, injected ``SlotFailure`` re-queue/terminate, wall-clock
  deadline shedding, and a ``finish_reason`` on every ``Completion``;
* the **execution core** (``units.ExecutionCore``): every prompt burst,
  K/V handoff and batched decode step is also charged to modeled
  per-unit clocks, giving each drain a deterministic multi-unit
  timeline — prefill/decode disaggregation and pipelined in-flight
  decode — without touching token content (``units=1``, the default,
  is the degenerate case: one clock, makespan == total work).

Per-slot ``cache_len`` makes the shared batch sound (decode attention
masks rows at position >= cache_len[slot], so mixed-length contexts
coexist in one step), and greedy decoding is per-request deterministic,
so every layout/policy/unit combination emits tokens bit-identical to
the static-bucket path (tests/test_conformance_matrix.py).
"""
from __future__ import annotations

import heapq
import time
from collections import Counter
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.observability import (SIZE_BUCKETS, TIME_BUCKETS_S,
                                         Observability, PagedKVReads)
from repro.runtime.policies import (BatchAdmission, EvictLatest,
                                    FifoAdmission, Sampler, make_admission,
                                    make_preemption, request_due_s)
from repro.runtime.scheduler import prefill as _prefill
from repro.runtime.scheduler.allocator import BlockAllocator
from repro.runtime.scheduler.layouts import (PagedLayout, SlottedLayout,
                                             prefill_programs)
from repro.runtime.scheduler.types import (Completion, Request, SchedEvent,
                                           SchedulerConfig, SlotFailure,
                                           _ChunkedPrefill, _Ticket,
                                           validate_request_fits)
from repro.runtime.scheduler.units import ExecutionCore

__all__ = ["ContinuousScheduler"]


class ContinuousScheduler:
    """Admission queue + shared decode batch over a slot/paged KV cache.

    Policies are injected (``admission``, ``preemption``, ``sampler``) —
    names or instances from ``runtime.policies``; the defaults (FIFO,
    evict-latest, greedy) reproduce the pre-policy scheduler exactly."""

    def __init__(self, cfg: ModelConfig, params: Any,
                 sched: Optional[SchedulerConfig] = None, *,
                 failures: Optional[List[SlotFailure]] = None,
                 admission: Any = None, preemption: Any = None,
                 sampler: Optional[Sampler] = None,
                 obs: Optional[Observability] = None):
        self.cfg = cfg
        self.params = params
        self.sched = s = sched or SchedulerConfig()
        self.admission = make_admission(admission) if admission is not None \
            else FifoAdmission()
        if isinstance(self.admission, BatchAdmission):
            raise ValueError(
                "batch admission is the Engine's static-bucket path; the "
                "continuous scheduler needs an ordering policy "
                "(fifo | priority | edf)")
        self.preemption = make_preemption(preemption) \
            if preemption is not None else EvictLatest()
        self.sampler = sampler or Sampler(greedy=s.greedy,
                                          temperature=s.temperature,
                                          seed=s.seed)
        # Injected slot failures, applied at decode-step boundaries. A
        # cursor (not destructive pops) tracks what has been applied, so
        # run() is re-entrant: a second run() with new submissions still
        # sees failures the first drain never reached.
        self.failures = sorted(failures or [], key=lambda f: f.step)
        self._failure_pos = 0
        # paged mode wants a whole number of blocks per slot
        self.max_len = s.max_len if not s.paged else \
            -(-s.max_len // s.block_size) * s.block_size
        max_len = self.max_len
        self._prefill_fn, self._extend_fn, routing = prefill_programs(
            cfg, max_len, obs)
        # chunked prefill (gated to configs the extend path supports)
        self._chunk = s.prefill_chunk \
            if (s.prefill_chunk > 0 and T.supports_chunked_prefill(cfg)) \
            else 0
        self._scratch_len = -(-max_len // self._chunk) * self._chunk \
            if self._chunk else max_len
        self._chunking: Optional[_ChunkedPrefill] = None
        if s.paged:
            kv_reads = PagedKVReads(obs.registry) if (
                obs is not None and obs.enabled) else None
            self.layout = PagedLayout(cfg, s, max_len, self._scratch_len,
                                      routing, kv_reads)
        else:
            self.layout = SlottedLayout(cfg, s, max_len, self._scratch_len,
                                        routing)
        # prefix sharing resumes prefill mid-prompt through the same
        # extend path chunked prefill uses (the layout re-checks config
        # support, so the flag is the effective one)
        self._prefix = getattr(self.layout, "prefix_cache", False)
        # prefill-work accounting for the serving bench: prompt tokens
        # admitted vs prompt tokens whose K/V came from a shared prefix
        self.prefill_tokens_total = 0
        self.prefill_tokens_saved = 0
        # Persistent slot state. cache_len/tokens (and the layout's block
        # tables) are host-side mirrors so admission/eviction never
        # touches device state beyond the insert.
        self.cache_len = np.zeros((s.max_slots,), np.int32)
        self.tokens = np.zeros((s.max_slots,), np.int32)
        self.free: List[int] = list(range(s.max_slots))[::-1]  # pop() -> 0,1,..
        self.active: Dict[int, _Ticket] = {}
        # waiting set: a heap keyed by the admission policy's (static,
        # total-order) key, so each admission is O(log n) instead of a
        # min-scan + remove. Cancelled entries are retired in place and
        # skipped lazily at the top; _queue_stale counts them.
        self.queue: List[tuple] = []
        self._queue_stale = 0
        self.backlog: List[_Ticket] = []  # submitted, not yet "arrived"
        self._backlog_pos = 0           # consumed-prefix cursor into backlog
        self._backlog_dirty = False
        self._admit_seq = 0
        self._submit_seq = 0
        self.events: List[SchedEvent] = []
        self.step_count = 0
        self._t0: Optional[float] = None
        self._cancel_requests: List[_Ticket] = []   # via request_cancel()
        # deadline enforcement: min-heap of (due_s, submit_seq, ticket)
        # over live deadline-carrying tickets, so the per-boundary shed
        # check is O(expired log n), not a scan of the waiting set.
        # Entries for finished tickets are skipped lazily at the top.
        self._deadline_heap: List[tuple] = []
        self.tokens_generated = 0
        # Observability (None = disabled; the hot path pays one `is None`
        # test per hook). Trace timestamps run on a *construction-epoch*
        # clock (`_obs_now`) rather than the scheduler's per-drain `_t0`:
        # `_t0` resets between drains, and a trace track's timestamps
        # must never go backwards. Metric *durations* are differences of
        # scheduler-clock stamps, so they are epoch-independent.
        self.obs = obs if (obs is not None and obs.enabled) else None
        if self.obs is not None:
            self._obs_epoch = time.perf_counter()
            self._phase: Dict[str, float] = {}
            r = self.obs.registry
            self._m = {
                "ttft": r.histogram(
                    "repro_ttft_seconds", TIME_BUCKETS_S,
                    help="arrival to first token (admission wait + prefill)"),
                "inter_token": r.histogram(
                    "repro_inter_token_seconds", TIME_BUCKETS_S,
                    help="steady-state gap between consecutive tokens "
                         "of one request"),
                "step": r.histogram(
                    "repro_step_duration_seconds", TIME_BUCKETS_S,
                    help="one scheduler iteration, boundary to boundary"),
                "queue_wait": r.histogram(
                    "repro_queue_wait_seconds", TIME_BUCKETS_S,
                    help="enqueue to admission pop"),
                "chunk": r.histogram(
                    "repro_prefill_chunk_tokens", SIZE_BUCKETS,
                    help="prompt tokens prefilled per admission/chunk step"),
                "blocks": r.histogram(
                    "repro_blocks_in_use", SIZE_BUCKETS,
                    help="paged KV blocks held, sampled each step"),
            }
            for ph in ("admission", "prefill", "decode", "sampling", "kv"):
                self._m["step_" + ph] = r.histogram(
                    f"repro_step_{ph}_seconds", TIME_BUCKETS_S,
                    help=f"per-step time inside the {ph} phase")
        # Multi-unit execution core: every prompt burst / handoff /
        # decode step is mirrored onto modeled per-unit clocks. Built
        # after obs so its per-unit tracks share the tracer.
        self.core = ExecutionCore(s, obs=self.obs)

    # -- legacy attribute surface (tests/benches reach for these) -----------

    @property
    def alloc(self) -> Optional[BlockAllocator]:
        return getattr(self.layout, "alloc", None)

    @property
    def block_tables(self) -> Optional[np.ndarray]:
        return getattr(self.layout, "block_tables", None)

    @property
    def cache(self):
        return self.layout.cache

    @property
    def key(self) -> jax.Array:
        return self.sampler.key

    @key.setter
    def key(self, k: jax.Array) -> None:
        self.sampler.key = k

    # -- public API ---------------------------------------------------------

    def submit(self, req: Request, arrival_s: float = 0.0) -> _Ticket:
        """Queue a request for admission at ``arrival_s`` (seconds from
        drain start). Returns the internal ticket — the Engine wraps it
        in a ``RequestHandle``; direct callers can ignore it."""
        validate_request_fits(self.cfg, req, self.max_len)
        self.layout.validate(req)
        if self.done:
            # a fresh drain after a completed one starts a fresh arrival
            # epoch, whichever drive path (run() or step_once()) follows
            self._t0 = None
        ticket = _Ticket(req=req, arrival_s=arrival_s,
                         submit_seq=self._submit_seq)
        self._submit_seq += 1
        self.backlog.append(ticket)
        self._backlog_dirty = True
        if self.sched.enforce_deadlines and req.deadline_s is not None:
            heapq.heappush(self._deadline_heap,
                           (request_due_s(ticket), ticket.submit_seq, ticket))
        return ticket

    def request_cancel(self, ticket: _Ticket) -> None:
        """Flag a ticket for cancellation (the RequestHandle's path).
        Only flips a flag and records the ticket — retirement happens
        at the next step boundary (or inside the admission loop, for a
        cancel from another stream's token callback mid-pass), so this
        is token-callback safe; the list keeps the purge O(#cancelled)."""
        ticket.cancelled = True
        self._cancel_requests.append(ticket)

    @property
    def done(self) -> bool:
        """True when nothing is queued, active, mid-prefill, or pending
        arrival — a step_once() now would be a no-op."""
        return (self._backlog_pos >= len(self.backlog)
                and self._waiting() == 0
                and not self.active and self._chunking is None)

    # -- waiting-set heap ---------------------------------------------------

    def _waiting(self) -> int:
        return len(self.queue) - self._queue_stale

    def _enqueue(self, ticket: _Ticket) -> None:
        """Push into the waiting heap under the admission policy's key
        (computed once — policy inputs are static per ticket); the
        submit_seq tiebreak keeps entries totally ordered without ever
        comparing tickets."""
        ticket.where = "queued"
        heapq.heappush(self.queue, (self.admission.key(ticket),
                                    ticket.submit_seq, ticket))
        if self.obs is not None:
            # only ever called while stepping, so _t0 is set
            ticket.queued_at_s = time.perf_counter() - self._t0
            self.obs.tracer.async_begin(
                "engine", "queue", f"req {ticket.req.id} queued",
                ticket.req.id, self._obs_now(),
                args={"restarts": ticket.restarts})

    def _queue_head(self) -> Optional[_Ticket]:
        """The policy's next pick, skipping entries retired by
        cancellation (lazy deletion)."""
        while self.queue and self.queue[0][2].retired:
            heapq.heappop(self.queue)
            self._queue_stale -= 1
        return self.queue[0][2] if self.queue else None

    def run(self, on_completion: Optional[Callable[[Completion], None]] = None
            ) -> List[Completion]:
        """Drain every submitted request; returns completions by id.
        ``on_completion`` (streaming mode) is invoked with each completion
        the moment its request finishes, before the drain returns.
        Re-entrant: a later run() continues from the same step counter and
        failure cursor, serving anything submitted since (arrivals are
        measured from *this* call when the scheduler is idle; a drain
        resumed mid-flight — e.g. after step-driven streaming — keeps
        the original epoch so in-flight timestamps stay coherent)."""
        if self._t0 is None or (self._waiting() == 0 and not self.active
                                and self._chunking is None):
            self._t0 = time.perf_counter()
        self._sort_pending()
        out: List[Completion] = []
        while not self.done:
            out.extend(self.step_once(on_completion))
        return sorted(out, key=lambda c: c.id)

    def step_once(self, on_completion: Optional[
            Callable[[Completion], None]] = None) -> List[Completion]:
        """One scheduler iteration: deliver arrivals, purge cancellations,
        apply due failures, advance the in-flight chunked prefill, admit,
        and (if anything is active) run one decode step. Returns the
        completions this iteration produced. Drives the step-wise Engine
        API (``RequestHandle.stream()`` pulls this between tokens)."""
        if self.obs is None:
            return self._step_impl(on_completion)
        self._phase = {}
        w0 = time.perf_counter()
        out = self._step_impl(on_completion)
        self._obs_step_done(w0, time.perf_counter())
        return out

    def _step_impl(self, on_completion: Optional[
            Callable[[Completion], None]] = None) -> List[Completion]:
        if self._t0 is None:
            self._t0 = time.perf_counter()
        if self._backlog_dirty:
            self._sort_pending()
        t0 = self._t0
        obs = self.obs
        done: List[Completion] = []
        now = time.perf_counter() - t0
        while (self._backlog_pos < len(self.backlog)
               and self.backlog[self._backlog_pos].arrival_s <= now):
            self._enqueue(self.backlog[self._backlog_pos])
            self._backlog_pos += 1
        done.extend(self._purge_cancelled(t0))
        done.extend(self._shed_expired(t0))
        if (self._waiting() == 0 and not self.active
                and self._chunking is None):
            if obs is not None:
                # an arrival-gap sleep (or a no-op boundary) is not an
                # engine step — keep it out of the step histograms
                self._phase["idle"] = 1.0
            if self._backlog_pos < len(self.backlog):
                # idle until the next arrival (virtual clock = wall
                # clock). Failures due at this step boundary still apply
                # — they must not be silently deferred past the gap.
                done.extend(self._apply_failures(t0))
                time.sleep(max(
                    0.0, self.backlog[self._backlog_pos].arrival_s - now))
            return self._deliver(done, on_completion)
        wa = time.perf_counter()
        done.extend(self._apply_failures(t0))
        self._advance_chunked(t0)
        done.extend(self._admit(t0))
        if obs is not None:
            # admission machinery = this whole region minus the prefill
            # compute the leaf helpers attributed to their own phase
            self._phase["admission"] = (
                time.perf_counter() - wa - self._phase.get("prefill", 0.0))
        if self.active:
            done.extend(self._decode_step(t0))
        if self.sched.debug:
            self._check_invariants()
        if self.sched.step_delay_s:
            time.sleep(self.sched.step_delay_s)   # device-speed handicap
        return self._deliver(done, on_completion)

    # -- observability hooks (self.obs is not None on every call) -----------

    def _obs_now(self) -> float:
        return time.perf_counter() - self._obs_epoch

    def _obs_step_done(self, w0: float, w1: float) -> None:
        ph = self._phase
        if "idle" in ph:
            return
        m = self._m
        m["step"].observe(w1 - w0)
        for k in ("admission", "prefill", "decode", "sampling", "kv"):
            if k in ph:
                m["step_" + k].observe(ph[k])
        alloc = self.alloc
        if alloc is not None:
            m["blocks"].observe(alloc.in_use)
        args = {k: round(v * 1e3, 4) for k, v in ph.items()}
        args.update(active=len(self.active), queued=self._waiting())
        self.obs.tracer.complete(
            "engine", "steps", f"step {self.step_count}",
            w0 - self._obs_epoch, w1 - w0, args=args)

    def _obs_dequeue(self, ticket: _Ticket) -> None:
        """Close the request's queued span (admission pop, queue-side
        shed/cancel — every way a ticket leaves the waiting set)."""
        self.obs.tracer.async_end(
            "engine", "queue", ticket.req.id, self._obs_now())

    def _obs_slot_begin(self, ticket: _Ticket, slot: int,
                        matched: int) -> None:
        ts = self._obs_now()
        tr = self.obs.tracer
        tr.begin("engine", f"slot {slot}", f"req {ticket.req.id}", ts,
                 args={"prompt_tokens": len(ticket.req.prompt),
                       "restarts": ticket.restarts})
        if matched:
            tr.instant("engine", f"slot {slot}", "prefix-hit", ts,
                       args={"request": ticket.req.id,
                             "matched_rows": matched})

    def _obs_prefill(self, slot: int, name: str, tp: float, dt: float,
                     tokens: int) -> None:
        """Attribute one prefill compute burst: phase accounting, the
        chunk-size histogram, and an X span nested in the slot track.
        ``tp`` is the raw perf_counter() start stamp."""
        self._phase["prefill"] = self._phase.get("prefill", 0.0) + dt
        self._m["chunk"].observe(tokens)
        self.obs.tracer.complete("engine", f"slot {slot}", name,
                                 tp - self._obs_epoch, dt,
                                 args={"tokens": tokens})

    def kv_stats(self) -> Dict[str, float]:
        """KV-memory accounting for the serving bench: what a dense
        slotted cache reserves vs what the paged pool holds / has ever
        held (high-water mark), in bytes of global-attention K/V."""
        return self.layout.kv_stats(self.sched, self.cfg)

    def stats(self) -> Dict[str, int]:
        """Lifecycle counters accumulated so far (the serving bench
        reports preemptions when sweeping the admission watermark)."""
        c, lay = Counter(e.kind for e in self.events), self.layout
        return {"requests_submitted": self._submit_seq,
                "admissions": c["admit"], "evictions": c["evict"],
                "preemptions": c["preempt"], "slot_failures": c["fail"],
                "cancellations": c["cancel"], "sheds": c["shed"],
                "steps": self.step_count,
                "tokens_generated": self.tokens_generated,
                "prefix_hits": getattr(lay, "prefix_hits", 0),
                "victim_hits": getattr(lay, "victim_hits", 0),
                "victim_evictions": getattr(lay, "victim_evictions", 0),
                "prefill_tokens_total": self.prefill_tokens_total,
                "prefill_tokens_saved": self.prefill_tokens_saved}

    def unit_stats(self) -> Dict[str, Any]:
        """The execution core's modeled multi-unit timeline: unit roster,
        per-unit busy seconds, makespan, and the speedup over serializing
        the same work on one unit (serving bench / snapshot surface)."""
        return self.core.summary()

    # -- internals ----------------------------------------------------------

    def _sort_pending(self) -> None:
        pending = sorted(self.backlog[self._backlog_pos:],
                         key=lambda t: t.arrival_s)
        self.backlog[self._backlog_pos:] = pending
        self._backlog_dirty = False

    @staticmethod
    def _deliver(done: List[Completion],
                 on_completion: Optional[Callable[[Completion], None]]
                 ) -> List[Completion]:
        if on_completion is not None:
            for c in done:
                on_completion(c)
        return done

    def _event(self, t_s: float, kind: str, rid: int, slot: int) -> None:
        """Record a lifecycle event; disruptions (preempt/fail/shed/
        cancel) additionally land as instant markers on the trace track
        of the slot (or the queue, for never-admitted requests)."""
        self.events.append(SchedEvent(t_s, kind, rid, slot, self.step_count))
        if self.obs is not None and kind in ("preempt", "fail",
                                             "shed", "cancel"):
            thread = f"slot {slot}" if slot >= 0 else "queue"
            self.obs.tracer.instant("engine", thread, kind, self._obs_now(),
                                    args={"request": rid})

    def _emit(self, ticket: _Ticket, tok: int) -> None:
        """Append a token and stream it to the handle. After a failure
        re-queue the greedy re-decode re-produces the already-streamed
        prefix; the handle dedups by index: each token seen once."""
        ticket.emitted.append(tok)
        self.tokens_generated += 1
        if ticket.handle is not None:
            ticket.handle._emit(len(ticket.emitted) - 1, tok)

    def _finish(self, ticket: _Ticket, reason: str, t0: float) -> Completion:
        now = time.perf_counter() - t0
        decode_s = now - ticket.first_token_s if ticket.first_token_s > 0.0 \
            else 0.0
        c = Completion(
            ticket.req.id, ticket.emitted, ticket.prefill_s, decode_s,
            arrival_s=ticket.arrival_s, first_token_s=ticket.first_token_s,
            finish_s=now, finish_reason=reason, restarts=ticket.restarts)
        ticket.where = "done"
        if ticket.handle is not None:
            ticket.handle._complete(c)
        return c

    def _release_slot(self, slot: int) -> None:
        """Return a slot (and, paged, its blocks — exactly once) to the
        free pool, zeroing every host-side mirror so no stale state
        outlives the occupancy."""
        self.free.append(slot)
        self.cache_len[slot] = 0
        self.tokens[slot] = 0
        self.layout.release(slot)
        self.core.release(slot)
        if self.obs is not None:
            # every occupied slot opened its span at admission; closing
            # here covers every exit path (finish/evict/preempt/fail/
            # shed/cancel, mid-chunking included)
            self.obs.tracer.end("engine", f"slot {slot}", self._obs_now())

    @staticmethod
    def _reset_ticket(ticket: _Ticket) -> None:
        ticket.slot = -1
        ticket.emitted = []
        ticket.prefill_s = 0.0
        ticket.first_token_s = 0.0
        ticket.admit_seq = -1

    def _purge_cancelled(self, t0: float) -> List[Completion]:
        """Retire every cancelled request at this step boundary: waiting
        and not-yet-arrived requests complete with no tokens, an active
        slot or in-flight chunked prefill is released. cancel() itself
        only flips a flag, so a request cancelled *during* a decode step
        (from another stream's token callback) is caught before its next
        token is emitted. O(#cancelled): dispatches over the recorded
        cancel requests by ticket state, never scanning the waiting set
        (waiting entries are retired in place in the heap)."""
        out: List[Completion] = []
        if not self._cancel_requests:
            return out
        requests, self._cancel_requests = self._cancel_requests, []
        for ticket in requests:
            if ticket.where == "done":      # raced a finish; nothing to do
                continue
            if ticket.where == "backlog":
                self.backlog.remove(ticket)     # always at index >= cursor
                out.append(self._cancel_ticket(ticket, t0))
            elif ticket.where == "queued":
                ticket.retired = True           # lazy heap deletion
                self._queue_stale += 1
                if self.obs is not None:
                    self._obs_dequeue(ticket)
                out.append(self._cancel_ticket(ticket, t0))
            elif ticket.where == "active":
                out.append(self._evict(ticket.slot, t0, "cancelled",
                                       kind="cancel"))
            elif ticket.where == "chunking":
                st = self._chunking
                self._chunking = None
                self._release_slot(st.slot)
                out.append(self._cancel_ticket(ticket, t0, slot=st.slot))
        return out

    def _cancel_ticket(self, ticket: _Ticket, t0: float,
                       slot: int = -1) -> Completion:
        now = time.perf_counter() - t0
        self._event(now, "cancel", ticket.req.id, slot)
        return self._finish(ticket, "cancelled", t0)

    def _shed_expired(self, t0: float) -> List[Completion]:
        """Deadline enforcement at a step boundary: complete every
        live request whose due instant has passed with
        ``finish_reason="timeout"``. A waiting request is retired in
        place (never prefilled); an active one is evicted mid-decode —
        its slot and (paged) block references are released, and with the
        shed happening *before* the decode step, not one token is
        emitted after it. A ticket mid-chunked-prefill releases its slot
        and reserved blocks the same way. No-op unless the scheduler was
        built with ``enforce_deadlines=True`` (the heap is only fed
        then), so the conformance-matrix identity paths never pay for
        this."""
        out: List[Completion] = []
        if not self._deadline_heap:
            return out
        now = time.perf_counter() - t0
        while self._deadline_heap and self._deadline_heap[0][0] <= now:
            _, _, ticket = heapq.heappop(self._deadline_heap)
            if ticket.where == "done" or ticket.cancelled:
                continue                    # finished/cancelled first
            if ticket.where == "backlog":
                # due <= now implies arrival_s <= now, so arrivals have
                # normally been delivered already — defensive only
                self.backlog.remove(ticket)
                out.append(self._shed_ticket(ticket, t0))
            elif ticket.where == "queued":
                ticket.retired = True       # lazy heap deletion
                self._queue_stale += 1
                if self.obs is not None:
                    self._obs_dequeue(ticket)
                out.append(self._shed_ticket(ticket, t0))
            elif ticket.where == "active":
                out.append(self._evict(ticket.slot, t0, "timeout",
                                       kind="shed"))
            elif ticket.where == "chunking":
                st = self._chunking
                self._chunking = None
                self._release_slot(st.slot)
                out.append(self._shed_ticket(ticket, t0, slot=st.slot))
        return out

    def _shed_ticket(self, ticket: _Ticket, t0: float,
                     slot: int = -1) -> Completion:
        now = time.perf_counter() - t0
        self._event(now, "shed", ticket.req.id, slot)
        return self._finish(ticket, "timeout", t0)

    def _retire_from_admission(self, ticket: _Ticket,
                               t0: float) -> Completion:
        """A cancel issued mid-admission-pass (from an earlier admitted
        request's token callback) reaches the ticket before the purge
        does: complete it here so it is never prefilled — the 'not one
        more token after cancel() returns' contract covers the first
        token too."""
        heapq.heappop(self.queue)
        if self.obs is not None:
            self._obs_dequeue(ticket)
        return self._cancel_ticket(ticket, t0)

    def _requeue_or_fail(self, victims: List[_Ticket],
                         t0: float) -> List[Completion]:
        """Post-failure/preemption routing: re-queue (restart from the
        prompt) while the request has restart budget, else complete as
        "failed" with the tokens already streamed."""
        out: List[Completion] = []
        for ticket in sorted(victims, key=lambda t: t.arrival_s):
            mr = ticket.req.max_restarts
            if mr is not None and ticket.restarts >= mr:
                if ticket.handle is not None:
                    # after earlier restarts, this attempt's replay may be
                    # shorter than what was already streamed — the handle
                    # holds the longest (deduped) history, and "failed"
                    # reports the tokens streamed before the loss
                    ticket.emitted = list(ticket.handle.tokens)
                out.append(self._finish(ticket, "failed", t0))
                continue
            ticket.restarts += 1
            self._reset_ticket(ticket)
            if ticket.handle is not None and not self.sampler.greedy:
                # a stochastic re-decode can't replay the streamed prefix
                # (the key advanced), so the handle's index dedup would
                # splice two different runs — restart its stream instead
                ticket.handle._restart()
            self._enqueue(ticket)
        return out

    def _apply_failures(self, t0: float) -> List[Completion]:
        """Apply injected slot failures due at the current step boundary:
        every request on a failed slot is *re-queued, not dropped* — its
        KV state (and paged blocks) is gone, so it goes back into the
        admission queue (where its original arrival keys it ahead of
        younger work under FIFO) and is re-prefilled from its original
        prompt. A prompt mid-way through chunked prefill on a failed slot
        restarts the same way. Greedy decoding makes the re-run
        deterministic, so its final tokens — and those of every
        unaffected request, whose slots are untouched — are bit-identical
        to a failure-free run. Requests whose ``max_restarts`` budget is
        exhausted complete as "failed" instead."""
        out: List[Completion] = []
        while (self._failure_pos < len(self.failures)
               and self.failures[self._failure_pos].step <= self.step_count):
            f = self.failures[self._failure_pos]
            self._failure_pos += 1
            slots = list(self.active) if f.slots is None \
                else [s for s in f.slots if s in self.active]
            now = time.perf_counter() - t0
            victims = []
            for slot in slots:
                ticket = self.active.pop(slot)
                self._release_slot(slot)
                self._event(now, "fail", ticket.req.id, slot)
                victims.append(ticket)
            st = self._chunking
            if st is not None and (f.slots is None or st.slot in f.slots):
                self._chunking = None
                self._release_slot(st.slot)
                self._event(now, "fail", st.ticket.req.id, st.slot)
                victims.append(st.ticket)
            out.extend(self._requeue_or_fail(victims, t0))
        return out

    def _admit(self, t0: float) -> List[Completion]:
        """Admit waiting requests into free slots, in the admission
        policy's order, until slots or (paged) blocks run out. When the
        policy's next pick can't be served, admission stops — no head-of-
        line bypass, so the policy order is also the service order.
        Returns completions of requests cancelled mid-pass (by an
        earlier admission's token callback) before they were prefilled."""
        out: List[Completion] = []
        while self.free:
            ticket = self._queue_head()
            if ticket is None:
                break
            if ticket.cancelled:
                out.append(self._retire_from_admission(ticket, t0))
                continue
            if (self.sched.enforce_deadlines
                    and request_due_s(ticket) <= time.perf_counter() - t0):
                # expired while queued behind this pass's earlier
                # prefills: shed before prefill, not after
                heapq.heappop(self.queue)
                if self.obs is not None:
                    self._obs_dequeue(ticket)
                out.append(self._shed_ticket(ticket, t0))
                continue
            r = ticket.req
            chunked = self._chunk > 0 and r.embeds is None
            if chunked and self._chunking is not None:
                break           # one chunked prefill in flight at a time
            res = self.layout.try_reserve(r)
            if res is None:
                break           # pool exhausted: wait, don't over-commit
            heapq.heappop(self.queue)
            slot = self.free.pop()
            ticket.admit_seq = self._admit_seq
            self._admit_seq += 1
            self.layout.bind(slot, res)
            self.prefill_tokens_total += len(r.prompt)
            matched = getattr(res, "matched_rows", 0)
            if self.obs is not None:
                self._m["queue_wait"].observe(
                    time.perf_counter() - t0 - ticket.queued_at_s)
                self._obs_dequeue(ticket)
                self._obs_slot_begin(ticket, slot, matched)
            if chunked:
                # resume at the last chunk boundary inside the matched
                # region, so every extend step keeps the compiled chunk
                # shape (shared pages beyond the resume point still save
                # memory; their recomputed rows are dropped at insert)
                resume = (matched // self._chunk) * self._chunk
                scratch = T.init_cache(self.cfg, 1, self._scratch_len)
                if resume:
                    scratch = self.layout.seed_scratch(scratch, res, resume)
                    self.prefill_tokens_saved += resume
                ticket.slot = slot
                ticket.where = "chunking"
                self._chunking = _ChunkedPrefill(
                    ticket=ticket, slot=slot, cache=scratch, pos=resume)
            elif matched:
                _prefill.admit_prefix_resume(self, ticket, slot, res,
                                             matched, t0)
            else:
                _prefill.admit_one_shot(self, ticket, slot, t0)
        return out

    def _advance_chunked(self, t0: float) -> None:
        _prefill.advance_chunked(self, t0)

    def _activate(self, ticket: _Ticket, slot: int, first: int, clen: int,
                  t0: float) -> None:
        ticket.first_token_s = time.perf_counter() - t0
        ticket.slot = slot
        ticket.where = "active"
        self._emit(ticket, first)
        self.cache_len[slot] = clen
        self.tokens[slot] = first
        self.active[slot] = ticket
        # prefill -> decode handoff: the slot's K/V joins the decode
        # batch in place (zero-copy — same pool blocks, same refcounts)
        self.core.handoff(slot, blocks=len(
            getattr(self.layout, "_slot_blocks", {}).get(slot, ())))
        self._event(ticket.first_token_s, "admit", ticket.req.id, slot)
        if self.obs is not None:
            self._m["ttft"].observe(ticket.first_token_s - ticket.arrival_s)
            ticket.last_emit_s = ticket.first_token_s

    def _finished(self, ticket: _Ticket) -> bool:
        return len(ticket.emitted) >= ticket.req.max_new_tokens

    def _pick_preempt_victim(self) -> int:
        """Ask the preemption policy for a victim among the current block
        holders: every active slot, the growing one included, and an
        in-flight chunked prefill (it holds its prompt blocks, so a pool
        dried out by a half-prefilled prompt can still be reclaimed)."""
        cands = list(self.active.values())
        if self._chunking is not None:
            cands.append(self._chunking.ticket)
        return self.preemption.pick(cands).slot

    def _preempt(self, slot: int, t0: float) -> Optional[Completion]:
        """Evict-and-requeue to reclaim blocks for decode growth (another
        request's, or its own when the grower yields): the victim restarts
        from its prompt (greedy decode makes the re-run bit-identical) —
        or completes as "failed" once its restart budget is spent."""
        if self._chunking is not None and self._chunking.slot == slot:
            ticket = self._chunking.ticket
            self._chunking = None
        else:
            ticket = self.active.pop(slot)
        self._release_slot(slot)
        now = time.perf_counter() - t0
        self._event(now, "preempt", ticket.req.id, slot)
        out = self._requeue_or_fail([ticket], t0)
        return out[0] if out else None

    def _grow_blocks(self, t0: float) -> List[Completion]:
        """Paged decode growth: before a decode step, every active slot
        whose next KV write position falls in an unallocated page gets one
        fresh block. On a dry pool the preemption policy ranks every
        block holder, the grower included, and its pick is requeued: a
        holder ranked below the grower, or else the grower itself, which
        yields. Growth thus never preempts the top-ranked holder, and as
        submit() validates that any single request's worst case fits the
        pool, a lower-ranked holder exists whenever its growth finds the
        pool dry: it finishes, and by induction every drain ends (only a
        later, higher-priority admission outranks it, finitely often).
        Returns completions of victims that ran out of restart budget."""
        out: List[Completion] = []
        if not self.layout.paged:
            return out
        for slot in sorted(self.active,
                           key=lambda s: self.active[s].admit_seq):
            if slot not in self.active:     # preempted earlier this pass
                continue
            pos = int(self.cache_len[slot])
            if not self.layout.needs_block(slot, pos):
                continue
            while not self.layout.grow_one(slot, pos):
                victim = self._pick_preempt_victim()
                c = self._preempt(victim, t0)
                if c is not None:
                    out.append(c)
                if victim == slot:          # the grower yielded
                    break
        return out

    def _decode_step(self, t0: float) -> List[Completion]:
        done: List[Completion] = []
        obs = self.obs
        # Requests satisfied by the prefill token alone never decode.
        for slot in [s for s, tk in self.active.items() if self._finished(tk)]:
            done.append(self._evict(slot, t0, "length"))
        if not self.active:
            return done
        wk = time.perf_counter()
        done.extend(self._grow_blocks(t0))
        if obs is not None:
            wd = time.perf_counter()
            self._phase["kv"] = self._phase.get("kv", 0.0) + (wd - wk)
        logits = self.layout.decode(self.params, jnp.asarray(self.tokens),
                                    self.cache_len)
        if obs is not None:
            # force the async dispatch so decode vs sampling attribution
            # is real; values are untouched, so greedy identity holds
            logits = jax.block_until_ready(logits)
            ws = time.perf_counter()
            self._phase["decode"] = self._phase.get("decode", 0.0) + (ws - wd)
        toks = np.asarray(self.sampler(logits))
        if obs is not None:
            now_s = time.perf_counter()
            self._phase["sampling"] = \
                self._phase.get("sampling", 0.0) + (now_s - ws)
            now_s -= t0
        self.step_count += 1
        # mirror the batched step onto the modeled decode pipeline
        # (sorted: lane membership must not depend on dict order)
        self.core.decode_step(sorted(self.active))
        for slot in self.active:     # free slots keep cache_len == 0
            self.cache_len[slot] += 1
        for slot, ticket in list(self.active.items()):
            if ticket.cancelled:
                # cancelled mid-step by another stream's token callback:
                # this step's token is dropped, nothing was emitted after
                # cancel() returned
                done.append(self._evict(slot, t0, "cancelled",
                                        kind="cancel"))
                continue
            t = int(toks[slot])
            if ticket.req.eos is not None and t == ticket.req.eos:
                done.append(self._evict(slot, t0, "eos"))
                continue
            self._emit(ticket, t)
            if obs is not None:
                self._m["inter_token"].observe(now_s - ticket.last_emit_s)
                ticket.last_emit_s = now_s
            self.tokens[slot] = t
            if self._finished(ticket):
                done.append(self._evict(slot, t0, "length"))
        return done

    def _evict(self, slot: int, t0: float, reason: str,
               kind: str = "evict") -> Completion:
        ticket = self.active.pop(slot)
        self._release_slot(slot)
        now = time.perf_counter() - t0
        self._event(now, kind, ticket.req.id, slot)
        return self._finish(ticket, reason, t0)

    def _check_invariants(self) -> None:
        """Step-boundary slot/block accounting (SchedulerConfig(debug=
        True)): a free slot has no residual length/token/table state, and
        the layout's books balance — every held block is named by exactly
        one table entry of exactly one occupied slot."""
        free = set(self.free)
        occupied = set(self.active)
        if self._chunking is not None:
            occupied.add(self._chunking.slot)
        assert not (free & occupied), (free, occupied)
        for slot in range(self.sched.max_slots):
            if slot in free:
                assert self.cache_len[slot] == 0, f"slot {slot}: stale len"
                assert self.tokens[slot] == 0, f"slot {slot}: stale token"
        self.layout.check(occupied, self.sched.max_slots)
