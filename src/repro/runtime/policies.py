"""Serving policies: admission order, preemption victims, sampling.

The ``Engine``/``ContinuousScheduler`` split is policy vs mechanism: the
scheduler owns the decode loop, KV layout surgery and failure handling
(mechanism), while *which* request is admitted next, *who* gets
preempted when the paged pool runs dry, and *how* logits become tokens
are pluggable objects defined here — each testable in isolation with
plain Python (no JAX, no model) by feeding it ticket-shaped records.

Admission policies are priority orders, not queues: the scheduler keeps
the waiting set and repeatedly admits ``min(waiting, key=policy.key)``.
A smaller key means sooner. Every key ends with the submission sequence
number, so ties break FIFO and the order is total (deterministic).
Because greedy decoding is per-request deterministic regardless of what
else shares the batch, *any* admission order emits tokens identical to
the static-bucket path — policies change waiting time, never content.

``BatchAdmission`` is the odd one out: it declares the static-bucket
execution mode (the seed path — group requests by prompt length, compile
per bucket, decode each bucket to completion). The ``Engine`` facade
routes to the bucket executor when it sees this policy, so the legacy
``mode="static-bucket"`` becomes just another admission policy instead
of a parallel API.
"""
from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def sample_tokens(key: jax.Array, logits: jax.Array, *, greedy: bool,
                  temperature: float) -> Tuple[jax.Array, jax.Array]:
    """Shared sampling rule for every engine path — the continuous ==
    static token-identity contract depends on there being exactly one.
    Returns (tokens (B,) int32, next key)."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
    key, sub = jax.random.split(key)
    return jax.random.categorical(
        sub, logits / temperature, axis=-1).astype(jnp.int32), key


class Sampler:
    """Owns the PRNG state for one engine. Greedy sampling never touches
    the key, so every greedy configuration is trivially reproducible;
    stochastic sampling splits the key per call, so the emitted stream
    depends on the order of sample calls (which is why the token-identity
    tests all run greedy)."""

    def __init__(self, *, greedy: bool = True, temperature: float = 1.0,
                 seed: int = 0):
        self.greedy = greedy
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)

    def __call__(self, logits: jax.Array) -> jax.Array:
        toks, self.key = sample_tokens(self.key, logits, greedy=self.greedy,
                                       temperature=self.temperature)
        return toks


# ---------------------------------------------------------------------------
# admission policies
# ---------------------------------------------------------------------------
# A ticket (scheduler._Ticket, or any duck-typed record in unit tests)
# exposes: .req (with .priority / .deadline_s), .arrival_s, .submit_seq.


def request_due_s(ticket) -> float:
    """Absolute due instant of a ticket on the engine clock (seconds
    from drain start): ``arrival_s + Request.deadline_s``, or +inf for
    background work without a deadline. One definition shared by EDF
    admission *ordering* and the scheduler's wall-clock deadline
    *enforcement* (``SchedulerConfig(enforce_deadlines=True)`` sheds a
    request whose due instant passes — before prefill, or mid-decode —
    completing it with ``finish_reason="timeout"``)."""
    d = ticket.req.deadline_s
    return ticket.arrival_s + d if d is not None else math.inf


class FifoAdmission:
    """Arrival order; ties (equal arrival instants, e.g. a closed-loop
    batch submitted at t=0) break by submission order. Failure/preemption
    victims re-sort to the head automatically: they were admitted once,
    so their (arrival_s, seq) precedes everything still waiting."""

    name = "fifo"

    def key(self, ticket) -> tuple:
        return (ticket.arrival_s, ticket.submit_seq)


class PriorityAdmission:
    """Highest ``Request.priority`` first; FIFO within a priority level.
    A late high-priority arrival jumps the queue at the next admission
    boundary — it never displaces an already-running request (that is
    the preemption policy's business, and only under pool pressure)."""

    name = "priority"

    def key(self, ticket) -> tuple:
        return (-ticket.req.priority, ticket.arrival_s, ticket.submit_seq)


class DeadlineAdmission:
    """Earliest deadline first. ``Request.deadline_s`` is seconds from
    the request's arrival; requests without a deadline sort last (they
    are background work). FIFO among equal deadlines."""

    name = "edf"

    def key(self, ticket) -> tuple:
        return (request_due_s(ticket), ticket.arrival_s, ticket.submit_seq)


class BatchAdmission:
    """The static-bucket mode as a policy: all requests are admitted as
    closed batches bucketed by prompt length (one compiled
    ``(batch, prompt_len)`` prefill/decode pair per bucket), each bucket
    decoded to completion before the next starts — exactly the seed
    ``ServeEngine`` path. The Engine routes to the bucket executor when
    configured with this policy; there is no admission queue, so
    ``arrivals=`` is rejected."""

    name = "batch"

    def buckets(self, items: Sequence[Any],
                prompt_of=lambda r: r.prompt) -> List[Tuple[int, List[Any]]]:
        """Group ``items`` (requests, or any carrier — ``prompt_of``
        extracts the prompt) by prompt length, shortest bucket first."""
        by_len: dict = {}
        for it in items:
            by_len.setdefault(len(prompt_of(it)), []).append(it)
        return sorted(by_len.items())


# ---------------------------------------------------------------------------
# preemption policies
# ---------------------------------------------------------------------------
# Candidates are the tickets currently holding KV blocks (active slots
# plus an in-flight chunked prefill), the slot whose growth needs the
# blocks included. .pick returns the victim ticket: the holder the
# policy ranks lowest. Progress rule: a grower may only evict a holder
# ranked below itself; when the pick is the grower, it yields — it is
# requeued, and the holders above it keep their blocks. Growth thus
# never preempts the top-ranked holder, whose worst case is validated to
# fit the whole pool at submit time, so it finishes, and by induction
# every drain ends. (Leaving the grower out of the candidates breaks
# this: a newer grower evicts the older holder, which is re-admitted as
# the newer one and evicts it back, for ever.)
#
# Under prefix sharing (``prefix_cache``), evicting a victim *releases
# its references* rather than freeing blocks outright: a block the
# victim shares with another live request stays resident (refcount > 0)
# and only the victim's private blocks return to the pool. A preemption
# may therefore reclaim fewer blocks than the victim's context length
# suggests; the scheduler keeps preempting until growth succeeds or the
# grower yields.


class EvictLatest:
    """Admission order wins: preempt the latest-admitted holder. A
    grower only ever evicts requests admitted after it, and yields when
    it is itself the latest, so the oldest admission always makes
    progress and two requests can never take turns restarting each
    other."""

    name = "evict-latest"

    def pick(self, candidates: List[Any]):
        return max(candidates, key=lambda t: t.admit_seq)


class LowestPriority:
    """Preempt the lowest-priority holder; among equals, the latest
    admitted. High-priority work keeps its KV blocks under pool pressure
    at the cost of restarting background requests: a grower only evicts
    a holder of lower priority, or of equal priority admitted later, and
    yields otherwise, so the oldest admission of the highest priority
    always makes progress."""

    name = "lowest-priority"

    def pick(self, candidates: List[Any]):
        return min(candidates, key=lambda t: (t.req.priority, -t.admit_seq))


# ---------------------------------------------------------------------------
# escalation policies
# ---------------------------------------------------------------------------
# Whether a request submitted to a TieredEngine (runtime.escalation) is
# answered by the small local engine or escalated to the server tier.
# Policies receive an EscalationContext-shaped record exposing:
#   .req            the Request (priority / deadline_s / max_new_tokens)
#   .snapshot       lazy local-load view: queue_depth, active_slots and
#                   best-effort kv occupancy, read lock-free (submit()
#                   must not convoy behind the engine's drain lock)
#   .now_s          seconds on the tiered engine's clock
#   .confidence()   lazy local-model confidence in [0, 1] — the max
#                   softmax probability of the local model's next-token
#                   prediction (the LLM analogue of the shallow-head
#                   gate in examples/early_exit_offload.py). Computed at
#                   most once per request, and only if some policy asks.
# ``decide(ctx)`` returns a short reason string to escalate, or None to
# answer locally. The TieredEngine ORs its policy list: the first
# non-None reason wins and is recorded on the handle / in the trace.


class NeverEscalate:
    """Local-only: the endpoint answers everything itself (the paper's
    endpoint-alone baseline; also the privacy-maximal configuration —
    no request ever leaves the device)."""

    name = "never"

    def decide(self, ctx) -> Optional[str]:
        return None


class AlwaysEscalate:
    """Server-only: every request escalates (the always-offload baseline
    the paper's collaborative numbers are compared against)."""

    name = "always"

    def decide(self, ctx) -> Optional[str]:
        return "always"


class ConfidenceEscalation:
    """Escalate the hard residue: requests the local model is *unsure*
    about (next-token max softmax probability below ``threshold``) go to
    the server; confident requests exit early on-device — VR-PRUNE's
    CA gate (examples/early_exit_offload.py) applied to served traffic,
    and PAPERS.md's 2-step-pruning escalation criterion."""

    name = "confidence"

    def __init__(self, threshold: float = 0.35):
        self.threshold = threshold

    def decide(self, ctx) -> Optional[str]:
        if ctx.confidence() < self.threshold:
            return "low_confidence"
        return None


class DeadlineRiskEscalation:
    """Escalate when the local tier probably cannot meet the request's
    deadline: estimated local completion time (queue ahead + own decode,
    at ``sec_per_token`` a token) times ``safety`` exceeds the deadline.
    Deadline-free requests never trip this policy."""

    name = "deadline-risk"

    def __init__(self, sec_per_token: float = 5e-3, safety: float = 1.5):
        self.sec_per_token = sec_per_token
        self.safety = safety

    def estimate_local_s(self, ctx) -> float:
        """Queue-depth-scaled service estimate: every queued request is
        assumed as long as this one (the tiers share the workload mix)."""
        waiting = ctx.snapshot.get("queue_depth", 0) + 1
        return waiting * ctx.req.max_new_tokens * self.sec_per_token

    def decide(self, ctx) -> Optional[str]:
        if ctx.req.deadline_s is None:
            return None
        if self.estimate_local_s(ctx) * self.safety > ctx.req.deadline_s:
            return "deadline_risk"
        return None


class LocalOverloadEscalation:
    """Escalate on local pressure: the endpoint's admission queue is
    deeper than ``max_queue_depth``, or (paged KV) the pool high-water
    mark has climbed past ``kv_frac`` of capacity — the request would
    only deepen a backlog the small tier cannot drain."""

    name = "overload"

    def __init__(self, max_queue_depth: int = 2, kv_frac: float = 1.0):
        self.max_queue_depth = max_queue_depth
        self.kv_frac = kv_frac

    def decide(self, ctx) -> Optional[str]:
        if ctx.snapshot.get("queue_depth", 0) > self.max_queue_depth:
            return "local_overload"
        kv = ctx.snapshot.get("kv", {})
        pool = kv.get("paged_kv_pool_bytes", 0.0)
        if pool and kv.get("paged_kv_hwm_bytes", 0.0) >= self.kv_frac * pool:
            return "local_overload"
        return None


# ---------------------------------------------------------------------------
# victim-cache eviction: which reclaimable prefix block goes first
# ---------------------------------------------------------------------------
#
# The victim pool (scheduler/prefix_pool.VictimCache) sorts its blocks
# by ``policy.key(view)`` ascending and evicts from the front when an
# allocation comes up short. ``view`` is an EvictionView: per-block
# re-match count (``hits``, persistent across revive/re-admit cycles),
# monotonic admission stamp (``stamp``, one per released chain), page
# depth within the chain (``page``), and owning ``tenant``. Keys are
# pure value tuples so eviction order is deterministic.


class LruEviction:
    """Plain LRU: least recently admitted chain first; within a chain,
    deepest page first — so the chain *head* (the part a shorter match
    can still use) survives longest."""
    name = "lru"

    def key(self, view) -> Any:
        return (view.stamp, -view.page)


class WeightedLruEviction:
    """Recency weighted by proven reuse: a never-re-matched chain
    evicts before a once-matched one regardless of age (hits is the
    primary key), then LRU stamp, then deepest page first. The default:
    a tenant's hot system prompt outlives a burst of one-off prompts
    admitted after it."""
    name = "weighted-lru"

    def key(self, view) -> Any:
        return (view.hits, view.stamp, -view.page)


# ---------------------------------------------------------------------------
# factories (EngineConfig carries policy names or instances)
# ---------------------------------------------------------------------------

ADMISSION_POLICIES = {
    "fifo": FifoAdmission,
    "priority": PriorityAdmission,
    "edf": DeadlineAdmission,
    "deadline": DeadlineAdmission,
    "batch": BatchAdmission,
    "static-bucket": BatchAdmission,    # legacy mode name
}

PREEMPTION_POLICIES = {
    "evict-latest": EvictLatest,
    "lowest-priority": LowestPriority,
}

VICTIM_EVICTION_POLICIES = {
    "lru": LruEviction,
    "weighted-lru": WeightedLruEviction,
}

ESCALATION_POLICIES = {
    "never": NeverEscalate,
    "always": AlwaysEscalate,
    "confidence": ConfidenceEscalation,
    "deadline-risk": DeadlineRiskEscalation,
    "overload": LocalOverloadEscalation,
}


def make_admission(spec) -> Any:
    """Resolve an admission policy name or pass an instance through."""
    if isinstance(spec, str):
        try:
            return ADMISSION_POLICIES[spec]()
        except KeyError:
            raise ValueError(
                f"admission policy {spec!r} not in "
                f"{sorted(set(ADMISSION_POLICIES))}") from None
    return spec


def make_preemption(spec) -> Any:
    """Resolve a preemption policy name or pass an instance through."""
    if isinstance(spec, str):
        try:
            return PREEMPTION_POLICIES[spec]()
        except KeyError:
            raise ValueError(
                f"preemption policy {spec!r} not in "
                f"{sorted(PREEMPTION_POLICIES)}") from None
    return spec


def make_victim_eviction(spec) -> Any:
    """Resolve a victim-eviction policy name or pass an instance
    through."""
    if isinstance(spec, str):
        try:
            return VICTIM_EVICTION_POLICIES[spec]()
        except KeyError:
            raise ValueError(
                f"victim-eviction policy {spec!r} not in "
                f"{sorted(VICTIM_EVICTION_POLICIES)}") from None
    return spec


def make_escalation(spec) -> List[Any]:
    """Resolve an escalation policy specification into a policy *list*
    (the TieredEngine ORs them): a name, an instance, or a sequence of
    either."""
    if isinstance(spec, str) or not isinstance(spec, (list, tuple)):
        spec = [spec]
    out = []
    for s in spec:
        if isinstance(s, str):
            try:
                out.append(ESCALATION_POLICIES[s]())
            except KeyError:
                raise ValueError(
                    f"escalation policy {s!r} not in "
                    f"{sorted(ESCALATION_POLICIES)}") from None
        else:
            out.append(s)
    return out
