"""KV-cache layouts: the cache-shape half of the scheduler, one object each.

``SlottedLayout`` gives every slot dense ``max_len`` rows;
``PagedLayout`` pools global-attention K/V behind per-slot block tables
(allocator-backed, prefix-sharing, copy-on-write). Both compile their
decode step once.

Every serving program of an expert model also reports its routing
(``moe_counts``); with observability on, an ``ExpertRouting`` folds the
reports into the expert counters (``prefill_programs``).
"""
from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.observability import (ExpertRouting, Observability,
                                         PagedKVReads)
from repro.runtime.scheduler.allocator import BlockAllocator
from repro.runtime.scheduler.prefix_pool import VictimCache
from repro.runtime.scheduler.types import Request, SchedulerConfig

__all__ = ["SlottedLayout", "PagedLayout", "_PagedReservation",
           "prefill_programs"]


def _program(cfg: ModelConfig, fn, routing: Optional[ExpertRouting],
             decode: bool = False):
    """``fn`` compiled. An expert model's program is asked for its
    routing as well (``moe_counts``), which goes to ``routing`` when
    there is one; the caller gets the program's other outputs."""
    if cfg.moe is None:
        return jax.jit(fn)
    prog = jax.jit(partial(fn, moe_counts=True))

    def call(*args):
        *out, report = prog(*args)
        if routing is not None:
            routing.add(report, decode)
        return tuple(out)
    return call


def prefill_programs(cfg: ModelConfig, max_len: int,
                     obs: Optional[Observability]):
    """The scheduler's compiled one-shot prefill and chunked extend, and
    the expert counters that every serving program of an expert model
    feeds (None without experts or with observability off)."""
    routing = ExpertRouting(obs.registry) if (
        cfg.moe is not None and obs is not None and obs.enabled) else None
    return (_program(cfg, lambda p, b, **kw: T.prefill(
                p, cfg, b, max_len=max_len, **kw), routing),
            _program(cfg, lambda p, tok, c, cl, **kw: T.prefill_extend(
                p, cfg, tok, c, cl, **kw), routing),
            routing)


def _decode_fn(cfg: ModelConfig, *, paged: bool,
               routing: Optional[ExpertRouting] = None):
    """The layout's compiled decode step."""
    if paged:
        fn = (lambda p, tok, cache, clen, tbl, **kw:
              T.decode_step(p, cfg, tok, cache, clen, block_tables=tbl, **kw))
    else:
        fn = (lambda p, tok, cache, clen, **kw:
              T.decode_step(p, cfg, tok, cache, clen, **kw))
    return _program(cfg, fn, routing, decode=True)


class SlottedLayout:
    """Dense per-slot KV rows: slot ``i`` owns rows ``[i, :max_len]`` of
    every cache leaf. Reservation always succeeds (the rows exist by
    construction), growth never happens, release is a no-op."""

    paged = False

    def __init__(self, cfg: ModelConfig, s: SchedulerConfig, max_len: int,
                 scratch_len: int, routing: Optional[ExpertRouting] = None):
        self.max_len = max_len
        self.cache = T.init_cache(cfg, s.max_slots, max_len)
        self._decode = _decode_fn(cfg, paged=False, routing=routing)
        self._insert = jax.jit(self._insert_impl)
        self._insert_sliced = jax.jit(self._insert_sliced_impl)

    @staticmethod
    def _insert_impl(batch_cache, req_cache, slot):
        """Write a batch=1 prefill cache into slot ``slot`` of the shared
        batch cache. Scanned-period leaves are (P, B, ...), remainder
        leaves (B, ...)."""
        scan = jax.tree.map(lambda big, small: big.at[:, slot].set(small[:, 0]),
                            batch_cache["scan"], req_cache["scan"])
        rem = jax.tree.map(lambda big, small: big.at[slot].set(small[0]),
                           batch_cache["rem"], req_cache["rem"])
        return {"scan": scan, "rem": rem}

    def _insert_sliced_impl(self, batch_cache, req_cache, slot):
        """Insert from the chunk-rounded scratch cache: keep the first
        max_len rows of every K/V leaf. Only reachable for chunked-
        prefill configs (all-global-attn), where every cache leaf has the
        row dim right after batch."""
        ml = self.max_len
        scan = jax.tree.map(
            lambda big, small: big.at[:, slot].set(small[:, 0, :ml]),
            batch_cache["scan"], req_cache["scan"])
        rem = jax.tree.map(
            lambda big, small: big.at[slot].set(small[0, :ml]),
            batch_cache["rem"], req_cache["rem"])
        return {"scan": scan, "rem": rem}

    def validate(self, req: Request) -> None:
        pass

    def try_reserve(self, req: Request) -> Optional[List[int]]:
        return []

    def bind(self, slot: int, blocks: List[int]) -> None:
        pass

    def register_prefix(self, slot: int, prompt: np.ndarray) -> None:
        pass                            # sharing is a paged-pool feature

    def insert(self, req_cache, slot: int) -> None:
        self.cache = self._insert(self.cache, req_cache, jnp.int32(slot))

    def insert_scratch(self, scratch_cache, slot: int) -> None:
        self.cache = self._insert_sliced(self.cache, scratch_cache,
                                         jnp.int32(slot))

    def decode(self, params, tokens: jax.Array, cache_len: np.ndarray):
        logits, self.cache, _ = self._decode(params, tokens, self.cache,
                                             jnp.asarray(cache_len))
        return logits

    def needs_block(self, slot: int, pos: int) -> bool:
        return False

    def grow_one(self, slot: int, pos: int) -> bool:
        raise RuntimeError("slotted layout never grows")

    def release(self, slot: int) -> None:
        pass

    def kv_stats(self, s: SchedulerConfig, cfg: ModelConfig) -> Dict[str, float]:
        row = T.kv_row_bytes(cfg)
        return {"slotted_kv_reserved_bytes":
                float(s.max_slots * s.max_len * row)}

    def check(self, occupied_slots: set, max_slots: int) -> None:
        pass


@dataclass
class _PagedReservation:
    """Outcome of a paged admission reservation. ``blocks`` is the
    slot's table in page order: the first ``shared_pages`` entries are
    resident blocks mapped in by the prefix match (refcount already
    incremented), the rest freshly allocated private blocks.
    ``seed_blocks`` are the source blocks whose pool rows cover prompt
    positions ``[0, matched_rows)`` — the scratch cache is seeded from
    them so ``prefill_extend`` can resume mid-prompt. The boundary page
    (the one containing row ``matched_rows``) is always private: its
    shared rows are copied through the scratch and written at insert
    time — copy-on-write realized at admission."""
    blocks: List[int]
    shared_pages: int = 0
    seed_blocks: List[int] = field(default_factory=list)
    matched_rows: int = 0
    tenant: str = ""                    # prefix-cache namespace (Request.tenant)


class PagedLayout:
    """Block-pool KV: global-attention K/V in shared fixed-size blocks
    addressed through per-slot block tables; local-window / recurrent
    state stays slot-indexed inside the same cache pytree. Owns the
    allocator, the tables, the per-slot block bookkeeping (references
    released exactly once, whoever triggers it) and — with
    ``prefix_cache`` — the prefix index that lets admissions share
    resident block chains."""

    paged = True

    def __init__(self, cfg: ModelConfig, s: SchedulerConfig, max_len: int,
                 scratch_len: int, routing: Optional[ExpertRouting] = None,
                 kv_reads: Optional[PagedKVReads] = None):
        if cfg.max_cache_len:
            raise ValueError(
                "paged KV cache is position-indexed; max_cache_len ring "
                "caps are a slotted-path feature")
        if all(k != "attn" for k in cfg.layer_kinds):
            raise ValueError(
                f"{cfg.name}: paged KV cache pages global-attention K/V, "
                "but this config has none (local windows and recurrent "
                "state are fixed-size per slot) — use the slotted layout; "
                "its memory is already bounded")
        self.max_len = max_len
        self.kv_reads = kv_reads
        self.block_size = s.block_size
        self.watermark = s.watermark
        self.pages_per_slot = max_len // s.block_size
        num_blocks = s.num_blocks or (s.max_slots * self.pages_per_slot + 1)
        self.alloc = BlockAllocator(num_blocks, s.block_size)
        if self.watermark >= self.alloc.capacity:
            raise ValueError(
                f"watermark {self.watermark} leaves no admissible blocks "
                f"in a pool of {self.alloc.capacity}")
        self.block_tables = np.zeros((s.max_slots, self.pages_per_slot),
                                     np.int32)
        self._slot_blocks: Dict[int, List[int]] = {}
        self.cache = T.init_paged_cache(cfg, num_blocks, s.block_size,
                                        s.max_slots, max_len=max_len)
        self._decode = _decode_fn(cfg, paged=True, routing=routing)
        self._insert_paged = jax.jit(
            lambda c, rc, bids, slot: T.paged_insert(
                cfg, c, rc, bids, slot, block_size=s.block_size))
        # prefix sharing: the mid-prompt resume runs through
        # prefill_extend, so gate on the same support predicate as
        # chunked prefill (silent fallback, like prefill_chunk)
        self.prefix_cache = s.prefix_cache and T.supports_chunked_prefill(cfg)
        # tenant-scoped prefix index: each namespace maps a chained hash
        # of a block-aligned token prefix -> (resident block holding its
        # last page of K/V rows, that page's tokens, parent chain key).
        # A request only probes its own tenant's namespace, so a hash
        # hit can never map another tenant's K/V; the tokens are also
        # compared on every match, so a collision within a namespace
        # degrades to a miss, never to sharing foreign K/V. The parent
        # key makes the index walkable for checkpoint export (hashes
        # are not invertible); empty namespaces are pruned so the outer
        # dicts are empty exactly when the index is.
        self._prefix_full: Dict[str, Dict[int, Tuple[int, np.ndarray,
                                                     int]]] = {}
        # tenant -> chained hash of a prompt's full pages -> [(tail
        # block, prompt length, tail tokens), ...] for prompts whose
        # last page is partially filled: one bucket per full-page
        # chain, so a boundary probe is a single lookup plus tail
        # comparisons
        self._prefix_partial: Dict[str, Dict[int, List[Tuple[
            int, int, np.ndarray]]]] = {}
        self._block_keys: Dict[int, List[Tuple[str, str, int]]] = {}
        self._block_tenant: Dict[int, str] = {}     # indexed block -> owner
        self._slot_tenant: Dict[int, str] = {}      # bound slot -> tenant
        self._shared_pages: Dict[int, int] = {}     # slot -> shared table pages
        self._table_pending: Dict[int, List[int]] = {}  # bound, not inserted
        self._seed = jax.jit(
            lambda sc, c, bids: T.paged_seed(cfg, sc, c, bids))
        self._copy_block = jax.jit(
            lambda c, src, dst: T.paged_copy_block(cfg, c, src, dst))
        self.prefix_hits = 0            # admissions that matched a chain
        self.victim_hits = 0            # matches that touched pooled blocks
        self.victim_evictions = 0       # pooled blocks freed under pressure
        # victim cache: released refcount-1 indexed blocks park here
        # (still held, K/V resident, index entries alive) instead of
        # freeing, so the prefix index outlives requests and drain
        # epochs; evicted only under allocation pressure
        self.victim: Optional[VictimCache] = None
        self._protect: frozenset = frozenset()      # mid-reservation blocks
        if self.prefix_cache and s.victim_cache:
            self.victim = VictimCache(
                block_bytes=s.block_size * T.kv_row_bytes(cfg),
                policy=s.victim_eviction,
                quotas=s.prefix_cache_tenants)

    def _prompt_need(self, req: Request) -> int:
        return max(1, -(-len(req.prompt) // self.block_size))

    # -- prefix index -------------------------------------------------------

    # Keys are *chained* hashes: key_p = hash(key_{p-1}, page-p tokens),
    # so matching/registering a prompt hashes every token once — O(L) —
    # instead of re-hashing the prefix from position 0 per boundary
    # (O(L^2/bs)). Entries carry the tokens they summarize; a match
    # compares them, so a hash collision degrades to a cache miss, never
    # to sharing foreign K/V.

    @staticmethod
    def _chain(key: int, tokens: np.ndarray) -> int:
        return hash((key, np.ascontiguousarray(tokens, np.int32).tobytes()))

    def match_prefix(self, prompt: np.ndarray,
                     tenant: str = "") -> Tuple[List[int], int]:
        """Longest resident match for ``prompt`` within ``tenant``'s
        namespace: returns (source blocks covering pages
        0..ceil(matched/bs)-1, matched row count). Matches are capped at
        ``len(prompt) - 1`` rows — the last prompt token is always
        recomputed so admission has logits to sample the first output
        token from."""
        bs = self.block_size
        full = self._prefix_full.get(tenant, {})
        partial = self._prefix_partial.get(tenant, {})
        cap = len(prompt) - 1
        src: List[int] = []
        key = 0
        while (len(src) + 1) * bs <= cap:
            page = prompt[len(src) * bs:(len(src) + 1) * bs]
            nxt = self._chain(key, page)
            entry = full.get(nxt)
            if entry is None or not np.array_equal(entry[1], page):
                break
            src.append(entry[0])
            key = nxt
        k = len(src)
        matched = k * bs
        # boundary extension into page k: (a) a full resident block whose
        # prefix covers this whole prompt (the capped exact-cover case),
        # else (b) a resident partial tail block with an identical fill
        if (k + 1) * bs == len(prompt):
            page = prompt[k * bs:]
            entry = full.get(self._chain(key, page))
            if entry is not None and np.array_equal(entry[1], page):
                return src + [entry[0]], cap
        best = None
        for blk, length, tail in partial.get(key, ()):
            if length <= cap and (best is None or length > best[1]) \
                    and np.array_equal(tail, prompt[k * bs:length]):
                best = (blk, length)
        if best is not None:
            return src + [best[0]], best[1]
        return src, matched

    def register_prefix(self, slot: int, prompt: np.ndarray) -> None:
        """Index ``slot``'s freshly-inserted prompt K/V so later
        admissions can share it: one entry per block-aligned prefix
        (full blocks only) plus a whole-prompt entry for a partially
        filled tail block. First writer wins; entries die with their
        block (refcount 0 -> unregister)."""
        if not self.prefix_cache:
            return
        bs = self.block_size
        tenant = self._slot_tenant.get(slot, "")
        table = self.block_tables[slot]
        key = 0
        for p in range(len(prompt) // bs):
            page = prompt[p * bs:(p + 1) * bs]
            nxt = self._chain(key, page)
            full = self._prefix_full.setdefault(tenant, {})
            if nxt not in full:
                blk = int(table[p])
                full[nxt] = (blk, np.array(page, np.int32), key)
                self._block_keys.setdefault(blk, []).append(
                    ("full", tenant, nxt))
                self._block_tenant[blk] = tenant
            key = nxt
        if len(prompt) % bs:
            tail = np.array(prompt[-(len(prompt) % bs):], np.int32)
            bucket = self._prefix_partial.setdefault(
                tenant, {}).setdefault(key, [])
            if not any(length == len(prompt) and np.array_equal(t, tail)
                       for _, length, t in bucket):
                blk = int(table[len(prompt) // bs])
                bucket.append((blk, len(prompt), tail))
                self._block_keys.setdefault(blk, []).append(
                    ("partial", tenant, key))
                self._block_tenant[blk] = tenant

    def _unregister(self, freed: List[int]) -> None:
        for b in freed:
            self._block_tenant.pop(b, None)
            for kind, tenant, key in self._block_keys.pop(b, ()):
                if kind == "full":
                    ns = self._prefix_full.get(tenant)
                    if ns is not None:
                        ns.pop(key, None)
                        if not ns:
                            del self._prefix_full[tenant]
                    continue
                tns = self._prefix_partial.get(tenant)
                bucket = tns.get(key) if tns is not None else None
                if bucket is not None:
                    bucket[:] = [e for e in bucket if e[0] != b]
                    if not bucket:
                        del tns[key]
                        if not tns:
                            del self._prefix_partial[tenant]

    def validate(self, req: Request) -> None:
        """Reject requests the pool can never serve. Two separate
        bounds: the worst case must fit the *whole* pool (decode growth
        bypasses the watermark, and _grow_blocks' termination guarantee
        rests on this), and the prompt plus the watermark must fit at
        admission time (else the request waits forever)."""
        rows = max(1, len(req.prompt) + max(req.max_new_tokens - 1, 0))
        worst = -(-rows // self.block_size)
        if worst > self.alloc.capacity:
            raise ValueError(
                f"request {req.id}: needs {worst} KV blocks worst-case, "
                f"pool holds {self.alloc.capacity}")
        prompt_need = self._prompt_need(req)
        if prompt_need + self.watermark > self.alloc.capacity:
            raise ValueError(
                f"request {req.id}: prompt needs {prompt_need} KV blocks "
                f"but admission holds back watermark {self.watermark} of "
                f"{self.alloc.capacity} — can never be admitted")

    def try_reserve(self, req: Request) -> Optional[_PagedReservation]:
        """Reserve the prompt's blocks, sharing what the prefix index can
        supply: fully-matched pages map resident blocks into the table
        (one extra reference each), only the remainder is allocated. The
        boundary page is always among the private blocks (see
        ``_PagedReservation``). Returns None when the pool (minus the
        admission watermark) can't supply the private need — admission
        waits rather than over-commit. Victim-pooled blocks count as
        available (they are reclaimable on demand); a matched chain's
        pooled blocks are *revived* — the pool's reference becomes the
        slot's — rather than re-allocated, which is what makes a hit on
        a completed request's chain (a cross-request victim hit) free."""
        victims = len(self.victim) if self.victim is not None else 0
        if 1 + self.watermark > self.alloc.available + victims:
            # the boundary page is always private, so no reservation can
            # succeed — skip the O(prompt) prefix match a dry pool would
            # otherwise re-run every scheduler step
            return None
        need = self._prompt_need(req)
        while True:
            src: List[int] = []
            matched = 0
            if self.prefix_cache and req.embeds is None:
                src, matched = self.match_prefix(req.prompt, req.tenant)
            shared_pages = matched // self.block_size
            reclaim = None
            if self.victim is not None:
                # eviction under this allocation's pressure must not eat
                # the chain it is about to share or seed from
                self._protect = frozenset(src)
                reclaim = self._reclaim
            try:
                private = self.alloc.alloc(need - shared_pages,
                                           watermark=self.watermark,
                                           reclaim=reclaim)
            finally:
                self._protect = frozenset()
            if private is not None:
                break
            if self.victim is None:
                return None
            pooled = [b for b in src if b in self.victim]
            if not pooled:
                return None
            # every evictable block is protected by this very match:
            # sacrifice the deepest matched page and retry shorter
            self.victim.drop(pooled[-1:])
            self._free_blocks(pooled[-1:])
            self.victim_evictions += 1
        chain = src[:shared_pages]
        if matched:
            self.prefix_hits += 1
        if self.victim is None:
            self.alloc.share(chain)
        else:
            if matched and any(b in self.victim for b in src):
                self.victim_hits += 1
            share = []
            for b in chain:
                if b in self.victim:
                    self.victim.revive(b)
                else:
                    share.append(b)
            self.alloc.share(share)
            if matched:
                self.victim.record_match(src)
        return _PagedReservation(blocks=chain + private,
                                 shared_pages=shared_pages,
                                 seed_blocks=src, matched_rows=matched,
                                 tenant=req.tenant)

    def bind(self, slot: int, res: _PagedReservation) -> None:
        """Take ownership of the reservation's blocks for ``slot``. The
        block table row stays zeroed (null block) until the insert
        commits it: decode steps interleave with a chunked prefill, and
        the batched decode writes every slot's (masked, never-read) K/V
        row through the table — a mid-prefill slot must direct those at
        the null block, not at a block another request shares."""
        self._slot_blocks[slot] = list(res.blocks)
        self._slot_tenant[slot] = res.tenant
        self._shared_pages[slot] = res.shared_pages
        self._table_pending[slot] = list(res.blocks)

    def _commit_table(self, slot: int) -> None:
        blocks = self._table_pending.pop(slot, None)
        if blocks is not None:
            self.block_tables[slot, :len(blocks)] = blocks

    def _insert_ids(self, slot: int) -> np.ndarray:
        """Block ids for a prompt insert: shared pages are redirected to
        the null block so their (already-resident, possibly recomputed)
        rows are dropped instead of overwriting a block another request
        reads — the write half of copy-on-write."""
        ids = self.block_tables[slot].copy()
        ids[:self._shared_pages.get(slot, 0)] = 0
        return ids

    def insert(self, req_cache, slot: int) -> None:
        self._commit_table(slot)
        self.cache = self._insert_paged(
            self.cache, req_cache, jnp.asarray(self._insert_ids(slot)),
            jnp.int32(slot))

    # the chunk-rounded scratch cache inserts through the same block
    # table; rows past the table's coverage are never addressed
    insert_scratch = insert

    def seed_scratch(self, scratch_cache, res: _PagedReservation,
                     rows: int):
        """Copy the matched prefix's K/V out of the resident pool blocks
        into the head of a batch=1 scratch cache, so ``prefill_extend``
        can resume at ``rows`` instead of position 0. Whole pages are
        copied (rows past ``rows`` in the last page are overwritten by
        the extend, or sit beyond the prompt where attention never
        reads); the source blocks are read synchronously at admission,
        so no reference is taken."""
        pages = -(-rows // self.block_size)
        return self._seed(scratch_cache, self.cache,
                          jnp.asarray(res.seed_blocks[:pages], jnp.int32))

    def decode(self, params, tokens: jax.Array, cache_len: np.ndarray):
        if self.kv_reads is not None:
            self.kv_reads.add(cache_len, self.block_size,
                              self.block_tables.size)
        logits, self.cache, _ = self._decode(
            params, tokens, self.cache, jnp.asarray(cache_len),
            jnp.asarray(self.block_tables))
        return logits

    def needs_block(self, slot: int, pos: int) -> bool:
        blk = int(self.block_tables[slot, pos // self.block_size])
        return not blk or self.alloc.refcount(blk) > 1

    def grow_one(self, slot: int, pos: int) -> bool:
        """Make the block covering position ``pos`` privately writable
        for ``slot``: allocate it if the table entry is empty, or — if
        the entry names a block some other request still references —
        copy-on-write it into a fresh block first. (With prompt-only
        sharing the COW branch is structurally unreachable: shared pages
        lie strictly below the prompt tail, decode writes strictly above
        it. It is kept as the safety net the sharing invariant promises.)
        Growth ignores the admission watermark — the headroom it guards
        exists precisely for the running requests' growth — but does
        reclaim victim-pooled blocks before failing into a preemption:
        idle cached prefixes must never evict a live request."""
        page = pos // self.block_size
        blocks = self.alloc.alloc(
            1, reclaim=self._reclaim if self.victim is not None else None)
        if blocks is None:
            return False
        cur = int(self.block_tables[slot, page])
        if cur:                         # shared entry: copy before write
            self.cache = self._copy_block(self.cache, jnp.int32(cur),
                                          jnp.int32(blocks[0]))
            held = self._slot_blocks[slot]
            held[held.index(cur)] = blocks[0]
            self._free_blocks([cur])
        else:
            self._slot_blocks[slot].append(blocks[0])
        self.block_tables[slot, page] = blocks[0]
        return True

    def _free_blocks(self, blocks: List[int]) -> None:
        """The one true-free path: drop a reference per block, and for
        blocks that actually leave the pool, invalidate their index
        entries and their victim-cache hit history (block ids are
        reused; a fresh allocation must not inherit a dead chain's
        heat)."""
        freed = self.alloc.release(blocks)
        self._unregister(freed)
        if self.victim is not None:
            self.victim.forget(freed)

    def _reclaim(self, shortfall: int) -> None:
        """Allocation-pressure hook (see BlockAllocator.alloc): evict up
        to ``shortfall`` victim-pooled blocks, least valuable first, so
        the retried allocation can succeed. Blocks the in-flight
        reservation matched are protected."""
        picks = self.victim.pick(shortfall, exclude=self._protect)
        if picks:
            self.victim.drop(picks)
            self._free_blocks(picks)
            self.victim_evictions += len(picks)

    def enforce_quota(self, tenant: str) -> None:
        """Evict the tenant's own pooled blocks (never another's) until
        it is back under its configured byte budget."""
        evict = self.victim.over_quota(tenant)
        if evict:
            self.victim.drop(evict)
            self._free_blocks(evict)
            self.victim_evictions += len(evict)

    def release(self, slot: int) -> None:
        """Give back a slot's blocks. Without a victim cache every last
        reference frees the block (and kills its index entries); with
        one, indexed blocks whose last reference this was transfer
        ownership to the victim pool instead — K/V resident, index
        alive — so the chain survives the request (and the drain epoch)
        until allocation pressure reclaims it."""
        blocks = self._slot_blocks.pop(slot, [])
        self._shared_pages.pop(slot, None)
        self._table_pending.pop(slot, None)
        tenant = self._slot_tenant.pop(slot, "")
        if blocks:
            if self.victim is not None:
                keep = [(self._block_tenant.get(b, tenant), page, b)
                        for page, b in enumerate(blocks)
                        if self.alloc.refcount(b) == 1
                        and b in self._block_keys]
                keepset = {b for _, _, b in keep}
                rest = [b for b in blocks if b not in keepset]
            else:
                keep, rest = [], blocks
            if rest:
                self._free_blocks(rest)
            if keep:
                self.victim.admit(keep)
                for t in {t for t, _, _ in keep}:
                    self.enforce_quota(t)
        self.block_tables[slot] = 0

    def kv_stats(self, s: SchedulerConfig, cfg: ModelConfig) -> Dict[str, float]:
        row = T.kv_row_bytes(cfg)
        bs = s.block_size
        # the slotted baseline reserves the *configured* max_len, not the
        # paged path's block-rounded max_len
        out = {
            "slotted_kv_reserved_bytes": float(s.max_slots * s.max_len * row),
            "paged_kv_pool_bytes": float(self.alloc.capacity * bs * row),
            "paged_kv_hwm_bytes": float(self.alloc.hwm * bs * row),
            "paged_kv_hwm_blocks": float(self.alloc.hwm),
        }
        if self.victim is not None:
            out["victim_kv_blocks"] = float(len(self.victim))
            out["victim_kv_bytes"] = float(self.victim.total_bytes)
        return out

    def prefix_cache_stats(self) -> Dict[str, object]:
        """Cache-service gauges for Engine.snapshot / the server's
        /status: hit counters plus victim-pool occupancy, per tenant."""
        out: Dict[str, object] = {
            "enabled": self.prefix_cache,
            "victim_cache": self.victim is not None,
            "prefix_hits": self.prefix_hits,
            "victim_hits": self.victim_hits,
            "victim_evictions": self.victim_evictions,
        }
        if self.victim is not None:
            out["victim_blocks"] = len(self.victim)
            out["victim_bytes"] = self.victim.total_bytes
            out["per_tenant_bytes"] = self.victim.per_tenant_bytes()
            out["tenant_quotas"] = dict(self.victim.quotas)
        return out

    def check(self, occupied_slots: set, max_slots: int) -> None:
        """Block books: every held block's reference count equals the
        number of table entries naming it across occupied slots (one
        per slot — a slot never maps the same block at two pages) plus
        one for victim-pool ownership — a block is never simultaneously
        live and pooled — and the prefix index only names held
        blocks."""
        self.alloc.check()
        assert set(self._slot_blocks) == occupied_slots, \
            (set(self._slot_blocks), occupied_slots)
        refs: Counter = Counter()
        for slot, blocks in self._slot_blocks.items():
            assert len(blocks) == len(set(blocks)), \
                f"slot {slot} references a block at two pages"
            entries = self.block_tables[slot][self.block_tables[slot] > 0]
            if slot in self._table_pending:     # bound, prefill in flight
                assert not entries.size, \
                    f"slot {slot}: table committed before insert"
            else:
                assert sorted(entries.tolist()) == sorted(blocks), \
                    f"slot {slot}: table and block list disagree"
            refs.update(blocks)
        if self.victim is not None:
            for blk in self.victim.blocks:
                assert blk not in refs, \
                    f"block {blk} simultaneously live and in victim pool"
                assert blk in self._block_keys, \
                    f"victim pool holds unindexed block {blk}"
                refs[blk] += 1
        assert dict(refs) == self.alloc._refs, (dict(refs), self.alloc._refs)
        for slot in range(max_slots):
            if slot not in occupied_slots:
                assert not self.block_tables[slot].any(), \
                    f"slot {slot}: stale block table"
        for blk, keys in self._block_keys.items():
            assert blk in self.alloc._refs, \
                f"prefix index names freed block {blk}"
            assert all(t == self._block_tenant.get(blk) for _, t, _ in keys), \
                f"block {blk} indexed under two tenants"
