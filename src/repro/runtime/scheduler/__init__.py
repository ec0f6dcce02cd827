"""Continuous-batching scheduler package.

Formerly the single module ``repro.runtime.scheduler``; now split by
concern:

* ``types`` — ``Request`` / ``Completion`` / ``SchedulerConfig`` /
  ``SchedEvent`` / ``SlotFailure`` and the admission-time validators;
* ``allocator`` — the refcounted fixed-pool ``BlockAllocator``;
* ``layouts`` — ``SlottedLayout`` / ``PagedLayout`` KV-cache surgery
  (block tables, prefix sharing, copy-on-write);
* ``prefix_pool`` — ``VictimCache``: retention of released prefix
  chains (tenant quotas, weighted-LRU eviction) and its checkpoint
  save/restore;
* ``prefill`` — one-shot / prefix-resume / chunked prompt admission;
* ``core`` — ``ContinuousScheduler``, the loop tying them together.

**Migration note:** every name the old module exported is re-exported
here, so ``from repro.runtime.scheduler import ContinuousScheduler``
(and every other pre-split import) keeps working unchanged. New code
can import from the submodules directly.
"""
from repro.runtime.policies import sample_tokens
from repro.runtime.scheduler.allocator import BlockAllocator
from repro.runtime.scheduler.core import ContinuousScheduler
from repro.runtime.scheduler.layouts import (PagedLayout, SlottedLayout,
                                             _PagedReservation)
from repro.runtime.scheduler.prefix_pool import (VictimCache,
                                                 restore_victim_cache,
                                                 save_victim_cache)
from repro.runtime.scheduler.types import (COUNTER_KEYS, FINISH_REASONS,
                                           Completion, Request, SchedEvent,
                                           SchedulerConfig, SlotFailure,
                                           _ChunkedPrefill, _Ticket,
                                           validate_request_fits)

__all__ = [
    # pre-split surface (unchanged)
    "Request", "Completion", "SchedulerConfig", "SchedEvent", "SlotFailure",
    "BlockAllocator", "SlottedLayout", "PagedLayout", "ContinuousScheduler",
    "sample_tokens", "validate_request_fits", "FINISH_REASONS",
    "COUNTER_KEYS",
    # prefix-cache service
    "VictimCache", "save_victim_cache", "restore_victim_cache",
]
