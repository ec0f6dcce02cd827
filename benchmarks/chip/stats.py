"""Quantities the metric readers share, taken from one run's record.

A run record (``run`` below) is the dict the harness hands every reader:

* ``records``: the load generator's records (due, sent, status,
  finish, error, token arrival ``times``, ``tokens``), all on the
  ``time.monotonic()`` clock; ``in_window``: ids of the open-loop
  requests due in the window; ``window``: (open, close); ``stopped_at``:
  when the generator stopped waiting;
* ``status0``, ``status1``: the server's ``GET /status`` at the window's
  open and close (lifecycle counters, and with ``--trace 1`` the
  scheduler's histograms);
* ``trace``: the profiler trace's reduction (``--trace 1``), or None;
* ``spec``: the model's sizes (``weights.Spec``); ``engine``: its engine
  settings; ``peak``: the chip's peaks; ``setup_s``.

A reader returns None when the run holds nothing for it to read.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np


def window_requests(run) -> List[dict]:
    ids = set(run["in_window"])
    return [r for r in run["records"] if r["id"] in ids and
            r["due"] is not None]


def ttfts_s(run) -> List[float]:
    """Due time to first token of every request due in the window. A
    request that was refused, failed or never streamed a token counts
    as waiting until the generator gave up on it."""
    out = []
    by_id = {}
    for r in run["records"]:
        by_id.setdefault(r["id"], r)
    for rid in run["in_window"]:
        r = by_id.get(rid)
        if r is None:
            out.append(run["stopped_at"] - run["window"][1])
        elif r["times"]:
            out.append(r["times"][0] - r["due"])
        else:
            out.append(run["stopped_at"] - r["due"])
    return out


def token_gaps_s(run) -> List[float]:
    gaps: List[float] = []
    for r in window_requests(run):
        t = r["times"]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    return gaps


def tokens_in_window(run) -> int:
    w0, w1 = run["window"]
    return sum(int(np.count_nonzero((np.asarray(r["times"]) >= w0)
                                    & (np.asarray(r["times"]) < w1)))
               for r in run["records"] if r["times"])


def percentile(xs, q: float) -> Optional[float]:
    return float(np.percentile(xs, q)) if len(xs) else None


def counter_delta(run, key: str) -> Optional[float]:
    a = run["status0"].get("counters", {}).get(key)
    b = run["status1"].get("counters", {}).get(key)
    return None if a is None or b is None else float(b - a)


def hist_delta(run, name: str) -> Optional[Tuple[float, float]]:
    """(count, sum) of a scheduler histogram over the window, or None
    when the histogram was not recorded (observability off)."""
    h0 = run["status0"].get("metrics", {}).get("histograms", {}).get(name)
    h1 = run["status1"].get("metrics", {}).get("histograms", {}).get(name)
    if not h0 or not h1:
        return None
    n = h1["count"] - h0["count"]
    if n <= 0:
        return None
    return float(n), float(h1["sum"] - h0["sum"])


def hist_mean(run, name: str) -> Optional[float]:
    d = hist_delta(run, name)
    return None if d is None else d[1] / d[0]


def decode_batch(run) -> Optional[float]:
    """Mean tokens per decode step in the window (first tokens come
    from prefill and are left out)."""
    steps = counter_delta(run, "steps")
    toks = counter_delta(run, "tokens_generated")
    adm = counter_delta(run, "admissions")
    if not steps or toks is None or adm is None:
        return None
    return max(toks - adm, 0.0) / steps


def live_kv_rows(run) -> Optional[float]:
    """Mean KV rows held in the paged pool per decode step."""
    blocks = hist_mean(run, "repro_blocks_in_use")
    if blocks is None:
        return None
    return blocks * run["engine"]["block_size"]


def idle_share(run) -> Optional[float]:
    tr = run["trace"]
    if not tr or not tr.get("busy_s") or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
