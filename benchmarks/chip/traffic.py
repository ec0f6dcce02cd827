"""Seeded request generator: one general reader of every traffic mix.

A traffic mix is a JSON file under ``traffic/`` (lengths, sharing,
open or closed loop); a cell file under ``cells/`` fixes the offered
load (a Poisson rate, or a number of closed-loop clients). This module
turns (mix, load, seed, window) into the list of requests a run sends.
It imports numpy only: the load generator, which must never touch JAX,
imports it too.

Every seed gets the same work. The lengths, the system-prompt
assignments and the arrival times are drawn once from the mix's
``base_seed``, in one fixed order; the run's seed draws only the token
ids. So two seeds differ in content, never in how much work arrives
when: a permuted order would change how arrivals bunch, and the TTFT
tail with it (measured on the chip: p90 spread about 20 % across seeds
against a few % between two runs of one seed). An open-loop window
always holds exactly ``round(rate * seconds)`` arrivals.

Mix keys:

* ``loop``: ``"open"`` (Poisson arrivals at the cell's ``rate_per_s``)
  or ``"closed"`` (the cell's ``clients`` each send their next request
  when the previous one ends);
* ``prefix`` (optional): a system prompt that opens every prompt.
  ``{"pool": 8, "len": {...}, "zipf": 1.1}`` draws it from a pool of 8
  shared prompts with Zipf popularity; ``"pool": 0`` gives every
  request a fresh one of the same length distribution;
* ``user``: the length of the rest of the prompt;
* ``output``: ``max_new_tokens``;
* a length is ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
  "max": b}`` or ``{"dist": "uniform", "min": a, "max": b}`` (inclusive).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent
# open loop: arrivals keep coming after the window closes, at the same
# rate, so the requests due in the window finish under the same load;
# this many seconds of them are generated (the run stops sending once
# every in-window request has finished)
TAIL_S = 90.0
# closed loop: requests generated per client (a client that runs out
# starts over from the top of the shared list)
PER_CLIENT = 8


@dataclass
class Req:
    """One request of a run. ``offset_s`` is its due time from the start
    of sending (open loop); closed-loop requests are due when a client
    takes them."""
    id: int
    prompt: List[int]
    max_new: int
    offset_s: float = 0.0
    in_window: bool = False
    prefix_id: int = -1         # index into the shared pool, -1 = none
    prefix_len: int = 0


def load_json(kind: str, name: str) -> dict:
    """``kind``/``name``.json under the benchmark directory."""
    return json.loads((HERE / kind / f"{name}.json").read_text())


def _lengths(rng: np.random.RandomState, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.randint(lo, hi + 1, n)
    if spec["dist"] == "lognormal":
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * rng.randn(n))
        return np.clip(np.round(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _zipf_assign(rng: np.random.RandomState, pool: int, s: float,
                 n: int) -> np.ndarray:
    p = 1.0 / np.arange(1, pool + 1) ** s
    return rng.choice(pool, size=n, p=p / p.sum())


def n_requests(mix: dict, load: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        rate = float(load["rate_per_s"])
        return int(math.ceil(rate * (load.get("ramp_s", 0.0) + seconds
                                     + TAIL_S))) + 1
    return int(load["clients"]) * PER_CLIENT


def arrival_offsets(mix: dict, load: dict, seconds: float) -> np.ndarray:
    """Open-loop due times (seconds from the start of sending). The
    ``round(rate * seconds)`` arrivals of the window span it exactly;
    ramp and tail arrivals keep the same rate around it."""
    rate = float(load["rate_per_s"])
    ramp = float(load.get("ramp_s", 0.0))
    base = np.random.RandomState(mix["base_seed"] + 1)
    n_ramp = int(round(rate * ramp))
    n_win = max(1, int(round(rate * seconds)))
    n_tail = n_requests(mix, load, seconds) - n_ramp - n_win
    parts = []
    for n, span in ((n_ramp, ramp), (n_win, seconds)):
        gaps = base.exponential(1.0, n + 1)
        # n arrivals strictly inside a span of length `span`
        parts.append(np.cumsum(gaps)[:n] / gaps.sum() * span)
    tail = np.cumsum(base.exponential(1.0 / rate, n_tail))
    return np.concatenate([parts[0], ramp + parts[1],
                           ramp + seconds + tail])


def build(mix: dict, load: dict, seed: int, seconds: float,
          vocab: int) -> List[Req]:
    """The requests of one run, in the order they are due."""
    n = n_requests(mix, load, seconds)
    base = np.random.RandomState(mix["base_seed"])
    ids = np.random.RandomState(seed % (2 ** 32))
    user = _lengths(base, mix["user"], n)
    out = _lengths(base, mix["output"], n)
    pre = mix.get("prefix")
    if pre is None:
        pre_len = np.zeros(n, np.int64)
        pre_id = np.full(n, -1)
        pool = []
    elif pre["pool"] > 0:
        pool_len = _lengths(base, pre["len"], pre["pool"])
        pre_id = _zipf_assign(base, pre["pool"], pre["zipf"], n)
        pre_len = pool_len[pre_id]
        pool = [ids.randint(0, vocab, int(m)) for m in pool_len]
    else:
        pre_len = _lengths(base, pre["len"], n)
        pre_id = np.full(n, -1)
        pool = []
    if mix["loop"] == "open":
        offsets = arrival_offsets(mix, load, seconds)
        ramp = float(load.get("ramp_s", 0.0))
        window = (offsets >= ramp) & (offsets < ramp + seconds)
    else:
        offsets = np.zeros(n)
        window = np.zeros(n, bool)
    reqs = []
    for i in range(n):
        body = ids.randint(0, vocab, int(user[i]))
        if pre_id[i] >= 0:
            head = pool[pre_id[i]]
        else:
            head = ids.randint(0, vocab, int(pre_len[i]))
        prompt = np.concatenate([head, body]).astype(np.int64)
        reqs.append(Req(id=i, prompt=[int(t) for t in prompt],
                        max_new=int(out[i]), offset_s=float(offsets[i]),
                        in_window=bool(window[i]), prefix_id=int(pre_id[i]),
                        prefix_len=int(pre_len[i])))
    return reqs


def longest_context(mix: dict) -> int:
    """The most KV rows one request of the mix can need."""
    pre = mix.get("prefix")
    head = int(pre["len"]["max"]) if pre else 0
    return head + int(mix["user"]["max"]) + int(mix["output"]["max"])

