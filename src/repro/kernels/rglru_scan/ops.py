"""Jitted public wrapper for the RG-LRU scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas


@partial(jax.jit, static_argnames=("bs", "bd"))
def rglru_scan(a, b, h0, *, bs: int = 256, bd: int = 512):
    return rglru_scan_pallas(a, b, h0, bs=bs, bd=bd,
                             interpret=interpret_mode())
