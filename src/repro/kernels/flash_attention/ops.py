"""Jitted public wrapper for the flash-attention kernel.

On TPU this dispatches to the compiled Pallas kernel; on CPU (this
container) it runs the kernel body in interpret mode, which executes the
exact same tiling logic in Python for correctness validation.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 256, bk: int = 256):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    while sq % bq:
        bq //= 2
    while sk % bk:
        bk //= 2
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=max(bq, 1), bk=max(bk, 1),
                                  interpret=interpret_mode())
