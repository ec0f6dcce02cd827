"""Jitted public wrapper for the decode-attention kernel (interpret mode
on CPU, compiled Pallas on TPU)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas)


@partial(jax.jit, static_argnames=("bk",))
def decode_attention(q, k_cache, v_cache, lengths, *, bk: int = 512):
    return decode_attention_pallas(q, k_cache, v_cache, lengths, bk=bk,
                                   interpret=interpret_mode())


@jax.jit
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths):
    return paged_decode_attention_pallas(q, k_pool, v_pool, block_tables,
                                         lengths, interpret=interpret_mode())
