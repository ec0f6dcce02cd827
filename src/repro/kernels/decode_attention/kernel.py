"""Pallas TPU decode-attention kernel (flash-decode style).

One new token per sequence attends over a (B, S, Hk, D) KV cache with a
per-sequence valid length. Grid: (B, kv-blocks); the kv dimension is
innermost/sequential, carrying the online-softmax state for all H query
heads of the sequence in VMEM scratch. The per-sequence ``lengths``
array is a scalar-prefetch operand — Pallas TPU loads it into SMEM
before the kernel body runs, so block masking is branch-free.

Layout: the wrapper views the cache as (B, S * Hk, D) — a free,
copy-less reshape of the row-major (B, S, Hk, D) buffer — so one kv
block is a (bk * Hk, D) tile whose last two dims meet the TPU's (8, 128)
tiling for any head count. Row ``r`` of that tile is position
``r // Hk`` of kv head ``r % Hk``. The H = Hk * g query heads score
against every row in one matmul, and a head mask keeps query head ``i``
on kv head ``i // g`` (GQA). That spends Hk x the minimal FLOPs, which a
memory-bound decode step can afford, to avoid any per-head slicing.

This is the memory-bound kernel of serving: per step it streams the
whole cache once, so the roofline term is bytes(cache)/HBM_bw — the
Pallas win over naive XLA decode is avoiding the (B, H, S) logits
round-trip to HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bk: int, nk: int, scale: float,
                   hk: int, g: int):
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]

    @pl.when(ik * bk < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (H, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk * hk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ik * bk + col // hk
        keep = jnp.logical_and(row // g == col % hk, kpos < length)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]                               # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _paged_decode_kernel(tables_ref, *refs, **kw):
    """Block-table decode attention. The body is ``_decode_kernel``'s
    (one page per kv step); the difference is entirely in the BlockSpec
    index maps, which chase ``tables_ref`` (scalar-prefetched to SMEM) so
    each kv step DMAs one *physical* pool block instead of the next
    contiguous cache slice — dead blocks are never streamed."""
    del tables_ref
    _decode_kernel(*refs, **kw)


def _state_scratch(h: int, d: int):
    return [pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32)]


def paged_decode_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, block_tables: jax.Array,
                                  lengths: jax.Array, *, scale=None,
                                  interpret: bool = False) -> jax.Array:
    """q: (B, H, D); pools: (N, bs, Hk, D); block_tables: (B, nb) int32
    physical block per logical page; lengths: (B,) valid rows. Returns
    (B, H, D).

    The kv grid dimension walks logical pages 0..nb-1; the k/v BlockSpec
    index maps read the prefetched table to pick the physical block, so
    the DMA stream follows the page chain. Pages at or past a sequence's
    length are skipped via ``pl.when`` (their table entries point at the
    null block and are never read). On TPU ``bs * Hk`` should be a
    multiple of the sublane tile (8 for fp32, 16 for bf16)."""
    b, h, d = q.shape
    n, bs, hk, _ = k_pool.shape
    nb = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kp = k_pool.reshape(n, bs * hk, d)
    vp = v_pool.reshape(n, bs * hk, d)
    kernel = functools.partial(_paged_decode_kernel, bk=bs, nk=nb,
                               scale=scale, hk=hk, g=h // hk)
    kv_spec = pl.BlockSpec((1, bs * hk, d),
                           lambda ib, ip, tbl, lens: (tbl[ib, ip], 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, h, d),
                             lambda ib, ip, tbl, lens: (ib, 0, 0)),
                kv_spec, kv_spec,
            ],
            out_specs=pl.BlockSpec((1, h, d),
                                   lambda ib, ip, tbl, lens: (ib, 0, 0)),
            scratch_shapes=_state_scratch(h, d),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, kp, vp)


def decode_attention_pallas(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, lengths: jax.Array, *,
                            bk: int = 512, scale=None,
                            interpret: bool = False) -> jax.Array:
    """q: (B, H, D); caches: (B, S, Hk, D); lengths: (B,) valid entries.
    Returns (B, H, D)."""
    b, h, d = q.shape
    _, s, hk, _ = k_cache.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bk = min(bk, s)
    while s % bk:
        bk //= 2
    nk = s // bk
    kc = k_cache.reshape(b, s * hk, d)
    vc = v_cache.reshape(b, s * hk, d)
    kernel = functools.partial(_decode_kernel, bk=bk, nk=nk, scale=scale,
                               hk=hk, g=h // hk)
    kv_spec = pl.BlockSpec((1, bk * hk, d),
                           lambda ib, ik, lens: (ib, ik, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nk),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda ib, ik, lens: (ib, 0, 0)),
                kv_spec, kv_spec,
            ],
            out_specs=pl.BlockSpec((1, h, d),
                                   lambda ib, ik, lens: (ib, 0, 0)),
            scratch_shapes=_state_scratch(h, d),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, kc, vc)
