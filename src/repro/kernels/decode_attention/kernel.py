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

The paged kernel (``paged_decode_attention_pallas``, the decode step's
attention on a TPU) reads a block pool instead: it leaves the pools in
HBM and copies each slot's live pages into VMEM itself, many pages per
DMA group, so it reads ``ceil(length / bs)`` pages per slot where a
gather of the block table reads all ``nb``.
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


def _state_scratch(h: int, d: int):
    return [pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32)]


# bytes of K (and again of V) that one DMA group brings into VMEM: large
# enough that the per-chunk softmax update is amortised over many pages,
# small enough that a slot's last, partly dead chunk costs little compute
CHUNK_BYTES = 256 * 1024
# bytes of q (and again of the output) that one grid step holds in VMEM
GROUP_BYTES = 1024 * 1024


def chunk_pages(bs: int, hk: int, d: int, nb: int, itemsize: int) -> int:
    """Pages per DMA group of the paged kernel, from the shapes alone."""
    return max(1, min(nb, CHUNK_BYTES // (bs * hk * d * itemsize)))


def group_slots(b: int, h: int, d: int, itemsize: int) -> int:
    """Slots per grid step: the largest divisor of ``b`` whose queries
    fit ``GROUP_BYTES``."""
    cap = max(1, GROUP_BYTES // (h * d * itemsize))
    return max(s for s in range(1, min(b, cap) + 1) if b % s == 0)


def _paged_decode_kernel(lengths_ref, tables_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
                         *, bs: int, hk: int, g: int, nb: int, chunk: int,
                         slots: int, scale: float):
    """One grid step serves ``slots`` slots, one after another. Each slot
    streams its live pages, ``ceil(length / bs)`` of them, from the pools
    in HBM into VMEM, ``chunk`` pages per group, double-buffered: the
    next group (of this slot, or the next slot's first) is in flight
    while the current one computes. Pages past a slot's length are
    neither copied nor iterated."""
    base = pl.program_id(0) * slots
    rows = bs * hk                                  # tile rows per page
    span = chunk * bs                               # positions per group

    def n_pages(s):
        return jnp.minimum((lengths_ref[base + s] + bs - 1) // bs, nb)

    def n_chunks(s):
        return jnp.maximum((n_pages(s) + chunk - 1) // chunk, 1)

    def copies(s, c, buf, j):
        """The K and V copies of page ``j`` of group ``c`` of slot ``s``
        into buffer ``buf``."""
        blk = tables_ref[(base + s) * nb + c * chunk + j]
        dst = pl.ds(pl.multiple_of(j * rows, rows), rows)
        return (pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[buf, dst],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[buf, dst],
                                      sems.at[1, buf]))

    def each_live_page(s, c, fn):
        live = jnp.minimum(n_pages(s) - c * chunk, chunk)
        jax.lax.fori_loop(0, live, lambda j, _: fn(j), None)

    def start(s, c, buf):
        def go(j):
            for cp in copies(s, c, buf, j):
                cp.start()
        each_live_page(s, c, go)

    def wait(s, c, buf):
        def go(j):
            for cp in copies(s, c, buf, j):
                cp.wait()
        each_live_page(s, c, go)

    # rows of a group past its slot's live pages keep what an earlier
    # group left there; zeroed once, they are always finite, so their
    # zero softmax weight keeps them out of the sum
    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)
    start(0, 0, 0)

    def slot_body(s, buf):
        length = lengths_ref[base + s]
        nc = n_chunks(s)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_ref[s]                                        # (H, D)

        def chunk_body(c, buf):
            nxt = 1 - buf

            @pl.when(c + 1 < nc)
            def _():
                start(s, c + 1, nxt)

            @pl.when(jnp.logical_and(c + 1 == nc, s + 1 < slots))
            def _():
                start(s + 1, 0, nxt)

            wait(s, c, buf)
            k = k_buf[buf]                                  # (chunk*rows, D)
            v = v_buf[buf]
            sc = jax.lax.dot_general(
                q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
            # row r of the tile is position r // hk of kv head r % hk
            keep = jnp.logical_and(row // g == col % hk,
                                   col < (length - c * span) * hk)
            sc = jnp.where(keep, sc, NEG_INF)
            m_prev = m_ref[...]                             # (H, 1)
            m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new
            return nxt

        buf = jax.lax.fori_loop(0, nc, chunk_body, buf)
        o_ref[s] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)
        return buf

    jax.lax.fori_loop(0, slots, slot_body, 0)


def paged_decode_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, block_tables: jax.Array,
                                  lengths: jax.Array, *, scale=None,
                                  interpret: bool = False) -> jax.Array:
    """q: (B, H, D); pools: (N, bs, Hk, D); block_tables: (B, nb) int32
    physical block per logical page; lengths: (B,) valid rows. Returns
    (B, H, D).

    The pools stay in HBM (``pl.ANY``); the kernel copies each slot's
    live pages itself, ``chunk_pages`` of them per DMA group, and runs
    online-softmax attention over each group (``_paged_decode_kernel``).
    Nothing the size of ``B x nb x bs`` rows is written. The grid walks
    groups of ``group_slots`` slots; the block table and the lengths are
    scalar-prefetched to SMEM. On TPU ``bs * Hk`` should be a multiple
    of the sublane tile (8 for fp32, 16 for bf16)."""
    b, h, d = q.shape
    n, bs, hk, _ = k_pool.shape
    nb = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    chunk = chunk_pages(bs, hk, d, nb, itemsize)
    slots = group_slots(b, h, d, jnp.dtype(q.dtype).itemsize)
    kernel = functools.partial(_paged_decode_kernel, bs=bs, hk=hk,
                               g=h // hk, nb=nb, chunk=chunk, slots=slots,
                               scale=scale)
    qo_spec = pl.BlockSpec((slots, h, d), lambda i, lens, tbl: (i, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, chunk * bs * hk, d), k_pool.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // slots,),
            in_specs=[qo_spec, any_spec, any_spec],
            out_specs=qo_spec,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))]
            + _state_scratch(h, d),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32).reshape(-1),
      q, k_pool.reshape(n, bs * hk, d), v_pool.reshape(n, bs * hk, d))


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
