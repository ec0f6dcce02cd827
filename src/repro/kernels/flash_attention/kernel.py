"""Pallas TPU flash-attention (prefill) kernel.

TPU adaptation of the FlashAttention tiling: the grid is
(batch x kv-head, q-blocks, kv-blocks) with the kv-block dimension
innermost — TPU grids execute sequentially per core, so the running
online-softmax state (m, l, acc) lives in VMEM scratch and is carried
across kv iterations without HBM round-trips. Block shapes are chosen so
one (g*bq, d) q-tile, one (bk, d) kv-tile and the (g*bq, bk) score tile
fit VMEM together, with the matmul dims aligned to the 128-lane MXU.

GQA layout: q is passed as (B, Hk, g, Sq, D) and k/v as (B, Hk, Sk, D)
— all g query heads of one kv head share a grid step, so k/v tiles are
loaded once per group (g x bandwidth saving vs. per-q-head grids, the
reason GQA exists). Head-major k/v make every block's last two dims
(bk, D), which the TPU's (8, 128) tiling accepts for any head count; the
wrapper pays one transpose of k/v for it, small next to the O(S^2)
attention.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int, bq: int, bk: int,
               nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level causal/window skip: only compute when the tile overlaps
    # the mask support
    q_lo = iq * bq
    q_hi = q_lo + bq - 1
    k_lo = ik * bk
    k_hi = k_lo + bk - 1
    live = True
    if causal:
        live = k_lo <= q_hi
    if window > 0:
        live = jnp.logical_and(live, q_lo - k_hi < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].reshape(-1, q_ref.shape[-1])     # (g*bq, d)
        k = k_ref[0, 0]                                  # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (g*bq, bk)
        qpos = q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], bk), 0) % bq
        # rows are g-major: row = g_idx * bq + q_idx -> q position uses % bq
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], bk), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                              # (g*bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.reshape(o_ref.shape[2], o_ref.shape[3],
                                  o_ref.shape[4]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           bq: int = 256, bk: int = 256,
                           scale=None, interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, Hk, D). Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    assert h % hk == 0
    g = h // hk
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    nq, nk = sq // bq, sk // bk

    # (B, Hk, g, Sq, D) so one grid step covers all g q-heads of a kv head
    qg = q.reshape(b, sq, hk, g, d).transpose(0, 2, 3, 1, 4)
    kh = k.transpose(0, 2, 1, 3)                         # (B, Hk, Sk, D)
    vh = v.transpose(0, 2, 1, 3)

    grid = (b * hk, nq, nk)
    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda bh, iq, ik: (bh // hk, bh % hk, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, bq, d),
                         lambda bh, iq, ik: (bh // hk, bh % hk, 0, iq, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, bq, d),
            lambda bh, iq, ik: (bh // hk, bh % hk, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hk, g, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kh, vh)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
