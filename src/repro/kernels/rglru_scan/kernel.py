"""Pallas TPU RG-LRU linear-recurrence kernel.

Computes y_t = a_t * y_{t-1} + b_t over the sequence dim. TPU adaptation
of RecurrentGemma's GPU linear-scan kernel: the grid is
(batch, feature-blocks, seq-blocks) with the seq dimension innermost;
the hidden state h (one (1, bd) row per feature block) is carried in
VMEM scratch across seq blocks, and each block runs a fori_loop over its
rows — elementwise VPU work on 128-lane vectors, no MXU. The block shape
trade-off: larger bs amortizes grid overhead, larger bd raises VPU
utilization; (bs, bd) must fit VMEM alongside a, b and y tiles. The
initial state travels as (B, 1, D) so its block's last two dims are
(1, bd), which the TPU's tiling accepts.

Unlike the associative-scan lowering (log-depth, 2x flops), the kernel
does the work-optimal sequential scan per block while still exposing
batch x feature parallelism across TPU cores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, h_ref, *, bs: int):
    isq = pl.program_id(2)

    @pl.when(isq == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    def step(t, h):
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bs, step, h_ref[...])


def rglru_scan_pallas(a: jax.Array, b: jax.Array, h0: jax.Array, *,
                      bs: int = 256, bd: int = 512,
                      interpret: bool = False) -> jax.Array:
    """a, b: (B, S, D) f32 decay/input; h0: (B, D). Returns y (B, S, D)."""
    B, S, D = a.shape
    bs = min(bs, S)
    while S % bs:
        bs //= 2
    bd = min(bd, D)
    while D % bd:
        bd //= 2
    ns, nd = S // bs, D // bd

    grid = (B, nd, ns)   # seq innermost: sequential state carry
    kernel = functools.partial(_rglru_kernel, bs=bs)
    seq_spec = pl.BlockSpec((1, bs, bd), lambda ib, id_, is_: (ib, is_, id_))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seq_spec, seq_spec,
            pl.BlockSpec((1, 1, bd), lambda ib, id_, is_: (ib, 0, id_)),
        ],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        interpret=interpret,
    )(a, b, h0[:, None, :])
