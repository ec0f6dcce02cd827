"""The comparison that decides ``correct``, generic over model families.

The float32 reference of a configuration is its family's own layers
(``families/<family>.py``: ``hidden``, ``logits_at``), run here in
straight ``jax.numpy``: no cache, no kernels, no batching of requests,
every matmul at ``jax.default_matmul_precision("highest")`` (on a TPU a
float32 matmul otherwise runs in bf16 passes). It imports nothing of
the program. It reads the parameter tree that the family's
``make_params`` draws, which is the tree the served model reads.

The comparison (``gaps``): run the reference once over each
prompt with the tokens the server streamed for it, and at every
position read how far the served token's logit lies below the
reference's best. Greedy decoding picks the program's own best, so a
sound program reads 0 except where rounding flipped a near-tie, and a
wrong token reads a gap of the logits' own spread.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512       # query rows per attention block
HEAD_BLOCK = 256    # positions per block of the head's logits


def _head_blocks(fam, spec, params, x, fn):
    """Apply ``fn(i, logits)`` to the head's logits HEAD_BLOCK rows at a
    time (the whole (S, vocab) array would not fit beside the model)."""
    def blk(i):
        rows = jax.lax.dynamic_slice_in_dim(x, i * HEAD_BLOCK, HEAD_BLOCK)
        return fn(i, fam.logits_at(spec, params, rows))
    out = jax.lax.map(blk, jnp.arange(x.shape[0] // HEAD_BLOCK))
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), out)


@partial(jax.jit, static_argnums=(0, 1))
def _picks(fam, spec, params, tokens):
    """The token the model puts first at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = fam.hidden(spec, params, tokens)
        return _head_blocks(fam, spec, params, x,
                            lambda i, lg: lg.argmax(-1).astype(jnp.int32))


@partial(jax.jit, static_argnums=(0, 1))
def _gaps(fam, spec, params, tokens, targets):
    """targets: (K, S) token ids. Returns (K + 1, S): at each position
    the best logit minus the logit of each target, and last the best
    minus the second best (how near a tie the position is)."""
    with jax.default_matmul_precision("highest"):
        x = fam.hidden(spec, params, tokens)

        def fn(i, lg):
            t = jax.lax.dynamic_slice_in_dim(targets, i * HEAD_BLOCK,
                                             HEAD_BLOCK, axis=1)
            got = jnp.take_along_axis(lg, jnp.maximum(t, 0).T, axis=1)
            top2 = jax.lax.top_k(lg, 2)[0]
            return jnp.concatenate(
                [top2[:, :1] - got, top2[:, :1] - top2[:, 1:]], axis=1)
        return _head_blocks(fam, spec, params, x, fn).T


def padded(prompt, served, length: int):
    """The sequence the reference reads for one request (the prompt and
    the served tokens but the last, zero-padded to ``length`` rounded up
    to HEAD_BLOCK), and the slice of positions at which the served
    tokens were due."""
    n, m = len(prompt), len(served)
    seq = list(prompt) + list(served[:-1])
    length = -(-length // HEAD_BLOCK) * HEAD_BLOCK
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} exceeds {length}")
    toks = np.zeros(length, np.int32)
    toks[:len(seq)] = seq
    return toks, slice(n - 1, n - 1 + m)


def picks(fam, spec, params, toks) -> np.ndarray:
    """The token the reference puts first at every position."""
    return np.asarray(_picks(fam, spec, params, jnp.asarray(toks)))


def gaps(fam, spec, params, toks, where: slice, *targets) -> list:
    """Gap of each target sequence (ids due at the positions ``where``)
    under the reference's logits, and last the reference's own margin
    between its best and second-best token at those positions."""
    full = np.zeros((len(targets), len(toks)), np.int32)
    for k, t in enumerate(targets):
        full[k, where] = np.asarray(t)[:where.stop - where.start]
    out = np.asarray(_gaps(fam, spec, params, jnp.asarray(toks),
                           jnp.asarray(full)))
    return [out[k, where] for k in range(len(targets) + 1)]


def round_e4m3(x):
    """Round float32 values (|x| <= 448) to the nearest float8 e4m3fn
    value, ties to even, in float32 arithmetic: 3 mantissa bits, the
    smallest normal 2**-6, subnormals in steps of 2**-9. A cast to
    ``float8_e4m3fn`` and back is not relied on: on the chip it left
    every logit of the control unchanged."""
    _, e = jnp.frexp(x)                      # |x| = m * 2**e, m in [0.5, 1)
    step = jnp.exp2((jnp.maximum(e - 1, -6) - 3).astype(jnp.float32))
    return jnp.round(x / step) * step


def rounded(w, kind: str, axes: tuple):
    """``w`` rounded to ``kind`` ("int8" or "fp8" e4m3) with one scale
    per output channel, the largest magnitude over the contracted
    ``axes``, and back in float32: a family's ``quantize`` applies it to
    each matmul weight."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    if kind == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if kind == "fp8":
        s = amax / 448.0
        return round_e4m3(w / s) * s
    raise ValueError(kind)
