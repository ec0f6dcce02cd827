"""What every model family's seeded weights share.

A family's ``make_params(spec, seed)`` draws every weight from the
run's seed in one jitted call, on the device, in the parameter dtype
the configuration serves, laid out as the tree the served model reads
(and the reference reads the same tree). The reference draws its own
copy from the same seed after the program's state is freed, so it
takes nothing the program made.

Every leaf is drawn, biases and norm scales included, so that a path
that drops one of them shows in the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# std of the norm-scale and bias leaves: large enough that leaving one
# out moves the logits well past the comparison's limit
LEAF_STD = 0.1
EMBED_STD = 0.02
# A tied head reads the embedding back. The embedded input (scaled by
# sqrt(d_model)) stays in the residual stream and lifts the logit of the
# position's own token by about d_model * std / rms(residual) standard
# deviations of the other logits. At EMBED_STD greedy decoding repeats
# one token with a top-2 margin above 1; at 0.006 (a lift of about 5)
# each reply still held 1-8 distinct tokens with few near-ties (measured
# on the chip for qwen2-1.5b), so no rounding could change a served
# token and the comparison was blind. At 0.002 the lift is under 2 and
# replies wander over the vocabulary, as an untied head's do.
EMBED_STD_TIED = 0.002


def padded_vocab(vocab_size: int) -> int:
    """The served model pads its vocabulary to a multiple of 128 and
    masks the padding out of the logits."""
    return -(-vocab_size // 128) * 128


def drawer(key: jax.Array, dtype: str):
    """``leaf(shape, std)``: the next leaf, normal at ``std``, each from
    its own ``fold_in`` of ``key`` in the order drawn, cast to
    ``dtype``."""
    dt = jnp.dtype(dtype)
    drawn = [0]

    def leaf(shape, std):
        drawn[0] += 1
        k = jax.random.fold_in(key, drawn[0])
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)
    return leaf


def draw(fn, spec, seed: int) -> dict:
    """``fn(spec, key)`` run on the default device in one compiled call,
    with the key of ``seed``."""
    key = jax.random.key(seed % (2 ** 32), impl="threefry2x32")
    return jax.jit(fn, static_argnums=0)(spec, key)
