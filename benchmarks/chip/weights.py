"""The benchmark's model sizes and its seeded weights.

``Spec`` holds the sizes of a dense GQA decoder as a configuration file
under ``configs/`` states them. ``make_params`` draws every weight from
the run's seed in one jitted call, on the device, in the parameter
dtype the configuration serves, laid out as the tree the served model
reads (and the reference reads the same tree). The reference draws its
own copy from the same seed after the program's state is freed, so it
takes nothing the program made.

Every leaf is drawn, biases and norm scales included, so that a path
that drops one of them shows in the comparison.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

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


@dataclass(frozen=True)
class Spec:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0
    qkv_bias: bool = True
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    @classmethod
    def from_config(cls, cfg: dict) -> "Spec":
        keys = cls.__dataclass_fields__
        return cls(**{k: v for k, v in cfg.items() if k in keys})

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """The served model pads its vocabulary to a multiple of 128 and
        masks the padding out of the logits."""
        return -(-self.vocab_size // 128) * 128


def _draw(spec: Spec, key: jax.Array) -> dict:
    d, hd, L = spec.d_model, spec.hd, spec.n_layers
    h, hk, ff, vp = spec.n_heads, spec.n_kv_heads, spec.d_ff, spec.padded_vocab
    dt = jnp.dtype(spec.param_dtype)
    drawn = [0]

    def leaf(shape, std):
        drawn[0] += 1
        k = jax.random.fold_in(key, drawn[0])
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    block = {"wq": leaf((L, d, h, hd), 1 / math.sqrt(d)),
             "wk": leaf((L, d, hk, hd), 1 / math.sqrt(d)),
             "wv": leaf((L, d, hk, hd), 1 / math.sqrt(d)),
             "wo": leaf((L, h, hd, d), 1 / math.sqrt(h * hd)),
             "ln": leaf((L, d), LEAF_STD)}
    if spec.qkv_bias:
        block.update(bq=leaf((L, h, hd), LEAF_STD),
                     bk=leaf((L, hk, hd), LEAF_STD),
                     bv=leaf((L, hk, hd), LEAF_STD))
    mlp = {"w_gate": leaf((L, d, ff), 1 / math.sqrt(d)),
           "w_up": leaf((L, d, ff), 1 / math.sqrt(d)),
           "w_down": leaf((L, ff, d), 1 / math.sqrt(ff)),
           "ln": leaf((L, d), LEAF_STD)}
    embed_std = EMBED_STD_TIED if spec.tie_embeddings else EMBED_STD
    params = {"embed": leaf((vp, d), embed_std),
              "final_norm": leaf((d,), LEAF_STD),
              "scan": [{"block": block, "mlp": mlp}], "rem": []}
    if not spec.tie_embeddings:
        params["lm_head"] = leaf((d, vp), 1 / math.sqrt(d))
    return params


def make_params(spec: Spec, seed: int) -> dict:
    """Every weight of the model, drawn from ``seed`` on the default
    device in one compiled call."""
    key = jax.random.key(seed % (2 ** 32), impl="threefry2x32")
    return jax.jit(_draw, static_argnums=0)(spec, key)
