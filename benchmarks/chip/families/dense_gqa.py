"""The dense GQA decoder family (Qwen2, ChatGLM3): the sizes a
configuration states, the model config ``Engine`` serves, the seeded
weights, the float32 reference's layers and the work counts.

A configuration under ``configs/`` that names no ``family`` is of this
family. Every family module under ``families/`` exposes the same names
(``run.FAMILY_NAMES``); nothing else in the harness knows a model's
shape.

The layer: RMSNorm, GQA attention with q/k/v bias, rotary position
embedding on the first ``rope_fraction`` of each head's dims, causal
softmax, output projection, residual; RMSNorm, SwiGLU MLP, residual; a
final RMSNorm and the head (tied to the embedding or not).

Departures from the published models, all of them conventions the
served model has and the reference therefore follows:

* the embedding output is multiplied by ``sqrt(d_model)``;
* an RMSNorm scale ``w`` is applied as ``(1 + w)``;
* rotary embedding rotates the two halves of the rotated dims
  (GPT-NeoX layout); ChatGLM3 interleaves pairs of dims instead;
* the vocabulary is padded to a multiple of 128 and the padding's
  logits are masked out.

Work counts are of the algorithm, from the shapes: matmul FLOPs count 2
per multiply-add, attention counts QK^T and PV over the live context.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import weights
from reference import Q_BLOCK, rounded
from workcount import COMPUTE_BYTES


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

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return weights.padded_vocab(self.vocab_size)


def spec(cfg: dict) -> Spec:
    keys = Spec.__dataclass_fields__
    return Spec(**{k: v for k, v in cfg.items() if k in keys})


def model_config(name: str, cfg: dict):
    """The ``repro`` ``ModelConfig`` that ``Engine`` serves. A key the
    served model reads and this family does not (experts, windows, layer
    patterns) is an error: the reference would not follow it."""
    from repro.models.config import ModelConfig
    keys = ModelConfig.__dataclass_fields__
    kw = {k: v for k, v in cfg.items() if k in keys and k != "name"}
    other = sorted(set(kw) - set(Spec.__dataclass_fields__))
    if other:
        raise ValueError(f"the dense_gqa family serves no {other}; a "
                         "configuration that needs them names its family")
    return ModelConfig(name=name, arch_type="dense", layer_pattern=("attn",),
                       **kw)


# -- seeded weights --------------------------------------------------------


def _draw(spec: Spec, key: jax.Array) -> dict:
    d, hd, L = spec.d_model, spec.hd, spec.n_layers
    h, hk, ff, vp = spec.n_heads, spec.n_kv_heads, spec.d_ff, spec.padded_vocab
    leaf = weights.drawer(key, spec.param_dtype)
    block = {"wq": leaf((L, d, h, hd), 1 / math.sqrt(d)),
             "wk": leaf((L, d, hk, hd), 1 / math.sqrt(d)),
             "wv": leaf((L, d, hk, hd), 1 / math.sqrt(d)),
             "wo": leaf((L, h, hd, d), 1 / math.sqrt(h * hd)),
             "ln": leaf((L, d), weights.LEAF_STD)}
    if spec.qkv_bias:
        block.update(bq=leaf((L, h, hd), weights.LEAF_STD),
                     bk=leaf((L, hk, hd), weights.LEAF_STD),
                     bv=leaf((L, hk, hd), weights.LEAF_STD))
    mlp = {"w_gate": leaf((L, d, ff), 1 / math.sqrt(d)),
           "w_up": leaf((L, d, ff), 1 / math.sqrt(d)),
           "w_down": leaf((L, ff, d), 1 / math.sqrt(ff)),
           "ln": leaf((L, d), weights.LEAF_STD)}
    embed_std = (weights.EMBED_STD_TIED if spec.tie_embeddings
                 else weights.EMBED_STD)
    params = {"embed": leaf((vp, d), embed_std),
              "final_norm": leaf((d,), weights.LEAF_STD),
              "scan": [{"block": block, "mlp": mlp}], "rem": []}
    if not spec.tie_embeddings:
        params["lm_head"] = leaf((d, vp), 1 / math.sqrt(d))
    return params


def make_params(spec: Spec, seed: int) -> dict:
    """Every weight of the model, drawn from ``seed`` on the default
    device in one compiled call."""
    return weights.draw(_draw, spec, seed)


# -- the float32 reference's layers ----------------------------------------


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, positions, theta: float, fraction: float):
    """x: (S, H, D); rotates the first ``fraction`` of D, half against
    half."""
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], axis=-1)


def attention(q, k, v):
    """Causal GQA attention, one query block at a time. q: (S, H, D);
    k, v: (S, Hk, D)."""
    s, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    nb = -(-s // Q_BLOCK)
    pad = nb * Q_BLOCK - s
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, Q_BLOCK, h, d)
    kpos = jnp.arange(s)

    def one(i, qi):
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(d)
        sc = jnp.where(kpos[None, None] <= qpos[None, :, None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(lambda a: one(*a), (jnp.arange(nb), qb))
    return out.reshape(nb * Q_BLOCK, h, d)[:s]


def layer(spec: Spec, x, p, positions):
    a, m = p["block"], p["mlp"]
    hx = rms_norm(x, a["ln"], spec.norm_eps)
    q = jnp.einsum("sd,dhk->shk", hx, a["wq"])
    k = jnp.einsum("sd,dhk->shk", hx, a["wk"])
    v = jnp.einsum("sd,dhk->shk", hx, a["wv"])
    if spec.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q, positions, spec.rope_theta, spec.rope_fraction)
    k = rope(k, positions, spec.rope_theta, spec.rope_fraction)
    x = x + jnp.einsum("shk,hkd->sd", attention(q, k, v), a["wo"])
    hx = rms_norm(x, m["ln"], spec.norm_eps)
    g = jax.nn.silu(hx @ m["w_gate"]) * (hx @ m["w_up"])
    return x + g @ m["w_down"]


def hidden(spec: Spec, params, tokens):
    """Final-normed hidden states of one sequence. tokens: (S,)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa
    x = params["embed"][tokens].astype(jnp.float32) * math.sqrt(spec.d_model)
    positions = jnp.arange(tokens.shape[0])

    def body(x, p):
        return layer(spec, x, f32(p), positions), None

    x, _ = jax.lax.scan(body, x, params["scan"][0])
    return rms_norm(x, params["final_norm"].astype(jnp.float32),
                    spec.norm_eps)


def logits_at(spec: Spec, params, x):
    """Head logits of hidden rows x: (N, d) -> (N, vocab_size)."""
    if spec.tie_embeddings:
        lg = x @ params["embed"].astype(jnp.float32).T
    else:
        lg = x @ params["lm_head"].astype(jnp.float32)
    return lg[:, :spec.vocab_size]


def quantize(params, kind: str):
    """The control's weights: every matmul weight rounded to ``kind``
    (``reference.rounded``: one scale per output channel) and back to
    float32. ``params`` is consumed (its buffers are donated)."""
    def one(path, w):
        name = str(getattr(path[-1], "key", ""))
        if not name.startswith("w") and name not in ("embed", "lm_head"):
            return w
        # the contracted axes (stacked layer weights carry the layer
        # axis first)
        return rounded(
            w, kind, {"lm_head": (0,), "wo": (1, 2)}.get(name, (1,)))
    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(one, p),
                   donate_argnums=0)(params)


# -- work counts -----------------------------------------------------------


def layer_matmul_params(s: Spec) -> int:
    d, hd = s.d_model, s.hd
    return (d * (s.n_heads + 2 * s.n_kv_heads) * hd + s.n_heads * hd * d
            + 3 * d * s.d_ff)


def layer_small_params(s: Spec) -> int:
    """Norm scales and q/k/v biases of one layer."""
    bias = (s.n_heads + 2 * s.n_kv_heads) * s.hd if s.qkv_bias else 0
    return 2 * s.d_model + bias


def head_params(s: Spec) -> int:
    return s.vocab_size * s.d_model


def param_count(s: Spec) -> int:
    """Every parameter: embedding, head (when untied), layers, final
    norm."""
    n = s.vocab_size * s.d_model
    if not s.tie_embeddings:
        n += head_params(s)
    return (n + s.n_layers * (layer_matmul_params(s) + layer_small_params(s))
            + s.d_model)


def attn_flops(s: Spec, context: float) -> float:
    """QK^T and PV of one query over ``context`` keys, every layer."""
    return 4.0 * s.n_layers * s.n_heads * s.hd * context


def decode_flops_per_token(s: Spec, context: float) -> float:
    """One generated token: every matmul, the head, attention."""
    return (2.0 * (s.n_layers * layer_matmul_params(s) + head_params(s))
            + attn_flops(s, context))


def prompt_flops_per_token(s: Spec, context: float) -> float:
    """One prompt token at mean causal context ``context``: the layers'
    matmuls and attention. The head runs once per prompt, not per
    token, and is left out."""
    return 2.0 * s.n_layers * layer_matmul_params(s) + attn_flops(s, context)


def kv_row_bytes(s: Spec) -> int:
    """K and V of one token, every layer, in the compute dtype."""
    return s.n_layers * 2 * s.n_kv_heads * s.hd * COMPUTE_BYTES[s.dtype]


def weight_bytes(s: Spec, batch: float) -> int:
    """Every weight a decode step reads once, in the compute dtype,
    whatever its ``batch``. A tied head reads the embedding; the
    embedding lookup itself reads a few rows and is left out."""
    layers = s.n_layers * (layer_matmul_params(s) + layer_small_params(s))
    return (layers + head_params(s) + s.d_model) * COMPUTE_BYTES[s.dtype]
