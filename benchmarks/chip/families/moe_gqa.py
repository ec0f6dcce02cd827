"""The sparse-expert GQA decoder family (Qwen3-MoE): the sizes a
configuration states, the model config ``Engine`` serves, the seeded
weights, the float32 reference's layers and the work counts.

The layer: RMSNorm, GQA attention with RMSNorm over each q and k head
before the rotary embedding (``qk_norm``), causal softmax, output
projection, residual; RMSNorm, a router over all ``n_experts`` experts
(softmax, top ``top_k``, renormalised over the k chosen: a
configuration's ``norm_topk_prob`` must be true), the chosen experts'
SwiGLU MLPs weighted by their probabilities, residual; a final RMSNorm and an untied head.

One chip holds ``experts_held`` of the experts, from ``held_first`` on,
as one rank of an expert-parallel deployment does. The router keeps its
published width; the layer adds the held experts' part of the result
and leaves the absent experts' part out, in the program and in the
reference alike (no exchange is modelled). The reference computes every
held expert densely for every token and weights it by the renormalised
top-k probability (zero where the expert was not chosen): no capacity,
no sorting, no grouped matmul.

Departures from the published model, all of them conventions the served
model has and the reference therefore follows:

* the embedding output is multiplied by ``sqrt(d_model)``;
* an RMSNorm scale ``w`` (the q/k norms' too) is applied as ``(1 + w)``;
* one chip's share of the experts, as above;
* the vocabulary is padded to a multiple of 128 and the padding's
  logits are masked out.

Work counts are of the algorithm, from the shapes: matmul FLOPs count 2
per multiply-add; a token's expected held-expert work is ``k * H / E``
expert MLPs (uniform routing); attention counts QK^T and PV over the
live context.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import weights
from families.dense_gqa import attention, rms_norm, rope
from reference import rounded
from workcount import COMPUTE_BYTES


@dataclass(frozen=True)
class Spec:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    vocab_size: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    experts_held: int = 0           # 0: every expert
    held_first: int = 0
    head_dim: int = 0
    qkv_bias: bool = False
    qk_norm: bool = True
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def padded_vocab(self) -> int:
        return weights.padded_vocab(self.vocab_size)


def spec(cfg: dict) -> Spec:
    keys = Spec.__dataclass_fields__
    return Spec(**{k: v for k, v in cfg.items() if k in keys})


def model_config(name: str, cfg: dict):
    """The ``repro`` ``ModelConfig`` that ``Engine`` serves. A key the
    served model reads and this family does not (windows, layer
    patterns, a dense MLP) is an error: the reference would not follow
    it."""
    from repro.models.config import ModelConfig, MoEConfig
    s = spec(cfg)
    keys = ModelConfig.__dataclass_fields__
    kw = {k: v for k, v in cfg.items() if k in keys and k != "name"}
    other = sorted(set(kw) - set(Spec.__dataclass_fields__))
    if other:
        raise ValueError(f"the moe_gqa family serves no {other}")
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("the moe_gqa family renormalises the top-k "
                         "probabilities (norm_topk_prob true)")
    moe = MoEConfig(n_experts=s.n_experts, top_k=s.top_k,
                    d_ff_expert=s.d_ff_expert, held=(s.held_first, s.held))
    return ModelConfig(name=name, arch_type="moe", layer_pattern=("attn",),
                       d_ff=0, moe=moe, **kw)


# -- seeded weights --------------------------------------------------------


def _draw(spec: Spec, key: jax.Array) -> dict:
    d, hd, L = spec.d_model, spec.hd, spec.n_layers
    h, hk, vp = spec.n_heads, spec.n_kv_heads, spec.padded_vocab
    e, f, held = spec.n_experts, spec.d_ff_expert, spec.held
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
    if spec.qk_norm:
        block.update(q_norm=leaf((L, hd), weights.LEAF_STD),
                     k_norm=leaf((L, hd), weights.LEAF_STD))
    moe = {"router": leaf((L, d, e), 1 / math.sqrt(d)),
           "w_gate": leaf((L, held, d, f), 1 / math.sqrt(d)),
           "w_up": leaf((L, held, d, f), 1 / math.sqrt(d)),
           "w_down": leaf((L, held, f, d), 1 / math.sqrt(f)),
           "ln": leaf((L, d), weights.LEAF_STD)}
    params = {"embed": leaf((vp, d), weights.EMBED_STD),
              "final_norm": leaf((d,), weights.LEAF_STD),
              "scan": [{"block": block, "moe": moe}], "rem": []}
    if not spec.tie_embeddings:
        params["lm_head"] = leaf((d, vp), 1 / math.sqrt(d))
    return params


def make_params(spec: Spec, seed: int) -> dict:
    """Every weight of the model, drawn from ``seed`` on the default
    device in one compiled call, in the parameter dtype (bf16, the
    checkpoint's own)."""
    return weights.draw(_draw, spec, seed)


# -- the float32 reference's layers ----------------------------------------


def expert_weights(spec: Spec, hx, router):
    """(S, E_held) weight of each held expert for each row of hx: its
    renormalised top-k router probability, zero where not chosen."""
    probs = jax.nn.softmax(hx @ router, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, spec.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    rows = jnp.arange(hx.shape[0])[:, None]
    w = jnp.zeros_like(probs).at[rows, top_idx].set(top_p)
    return w[:, spec.held_first:spec.held_first + spec.held]


def experts(spec: Spec, hx, m):
    """The held experts' part of the layer's output, every held expert
    computed for every row."""
    w = expert_weights(spec, hx, m["router"])
    g = jax.nn.silu(jnp.einsum("sd,edf->sef", hx, m["w_gate"])) \
        * jnp.einsum("sd,edf->sef", hx, m["w_up"])
    return jnp.einsum("se,sef,efd->sd", w, g, m["w_down"])


def layer(spec: Spec, x, p, positions):
    a, m = p["block"], p["moe"]
    hx = rms_norm(x, a["ln"], spec.norm_eps)
    q = jnp.einsum("sd,dhk->shk", hx, a["wq"])
    k = jnp.einsum("sd,dhk->shk", hx, a["wk"])
    v = jnp.einsum("sd,dhk->shk", hx, a["wv"])
    if spec.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    if spec.qk_norm:
        q = rms_norm(q, a["q_norm"], spec.norm_eps)
        k = rms_norm(k, a["k_norm"], spec.norm_eps)
    q = rope(q, positions, spec.rope_theta, spec.rope_fraction)
    k = rope(k, positions, spec.rope_theta, spec.rope_fraction)
    x = x + jnp.einsum("shk,hkd->sd", attention(q, k, v), a["wo"])
    return x + experts(spec, rms_norm(x, m["ln"], spec.norm_eps), m)


def hidden(spec: Spec, params, tokens):
    """Final-normed hidden states of one sequence. tokens: (S,). The
    tree stays in bf16; each layer is upcast inside the scan."""
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


# the contracted axes of each stacked matmul weight (the layer axis
# first; the experts' axis next to it)
_CONTRACTED = {"lm_head": (0,), "wo": (1, 2), "w_gate": (2,), "w_up": (2,),
               "w_down": (2,)}


def quantize(params, kind: str):
    """The control's weights: every matmul weight but the router's
    rounded to ``kind`` (``reference.rounded``: one scale per output
    channel), held in the parameter dtype. ``params`` is consumed (its
    buffers are donated)."""
    def one(path, w):
        name = str(getattr(path[-1], "key", ""))
        if not name.startswith("w") and name not in ("embed", "lm_head"):
            return w
        return rounded(w, kind, _CONTRACTED.get(name, (1,))).astype(w.dtype)
    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(one, p),
                   donate_argnums=0)(params)


# -- work counts -----------------------------------------------------------


def layer_attn_params(s: Spec) -> int:
    d, hd = s.d_model, s.hd
    return d * (s.n_heads + 2 * s.n_kv_heads) * hd + s.n_heads * hd * d


def layer_small_params(s: Spec) -> int:
    """Norm scales (the q/k norms' too) and q/k/v biases of one layer."""
    bias = (s.n_heads + 2 * s.n_kv_heads) * s.hd if s.qkv_bias else 0
    return 2 * s.d_model + (2 * s.hd if s.qk_norm else 0) + bias


def router_params(s: Spec) -> int:
    return s.d_model * s.n_experts


def expert_params(s: Spec) -> int:
    """One expert's SwiGLU MLP."""
    return 3 * s.d_model * s.d_ff_expert


def head_params(s: Spec) -> int:
    return s.vocab_size * s.d_model


def param_count(s: Spec) -> int:
    """Every parameter this chip holds: embedding, head (when untied),
    layers with their held experts, final norm."""
    n = s.vocab_size * s.d_model
    if not s.tie_embeddings:
        n += head_params(s)
    per_layer = (layer_attn_params(s) + layer_small_params(s)
                 + router_params(s) + s.held * expert_params(s))
    return n + s.n_layers * per_layer + s.d_model


def held_pairs_per_token(s: Spec) -> float:
    """Expected (token, choice) pairs per token and layer whose expert
    is held here, under uniform routing."""
    return s.top_k * s.held / s.n_experts


def attn_flops(s: Spec, context: float) -> float:
    """QK^T and PV of one query over ``context`` keys, every layer."""
    return 4.0 * s.n_layers * s.n_heads * s.hd * context


def _layer_flops(s: Spec) -> float:
    return 2.0 * (layer_attn_params(s) + router_params(s)
                  + held_pairs_per_token(s) * expert_params(s))


def decode_flops_per_token(s: Spec, context: float) -> float:
    """One generated token: attention and router matmuls, the held
    experts it is expected to reach, the head, attention."""
    return (s.n_layers * _layer_flops(s) + 2.0 * head_params(s)
            + attn_flops(s, context))


def prompt_flops_per_token(s: Spec, context: float) -> float:
    """One prompt token at mean causal context ``context``: the layers'
    matmuls and attention. The head runs once per prompt, not per
    token, and is left out."""
    return s.n_layers * _layer_flops(s) + attn_flops(s, context)


def kv_row_bytes(s: Spec) -> int:
    """K and V of one token, every layer, in the compute dtype."""
    return s.n_layers * 2 * s.n_kv_heads * s.hd * COMPUTE_BYTES[s.dtype]


def touched_experts(s: Spec, batch: float) -> float:
    """Expected held experts that a step of ``batch`` tokens reaches,
    per layer: H * (1 - (1 - k/E)^batch) under uniform routing."""
    return s.held * (1.0 - (1.0 - s.top_k / s.n_experts) ** batch)


def expert_bytes(s: Spec, batch: float) -> float:
    """The held experts' weights a step of ``batch`` tokens reads, every
    layer, in the compute dtype."""
    return (s.n_layers * touched_experts(s, batch) * expert_params(s)
            * COMPUTE_BYTES[s.dtype])


def weight_bytes(s: Spec, batch: float) -> float:
    """The weights a decode step of ``batch`` tokens reads once, in the
    compute dtype: attention, norms, router and head always, the held
    experts the step reaches. The embedding lookup reads a few rows and
    is left out."""
    always = (s.n_layers * (layer_attn_params(s) + layer_small_params(s)
                            + router_params(s))
              + head_params(s) + s.d_model)
    return always * COMPUTE_BYTES[s.dtype] + expert_bytes(s, batch)
