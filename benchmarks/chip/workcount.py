"""Work counts of the algorithm and the table of chip peaks.

Operations and bytes come from the configuration's shapes, not from the
program: a change that stops doing needless work raises a share of the
roofline without making the count stale. Matmul FLOPs count 2 per
multiply-add. Attention counts QK^T and PV over the live context.
"""
from __future__ import annotations

from weights import Spec

# Published peaks per chip, keyed by JAX's ``device_kind`` (a v5e
# reports "TPU v5 lite"). Source: Google Cloud documentation, "TPU v5e"
# (system architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
# HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}

COMPUTE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


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


def weight_bytes(s: Spec) -> int:
    """Every weight a decode step reads once, in the compute dtype. A
    tied head reads the embedding; the embedding lookup itself reads a
    few rows and is left out."""
    layers = s.n_layers * (layer_matmul_params(s) + layer_small_params(s))
    return (layers + head_params(s) + s.d_model) * COMPUTE_BYTES[s.dtype]


def decode_least_s(s: Spec, batch: float, context: float,
                   kv_bytes: float, peak: dict) -> tuple:
    """Least time of one decode step of ``batch`` tokens and which bound
    sets it: the larger of FLOPs over peak FLOP/s and bytes (weights
    once plus the live KV) over peak bandwidth."""
    t_flops = batch * decode_flops_per_token(s, context) \
        / peak["bf16_flops_per_s"]
    t_bytes = (weight_bytes(s) + kv_bytes) / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
