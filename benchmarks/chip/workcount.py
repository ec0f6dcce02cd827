"""The table of chip peaks, and the least time of a decode step from a
model family's work counts.

Each family counts its own operations and bytes (``families/<family>.py``:
``decode_flops_per_token``, ``prompt_flops_per_token``, ``kv_row_bytes``,
``weight_bytes``) from the configuration's shapes, not from the
program: a change that stops doing needless work raises a share of the
roofline without making the count stale.
"""
from __future__ import annotations

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


def decode_least_s(fam, s, batch: float, context: float,
                   kv_bytes: float, peak: dict) -> tuple:
    """Least time of one decode step of ``batch`` tokens of family
    ``fam`` and which bound sets it: the larger of FLOPs over peak
    FLOP/s and bytes (the weights such a step reads, plus the live KV)
    over peak bandwidth."""
    t_flops = batch * fam.decode_flops_per_token(s, context) \
        / peak["bf16_flops_per_s"]
    t_bytes = (fam.weight_bytes(s, batch) + kv_bytes) \
        / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
