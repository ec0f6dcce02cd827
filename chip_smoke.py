#!/usr/bin/env python3
"""Smoke test of the serving main path on one TPU chip.

    python chip_smoke.py [--seed N]

Everything runs in this one process, which holds the chip:

1. device check: a TPU, or exit non-zero (there is no CPU fallback);
2. serving: ``qwen2_1_5b`` at full published width and depth (random
   weights from ``--seed``) behind ``Engine`` (paged KV, prefix cache,
   chunked prefill, fifo) and ``EngineServer``; concurrent HTTP
   requests, streamed and not, whose prompts share a preamble;
3. correctness: last-position logits of ``T.prefill`` in the config's
   bf16 compute against ``T.forward`` of a float32 copy of the config,
   plus (information only) greedy-token agreement between the engine
   and a direct batch-1 ``prefill`` + ``decode_step`` loop;
4. kernels: the four Pallas kernels compiled (not interpreted) at
   qwen2-1.5b head widths and recurrentgemma-9b's RG-LRU width, each
   against its ``ref.py`` oracle.

Any failed check raises and the process exits non-zero. The last line
of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import place_compile_cache  # noqa: E402

ARCH = "qwen2_1_5b"
MAX_SLOTS = 8
MAX_LEN = 2048
PREFILL_CHUNK = 256
N_REQUESTS = 8
PROMPT_LEN = 512
PREAMBLE_LEN = 256
MAX_NEW = 32
# bf16 prefill vs float32 forward, max |logit diff| over the vocab. On
# CPU at full width the gap measured 0.023 with 1 layer and 0.041 with
# 4; rounding error grows about as sqrt(depth), so ~0.11 is expected at
# 28 layers. A wrong mask, position or cache shows up at the scale of
# the logits' spread (std ~0.78), far above this bound.
LOGIT_TOL = 0.25
# compiled kernel vs its pure-jnp oracle: bf16 outputs round at 2^-9
# relative (|o| <= a few units), fp32 scan outputs agree to rounding
BF16_TOL = 3e-2
F32_TOL = 1e-5


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _post(port: int, body: dict) -> dict:
    """One /generate call; streamed bodies are reassembled and checked
    against their terminal completion line."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", "/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        data = r.read()
    finally:
        conn.close()
    check(r.status == 200, f"/generate -> {r.status}: {data[:200]!r}")
    if not body.get("stream"):
        return json.loads(data)
    lines = [json.loads(ln) for ln in data.splitlines() if ln.strip()]
    toks = [ln["token"] for ln in lines if "token" in ln]
    final = lines[-1]
    check(bool(final.get("done")) and final["tokens"] == toks,
          f"streamed tokens disagree with the completion: {final}")
    return final


def _get(port: int, path: str):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def make_prompts(vocab: int, seed: int, *, n: int, prompt_len: int,
                 preamble_len: int):
    """``n`` prompts of ``prompt_len`` ids opening with one shared
    ``preamble_len``-token preamble."""
    import numpy as np
    rng = np.random.RandomState(seed)
    preamble = rng.randint(0, vocab, preamble_len)
    out = []
    for _ in range(n):
        p = rng.randint(0, vocab, prompt_len)
        p[:preamble_len] = preamble
        out.append(p.astype(np.int32))
    return out


def serve_phase(cfg, params, prompts, *, max_new: int, max_slots: int,
                max_len: int, prefill_chunk: int, label: str) -> list:
    """Serve ``prompts`` concurrently over HTTP through ``EngineServer``;
    returns each request's completion JSON in prompt order."""
    from repro.runtime.engine import Engine, EngineConfig
    from repro.runtime.server import EngineServer, ServerConfig

    eng = Engine(cfg, params, EngineConfig(
        kv_layout="paged", prefix_cache=True, prefill_chunk=prefill_chunk,
        admission="fifo", max_slots=max_slots, max_len=max_len))
    t0 = time.perf_counter()
    with EngineServer(eng, ServerConfig(port=0)) as srv:
        warmup_s = time.perf_counter() - t0
        deadline = time.monotonic() + 60
        while _get(srv.port, "/health/ready")[0] != 200:
            check(time.monotonic() < deadline, "/health/ready never 200")
            time.sleep(0.1)
        bodies = [{"prompt": [int(t) for t in p], "max_new_tokens": max_new,
                   "stream": i % 2 == 0} for i, p in enumerate(prompts)]
        t1 = time.perf_counter()
        with ThreadPoolExecutor(len(bodies)) as pool:
            futs = [pool.submit(_post, srv.port, b) for b in bodies]
            outs = [f.result() for f in futs]
        wall_s = time.perf_counter() - t1
        code, raw = _get(srv.port, "/status")
        check(code == 200, f"/status -> {code}")
        status = json.loads(raw)
    for i, o in enumerate(outs):
        check(o["finish_reason"] in ("length", "eos"),
              f"request {i}: finish_reason {o['finish_reason']!r}")
        check(o["finish_reason"] == "eos" or len(o["tokens"]) == max_new,
              f"request {i}: {len(o['tokens'])} tokens, wanted {max_new}")
        check(all(0 <= t < cfg.vocab_size for t in o["tokens"]),
              f"request {i}: token outside [0, {cfg.vocab_size})")
    hits = status["prefix_cache"]["prefix_hits"]
    check(hits > 0, f"/status shows no prefix hits: {status['prefix_cache']}")
    tokens = sum(len(o["tokens"]) for o in outs)
    print(f"serving [{label}]: warmup_compile_s={warmup_s:.3f} "
          f"wall_s={wall_s:.3f} requests={len(outs)} tokens={tokens} "
          f"prefix_hits={hits} prefill_tokens_saved="
          f"{status['prefix_cache']['prefill_tokens_saved']} "
          f"finish={sorted({o['finish_reason'] for o in outs})}",
          flush=True)
    return outs


def correctness_phase(cfg, params, prompt, engine_tokens, *,
                      label: str) -> None:
    """bf16 ``prefill`` logits vs a float32 ``forward`` (checked), and
    greedy agreement of a direct decode loop with the engine (printed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T

    toks = jnp.asarray(prompt[None])
    n_new = len(engine_tokens)
    prefill = jax.jit(lambda p, b: T.prefill(p, cfg, b,
                                             max_len=len(prompt) + n_new))
    logits, cache, clen = prefill(params, {"tokens": toks})
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, b: T.forward(p, cfg32, b, train=False)[0])
        ref = fwd(params, {"tokens": toks})[:, -1]
    got = np.asarray(logits, np.float32)[0, :cfg.vocab_size]
    want = np.asarray(ref, np.float32)[0, :cfg.vocab_size]
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          "non-finite logits")
    diff = float(np.abs(got - want).max())
    print(f"correctness [{label}]: prefill_bf16_vs_forward_f32 "
          f"max_abs_diff={diff:.6f} tol={LOGIT_TOL} ref_max_abs="
          f"{float(np.abs(want).max()):.4f} argmax_equal="
          f"{int(got.argmax()) == int(want.argmax())}", flush=True)
    check(diff <= LOGIT_TOL,
          f"prefill logits differ from the float32 forward by {diff}")

    decode = jax.jit(lambda p, t, c, n: T.decode_step(p, cfg, t, c, n))
    tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1).astype(jnp.int32)
    direct = [int(tok[0])]
    for _ in range(n_new - 1):
        lg, cache, clen = decode(params, tok, cache, clen)
        tok = jnp.argmax(lg[:, :cfg.vocab_size], axis=-1).astype(jnp.int32)
        direct.append(int(tok[0]))
    agree = sum(a == b for a, b in zip(direct, engine_tokens))
    lead = next((i for i, (a, b) in enumerate(zip(direct, engine_tokens))
                 if a != b), n_new)
    print(f"correctness [{label}]: greedy_agreement engine_vs_direct="
          f"{agree}/{n_new} identical_prefix={lead} (information only: "
          f"random-init logits have near-ties)", flush=True)


def kernel_phase(*, label: str, b: int = 8, h: int = 12, hk: int = 2,
                 d: int = 128, cache_len: int = 2048, block: int = 16,
                 pages: int = 32, prefill_len: int = 512,
                 scan_b: int = 2, scan_len: int = 512,
                 scan_width: int = 4096) -> None:
    """Each Pallas kernel through its public wrapper (compiled on an
    accelerator) against its ``ref.py`` oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention.ops import (decode_attention,
                                                    paged_decode_attention)
    from repro.kernels.decode_attention.ref import (
        decode_attention_ref, paged_decode_attention_ref)
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.rglru_scan.ref import rglru_scan_seq_ref

    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(11), 12)
    n_blocks = b * pages + 1
    q = jax.random.normal(ks[0], (b, h, d), bf)
    kp = jax.random.normal(ks[1], (n_blocks, block, hk, d), bf)
    vp = jax.random.normal(ks[2], (n_blocks, block, hk, d), bf)
    tables = jax.random.randint(ks[3], (b, pages), 0, n_blocks)
    lens = jax.random.randint(ks[4], (b,), 1, pages * block + 1)
    kc = jax.random.normal(ks[5], (b, cache_len, hk, d), bf)
    vc = jax.random.normal(ks[6], (b, cache_len, hk, d), bf)
    dlens = jax.random.randint(ks[7], (b,), 1, cache_len + 1)
    fq = jax.random.normal(ks[8], (1, prefill_len, h, d), bf)
    fk = jax.random.normal(ks[9], (1, prefill_len, hk, d), bf)
    fv = jax.random.normal(ks[10], (1, prefill_len, hk, d), bf)
    sk = jax.random.split(ks[11], 3)
    a = jax.random.uniform(sk[0], (scan_b, scan_len, scan_width),
                           minval=0.5, maxval=0.999)
    bb = jax.random.normal(sk[1], (scan_b, scan_len, scan_width)) * 0.1
    h0 = jax.random.normal(sk[2], (scan_b, scan_width))
    cases = [
        ("paged_decode_attention", BF16_TOL,
         paged_decode_attention(q, kp, vp, tables, lens),
         paged_decode_attention_ref(q, kp, vp, tables, lens)),
        ("decode_attention", BF16_TOL,
         decode_attention(q, kc, vc, dlens),
         decode_attention_ref(q, kc, vc, dlens)),
        ("flash_attention", BF16_TOL,
         flash_attention(fq, fk, fv, causal=True),
         attention_ref(fq, fk, fv, causal=True)),
        ("rglru_scan", F32_TOL, rglru_scan(a, bb, h0),
         rglru_scan_seq_ref(a, bb, h0)),
    ]
    for name, tol, out, ref in cases:
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        check(out.shape == ref.shape, f"{name}: shape {out.shape} vs "
              f"{ref.shape}")
        check(bool(np.isfinite(out).all()), f"{name}: non-finite output")
        err = float(np.abs(out - ref).max())
        bound = float((tol + tol * np.abs(ref)).max())
        print(f"kernel [{label}]: {name} shape={out.shape} "
              f"max_abs_err={err:.3e} tol=atol+rtol*|ref| "
              f"(atol=rtol={tol})", flush=True)
        check(bool(np.all(np.abs(out - ref) <= tol + tol * np.abs(ref))),
              f"{name}: max error {err} exceeds {bound}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)
    place_compile_cache()
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as T

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev.platform!r}); this smoke test runs only on a TPU",
              file=sys.stderr)
        return 2
    label = dev.device_kind
    print(f"device: platform={dev.platform} kind={label} "
          f"count={len(devices)}", flush=True)

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(T.init_params, static_argnums=0)(
            cfg, jax.random.PRNGKey(args.seed)))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"model [{label}]: {cfg.name} n_layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"vocab={cfg.vocab_size} params={n_params:.4e} "
          f"init_s={time.perf_counter() - t0:.3f}", flush=True)

    prompts = make_prompts(cfg.vocab_size, args.seed, n=N_REQUESTS,
                           prompt_len=PROMPT_LEN, preamble_len=PREAMBLE_LEN)
    outs = serve_phase(cfg, params, prompts, max_new=MAX_NEW,
                       max_slots=MAX_SLOTS, max_len=MAX_LEN,
                       prefill_chunk=PREFILL_CHUNK, label=label)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"memory [{label}]: peak_bytes_in_use={peak} after serving",
          flush=True)
    correctness_phase(cfg, params, prompts[0], outs[0]["tokens"],
                      label=label)
    kernel_phase(label=label)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"memory [{label}]: peak_bytes_in_use={peak} at exit", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
