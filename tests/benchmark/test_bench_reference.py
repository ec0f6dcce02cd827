"""The benchmark's float32 reference against the served model, and the
harness's ``correct`` against a broken timed path (CPU, small sizes).

The reference is compared on logits with the model's chunked prefill
(resumed from the prefix cache on the second request) and then its
paged decode, all through ``Engine``. Both compute in float32 here, so
they agree to rounding: the program's blocked attention and chunked
extend sum in another order than the reference's whole-row softmax,
which moves a logit by about 1e-6 at these widths. The tolerance, 1e-3,
is three orders above that and two below what a wrong position, mask,
bias or norm moves a logit (0.1 and more at these widths).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import reference  # noqa: E402
import weights  # noqa: E402

LOGIT_TOL = 1e-3

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "d_ff": 128, "vocab_size": 250, "qkv_bias": True,
        "rope_theta": 10000.0, "norm_eps": 1e-5, "dtype": "float32",
        "param_dtype": "float32"}


def _engine(cfg, params, chunk=8):
    import run
    from repro.runtime.engine import Engine, EngineConfig
    return Engine(run.model_config("tiny", cfg), params, EngineConfig(
        max_slots=2, max_len=96, kv_layout="paged", block_size=4,
        prefill_chunk=chunk, prefix_cache=True, victim_cache=True))


@pytest.mark.parametrize("tied,fraction", [(True, 1.0), (False, 0.5)])
def test_reference_matches_chunked_prefill_then_paged_decode(
        monkeypatch, tied, fraction):
    import jax
    import jax.numpy as jnp
    from repro.runtime import policies
    from repro.runtime.scheduler import Request

    cfg = dict(TINY, tie_embeddings=tied, rope_fraction=fraction)
    spec = weights.Spec.from_config(cfg)
    params = weights.make_params(spec, 2 ** 31 + 3)
    seen = []

    def sampler(self, logits):
        seen.append(np.asarray(logits, np.float32))
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(policies.Sampler, "__call__", sampler)
    eng = _engine(cfg, params)
    rng = np.random.RandomState(0)
    shared = rng.randint(0, 250, 20)
    prompts = [np.concatenate([shared, rng.randint(0, 250, 7)]),
               np.concatenate([shared, rng.randint(0, 250, 11)])]
    n_new = 9
    for i, p in enumerate(prompts):
        seen.clear()
        c = eng.generate([Request(id=i, prompt=p.astype(np.int32),
                                  max_new_tokens=n_new)])[0]
        # first token from the last prefill chunk, then decode rows of
        # slot 0 (one request at a time holds slot 0)
        got = np.stack([seen[0][0]] + [s[0] for s in seen[1:]])
        toks, where = reference.padded(list(p), c.tokens, len(p) + n_new)
        with jax.default_matmul_precision("highest"):
            x = reference.hidden(spec, params, jnp.asarray(toks))
            want = np.asarray(reference.logits_at(spec, params, x))[where]
        assert got.shape[0] == n_new
        err = np.abs(got[:, :spec.vocab_size] - want).max()
        assert err < LOGIT_TOL, (i, err)
        # the served tokens are the reference's own picks here
        g = reference.gaps(spec, params, toks, where, c.tokens)[0]
        assert g.max() < LOGIT_TOL
    assert eng.stats()["prefix_hits"] >= 1


def test_a_wrong_token_shows_as_a_gap():
    spec = weights.Spec.from_config(dict(TINY, tie_embeddings=True))
    params = weights.make_params(spec, 5)
    prompt = list(range(3, 30))
    toks, where = reference.padded(prompt, [0] * 8, 40)
    best = reference.picks(spec, params, toks)[where]
    right, wrong, margin = reference.gaps(spec, params, toks, where, best,
                                          (best + 1) % spec.vocab_size)
    assert right.max() == 0.0
    assert wrong.min() > 0.0
    assert margin.min() >= 0.0 and margin.max() > 0.0


def test_round_e4m3_matches_the_fp8_cast():
    """The control's fp8 rounding, done in float32 arithmetic, gives
    what a cast to float8_e4m3fn gives on the CPU: normals, subnormals,
    ties, the largest value and 0."""
    import jax
    import jax.numpy as jnp
    x = jnp.concatenate([
        jax.random.normal(jax.random.key(1), (20000,)) * 50,
        jax.random.uniform(jax.random.key(2), (20000,), minval=-0.05,
                           maxval=0.05),
        jnp.array([448.0, -448.0, 0.0, 2.0 ** -9, 2.0 ** -10,
                   3 * 2.0 ** -10, 240.0, 17.0, 19.0])])
    x = jnp.clip(x, -448.0, 448.0)
    want = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(reference.round_e4m3(x)),
                                  np.asarray(want))


def _tiny_cell(loop):
    cfg = dict(TINY, tie_embeddings=False, rope_fraction=0.5, engine={
        "max_slots": 4, "max_len": 256, "kv_layout": "paged",
        "block_size": 16, "prefill_chunk": 32, "prefix_cache": True,
        "victim_cache": True, "admission": "fifo"},
        server={"max_inflight": 64})
    mix = {"loop": loop, "base_seed": 7,
           "prefix": {"pool": 3, "zipf": 1.1,
                      "len": {"dist": "uniform", "min": 40, "max": 70}},
           "user": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                    "min": 4, "max": 60},
           "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 40}}
    load = {"limits": {"logit_gap_mean": 1e-4}}
    load.update({"rate_per_s": 4.0, "ramp_s": 1.0} if loop == "open"
                else {"clients": 8})
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    cell = {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1}
    return cell, cfg, mix, load, manifest


@pytest.mark.parametrize("loop,fault", [("open", None), ("closed", "token"),
                                        ("open", "state")])
def test_harness_run_decides_correct(monkeypatch, loop, fault):
    """A whole run past the look for a chip: sound, it is correct and
    reads the control (the reference with fp8 weights) further off than
    the program. With the timed path broken underneath it is not: each
    served token altered where it is produced ("token"), or a decode
    step that returns the KV cache it was given unchanged ("state")."""
    import jax.numpy as jnp
    import run
    from repro.models import transformer
    from repro.runtime import policies

    if fault == "token":
        def altered(self, logits):
            return ((jnp.argmax(logits, axis=-1) + 1)
                    % TINY["vocab_size"]).astype(jnp.int32)
        monkeypatch.setattr(policies.Sampler, "__call__", altered)
    if fault == "state":
        step = transformer.decode_step

        def unchanged(params, cfg, token, cache, cache_len, **kw):
            logits, _, *rest = step(params, cfg, token, cache, cache_len,
                                    **kw)
            return (logits, cache, *rest)
        monkeypatch.setattr(transformer, "decode_step", unchanged)
    cell, cfg, mix, load, manifest = _tiny_cell(loop)
    out = run.run_cell(cell, cfg, mix, load, manifest, seed=2 ** 31 + 17,
                       seconds=2.0, trace=False,
                       control="fp8" if fault is None else "")
    assert out["correct"] is (fault is None), out["compared"]
    assert out["compared"]["compared_requests"]["value"] >= 1
    assert set(out["metrics"]) >= {"setup_s"}
    if fault is None:
        limit = load["limits"]["logit_gap_mean"]
        assert out["compared"]["logit_gap_mean"]["value"] < limit
        assert out["control"]["fp8"]["logit_gap_mean"] > limit
        assert out["control"]["fp8"]["correct"] is False
    assert list(out)[-1] == "compared"
