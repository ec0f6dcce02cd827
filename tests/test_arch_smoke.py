"""Per-architecture smoke tests: every assigned arch instantiates a
REDUCED same-family variant (2 layers, d_model <= 256, <= 4 experts) and
runs one forward + one train step on CPU, asserting output shapes and the
absence of NaNs; plus a prefill+decode consistency check against the
full-sequence forward (the serving path must agree with training)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, all_configs, get_config
from repro.models import transformer as T
from repro.runtime import optim
from repro.runtime.trainstep import make_train_step

KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, b=2, s=16, labels=True):
    batch = {"tokens": jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)}
    if cfg.arch_type == "vlm":
        batch["embeds"] = jax.random.normal(
            KEY, (b, cfg.frontend_tokens, cfg.frontend_dim))
    elif cfg.arch_type == "audio":
        batch["embeds"] = jax.random.normal(KEY, (b, s, cfg.frontend_dim))
    if labels:
        total = s + (cfg.frontend_tokens if cfg.arch_type == "vlm" else 0)
        batch["labels"] = jax.random.randint(KEY, (b, total), 0,
                                             cfg.vocab_size)
    return batch


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch(request):
    return request.param


def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    expected = {
        "seamless_m4t_medium": (12, 1024, 16, 16, 4096, 256206),
        "qwen2_moe_a2_7b": (24, 2048, 16, 16, 0, 151936),
        "llava_next_mistral_7b": (32, 4096, 32, 8, 14336, 32000),
        "recurrentgemma_9b": (38, 4096, 16, 1, 12288, 256000),
        "gemma3_1b": (26, 1152, 4, 1, 6912, 262144),
        "llama3_2_3b": (28, 3072, 24, 8, 8192, 128256),
        "qwen3_moe_235b_a22b": (94, 4096, 64, 4, 0, 151936),
        "qwen2_1_5b": (28, 1536, 12, 2, 8960, 151936),
        "xlstm_350m": (24, 1024, 4, 4, 0, 50304),
        "chatglm3_6b": (28, 4096, 32, 2, 13696, 65024),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab_size)
    assert got == expected
    assert len(cfg.layer_kinds) == cfg.n_layers


def test_moe_configs():
    q2 = get_config("qwen2_moe_a2_7b").moe
    assert (q2.n_experts, q2.top_k, q2.n_shared_experts,
            q2.d_ff_expert) == (60, 4, 4, 1408)
    q3 = get_config("qwen3_moe_235b_a22b").moe
    assert (q3.n_experts, q3.top_k, q3.n_shared_experts,
            q3.d_ff_expert) == (128, 8, 0, 1536)


def test_smoke_forward_no_nan(arch):
    cfg = get_config(arch).smoke()
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    params = T.init_params(cfg, KEY)
    batch = _batch_for(cfg, labels=False)
    logits, aux = T.forward(params, cfg, batch, train=False)
    total = 16 + (cfg.frontend_tokens if cfg.arch_type == "vlm" else 0)
    assert logits.shape == (2, total, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_smoke_train_step_no_nan(arch):
    cfg = get_config(arch).smoke()
    params = T.init_params(cfg, KEY)
    opt = optim.init(params)
    step = make_train_step(cfg, optim.AdamWConfig(lr=1e-3, warmup_steps=1))
    batch = _batch_for(cfg)
    params2, opt2, metrics = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(opt2["step"]) == 1
    # params actually moved
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in
                zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert delta > 0


def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).smoke()
    params = T.init_params(cfg, KEY)
    b, s = 2, 12
    batch = _batch_for(cfg, b, s, labels=False)
    logits_full, _ = T.forward(params, cfg, batch, train=False)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :s - 1]
    lg, cache, clen = T.prefill(params, cfg, pre,
                                max_len=s + cfg.frontend_tokens + 4)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(logits_full[:, -2]),
                               rtol=2e-3, atol=2e-3)
    lg2, cache, clen = T.decode_step(params, cfg, batch["tokens"][:, s - 1],
                                     cache, clen)
    np.testing.assert_allclose(np.asarray(lg2),
                               np.asarray(logits_full[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_sliding_window_cache_is_ring_buffer():
    """Decode through a window-2 local-attn cache twice around the ring and
    compare against the quadratic reference."""
    cfg = get_config("gemma3_1b").smoke()
    assert any(k == "attn_local" for k in cfg.layer_kinds)
    assert cfg.window == 8
    params = T.init_params(cfg, KEY)
    b, s = 1, 24      # > 2x window -> wraps the ring
    batch = {"tokens": jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)}
    logits_full, _ = T.forward(params, cfg, batch, train=False)
    pre = {"tokens": batch["tokens"][:, :4]}
    lg, cache, clen = T.prefill(params, cfg, pre, max_len=s)
    for t in range(4, s):
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(logits_full[:, t - 1]),
            rtol=5e-3, atol=5e-3, err_msg=f"t={t}")
        lg, cache, clen = T.decode_step(params, cfg, batch["tokens"][:, t],
                                        cache, clen)


def test_param_count_analytic_close_to_actual(arch):
    cfg = get_config(arch).smoke()
    params = T.init_params(cfg, KEY)
    actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    analytic = cfg.param_count()
    assert abs(actual - analytic) / actual < 0.35, (actual, analytic)


# sums and sums of squares (float64) of the logits of a seeded qwen2
# smoke model: forward, prefill, one decode step and one chunked extend,
# as the serving programs gave them before q/k norm and the expert
# routing report existed
DENSE_PINNED = {
    "float32": [(21.24668483667483, 1299.467671411802),
                (16.5801381712663, 98.6563701844836),
                (10.119386524951551, 111.02293547557547),
                (-1.1912203576794127, 868.2960673877167)],
    "bfloat16": [(21.47366952896118, 1300.0197832163324),
                 (16.56807041168213, 98.93249506955999),
                 (10.159677505493164, 111.44582874481057),
                 (-1.0423550605773926, 868.2958383502657)],
}


@pytest.mark.parametrize("dtype", sorted(DENSE_PINNED))
def test_qk_norm_off_leaves_dense_logits_as_they_were(dtype):
    """With ``qk_norm`` off (every dense config) the attention block
    holds no q/k norm leaves and the logits of every program are those
    pinned before the option existed, bit for bit."""
    import dataclasses
    cfg = dataclasses.replace(get_config("qwen2_1_5b").smoke(), dtype=dtype)
    assert not cfg.qk_norm
    params = T.init_params(cfg, jax.random.PRNGKey(5))
    assert "q_norm" not in params["scan"][0]["block"]
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 12), 0,
                              cfg.vocab_size)
    full, _ = T.forward(params, cfg, {"tokens": toks}, train=False)
    lg, cache, clen = T.prefill(params, cfg, {"tokens": toks[:, :11]},
                                max_len=16)
    lg2, _, _ = T.decode_step(params, cfg, toks[:, 11], cache, clen)
    lg3, _, _ = T.prefill_extend(params, cfg, toks[:, :8],
                                 T.init_cache(cfg, 2, 16),
                                 jnp.zeros((2,), jnp.int32))
    got = [(float(np.asarray(a, np.float64).sum()),
            float(np.square(np.asarray(a, np.float64)).sum()))
           for a in (full, lg, lg2, lg3)]
    assert got == DENSE_PINNED[dtype]


def test_qk_norm_normalises_each_head():
    """With ``qk_norm`` on, q and k leave the projection RMS-normed over
    each head's dims, scaled by (1 + w)."""
    import dataclasses

    from repro.models import layers as L
    cfg = dataclasses.replace(get_config("qwen3_moe_235b_a22b").smoke())
    assert cfg.qk_norm
    p = L.attn_init(KEY, cfg, jnp.float32)
    p["q_norm"] = jnp.full_like(p["q_norm"], 0.5)
    x = jax.random.normal(KEY, (1, 3, cfg.d_model)) * 7.0
    q, k, _ = L._qkv(p, x, cfg)
    rms = np.sqrt(np.mean(np.square(np.asarray(q)), axis=-1))
    np.testing.assert_allclose(rms, 1.5, rtol=1e-4)
    rms = np.sqrt(np.mean(np.square(np.asarray(k)), axis=-1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-4)
