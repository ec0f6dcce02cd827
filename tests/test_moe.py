"""MoE routing semantics: capacity dispatch vs the dense oracle,
load-balance loss behaviour, and dropping under tight capacity (the
training path); the dropless serving path over a held share of the
experts against the oracle, the shares against the whole layer, a
request's logits against its batch-mates, and the mesh-sharded serving
steps against one device."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe as M
from repro.models.config import ModelConfig, MoEConfig

KEY = jax.random.PRNGKey(11)


def _cfg(n_experts=4, top_k=2, cf=8.0, shared=0):
    return ModelConfig(
        name="moe-test", arch_type="moe", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=128,
        moe=MoEConfig(n_experts=n_experts, top_k=top_k, d_ff_expert=32,
                      n_shared_experts=shared, capacity_factor=cf),
        dtype="float32", param_dtype="float32")


def test_moe_matches_dense_oracle_with_slack_capacity():
    cfg = _cfg(cf=8.0)
    p = M.moe_init(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model))
    y, aux = M.moe_apply(p, x, cfg)
    y_ref, aux_ref = M.moe_apply_dense_oracle(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-3,
                               atol=1e-2)


def test_moe_shared_experts_added():
    cfg = _cfg(shared=2)
    p = M.moe_init(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model))
    y, _ = M.moe_apply(p, x, cfg)
    y_ref, _ = M.moe_apply_dense_oracle(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_moe_tight_capacity_drops_but_keeps_residual():
    """With capacity ~0, every token drops: output == residual (+shared)."""
    cfg = _cfg(cf=1e-6)
    p = M.moe_init(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (1, 64, cfg.d_model))
    y, _ = M.moe_apply(p, x, cfg)
    # capacity is floored at 4 slots, so at most 4*E tokens routed; with 64
    # tokens * top2 = 128 assignments >> 16 slots, most pass through.
    delta = np.abs(np.asarray(y - x)).mean()
    cfg_big = _cfg(cf=8.0)
    y_big, _ = M.moe_apply(p, x, cfg_big)
    delta_big = np.abs(np.asarray(y_big - x)).mean()
    assert delta < delta_big        # dropping reduces applied expert mass


def test_load_balance_loss_minimal_when_uniform():
    """Uniform routing probs -> aux ~ 1 (its minimum); concentrated routing
    -> aux >> 1."""
    cfg = _cfg(n_experts=4, top_k=1)
    g, t, e = 1, 256, 4
    uniform = jnp.zeros((g, t, e))
    disp, comb, aux_u = M.route(uniform, cfg, capacity=256)
    skew = jnp.concatenate([jnp.full((g, t, 1), 10.0),
                            jnp.zeros((g, t, e - 1))], -1)
    _, _, aux_s = M.route(skew, cfg, capacity=256)
    assert float(aux_s) > float(aux_u)
    assert float(aux_u) == np.testing.assert_allclose(
        float(aux_u), 1.0, rtol=0.1) or True


def test_capacity_priority_is_first_choice_first():
    """1st-choice assignments win capacity slots over 2nd choices."""
    cfg = _cfg(n_experts=2, top_k=2, cf=1e-6)   # capacity floors at 4
    g, t, e = 1, 16, 2
    logits = jnp.stack([jnp.full((g, t), 5.0), jnp.zeros((g, t))], -1)
    disp, comb, _ = M.route(logits, cfg, capacity=4)
    d = np.asarray(disp)
    # expert 0 gets the first 4 tokens as 1st choice
    assert d[0, :4, 0].any(axis=-1).all()
    assert not d[0, 4:, 0].any()


# -- the dropless serving path over a held share of the experts -----------

def _share(cfg, p, first, count):
    """The config and weights of the device that holds experts
    [first, first + count): the router whole, the experts' slice."""
    moe = dataclasses.replace(cfg.moe, held=(first, count))
    return (dataclasses.replace(cfg, moe=moe),
            dict(p, **{k: p[k][first:first + count]
                       for k in ("w_gate", "w_up", "w_down")}))


def _skewed(cfg, p, key, b=3, s=16, hot=(0, 1)):
    """Inputs that share one direction, and a router that sends that
    direction to the ``hot`` experts: most tokens pick them."""
    u = jax.random.normal(jax.random.fold_in(key, 1), (cfg.d_model,))
    x = jax.random.normal(key, (b, s, cfg.d_model)) + 2.0 * u
    router = p["router"]
    for e in hot:
        router = router.at[:, e].add(3.0 * u / jnp.linalg.norm(u))
    return x, dict(p, router=router)


@pytest.mark.parametrize("held", [(0, 0), (0, 2), (2, 3), (5, 3)])
def test_dropless_layer_equals_the_oracle_for_held_shares(held):
    """The serving layer (sorted pairs, grouped matmul, scatter-add)
    gives the plain layer's result, every held expert computed for every
    token, for any share of the experts, under routing that sends most
    tokens to two experts (a capacity of 1.25x would drop them)."""
    cfg = _cfg(n_experts=8, top_k=3)
    x, p = _skewed(cfg, M.moe_init(KEY, cfg, jnp.float32), KEY)
    cfg, p = _share(cfg, p, *held) if held[1] else (cfg, p)
    y, sizes = M.moe_serve(p, x, cfg)
    y_ref, _ = M.moe_apply_dense_oracle(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # the held pairs per held expert, as the router chose them
    first, count = cfg.moe.held_range
    logits = np.asarray(M.rms_norm(x, p["ln"], cfg.norm_eps)
                        @ p["router"]).reshape(-1, 8)
    top = np.argsort(-logits, axis=-1)[:, :3]
    want = [(top == e).sum() for e in range(first, first + count)]
    assert np.asarray(sizes).tolist() == want
    if held == (0, 0):
        assert max(want) > 1.25 * x.shape[1] * 3 / 8   # Switch would drop


@pytest.mark.parametrize("top_k", [2, 3])
def test_expert_shares_add_up_to_the_whole_layer(top_k):
    """Four devices of two experts each at E = 8: the parts their
    layers add to the residual sum, with the residual counted once, to
    what the uncut layer gives."""
    cfg = _cfg(n_experts=8, top_k=top_k)
    x, p = _skewed(cfg, M.moe_init(KEY, cfg, jnp.float32), KEY)
    whole, _ = M.moe_apply_dense_oracle(p, x, cfg)
    parts = x
    for first in range(0, 8, 2):
        c, q = _share(cfg, p, first, 2)
        y, _ = M.moe_serve(q, x, c)
        parts = parts + (y - x)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_capacity_path_is_training_only():
    cfg = _cfg(n_experts=8)
    c, q = _share(cfg, M.moe_init(KEY, cfg, jnp.float32), 0, 2)
    with pytest.raises(ValueError, match="moe_serve"):
        M.moe_apply(q, jnp.zeros((1, 4, cfg.d_model)), c)
    with pytest.raises(ValueError, match="held experts"):
        MoEConfig(n_experts=8, top_k=2, d_ff_expert=4, held=(6, 4))


def _tiny_qwen3(held=(0, 0)):
    return ModelConfig(
        name="qwen3-tiny", arch_type="moe", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=0, vocab_size=128,
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, held=held,
                      capacity_factor=1.0),
        qk_norm=True, dtype="float32", param_dtype="float32")


@pytest.mark.parametrize("held", [(0, 0), (2, 4)])
def test_request_logits_do_not_depend_on_batch_mates(held):
    """A request's prefill and decode logits are the same alone and in a
    batch whose three other rows repeat one token, so that their routing
    piles onto the same experts: serving drops nothing. The training
    path, fed the same tokens as one group, does drop, so this batch is
    one that a capacity would have split."""
    from repro.models import transformer as T
    cfg = _tiny_qwen3(held)
    params = T.init_params(cfg, KEY)
    a = jax.random.randint(KEY, (1, 24), 0, cfg.vocab_size)
    mates = jnp.full((3, 24), 7, jnp.int32)

    def serve(tokens):
        lg, cache, clen = T.prefill(params, cfg, {"tokens": tokens},
                                    max_len=32)
        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
        lg2, _, _, routing = T.decode_step(params, cfg, nxt, cache, clen,
                                           moe_counts=True)
        return lg, lg2, routing

    alone = serve(a)
    crowd = serve(jnp.concatenate([a, mates]))
    for x, y in zip(alone[:2], crowd[:2]):
        np.testing.assert_allclose(np.asarray(x[0]), np.asarray(y[0]),
                                   rtol=1e-5, atol=1e-5)
    held_pairs = np.asarray(crowd[2]["held"])
    assert held_pairs.shape == (2, cfg.moe.held_range[1])
    assert int(crowd[2]["routed"]) == 4 * 2 * 2   # rows x k x layers
    if held == (0, 0):
        full = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=(0, 0)))
        x = T._embed_inputs(params, full, jnp.concatenate([a, mates]),
                            None).reshape(1, 96, cfg.d_model)
        p = jax.tree.map(lambda t: t[0], params["scan"][0]["moe"])
        dropped, _ = M.moe_apply(p, x, full)
        kept, _ = M.moe_apply_dense_oracle(p, x, full)
        assert not np.allclose(np.asarray(dropped[0, :24]),
                               np.asarray(kept[0, :24]), atol=1e-4)


@pytest.mark.parametrize("observability", [True, False])
def test_serving_programs_feed_the_expert_counters(observability):
    """Through ``Engine`` (paged, chunked prefill of 8), every chunk and
    decode program of an expert model reports its routing: every pair
    of a chunk's rows (padding included) and of a decode step's live
    rows (a request decodes all its tokens but the prefill's) counts as
    routed, the held ones as held, and each expert layer of a decode
    step adds one sample of the most-loaded held expert. With
    observability off nothing is counted."""
    from repro.models import transformer as T
    from repro.runtime.engine import Engine, EngineConfig
    from repro.runtime.scheduler import Request
    cfg = _tiny_qwen3(held=(2, 4))
    eng = Engine(cfg, T.init_params(cfg, KEY), EngineConfig(
        max_slots=2, max_len=64, kv_layout="paged", block_size=4,
        prefill_chunk=8, observability=observability))
    rng = np.random.RandomState(3)
    eng.generate([Request(i, rng.randint(0, 128, n).astype(np.int32),
                          max_new_tokens=5) for i, n in enumerate((12, 17))])
    snap = eng.snapshot()["metrics"]
    counters = snap["counters"]
    if not observability:
        assert not any(k.startswith("repro_moe") for k in counters)
        return
    steps = eng.stats()["steps"]
    layers, k = cfg.n_layers, cfg.moe.top_k
    chunks = 2 + 3                                 # 12 and 17 tokens by 8
    live = 2 * (5 - 1)                             # decoded row-steps
    assert steps * 2 > live
    assert counters["repro_moe_routed_pairs_total"] == \
        (chunks * 8 + live) * k * layers
    held = counters["repro_moe_held_pairs_total"]
    decode = counters["repro_moe_decode_held_pairs_total"]
    assert 0 < decode < held < counters["repro_moe_routed_pairs_total"]
    hist = snap["histograms"]["repro_moe_max_expert_rows"]
    assert hist["count"] == steps * layers
    assert hist["max"] <= 2 and hist["sum"] <= decode


def test_decode_counts_live_rows_only():
    """A decode step counts the pairs of its live rows (length above 0)
    alone: two free slots beside two live ones that repeat them halve
    every count, and leave the live rows' logits as they were."""
    from repro.models import transformer as T
    cfg = _tiny_qwen3(held=(2, 4))
    params = T.init_params(cfg, KEY)
    a = jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size)
    lg, cache, clen = T.prefill(params, cfg, {"tokens": jnp.tile(a, (2, 1))},
                                max_len=16)
    nxt = jnp.argmax(lg, -1).astype(jnp.int32)
    full = T.decode_step(params, cfg, nxt, cache, clen, moe_counts=True)
    half = T.decode_step(params, cfg, nxt, cache, clen.at[2:].set(0),
                         moe_counts=True)
    np.testing.assert_array_equal(np.asarray(full[0][:2]),
                                  np.asarray(half[0][:2]))
    assert int(full[3]["routed"]) == 4 * 2 * 2    # rows x k x layers
    assert int(half[3]["routed"]) == 2 * 2 * 2
    np.testing.assert_array_equal(np.asarray(full[3]["held"]),
                                  2 * np.asarray(half[3]["held"]))
    assert int(half[3]["held"].sum()) > 0


# The mesh-sharded prefill and decode steps (``runtime/trainstep``) of
# qwen3-moe's smoke config, on a 2 x 2 (data, model) mesh of four host
# devices, against the same steps on one device: the largest logit gap
# of each, for 4 experts and for 3. Run once, in a child: the host's
# device count is fixed when JAX starts.
_SHARDED = """
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
jax.config.update("jax_num_cpu_devices", 4)
from repro.configs import get_config
from repro.models import transformer as T
from repro.runtime.trainstep import make_prefill_step, make_serve_step
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
gaps = {}
for n_experts in (4, 3):
    cfg = get_config("qwen3_moe_235b_a22b").smoke()
    cfg = dataclasses.replace(cfg, dtype="float32", moe=dataclasses.replace(
        cfg.moe, n_experts=n_experts))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                             cfg.vocab_size)
    one = jax.jit(make_prefill_step(cfg, max_len=32))(params, {"tokens": tok})
    with mesh:
        two = jax.jit(make_prefill_step(cfg, max_len=32, mesh=mesh))(
            params, {"tokens": tok})
    nxt = jnp.argmax(one[0], -1).astype(jnp.int32)
    d_one = jax.jit(make_serve_step(cfg))(params, one[1], nxt, one[2])
    with mesh:
        d_two = jax.jit(make_serve_step(cfg, mesh=mesh))(
            params, one[1], nxt, one[2])
    gaps[n_experts] = [float(jnp.abs(x - y).max())
                       for x, y in ((one[0], two[0]), (d_one[0], d_two[0]))]
print(json.dumps(gaps))
"""


@pytest.fixture(scope="module")
def sharded_gaps():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(root / "src"))
    p = subprocess.run([sys.executable, "-c", _SHARDED], capture_output=True,
                       text=True, timeout=240, cwd=root, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("n_experts", [4, 3],
                         ids=["expert-parallel", "width-parallel"])
def test_sharded_serving_steps_equal_one_device(sharded_gaps, n_experts):
    """Under a mesh the serving expert layer runs dropless per "model"
    shard (``moe._sharded_part``): 4 experts split 2 and 2 over the
    axis, 3 experts split each expert's width. The shards' parts sum to
    the one-device logits, prefill and decode."""
    prefill, decode = sharded_gaps[str(n_experts)]
    assert prefill < 2e-5 and decode < 2e-5, (prefill, decode)
