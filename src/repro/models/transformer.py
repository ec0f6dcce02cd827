"""Generic multi-family model: assembles any ModelConfig into init /
forward / prefill / decode functions, and exports the model as a VR-PRUNE
actor graph so Edge-PRUNE's partitioning applies to every architecture.

Depth handling: the ``layer_pattern`` period is executed under one
``jax.lax.scan`` over stacked per-period parameters (n_periods repeats),
with the remainder layers unrolled. HLO size — and dry-run compile time —
is therefore O(period), not O(n_layers). The decode path carries the
per-layer caches through the same scan.

Layer = block (attn / attn_local / rglru / mlstm / slstm) + optional
MLP/MoE sublayer (attention and rglru kinds only; xLSTM blocks embed
their own projections, d_ff == 0).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.config import ModelConfig

_HAS_MLP = ("attn", "attn_local", "rglru")


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# gate / router / state-decay leaves stay fp32 for numerical stability
_KEEP_F32 = ("lam", "router", "b_if", "w_if", "b")


def cast_params_for_compute(params, cfg: ModelConfig):
    """Master params (fp32) -> compute dtype (bf16), the standard
    mixed-precision scheme: matmuls run on the MXU in bf16. Training
    casts at every step entry, since optimizer state and updates stay
    fp32. Serving never updates the weights: ``Engine`` casts once, at
    construction, and the call at the top of each serving program then
    meets leaves already in the compute dtype (``astype`` to the same
    dtype is the identity, so no convert runs)."""
    ct = _dtype(cfg.dtype)

    def one(path, leaf):
        name = str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))
        if name in _KEEP_F32 or not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        return leaf.astype(ct)
    return jax.tree_util.tree_map_with_path(one, params)


@partial(jax.jit, static_argnums=1)
def _cast_leaves(leaves, dtypes):
    return [a.astype(dt) for a, dt in zip(leaves, dtypes)]


def hold_for_serving(params, cfg: ModelConfig):
    """The tree a server holds: ``cast_params_for_compute`` of the
    caller's tree, cast once. A leaf already in its compute dtype (a
    bf16 checkpoint's, or one of the leaves kept in fp32) is the
    caller's own device buffer, not a copy, so serving a tree in its
    compute dtype takes no second copy of it; the other leaves go
    through one compiled cast."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    want = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda p: cast_params_for_compute(p, cfg), params))
    cast = [i for i, (a, w) in enumerate(zip(leaves, want))
            if a.dtype != w.dtype or not isinstance(a, jax.Array)]
    for i, a in zip(cast, _cast_leaves([leaves[i] for i in cast],
                                       tuple(want[i].dtype for i in cast))):
        leaves[i] = a
    return jax.tree_util.tree_unflatten(tree, leaves)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _block_init(key, kind: str, cfg: ModelConfig, dtype) -> dict:
    if kind in ("attn", "attn_local"):
        return L.attn_init(key, cfg, dtype)
    if kind == "rglru":
        return R.rglru_init(key, cfg, dtype)
    if kind == "mlstm":
        return S.mlstm_init(key, cfg, dtype)
    if kind == "slstm":
        return S.slstm_init(key, cfg, dtype)
    raise ValueError(kind)


def _layer_init(key, kind: str, cfg: ModelConfig, dtype, *,
                cross: bool = False) -> dict:
    ks = jax.random.split(key, 3)
    p = {"block": _block_init(ks[0], kind, cfg, dtype)}
    if kind in _HAS_MLP:
        if cfg.moe is not None:
            p["moe"] = M.moe_init(ks[1], cfg, dtype)
        elif cfg.d_ff:
            p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
    if cross:
        p["cross"] = L.cross_attn_init(ks[2], cfg, dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = _dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(keys[0],
                                    (cfg.padded_vocab_size, d), jnp.float32)
                  * 0.02).astype(dtype),
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[1], d,
                                         (cfg.padded_vocab_size,), dtype)
    if cfg.frontend:
        params["frontend_proj"] = {
            "w1": L.dense_init(keys[2], cfg.frontend_dim, (d,), dtype),
            "w2": L.dense_init(keys[3], d, (d,), dtype),
        }
    cross = cfg.n_encoder_layers > 0

    # decoder stack: stacked periods + remainder
    period = cfg.layer_pattern
    nrep = cfg.n_periods

    def stack_init(k, kind):
        return jax.vmap(lambda kk: _layer_init(kk, kind, cfg, dtype,
                                               cross=cross))(
            jax.random.split(k, nrep))

    pk = jax.random.split(keys[4], len(period))
    params["scan"] = [stack_init(pk[i], kind) if nrep else None
                      for i, kind in enumerate(period)]
    rk = jax.random.split(keys[5], max(len(cfg.remainder_kinds), 1))
    params["rem"] = [_layer_init(rk[i], kind, cfg, dtype, cross=cross)
                     for i, kind in enumerate(cfg.remainder_kinds)]

    if cfg.n_encoder_layers:
        ek = jax.random.split(keys[6], cfg.n_encoder_layers)
        params["encoder"] = [
            {"block": L.attn_init(ek[i], cfg, dtype),
             "mlp": L.mlp_init(jax.random.fold_in(ek[i], 1), d, cfg.d_ff,
                               dtype)}
            for i in range(cfg.n_encoder_layers)]
        params["enc_norm"] = jnp.zeros((d,), dtype)
    return params


# ---------------------------------------------------------------------------
# forward (train / no-cache)
# ---------------------------------------------------------------------------

def _layer_apply(p, x, kind: str, cfg: ModelConfig, *, positions,
                 enc_out=None, ctx=None) -> Tuple[jax.Array, jax.Array]:
    if ctx is not None:
        p = ctx.layer(p)
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "attn_local"):
        x = L.attn_apply(p["block"], x, cfg, kind=kind, positions=positions)
    elif kind == "rglru":
        x = R.rglru_apply(p["block"], x, cfg)
    elif kind == "mlstm":
        x = S.mlstm_apply(p["block"], x, cfg)
    elif kind == "slstm":
        x = S.slstm_apply(p["block"], x, cfg)
    if "cross" in p and enc_out is not None:
        x = L.cross_attn_apply(p["cross"], x, enc_out, cfg)
    if "moe" in p:
        x, aux = M.moe_apply(p["moe"], x, cfg, ctx=ctx)
    elif "mlp" in p:
        x = L.mlp_apply(p["mlp"], x, cfg)
    return x, aux


def _run_stack(params, x, cfg: ModelConfig, *, positions, enc_out=None,
               train: bool = True, ctx=None) -> Tuple[jax.Array, jax.Array]:
    period = cfg.layer_pattern
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.n_periods:
        def body(carry, slice_params):
            x, aux = carry
            for i, kind in enumerate(period):
                x, a = _layer_apply(slice_params[i], x, kind, cfg,
                                    positions=positions, enc_out=enc_out,
                                    ctx=ctx)
                aux = aux + a
            if ctx is not None:
                # shard the scan carry: this is the residual AD saves per
                # period for the backward pass
                x = ctx.act(x)
            return (x, aux), None
        body_fn = jax.checkpoint(body) if (cfg.remat and train) else body
        (x, aux_total), _ = jax.lax.scan(body_fn, (x, aux_total),
                                         params["scan"])
    for i, kind in enumerate(cfg.remainder_kinds):
        x, a = _layer_apply(params["rem"][i], x, kind, cfg,
                            positions=positions, enc_out=enc_out, ctx=ctx)
        aux_total = aux_total + a
    return x, aux_total


def _head_logits(x, params, cfg: ModelConfig):
    """LM head with vocab padding masked to -1e30 (never sampled, zero
    loss contribution). x: (..., D) -> (..., padded_vocab)."""
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, head.astype(x.dtype))
    else:
        logits = x @ head.astype(x.dtype)
    if cfg.padded_vocab_size != cfg.vocab_size:
        pad = jnp.arange(cfg.padded_vocab_size) >= cfg.vocab_size
        logits = jnp.where(pad, -1e30, logits.astype(jnp.float32)).astype(
            logits.dtype)
    return logits


def _embed_inputs(params, cfg: ModelConfig, tokens, embeds):
    dtype = _dtype(cfg.dtype)
    parts = []
    if embeds is not None and cfg.frontend and cfg.n_encoder_layers == 0:
        fp = params["frontend_proj"]
        e = jax.nn.gelu(embeds.astype(dtype) @ fp["w1"]) @ fp["w2"]
        parts.append(e)
    if tokens is not None:
        parts.append(params["embed"].astype(dtype)[tokens]
                     * math.sqrt(cfg.d_model))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def encode(params, cfg: ModelConfig, embeds: jax.Array, *,
           ctx=None) -> jax.Array:
    """Encoder stack over frontend embeddings (enc-dec archs)."""
    dtype = _dtype(cfg.dtype)
    fp = params["frontend_proj"]
    x = jax.nn.gelu(embeds.astype(dtype) @ fp["w1"]) @ fp["w2"]
    positions = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                                 x.shape[:2])

    def enc_layer(x, lp):
        if ctx is not None:
            lp = ctx.layer(lp)
        x = L.attn_encoder_apply(lp["block"], x, cfg, positions=positions)
        x = L.mlp_apply(lp["mlp"], x, cfg)
        if ctx is not None:
            x = ctx.act(x)
        return x

    # rematerialize encoder layers like the decoder periods: without this
    # the 12-layer encoder at 4k dominates train temp (224 GB observed)
    if cfg.remat:
        enc_layer = jax.checkpoint(enc_layer)
    for lp in params["encoder"]:
        x = enc_layer(x, lp)
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            train: bool = True, ctx=None) -> Tuple[jax.Array, jax.Array]:
    """batch: {"tokens": (B,S) int32, optional "embeds": (B,F,fd)}.
    Returns (logits (B, S_total, V), moe_aux_loss)."""
    params = cast_params_for_compute(params, cfg)
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    enc_out = None
    if cfg.n_encoder_layers:
        enc_out = encode(params, cfg, embeds, ctx=ctx)
        x = _embed_inputs(params, cfg, tokens, None)
    else:
        x = _embed_inputs(params, cfg, tokens, embeds)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                                 x.shape[:2])
    x, aux = _run_stack(params, x, cfg, positions=positions, enc_out=enc_out,
                        train=train, ctx=ctx)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if ctx is not None:
        x = ctx.batch_only(x)   # avoid model-axis conflict with the vocab dim
    return _head_logits(x, params, cfg), aux


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            ctx=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy. ``labels`` (B, S_total) with -1 = masked
    (e.g. image-patch positions in VLMs)."""
    logits, aux = forward(params, cfg, batch, train=True, ctx=ctx)
    if ctx is not None:
        # keep the (B, S, V) logits sharded (batch x vocab-on-"model")
        # through the loss: without this GSPMD sometimes replicates the
        # vocab dim to simplify take_along_axis (68 GB/device on gemma3)
        logits = ctx.act(logits)
    labels = batch["labels"]
    logits = logits[:, :-1].astype(jnp.float32)
    targets = labels[:, 1:]
    mask = (targets >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # elementwise one-hot contraction instead of take_along_axis: the
    # gather (and its scatter transpose) over a sharded vocab dim makes
    # GSPMD replicate the (B, S, V) logits; the iota comparison stays
    # sharded in both passes and fuses to nothing.
    onehot = (targets[..., None]
              == jnp.arange(logits.shape[-1])[None, None]).astype(jnp.float32)
    picked = jnp.sum(logits * onehot, axis=-1)
    nll = (lse - picked) * mask
    loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * aux
    return loss, {"ce": nll.sum() / jnp.maximum(mask.sum(), 1.0), "aux": aux}


# ---------------------------------------------------------------------------
# prefill / decode with caches
# ---------------------------------------------------------------------------

def _cache_size_for(kind: str, cfg: ModelConfig, max_len: int) -> int:
    if kind == "attn_local":
        return min(cfg.window, max_len)
    if kind == "attn":
        return cfg.max_cache_len or max_len
    return 0  # recurrent kinds have fixed-size state


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               src_len: int = 0) -> Dict[str, Any]:
    """Allocate the decode cache pytree (shapes only depend on config).
    ``src_len``: encoder length for enc-dec archs — each decoder layer
    caches the precomputed cross-attention K/V."""
    dtype = _dtype(cfg.dtype)
    hd, hk = cfg.resolved_head_dim, cfg.n_kv_heads
    d = cfg.d_model

    def one(kind):
        if kind in ("attn", "attn_local"):
            s = _cache_size_for(kind, cfg, max_len)
            c = {"k": jnp.zeros((batch, s, hk, hd), dtype),
                 "v": jnp.zeros((batch, s, hk, hd), dtype)}
            if cfg.n_encoder_layers:
                c["cross_k"] = jnp.zeros((batch, src_len, hk, hd), dtype)
                c["cross_v"] = jnp.zeros((batch, src_len, hk, hd), dtype)
            return c
        if kind == "rglru":
            return {"h": jnp.zeros((batch, d)),
                    "conv": jnp.zeros((batch, cfg.rglru_conv_width - 1, d),
                                      dtype)}
        if kind == "mlstm":
            dm = int(cfg.mlstm_proj_factor * d)
            nh = cfg.n_heads
            dh = dm // nh
            return {"C": jnp.zeros((batch, nh, dh, dh)),
                    "n": jnp.zeros((batch, nh, dh)),
                    "m": jnp.full((batch, nh), -1e30),
                    "conv": jnp.zeros((batch, 3, dm), dtype)}
        if kind == "slstm":
            nh = cfg.n_heads
            dh = d // nh
            z = jnp.zeros((batch, nh, dh))
            return {"c": z, "n": jnp.ones((batch, nh, dh)),
                    "m": jnp.full((batch, nh, dh), -1e30), "h": z}
        raise ValueError(kind)

    def stacked(kind):
        c = one(kind)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape).copy(), c)

    return {
        "scan": [stacked(k) for k in cfg.layer_pattern] if cfg.n_periods else [],
        "rem": [one(k) for k in cfg.remainder_kinds],
    }


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill extends a position-indexed global-attention cache
    chunk by chunk. Ring-wrapping caches (attn_local windows,
    max_cache_len caps), recurrent state (whose prefill starts from the
    zero state, not a carried one), and encoder-decoder archs are served
    by the one-shot path instead."""
    return (all(k == "attn" for k in cfg.layer_kinds)
            and cfg.n_encoder_layers == 0 and not cfg.max_cache_len)


def kv_row_bytes(cfg: ModelConfig) -> int:
    """Bytes of global-attention K+V cached per token row — the paged
    pool's per-row footprint. Local-window and recurrent state are
    fixed-size per slot and excluded (they are identical between the
    paged and slotted layouts)."""
    n_global = sum(1 for k in cfg.layer_kinds if k == "attn")
    nbytes = 2 if cfg.dtype in ("bfloat16", "float16") else 4
    return n_global * 2 * cfg.n_kv_heads * cfg.resolved_head_dim * nbytes


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     max_slots: int, *, max_len: int,
                     src_len: int = 0) -> Dict[str, Any]:
    """Paged variant of ``init_cache``: global-attention K/V live in one
    shared pool of ``num_blocks`` blocks of ``block_size`` rows (leaf
    shape (N, bs, Hk, hd)) addressed through per-slot block tables,
    instead of a dense (max_slots, max_len, ...) buffer. Physical block 0
    is reserved as the null block. Everything that is fixed-size per
    sequence — local-attention windows, recurrent state, cross-attention
    K/V — stays slot-indexed exactly as in ``init_cache`` at ``max_len``,
    so only the layout of global-attention K/V changes (and the dense
    global buffers are never materialized)."""
    dtype = _dtype(cfg.dtype)
    hd, hk = cfg.resolved_head_dim, cfg.n_kv_heads
    proto = init_cache(cfg, 1, max_len, src_len=src_len)

    def widen(kind, one_slot, stacked: bool):
        """Re-batch a batch=1 cache dict: pool layout for global-attn K/V,
        max_slots batch for every other leaf."""
        def leaf(path_key, a):
            batch_axis = 1 if stacked else 0
            if kind == "attn" and path_key in ("k", "v"):
                shape = a.shape[:batch_axis] \
                    + (num_blocks, block_size) + a.shape[batch_axis + 2:]
                return jnp.zeros(shape, dtype)
            shape = a.shape[:batch_axis] + (max_slots,) \
                + a.shape[batch_axis + 1:]
            return jnp.broadcast_to(
                jnp.take(a, 0, axis=batch_axis)[
                    (slice(None),) * batch_axis + (None,)], shape).copy()
        return {key: leaf(key, a) for key, a in one_slot.items()}

    return {
        "scan": [widen(k, c, True) for k, c in zip(cfg.layer_pattern,
                                                   proto["scan"])],
        "rem": [widen(k, c, False) for k, c in zip(cfg.remainder_kinds,
                                                   proto["rem"])],
    }


def _layer_prefill(p, x, kind, cfg, *, positions, cache_size, enc_out,
                   ctx=None):
    if ctx is not None:
        p = ctx.layer(p)
    if kind in ("attn", "attn_local"):
        x, c = L.attn_prefill_cache(p["block"], x, cfg, kind=kind,
                                    positions=positions,
                                    cache_size=cache_size)
    elif kind == "rglru":
        x, c = R.rglru_prefill_cache(p["block"], x, cfg)
    elif kind == "mlstm":
        x, c = S.mlstm_prefill_cache(p["block"], x, cfg)
    elif kind == "slstm":
        x, c = S.slstm_prefill_cache(p["block"], x, cfg)
    if "cross" in p and enc_out is not None:
        x = L.cross_attn_apply(p["cross"], x, enc_out, cfg)
        # precompute + cache the cross-attention K/V so decode never
        # touches the encoder output again
        cp = p["cross"]
        ck = jnp.einsum("bsd,dhk->bshk", enc_out, cp["wk"])
        cv = jnp.einsum("bsd,dhk->bshk", enc_out, cp["wv"])
        if cfg.qkv_bias:
            ck, cv = ck + cp["bk"], cv + cp["bv"]
        c = {**c, "cross_k": ck.astype(x.dtype), "cross_v": cv.astype(x.dtype)}
    x, held = _serve_mlp(p, x, cfg, ctx)
    return x, c, held


def _serve_mlp(p, x, cfg, ctx=None, live=None):
    """The MLP sublayer of a serving program: experts route dropless
    (``moe_serve``), under a mesh with expert parallelism. Returns (x,
    the pairs per held expert of the ``live`` rows, or None)."""
    if "moe" in p:
        return M.moe_serve(p["moe"], x, cfg, ctx=ctx, live=live)
    if "mlp" in p:
        x = L.mlp_apply(p["mlp"], x, cfg)
    return x, None


def _routing(scan_held, rem_held, tokens, cfg: ModelConfig):
    """What a serving program reports of its expert routing: ``held``,
    the pairs routed to each held expert, (expert layers, E_held) int32
    in layer order, and ``routed``, every (token, choice) pair of every
    expert layer, held here or not. A decode step counts its live rows
    alone (``_live``); a prefill or chunk counts every row, padding
    included."""
    per = [h for h in scan_held if h is not None]
    parts = [jnp.stack(per, 1).reshape(-1, per[0].shape[-1])] if per else []
    parts += [h[None] for h in rem_held if h is not None]
    held = jnp.concatenate(parts)
    return {"held": held,
            "routed": jnp.asarray(tokens * cfg.moe.top_k * held.shape[0],
                                  jnp.int32)}


def prefill(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            max_len: int, ctx=None, moe_counts: bool = False):
    """Run the prompt through the model, materializing the decode cache.
    Returns (last-position logits (B, V), cache, cache_len (B,)), and
    with ``moe_counts`` the program's expert routing (``_routing``)
    last."""
    params = cast_params_for_compute(params, cfg)
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    enc_out = None
    if cfg.n_encoder_layers:
        enc_out = encode(params, cfg, embeds, ctx=ctx)
        x = _embed_inputs(params, cfg, tokens, None)
    else:
        x = _embed_inputs(params, cfg, tokens, embeds)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    period = cfg.layer_pattern
    cache: Dict[str, Any] = {"scan": [], "rem": []}

    scan_held, rem_held = [], []
    if cfg.n_periods:
        def body(x, slice_params):
            caches, helds = [], []
            for i, kind in enumerate(period):
                x, c, held = _layer_prefill(
                    slice_params[i], x, kind, cfg, positions=positions,
                    cache_size=_cache_size_for(kind, cfg, max_len),
                    enc_out=enc_out, ctx=ctx)
                caches.append(c)
                helds.append(held)
            if ctx is not None:
                x = ctx.act(x)
            return x, (caches, helds)
        x, (caches, scan_held) = jax.lax.scan(body, x, params["scan"])
        cache["scan"] = caches
    for i, kind in enumerate(cfg.remainder_kinds):
        x, c, held = _layer_prefill(
            params["rem"][i], x, kind, cfg, positions=positions,
            cache_size=_cache_size_for(kind, cfg, max_len),
            enc_out=enc_out, ctx=ctx)
        cache["rem"].append(c)
        rem_held.append(held)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(x[:, -1], params, cfg)
    out = (logits, cache, jnp.full((b,), s, jnp.int32))
    if moe_counts:
        out += (_routing(scan_held, rem_held, b * s, cfg),)
    return out


def _layer_decode(p, x, kind, cfg, *, cache, cache_len, enc_out,
                  tables=None, ctx=None):
    if ctx is not None:
        p = ctx.layer(p)
    if kind == "attn" and tables is not None:
        # paged layout: K/V in a shared block pool behind per-slot tables
        x, kp, vp = L.attn_decode_paged(p["block"], x, cfg, k_pool=cache["k"],
                                        v_pool=cache["v"], tables=tables,
                                        cache_len=cache_len)
        c = {**cache, "k": kp, "v": vp}
    elif kind in ("attn", "attn_local"):
        x, c = L.attn_decode(p["block"], x, cfg, kind=kind, cache=cache,
                             cache_len=cache_len)
    elif kind == "rglru":
        x, c = R.rglru_decode(p["block"], x, cfg, cache=cache)
    elif kind == "mlstm":
        x, c = S.mlstm_decode(p["block"], x, cfg, cache=cache)
    elif kind == "slstm":
        x, c = S.slstm_decode(p["block"], x, cfg, cache=cache)
    if "cross" in p and "cross_k" in cache:
        b = x.shape[0]
        ck, cv = cache["cross_k"], cache["cross_v"]
        h = L.rms_norm(x, p["cross"]["ln"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["wq"])
        if cfg.qkv_bias:
            q = q + p["cross"]["bq"]
        o = L.decode_attention_xla(
            q[:, 0], ck, cv, jnp.full((b,), ck.shape[1], jnp.int32))
        x = x + jnp.einsum("bhk,hkd->bd", o, p["cross"]["wo"])[:, None]
        c = {**c, "cross_k": ck, "cross_v": cv}
    x, held = _serve_mlp(p, x, cfg, ctx,
                         live=_live(cache_len) if "moe" in p else None)
    return x, c, held


def _live(cache_len: jax.Array) -> jax.Array:
    """(B, 1) rows of a decode step that hold a sequence: a free slot
    is kept at length 0 by the scheduler, a live one has its prompt."""
    return (cache_len > 0)[:, None]


def decode_step(params, cfg: ModelConfig, token: jax.Array,
                cache: Dict[str, Any], cache_len: jax.Array, *,
                block_tables: Optional[jax.Array] = None, ctx=None,
                moe_counts: bool = False):
    """One serving step: next-token logits for one new token per sequence.
    token: (B,) int32; cache_len: (B,) current context length.
    ``block_tables`` (B, nb) switches global-attention layers to the paged
    cache layout (``init_paged_cache``): they stream only the live blocks
    the tables name, while every slot-indexed leaf (local windows,
    recurrent state, cross K/V) behaves exactly as on the dense path.
    Returns (logits, cache, cache_len + 1), and with ``moe_counts`` the
    step's expert routing (``_routing``) last."""
    params = cast_params_for_compute(params, cfg)
    x = params["embed"].astype(_dtype(cfg.dtype))[token][:, None] \
        * math.sqrt(cfg.d_model)
    enc_out = None   # cross K/V live inside each layer's cache
    period = cfg.layer_pattern
    new_cache: Dict[str, Any] = {"scan": [], "rem": []}
    scan_held, rem_held = [], []

    if cfg.n_periods:
        def body(x, scanned):
            slice_params, slice_cache = scanned
            new_cs, helds = [], []
            for i, kind in enumerate(period):
                x, c, held = _layer_decode(
                    slice_params[i], x, kind, cfg, cache=slice_cache[i],
                    cache_len=cache_len, enc_out=enc_out,
                    tables=block_tables, ctx=ctx)
                new_cs.append(c)
                helds.append(held)
            return x, (new_cs, helds)
        x, (ncs, scan_held) = jax.lax.scan(body, x, (params["scan"],
                                                     cache["scan"]))
        new_cache["scan"] = ncs
    for i, kind in enumerate(cfg.remainder_kinds):
        x, c, held = _layer_decode(params["rem"][i], x, kind, cfg,
                                   cache=cache["rem"][i], cache_len=cache_len,
                                   enc_out=enc_out, tables=block_tables,
                                   ctx=ctx)
        new_cache["rem"].append(c)
        rem_held.append(held)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(x[:, 0], params, cfg)
    out = (logits, new_cache, cache_len + 1)
    if moe_counts:
        out += (_routing(scan_held, rem_held,
                                _live(cache_len).sum(dtype=jnp.int32), cfg),)
    return out


def decode_stage_bounds(cfg: ModelConfig, num_stages: int) -> list:
    """Contiguous near-even partition of the decode depth (scan periods
    first, then remainder layers) into ``num_stages`` groups: returns
    ``num_stages + 1`` monotone boundaries over
    ``n_periods + len(remainder_kinds)`` depth units. A stage may be
    empty when there are more stages than depth units."""
    total = cfg.n_periods + len(cfg.remainder_kinds)
    return [s * total // num_stages for s in range(num_stages + 1)]


def decode_step_staged(params, cfg: ModelConfig, token: jax.Array,
                       cache: Dict[str, Any], cache_len: jax.Array, *,
                       num_stages: int,
                       block_tables: Optional[jax.Array] = None, ctx=None,
                       moe_counts: bool = False):
    """``decode_step`` with the layer stack partitioned into
    ``num_stages`` contiguous depth groups (``decode_stage_bounds``) —
    the stage-partitioned step behind pipelined decode: the execution
    core models stage k of one microbatch overlapping stage k−1 of the
    next, and this function is the matching computation split. Each
    stage runs its slice of the scanned periods (``jax.lax.scan`` over a
    leading-axis slice of the stacked params/cache) and its remainder
    layers; the embed feeds the first stage and the head reads the last.
    The per-layer math is unchanged and runs in the same order on the
    same values, so logits and the reassembled cache are bit-identical
    to the unstaged step (tests/test_multi_unit.py pins this, and the
    conformance matrix pins greedy token identity end to end), and so
    is the expert routing ``moe_counts`` asks for."""
    if num_stages <= 1:
        return decode_step(params, cfg, token, cache, cache_len,
                           block_tables=block_tables, ctx=ctx,
                           moe_counts=moe_counts)
    params = cast_params_for_compute(params, cfg)
    x = params["embed"].astype(_dtype(cfg.dtype))[token][:, None] \
        * math.sqrt(cfg.d_model)
    enc_out = None
    period = cfg.layer_pattern
    n_scan = cfg.n_periods
    cuts = decode_stage_bounds(cfg, num_stages)

    def body(x, scanned):
        slice_params, slice_cache = scanned
        new_cs, helds = [], []
        for i, kind in enumerate(period):
            x, c, held = _layer_decode(
                slice_params[i], x, kind, cfg, cache=slice_cache[i],
                cache_len=cache_len, enc_out=enc_out, tables=block_tables,
                ctx=ctx)
            new_cs.append(c)
            helds.append(held)
        return x, (new_cs, helds)

    scan_parts, held_parts = [], []
    new_rem, rem_held = [], []
    for s in range(num_stages):
        lo, hi = cuts[s], cuts[s + 1]
        slo, shi = min(lo, n_scan), min(hi, n_scan)
        if shi > slo:
            part = (jax.tree.map(lambda a: a[slo:shi], params["scan"]),
                    jax.tree.map(lambda a: a[slo:shi], cache["scan"]))
            x, (ncs, helds) = jax.lax.scan(body, x, part)
            scan_parts.append(ncs)
            held_parts.append(helds)
        for i in range(max(lo - n_scan, 0), max(hi - n_scan, 0)):
            x, c, held = _layer_decode(
                params["rem"][i], x, cfg.remainder_kinds[i], cfg,
                cache=cache["rem"][i], cache_len=cache_len, enc_out=enc_out,
                tables=block_tables, ctx=ctx)
            new_rem.append(c)
            rem_held.append(held)
    new_cache: Dict[str, Any] = {"scan": [], "rem": new_rem}
    concat = partial(jax.tree.map, lambda *xs: jnp.concatenate(xs, axis=0))
    scan_held = held_parts[0] if len(held_parts) == 1 else \
        concat(*held_parts) if held_parts else []
    if len(scan_parts) == 1:
        new_cache["scan"] = scan_parts[0]
    elif scan_parts:
        new_cache["scan"] = concat(*scan_parts)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(x[:, 0], params, cfg)
    out = (logits, new_cache, cache_len + 1)
    if moe_counts:
        out += (_routing(scan_held, rem_held,
                                _live(cache_len).sum(dtype=jnp.int32), cfg),)
    return out


def prefill_extend(params, cfg: ModelConfig, tokens: jax.Array,
                   cache: Dict[str, Any], cache_len: jax.Array, *, ctx=None,
                   moe_counts: bool = False):
    """Chunked prefill: run a (B, C) token chunk through the model against
    an existing decode cache, appending its K/V rows at positions
    [cache_len, cache_len + C). Compiled once per chunk shape, so a long
    prompt is admitted as a sequence of identical extend steps interleaved
    with decode steps instead of one monolithic prefill.

    Requires ``supports_chunked_prefill(cfg)``. Returns per-position
    logits (B, C, V) — the caller samples at the last *real* (unpadded)
    position — plus the updated cache and cache_len + C, and with
    ``moe_counts`` the chunk's expert routing (``_routing``) last."""
    assert supports_chunked_prefill(cfg), cfg.name
    params = cast_params_for_compute(params, cfg)
    x = _embed_inputs(params, cfg, tokens, None)
    b, c = x.shape[:2]
    period = cfg.layer_pattern
    new_cache: Dict[str, Any] = {"scan": [], "rem": []}

    def layer(p, x, kind, lc):
        if ctx is not None:
            p = ctx.layer(p)
        x, nc = L.attn_extend(p["block"], x, cfg, kind=kind, cache=lc,
                              cache_len=cache_len)
        x, held = _serve_mlp(p, x, cfg, ctx)
        return x, nc, held

    scan_held, rem_held = [], []
    if cfg.n_periods:
        def body(x, scanned):
            slice_params, slice_cache = scanned
            ncs, helds = [], []
            for i, kind in enumerate(period):
                x, nc, held = layer(slice_params[i], x, kind, slice_cache[i])
                ncs.append(nc)
                helds.append(held)
            return x, (ncs, helds)
        x, (ncs, scan_held) = jax.lax.scan(body, x, (params["scan"],
                                                     cache["scan"]))
        new_cache["scan"] = ncs
    for i, kind in enumerate(cfg.remainder_kinds):
        x, nc, held = layer(params["rem"][i], x, kind, cache["rem"][i])
        new_cache["rem"].append(nc)
        rem_held.append(held)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    out = (_head_logits(x, params, cfg), new_cache, cache_len + c)
    if moe_counts:
        out += (_routing(scan_held, rem_held, b * c, cfg),)
    return out


def paged_insert(cfg: ModelConfig, cache: Dict[str, Any],
                 req_cache: Dict[str, Any], block_ids: jax.Array,
                 slot: jax.Array, *, block_size: int) -> Dict[str, Any]:
    """Write a batch=1 prefill cache into the paged cache: global-attn K/V
    rows are scattered page-wise into the physical blocks named by
    ``block_ids`` (one per logical page; 0 = null-block padding for pages
    past the allocation), every other leaf is written at ``slot`` exactly
    like the slotted insert. ``req_cache`` may be longer than the slot's
    page span (e.g. a chunk-rounded scratch cache) — extra rows are
    dropped; they are beyond ``max_len`` and never valid."""
    pages = block_ids.shape[0]
    sg = pages * block_size

    def ins_pool(pool, small, stacked):
        if stacked:  # (P, N, bs, hk, hd) <- (P, 1, S, hk, hd)
            rows = small[:, 0, :sg]
            blocks = rows.reshape(rows.shape[0], pages, block_size,
                                  *rows.shape[2:])
            return pool.at[:, block_ids].set(blocks)
        rows = small[0, :sg]
        blocks = rows.reshape(pages, block_size, *rows.shape[1:])
        return pool.at[block_ids].set(blocks)

    def ins_slot(big, small, stacked):
        if stacked:
            return big.at[:, slot].set(small[:, 0])
        return big.at[slot].set(small[0])

    def one(kind, c, r, stacked):
        if kind != "attn":
            return jax.tree.map(
                lambda big, small: ins_slot(big, small, stacked), c, r)
        out = {}
        for key in c:
            ins = ins_pool if key in ("k", "v") else ins_slot
            out[key] = ins(c[key], r[key], stacked)
        return out

    return {
        "scan": [one(k, c, r, True) for k, c, r in
                 zip(cfg.layer_pattern, cache["scan"], req_cache["scan"])],
        "rem": [one(k, c, r, False) for k, c, r in
                zip(cfg.remainder_kinds, cache["rem"], req_cache["rem"])],
    }


def paged_seed(cfg: ModelConfig, scratch: Dict[str, Any],
               cache: Dict[str, Any], block_ids: jax.Array
               ) -> Dict[str, Any]:
    """Inverse of ``paged_insert`` for a shared prompt prefix: gather the
    global-attention K/V rows of the pool blocks named by ``block_ids``
    (one per logical page, in page order) into the head of a batch=1
    dense scratch cache, so ``prefill_extend`` can resume mid-prompt
    against them. Whole pages are copied; rows past the true match in
    the last page are either recomputed by the extend or sit beyond the
    prompt where causal masking never reads them. Only used for
    ``supports_chunked_prefill`` configs, whose every cache leaf is
    global-attention K/V."""
    pages = block_ids.shape[0]

    def one(kind, sc, c, stacked):
        if kind != "attn":
            return sc
        out = dict(sc)
        for key in ("k", "v"):
            pool, s = c[key], sc[key]
            if stacked:     # pool (P, N, bs, hk, hd) -> scratch (P, 1, S, ...)
                rows = pool[:, block_ids]
                rows = rows.reshape(rows.shape[0],
                                    pages * pool.shape[2], *rows.shape[3:])
                out[key] = s.at[:, 0, :rows.shape[1]].set(rows)
            else:           # pool (N, bs, hk, hd) -> scratch (1, S, ...)
                rows = pool[block_ids].reshape(pages * pool.shape[1],
                                               *pool.shape[2:])
                out[key] = s.at[0, :rows.shape[0]].set(rows)
        return out

    return {
        "scan": [one(k, sc, c, True) for k, sc, c in
                 zip(cfg.layer_pattern, scratch["scan"], cache["scan"])],
        "rem": [one(k, sc, c, False) for k, sc, c in
                zip(cfg.remainder_kinds, scratch["rem"], cache["rem"])],
    }


def paged_copy_block(cfg: ModelConfig, cache: Dict[str, Any],
                     src: jax.Array, dst: jax.Array) -> Dict[str, Any]:
    """Copy one physical block's global-attention K/V rows to another —
    the device half of copy-on-write, giving a writer a private copy of
    a block whose other references must keep reading the original."""
    def one(kind, c, stacked):
        if kind != "attn":
            return c
        out = dict(c)
        for key in ("k", "v"):
            pool = c[key]
            if stacked:     # (P, N, bs, hk, hd)
                out[key] = pool.at[:, dst].set(pool[:, src])
            else:           # (N, bs, hk, hd)
                out[key] = pool.at[dst].set(pool[src])
        return out

    return {
        "scan": [one(k, c, True) for k, c in
                 zip(cfg.layer_pattern, cache["scan"])],
        "rem": [one(k, c, False) for k, c in
                zip(cfg.remainder_kinds, cache["rem"])],
    }


# ---------------------------------------------------------------------------
# VR-PRUNE actor-graph export (the Edge-PRUNE integration)
# ---------------------------------------------------------------------------

def to_actor_graph(cfg: ModelConfig, params: Optional[Dict[str, Any]] = None,
                   *, batch: int = 1, seq: int = 8,
                   group_size: int = 1):
    """Export the model as a VR-PRUNE dataflow graph: one actor per group
    of ``group_size`` layers (plus Input / Embed / Head actors), each edge
    annotated with its real token size — exactly how the paper expresses
    SSD-Mobilenet as 53 actors. When ``params`` is given the actors carry
    real fire functions, so the Simulator/Explorer can execute and
    partition the actual model (see examples/distributed_serving.py)."""
    from repro.core.graph import Actor, ActorType, Graph, Port, PortDir

    g = Graph(cfg.name)
    d = cfg.d_model
    act_bytes = 2 if cfg.dtype == "bfloat16" else 4
    tok_shape = (batch, seq, d)
    hd = cfg.resolved_head_dim
    qkv_out = (cfg.n_heads + 2 * cfg.n_kv_heads) * hd

    def block_flops(kind):
        f = 0.0
        if kind in ("attn", "attn_local"):
            ctx = min(seq, cfg.window) if kind == "attn_local" else seq
            f = 2.0 * seq * d * (qkv_out + cfg.n_heads * hd) \
                + 4.0 * seq * ctx * cfg.n_heads * hd
        elif kind == "rglru":
            f = 2.0 * seq * d * (2 * d + 2 * d + d) + 10.0 * seq * d
        elif kind == "mlstm":
            dm = int(cfg.mlstm_proj_factor * d)
            f = 2.0 * seq * d * 2 * dm + 2.0 * seq * dm * d \
                + 4.0 * seq * min(seq, 256) * dm
        elif kind == "slstm":
            ds = int(cfg.slstm_proj_factor * d)
            f = 2.0 * seq * d * (4 * d + 2 * ds) + 2.0 * seq * 4 * d * (d // max(cfg.n_heads, 1))
        if kind in _HAS_MLP:
            if cfg.moe:
                f += 2.0 * seq * d * 3 * cfg.moe.d_ff_expert \
                    * (cfg.moe.top_k + cfg.moe.n_shared_experts)
            else:
                f += 2.0 * seq * d * 3 * cfg.d_ff
        return batch * f

    kinds = cfg.layer_kinds
    groups = [list(range(i, min(i + group_size, len(kinds))))
              for i in range(0, len(kinds), group_size)]

    def flat_layer_params(idx):
        if params is None:
            return None
        period = len(cfg.layer_pattern)
        if idx < cfg.n_periods * period:
            pos, rep = idx % period, idx // period
            return jax.tree.map(lambda a: a[rep], params["scan"][pos])
        return params["rem"][idx - cfg.n_periods * period]

    # Input -> Embed -> LayerGroup_i ... -> Head
    inp = Actor("Input", ActorType.SPA,
                [], [Port("out", PortDir.OUT, token_shape=(batch, seq),
                          token_dtype="int32")],
                fire_fn=lambda inputs, st, atr: (
                    {"out": [inputs["__feed__"][0]]}, st))
    g.add_actor(inp)

    def embed_fire(inputs, st, atr):
        (tok,) = inputs["in"]
        x = _embed_inputs(params, cfg, tok, None)
        return {"out": [x]}, st

    emb = Actor("Embed", ActorType.SPA,
                [Port("in", PortDir.IN, token_shape=(batch, seq),
                      token_dtype="int32")],
                [Port("out", PortDir.OUT, token_shape=tok_shape,
                      token_dtype=cfg.dtype)],
                fire_fn=embed_fire if params is not None else None,
                cost_flops=2.0 * batch * seq * d)
    g.add_actor(emb)
    g.connect(inp.port("out"), emb.port("in"))

    prev = emb
    for gi, idxs in enumerate(groups):
        def make_fire(idxs):
            def fire(inputs, st, atr):
                (x,) = inputs["in"]
                positions = jnp.broadcast_to(
                    jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])
                for li in idxs:
                    x, _ = _layer_apply(flat_layer_params(li), x, kinds[li],
                                        cfg, positions=positions)
                return {"out": [x]}, st
            return fire

        a = Actor(f"Layers{idxs[0]}-{idxs[-1]}", ActorType.SPA,
                  [Port("in", PortDir.IN, token_shape=tok_shape,
                        token_dtype=cfg.dtype)],
                  [Port("out", PortDir.OUT, token_shape=tok_shape,
                        token_dtype=cfg.dtype)],
                  fire_fn=make_fire(idxs) if params is not None else None,
                  cost_flops=sum(block_flops(kinds[li]) for li in idxs),
                  meta={"layers": idxs})
        g.add_actor(a)
        g.connect(prev.port("out"), a.port("in"))
        prev = a

    def head_fire(inputs, st, atr):
        (x,) = inputs["in"]
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = (jnp.einsum("bsd,vd->bsv", x, head.astype(x.dtype))
                  if cfg.tie_embeddings else x @ head.astype(x.dtype))
        return {"result": logits}, st

    head = Actor("Head", ActorType.SPA,
                 [Port("in", PortDir.IN, token_shape=tok_shape,
                       token_dtype=cfg.dtype)], [],
                 fire_fn=head_fire if params is not None else None,
                 cost_flops=2.0 * batch * seq * d * cfg.vocab_size)
    g.add_actor(head)
    g.connect(prev.port("out"), head.port("in"))
    return g
