"""Shared transformer building blocks: norms, RoPE, GQA attention, MLPs.

Attention comes in three interchangeable implementations:

* ``ref`` — naive full-matrix softmax attention (``kernels.flash_attention.ref``),
  the oracle for tests; O(S^2) memory, never used in the compiled path.
* ``xla`` — double-blocked online-softmax attention built from
  ``jax.lax.scan`` (this module): O(S * chunk) live memory, the production
  path on CPU and the dry-run path (XLA fuses the scan body). Supports
  causal and sliding-window masking, GQA, and per-call positions.
* ``pallas`` — the TPU kernel (``kernels.flash_attention``), same tiling
  expressed with explicit BlockSpecs; validated against ``ref`` in
  interpret mode.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with fp32 accumulation but ELEMENTWISE math in x.dtype.

    The variance reduction upcasts per-element inside the fused reduction
    only; the (B, S, D) tensor itself never exists in fp32. This matters
    under SPMD: with the residual stream sharded on the feature dim, a
    leading x.astype(f32) makes the partitioner place the model-axis
    all-gather on the fp32 tensor — 2x the bytes of the bf16 gather, and
    the single largest remaining collective in the MoE train_4k baseline
    (9.7 GB x 9 per layer; EXPERIMENTS.md §Perf iteration 2)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + scale).astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True),
            "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, rot_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the first ``rot_dim`` dims of a head.
    ``rot_dim < head_dim`` implements partial rotary (ChatGLM applies RoPE
    to half of each head — its '2d' position encoding keeps the other half
    position-free)."""
    return 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rope_fraction: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32. Rotates the first
    ``rope_fraction * D`` dims pairwise (non-interleaved / NeoX style)."""
    b, s, h, d = x.shape
    rot = int(d * rope_fraction)
    rot -= rot % 2
    inv = rope_freqs(d, rot, theta)                       # (rot/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# blocked online-softmax attention (the "xla" implementation)
# ---------------------------------------------------------------------------

def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _fa_mask(qp, kp, kv_len, causal, window, sk_valid=None):
    """Attention mask for one (q-block, k-block) tile.

    Static path (kv_len is None, qp is (bq,)): returns (bq, bk) — batch-
    independent, so XLA hoists a few-MB predicate instead of materializing
    a (B, bq, bk) tensor per tile (which shows up as multi-GB pred buffers
    in the train dry-run). ``sk_valid`` (static int) masks the zero-padded
    kv tail — without it, non-causal (cross-)attention attends to padding.
    Ragged path (kv_len (B,), qp (B, bq)): (B, bq, bk).
    """
    if kv_len is None:
        mask = jnp.ones((qp.shape[-1], kp.shape[0]), bool)
        if sk_valid is not None:
            mask &= kp[None, :] < sk_valid
        if causal:
            mask &= qp[:, None] >= kp[None, :]
        if window > 0:
            mask &= qp[:, None] - kp[None, :] < window
        return mask                                    # (bq, bk)
    mask = kp[None, None, :] < kv_len[:, None, None]
    if causal:
        mask &= qp[:, :, None] >= kp[None, None, :]
    if window > 0:
        mask &= qp[:, :, None] - kp[None, None, :] < window
    return mask                                        # (B, bq, bk)


def _apply_mask(s, mask):
    """s: (B,Hk,g,bq,bk); mask: (bq,bk) or (B,bq,bk)."""
    if mask.ndim == 2:
        return jnp.where(mask, s, -1e30)
    return jnp.where(mask[:, None, None], s, -1e30)


def _fa_forward(q, k, v, q_offset, kv_len, causal, window, chunk, scale):
    """Returns (out (B,Sq,H,D), lse (B,Hk,g,Sq)) — blocked online softmax."""
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    g = h // hk
    bq = min(chunk, _ceil_to(sq, 8))
    bk = min(chunk, _ceil_to(sk, 8))
    nq, nk = -(-sq // bq), -(-sk // bk)
    pq, pk = nq * bq - sq, nk * bk - sk
    qf = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kf = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vf = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    qf = qf.reshape(b, nq, bq, hk, g, d)
    kf = kf.reshape(b, nk, bk, hk, d)
    vf = vf.reshape(b, nk, bk, hk, d)
    if q_offset is None:
        q_pos = jnp.arange(nq * bq).reshape(nq, bq)              # (nq, bq)
    else:
        q_pos = (q_offset[:, None]
                 + jnp.arange(nq * bq)[None]).reshape(b, nq, bq) \
            .transpose(1, 0, 2)                                  # (nq, B, bq)
    k_pos = jnp.arange(nk * bk).reshape(nk, bk)

    def q_block(args):
        qb, qp = args                                # (B,bq,Hk,g,D), (B,bq)

        def kv_step(carry, kv):
            m, l, acc = carry
            kb, vb, kp = kv                          # (B,bk,Hk,D), ..., (bk,)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            mask = _fa_mask(qp, kp, kv_len, causal, window, sk_valid=sk)
            s = _apply_mask(s, mask)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((b, hk, g, bq), -1e30)
        l0 = jnp.zeros((b, hk, g, bq))
        a0 = jnp.zeros((b, hk, g, bq, d))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kf.transpose(1, 0, 2, 3, 4), vf.transpose(1, 0, 2, 3, 4), k_pos))
        out = acc / jnp.maximum(l[..., None], 1e-30)  # (B,Hk,g,bq,D)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))      # (B,Hk,g,bq)
        return out.transpose(0, 3, 1, 2, 4), lse

    outs, lses = jax.lax.map(q_block, (qf.transpose(1, 0, 2, 3, 4, 5),
                                       q_pos))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * bq, h, d)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(b, hk, g, nq * bq)
    return out[:, :sq].astype(q.dtype), lse[..., :sq]


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _fa(q, k, v, q_offset, kv_len, causal, window, chunk, scale):
    out, _ = _fa_forward(q, k, v, q_offset, kv_len, causal, window, chunk,
                         scale)
    return out


def _fa_fwd(q, k, v, q_offset, kv_len, causal, window, chunk, scale):
    out, lse = _fa_forward(q, k, v, q_offset, kv_len, causal, window, chunk,
                           scale)
    return out, (q, k, v, q_offset, kv_len, out, lse)


def _fa_bwd(causal, window, chunk, scale, res, dout):
    """True flash-attention backward: recompute P blockwise from (q,k,lse);
    O(bq*bk) live memory, no stacked residuals — this is what keeps the
    train_4k dry-run's temp footprint bounded."""
    q, k, v, q_offset, kv_len, out, lse = res
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    g = h // hk
    bq = min(chunk, _ceil_to(sq, 8))
    bk = min(chunk, _ceil_to(sk, 8))
    nq, nk = -(-sq // bq), -(-sk // bk)
    pq, pk = nq * bq - sq, nk * bk - sk

    def padq(x):
        return jnp.pad(x, ((0, 0), (0, pq)) + ((0, 0),) * (x.ndim - 2)) \
            if pq else x

    def padk(x):
        return jnp.pad(x, ((0, 0), (0, pk)) + ((0, 0),) * (x.ndim - 2)) \
            if pk else x

    qf = padq(q).reshape(b, nq, bq, hk, g, d)
    dof = padq(dout.astype(jnp.float32)).reshape(b, nq, bq, hk, g, d)
    # delta = rowsum(dout * out)  (B,Hk,g,Sq)
    delta = jnp.einsum("bshd,bshd->bhs", dout.astype(jnp.float32),
                       out.astype(jnp.float32))
    delta = padq(delta.transpose(0, 2, 1)).transpose(0, 2, 1) \
        .reshape(b, hk, g, nq, bq)
    lsef = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, pq))) if pq else lse
    lsef = lsef.reshape(b, hk, g, nq, bq)
    kf = padk(k).reshape(b, nk, bk, hk, d)
    vf = padk(v).reshape(b, nk, bk, hk, d)
    if q_offset is None:
        q_pos = jnp.arange(nq * bq).reshape(nq, bq)
    else:
        q_pos = (q_offset[:, None]
                 + jnp.arange(nq * bq)[None]).reshape(b, nq, bq) \
            .transpose(1, 0, 2)
    k_pos = jnp.arange(nk * bk).reshape(nk, bk)

    def q_block(carry, xs):
        dk, dv = carry                       # (B,nk*bk,Hk,D) f32 accumulators
        qb, do, dl, ls, qp = xs

        def kv_step(dq, kv):
            kb, vb, kp, ki = kv
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            mask = _fa_mask(qp, kp, kv_len, causal, window, sk_valid=sk)
            s = _apply_mask(s, mask)
            p = jnp.exp(s - ls[..., None])                  # (B,Hk,g,bq,bk)
            dv_blk = jnp.einsum("bhgqk,bqhgd->bkhd", p, do)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", do, vb.astype(jnp.float32))
            ds = p * (dp - dl[..., None]) * scale
            dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                                 kb.astype(jnp.float32))
            dk_blk = jnp.einsum("bhgqk,bqhgd->bkhd", ds,
                                qb.astype(jnp.float32))
            return dq, (dk_blk, dv_blk, ki)

        dq0 = jnp.zeros((b, bq, hk, g, d))
        dq, (dk_blks, dv_blks, _) = jax.lax.scan(
            kv_step, dq0,
            (kf.transpose(1, 0, 2, 3, 4), vf.transpose(1, 0, 2, 3, 4),
             k_pos, jnp.arange(nk)))
        dk = dk + dk_blks.transpose(1, 0, 2, 3, 4).reshape(b, nk * bk, hk, d)
        dv = dv + dv_blks.transpose(1, 0, 2, 3, 4).reshape(b, nk * bk, hk, d)
        return (dk, dv), dq

    dk0 = jnp.zeros((b, nk * bk, hk, d))
    dv0 = jnp.zeros((b, nk * bk, hk, d))
    (dk, dv), dqs = jax.lax.scan(
        q_block, (dk0, dv0),
        (qf.transpose(1, 0, 2, 3, 4, 5), dof.transpose(1, 0, 2, 3, 4, 5),
         delta.transpose(3, 0, 1, 2, 4), lsef.transpose(3, 0, 1, 2, 4),
         q_pos))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * bq, h, d)
    return (dq[:, :sq].astype(q.dtype), dk[:, :sk].astype(k.dtype),
            dv[:, :sk].astype(v.dtype), None, None)


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        q_offset: Optional[jax.Array] = None,
                        kv_len: Optional[jax.Array] = None,
                        chunk: int = 1024, scale: Optional[float] = None
                        ) -> jax.Array:
    """Blocked online-softmax attention with a flash backward (custom VJP):
    O(bq*bk) live score memory in BOTH passes. GQA: q has H heads, k/v have
    Hk | H heads.

    q: (B, Sq, H, D); k, v: (B, Sk, Hk, D).
    ``q_offset``: (B,) absolute position of q[0] within the kv sequence
    (prefill: 0; decode: cache length). ``kv_len``: (B,) valid kv prefix
    length (entries beyond it are masked; enables ragged batches).
    ``window > 0``: sliding-window mask (position distance < window).
    """
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    assert h % hk == 0, (h, hk)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # q_offset / kv_len stay None on the static (train/prefill) path so
    # masks are batch-free (see _fa_mask); they are (B,) arrays only for
    # ragged/offset batches.
    return _fa(q, k, v, q_offset, kv_len, causal, window, chunk, scale)


def decode_attention_xla(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len: jax.Array, *, window: int = 0,
                         scale: Optional[float] = None) -> jax.Array:
    """Single-token GQA attention against a KV cache.

    q: (B, H, D); caches: (B, S, Hk, D); cache_len: (B,) number of valid
    entries INCLUDING the current token (already written at cache_len-1).
    """
    b, h, d = q.shape
    _, s, hk, _ = k_cache.shape
    g = h // hk
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, hk, g, d).astype(jnp.float32)
    kc = k_cache.astype(jnp.float32)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, kc) * scale
    pos = jnp.arange(s)[None]                       # (1, S)
    mask = pos < cache_len[:, None]
    if window > 0:
        mask &= pos >= cache_len[:, None] - window
    logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# parameter init helpers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_shape: Tuple[int, ...], dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim,) + out_shape, jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# attention block (init + apply)
# ---------------------------------------------------------------------------

def attn_init(key, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, hk = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 5)
    p = {
        "wq": dense_init(ks[0], d, (h, hd), dtype),
        "wk": dense_init(ks[1], d, (hk, hd), dtype),
        "wv": dense_init(ks[2], d, (hk, hd), dtype),
        "wo": dense_init(ks[3], h * hd, (d,), dtype).reshape(h, hd, d),
        "ln": jnp.zeros((d,), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((hk, hd), dtype)
        p["bv"] = jnp.zeros((hk, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _qkv(p, x, cfg):
    """q, k, v of the normed input; with ``qk_norm`` q and k are each
    RMS-normed over every head's dims (before the rotary embedding)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _theta_for(cfg, kind: str) -> float:
    if kind == "attn" and cfg.rope_theta_global is not None:
        return cfg.rope_theta_global
    return cfg.rope_theta


def attn_apply(p, x, cfg, *, kind: str, positions, mask_len=None) -> jax.Array:
    """Full-sequence (train/prefill) attention sublayer with residual."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _qkv(p, h, cfg)
    theta = _theta_for(cfg, kind)
    q = apply_rope(q, positions, theta, cfg.rope_fraction)
    k = apply_rope(k, positions, theta, cfg.rope_fraction)
    window = cfg.window if kind == "attn_local" else 0
    if cfg.attn_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        o = fa_ops.flash_attention(q, k, v, causal=True, window=window)
    else:
        o = flash_attention_xla(q, k, v, causal=True, window=window,
                                kv_len=mask_len, chunk=cfg.attn_chunk)
    return x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def attn_decode(p, x, cfg, *, kind: str, cache, cache_len) -> Tuple[jax.Array, dict]:
    """One-token decode. x: (B, 1, D). cache: {"k","v"}: (B, S_cache, Hk, hd).
    Ring-buffer semantics: the new KV is written at ``cache_len % S_cache``.
    Local-attention layers allocate ``S_cache == window`` so the buffer IS
    the sliding window (what bounds long_500k memory); global layers
    allocate the full context so the modulo is a no-op. RoPE is applied to
    keys before caching with absolute positions, which is sound because
    rotary encoding is relative."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    positions = cache_len[:, None]                        # (B, 1)
    q, k, v = _qkv(p, h, cfg)
    theta = _theta_for(cfg, kind)
    q = apply_rope(q, positions, theta, cfg.rope_fraction)
    k = apply_rope(k, positions, theta, cfg.rope_fraction)
    size = cache["k"].shape[1]
    idx = cache_len % size
    kc = jax.vmap(lambda c, kn, i: jax.lax.dynamic_update_slice_in_dim(
        c, kn, i, axis=0))(cache["k"], k, idx)
    vc = jax.vmap(lambda c, vn, i: jax.lax.dynamic_update_slice_in_dim(
        c, vn, i, axis=0))(cache["v"], v, idx)
    valid = jnp.minimum(cache_len + 1, size)
    if cfg.attn_impl == "pallas":
        from repro.kernels.decode_attention import ops as da_ops
        o = da_ops.decode_attention(q[:, 0], kc, vc, valid)
    else:
        o = decode_attention_xla(q[:, 0], kc, vc, valid)
    out = x + jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    return out, {"k": kc, "v": vc}


def attn_extend(p, x, cfg, *, kind: str, cache, cache_len
                ) -> Tuple[jax.Array, dict]:
    """Chunked-prefill extension: a (B, C) token chunk attends over the
    existing cache plus itself (causal within the chunk), and its K/V rows
    are appended at absolute positions [cache_len, cache_len + C).

    Global-attention only: the cache is position-indexed (no ring
    wrapping), which ``supports_chunked_prefill`` guarantees. The caller
    may pad the chunk past the real prompt — padded q rows sit at later
    positions, so causal masking keeps every real row's attention (and
    hence the emitted first token) independent of the padding."""
    assert kind == "attn", "chunked prefill pages global attention only"
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    c = x.shape[1]
    positions = cache_len[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
    q, k, v = _qkv(p, h, cfg)
    theta = _theta_for(cfg, kind)
    q = apply_rope(q, positions, theta, cfg.rope_fraction)
    k = apply_rope(k, positions, theta, cfg.rope_fraction)
    kc = jax.vmap(lambda cc, kn, i: jax.lax.dynamic_update_slice_in_dim(
        cc, kn, i, axis=0))(cache["k"], k, cache_len)
    vc = jax.vmap(lambda cc, vn, i: jax.lax.dynamic_update_slice_in_dim(
        cc, vn, i, axis=0))(cache["v"], v, cache_len)
    o = flash_attention_xla(q, kc, vc, causal=True, q_offset=cache_len,
                            kv_len=cache_len + c, chunk=cfg.attn_chunk)
    out = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, {"k": kc, "v": vc}


def paged_gather(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Materialize per-sequence contiguous KV views from a block pool.
    pool: (N, bs, Hk, D); tables: (B, nb) physical block per logical page.
    Returns (B, nb * bs, Hk, D) — row i of the result is the row that a
    slotted cache would hold at position i, so downstream attention (and
    its masking) is unchanged."""
    b, nb = tables.shape
    _, bs, hk, d = pool.shape
    return pool[tables].reshape(b, nb * bs, hk, d)


def _paged_attention_xla(q, k_pool, v_pool, tables, lengths):
    return decode_attention_xla(q, paged_gather(k_pool, tables),
                                paged_gather(v_pool, tables), lengths)


def paged_attention(q, k_pool, v_pool, tables, lengths):
    """Decode attention of q (B, H, D) over the pages ``tables`` names.
    A program lowered for a TPU runs the paged Pallas kernel, which
    streams each slot's live pages alone; every other platform gathers
    the whole table with XLA."""
    from repro.kernels.decode_attention.kernel import (
        paged_decode_attention_pallas)
    return jax.lax.platform_dependent(
        q, k_pool, v_pool, tables, lengths,
        tpu=paged_decode_attention_pallas, default=_paged_attention_xla)


def attn_decode_paged(p, x, cfg, *, k_pool, v_pool, tables, cache_len
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a paged KV pool (global attention).

    x: (B, 1, D); pools: (N, bs, Hk, hd); tables: (B, nb) with physical
    block 0 reserved as the null block — free slots and unallocated pages
    point there, so their (masked, never-read) writes collide harmlessly.
    The new K/V row is scattered into block ``tables[b, cache_len // bs]``
    at offset ``cache_len % bs`` (the scheduler allocates that block
    before the step), then attention reads the slot's pages
    (``paged_attention``). Returns (out, new_k_pool, new_v_pool)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    positions = cache_len[:, None]
    q, k, v = _qkv(p, h, cfg)
    theta = _theta_for(cfg, "attn")
    q = apply_rope(q, positions, theta, cfg.rope_fraction)
    k = apply_rope(k, positions, theta, cfg.rope_fraction)
    bs = k_pool.shape[1]
    blk = jnp.take_along_axis(tables, (cache_len // bs)[:, None], axis=1)[:, 0]
    off = cache_len % bs
    kp = k_pool.at[blk, off].set(k[:, 0])
    vp = v_pool.at[blk, off].set(v[:, 0])
    o = paged_attention(q[:, 0], kp, vp, tables, cache_len + 1)
    out = x + jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    return out, kp, vp


def attn_prefill_cache(p, x, cfg, *, kind: str, positions, cache_size: int
                       ) -> Tuple[jax.Array, dict]:
    """Full-sequence prefill that also materializes the decode cache.
    Returns (residual output, cache dict). The cache keeps the LAST
    ``cache_size`` positions in ring order (slot = position % cache_size),
    matching ``attn_decode``'s write rule."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _qkv(p, h, cfg)
    theta = _theta_for(cfg, kind)
    q = apply_rope(q, positions, theta, cfg.rope_fraction)
    k = apply_rope(k, positions, theta, cfg.rope_fraction)
    window = cfg.window if kind == "attn_local" else 0
    o = flash_attention_xla(q, k, v, causal=True, window=window,
                            chunk=cfg.attn_chunk)
    out = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    s = x.shape[1]
    if cache_size >= s:
        pad = cache_size - s
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        # last cache_size tokens, placed at their ring slots
        tail_k = k[:, s - cache_size:]
        tail_v = v[:, s - cache_size:]
        slots = (jnp.arange(s - cache_size, s) % cache_size)
        kc = jnp.zeros_like(tail_k).at[:, slots].set(tail_k)
        vc = jnp.zeros_like(tail_v).at[:, slots].set(tail_v)
    return out, {"k": kc, "v": vc}


def attn_encoder_apply(p, x, cfg, *, positions) -> jax.Array:
    """Bidirectional (encoder) self-attention sublayer."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _qkv(p, h, cfg)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    o = flash_attention_xla(q, k, v, causal=False, chunk=cfg.attn_chunk)
    return x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def cross_attn_init(key, cfg, dtype) -> dict:
    return attn_init(key, cfg, dtype)


def cross_attn_apply(p, x, enc_out, cfg) -> jax.Array:
    """Decoder cross-attention: queries from x, K/V from encoder output."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    o = flash_attention_xla(q, k, v, causal=False, chunk=cfg.attn_chunk)
    return x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d_model, (d_ff,), dtype),
        "w_up": dense_init(ks[1], d_model, (d_ff,), dtype),
        "w_down": dense_init(ks[2], d_ff, (d_model,), dtype),
        "ln": jnp.zeros((d_model,), dtype),
    }


def mlp_apply(p, x, cfg) -> jax.Array:
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    g = act_fn(cfg.act)(h @ p["w_gate"]) * (h @ p["w_up"])
    return x + g @ p["w_down"]
