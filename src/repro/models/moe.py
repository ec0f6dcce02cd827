"""Mixture-of-Experts MLP: token-choice top-k routing.

Covers qwen2-moe (4 shared + 60 routed, top-4) and qwen3-moe (128 routed,
top-8). Two formulations share the router (softmax over every expert,
top-k, renormalised over the k chosen):

* training (``forward``, the train step) routes with fixed capacity
  (``moe_apply``). ``ModelConfig.moe_impl`` picks the dispatch:
  "einsum" (the default) is Switch-Transformer-style *dense dispatch*, a
  one-hot (T, E, C) tensor routing each token to its top-k experts'
  capacity slots, expert FFNs as one batched einsum over the expert
  dimension, which shards cleanly over the mesh "model" axis (expert
  parallelism; the dispatch/combine einsums lower to all-to-all-like
  collectives under SPMD); "gather" is the index-form dispatch. Tokens
  beyond an expert's capacity are dropped (their residual passes
  through), the standard trade-off.
* serving (``prefill``, ``prefill_extend``, ``decode_step``) routes
  dropless (``moe_serve``): the (token, choice) pairs are sorted by
  expert, one grouped matmul (``jax.lax.ragged_dot``) runs over the
  experts, and the weighted rows are scattered back. Each expert's
  weights are read once per step, its FLOPs follow the pairs routed to
  it, and no token is dropped, so a request's output does not depend on
  its batch-mates. Under a mesh (the sharded serving steps of
  ``runtime/trainstep``) each "model" shard runs the same path over the
  experts it holds, and the shards' parts are summed.

``MoEConfig.held`` names the experts this device holds (its share under
expert parallelism). The router keeps every output; the serving layer
adds only the held experts' part, and no exchange with the other shares
is modelled. Training holds every expert.

``load_balance_loss`` is the usual Switch aux loss: E * sum(frac_tokens *
frac_router_prob); a router z-loss keeps logits bounded.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import act_fn, dense_init, mlp_apply, mlp_init, rms_norm


def moe_init(key, cfg, dtype) -> dict:
    d = cfg.d_model
    m = cfg.moe
    held = m.held_range[1]
    ks = jax.random.split(key, 5)
    p = {
        "ln": jnp.zeros((d,), dtype),
        "router": dense_init(ks[0], d, (m.n_experts,), jnp.float32),
        # stacked held expert FFNs: (E_held, d, f) / (E_held, f, d)
        "w_gate": dense_init(ks[1], d, (held, m.d_ff_expert),
                             dtype).transpose(1, 0, 2),
        "w_up": dense_init(ks[2], d, (held, m.d_ff_expert),
                           dtype).transpose(1, 0, 2),
        "w_down": dense_init(ks[3], m.d_ff_expert, (held, d),
                             dtype).transpose(1, 0, 2),
    }
    if m.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, m.d_ff_expert * m.n_shared_experts,
                               dtype)
    return p


def _top_k(probs: jax.Array, m) -> Tuple[jax.Array, jax.Array]:
    """The k largest router probabilities and their experts, renormalised
    over the k chosen (qwen-style ``norm_topk_prob``)."""
    top_p, top_idx = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_idx


def _capacity(tokens_per_group: int, cfg) -> int:
    m = cfg.moe
    c = int(tokens_per_group * m.top_k * m.capacity_factor / m.n_experts)
    # multiple of 32 so the capacity axis stays mesh-shardable (the
    # fallback expert-tensor layout for non-dividing expert counts)
    return max(c - c % -32, 32)


def route(router_logits: jax.Array, cfg, capacity: int, *,
          compute_dtype=jnp.float32
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """router_logits: (G, T, E). Returns (dispatch (G,T,E,C) bool,
    combine (G,T,E,C) compute_dtype, aux_loss scalar).

    The (G,T,E,C) tensors are the memory/collective hot spot of the MoE
    layer (94 x 32 GB of fp32 all-gathers in the qwen3 train_4k baseline
    — see EXPERIMENTS.md §Perf). Two structural choices keep them cheap:
    * everything per-expert (one-hot, position-in-queue, capacity mask)
      is ELEMENTWISE in E, so an E-sharded ("model"-axis) constraint
      applied by the caller propagates through the whole routing calc —
      only the (G,T,K) top-k selection sees the full expert dim;
    * ``combine`` is produced in the caller's compute dtype (bf16), and
      the capacity-slot one-hot is wrapped in stop_gradient (it is
      piecewise constant), so AD never rebuilds fp32 (G,T,E,C) tensors.
    """
    g, t, e = router_logits.shape
    m = cfg.moe
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_p, top_idx = _top_k(probs, m)                         # (G, T, K)

    # expert one-hot per k-slot: (G, T, K, E)
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.int32)
    # position of each (token, k) within its expert's queue, in token order
    # priority: lower k first, then token order (standard Switch ordering
    # flattens k-major so that 1st choices win capacity over 2nd choices).
    pos = jnp.cumsum(onehot.transpose(0, 2, 1, 3).reshape(g, t * m.top_k, e),
                     axis=1) - 1                              # (G, K*T, E)
    pos = pos.reshape(g, m.top_k, t, e).transpose(0, 2, 1, 3)  # (G, T, K, E)
    pos = (pos * onehot).sum(-1)                              # (G, T, K)
    keep = pos < capacity
    disp_k = (onehot * keep[..., None]).astype(jnp.bool_)     # (G, T, K, E)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1,
                            dtype=compute_dtype)[..., :capacity]  # (G,T,K,C)
    pos_oh = jax.lax.stop_gradient(pos_oh)
    disp_f = jax.lax.stop_gradient(disp_k.astype(compute_dtype))
    # (G, T, E, C)
    dispatch = jnp.einsum("gtke,gtkc->gtec", disp_f, pos_oh) > 0
    combine = jnp.einsum("gtke,gtkc,gtk->gtec", disp_f, pos_oh,
                         top_p.astype(compute_dtype))

    # Switch aux loss + router z-loss
    frac_tokens = jnp.mean(onehot.astype(jnp.float32).sum(2), axis=1)  # (G,E)
    frac_probs = probs.mean(axis=1)                                    # (G,E)
    aux = e * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))
    z = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    return dispatch, combine, aux + 1e-3 * z


def route_indices(router_logits: jax.Array, cfg, capacity: int):
    """Index-form routing: returns (top_idx (G,T,K) expert ids,
    pos (G,T,K) slot-in-expert, keep (G,T,K) bool, top_p (G,T,K) f32,
    aux_loss). Shares the exact assignment semantics of ``route`` (k-major
    first-choice-wins capacity) without materializing (G,T,E,C)."""
    g, t, e = router_logits.shape
    m = cfg.moe
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_p, top_idx = _top_k(probs, m)
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot.transpose(0, 2, 1, 3).reshape(g, t * m.top_k, e),
                     axis=1) - 1
    pos = pos.reshape(g, m.top_k, t, e).transpose(0, 2, 1, 3)
    pos = (pos * onehot).sum(-1)
    keep = pos < capacity
    frac_tokens = jnp.mean(onehot.astype(jnp.float32).sum(2), axis=1)
    aux = e * jnp.mean(jnp.sum(frac_tokens * probs.mean(axis=1), axis=-1))
    z = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    return top_idx, pos, keep, top_p, aux + 1e-3 * z


def moe_apply_gather(p, x, cfg, ctx=None) -> Tuple[jax.Array, jax.Array]:
    """Gather/scatter dispatch: the beyond-einsum formulation.

    The Switch-style dense dispatch spends two (T x E x C) x D einsums —
    pure masked data movement executed as matmuls — and their (G,T,E,C)
    operands dominated both collectives and temp memory in the qwen3
    train_4k dry-run. Here dispatch is one take-along gather into the
    (G, E, C, D) expert buffers (slot->token indices built by a tiny int32
    scatter) and combine is a (G, T, K, D) gather + weighted sum. AD gives
    the scatter-add transposes. No (G,T,E,C) tensor ever exists.
    See EXPERIMENTS.md §Perf iteration 4.
    """
    b, s, d = x.shape
    m = cfg.moe
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    logits = (h @ p["router"].astype(h.dtype)).astype(jnp.float32)
    if ctx is not None:
        logits = ctx.batch_only(logits)
    cap = _capacity(s, cfg)
    top_idx, pos, keep, top_p, aux = route_indices(logits, cfg, cap)

    # slot -> token index table (G, E, C); sentinel = s (the zero pad row).
    # dropped (token, k) pairs write to out-of-bounds slot c=cap and are
    # discarded by mode="drop".
    gi = jnp.arange(b)[:, None, None]
    ti = jnp.broadcast_to(jnp.arange(s)[None, :, None], top_idx.shape)
    idx_token = jnp.full((b, m.n_experts, cap), s, jnp.int32)
    idx_token = idx_token.at[gi, top_idx,
                             jnp.where(keep, pos, cap)].set(ti, mode="drop")
    if ctx is not None:
        idx_token = ctx.expert_tensor(idx_token, expert_axis=1)

    h_pad = jnp.concatenate([h, jnp.zeros((b, 1, d), h.dtype)], axis=1)
    xe = jax.vmap(lambda hh, ii: hh[ii])(h_pad, idx_token)  # (G, E, C, D)
    if ctx is not None:
        xe = ctx.expert_tensor(xe, expert_axis=1)
    act = act_fn(cfg.act)
    hidden = act(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) \
        * jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", hidden, p["w_down"])
    if ctx is not None:
        ye = ctx.expert_tensor(ye, expert_axis=1)

    # combine: gather each token's K slots and weight (dropped -> w=0)
    flat_slot = top_idx * cap + jnp.where(keep, pos, 0)       # (G, T, K)
    ye_flat = ye.reshape(b, m.n_experts * cap, d)
    yk = jax.vmap(lambda yy, ii: yy[ii])(ye_flat, flat_slot)  # (G, T, K, D)
    w = (top_p * keep).astype(h.dtype)                        # (G, T, K)
    y = jnp.einsum("gtk,gtkd->gtd", w, yk)
    if m.n_shared_experts:
        sh = p["shared"]
        gx = act(h @ sh["w_gate"]) * (h @ sh["w_up"])
        y = y + gx @ sh["w_down"]
    return x + y.astype(x.dtype), aux


def moe_apply(p, x, cfg, ctx=None) -> Tuple[jax.Array, jax.Array]:
    """The training path (fixed capacity). Dispatch on cfg.moe_impl:
    "einsum" (the default, Switch-style dense dispatch) or "gather"
    (index-form dispatch, kept for ablation)."""
    if not cfg.moe.holds_all:
        raise ValueError(
            f"{cfg.name}: the capacity path trains every expert; a held "
            "share of the experts is served by moe_serve")
    if cfg.moe_impl == "gather":
        return moe_apply_gather(p, x, cfg, ctx=ctx)
    return moe_apply_einsum(p, x, cfg, ctx=ctx)


def moe_apply_einsum(p, x, cfg, ctx=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (B, S, D), aux_loss. Groups = batch rows, so the
    capacity is per-row — this keeps the dispatch tensor's token dim
    shardable along the batch/data axis. ``ctx`` (ShardCtx) pins the
    (G,T,E,C) routing tensors and (G,E,C,D) expert buffers to
    expert-on-"model" sharding — expert parallelism — so the dispatch/
    combine einsums lower to all-to-all-sized transfers instead of
    full-tensor all-gathers."""
    b, s, d = x.shape
    m = cfg.moe
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    # router matmul in compute dtype; only the small (G,T,E) logits go f32
    logits = (h @ p["router"].astype(h.dtype)).astype(jnp.float32)
    if ctx is not None:
        # keep the (G,T,E) logits batch-sharded: otherwise the top_k /
        # aux-loss reductions pull a full-batch gather into every layer
        # (2 x 537 MB/layer observed; §Perf iteration 3)
        logits = ctx.batch_only(logits)
    cap = _capacity(s, cfg)
    dispatch, combine, aux = route(logits, cfg, cap, compute_dtype=h.dtype)
    if ctx is not None:
        dispatch = ctx.expert_tensor(dispatch, expert_axis=2)
        combine = ctx.expert_tensor(combine, expert_axis=2)
    # dispatch tokens into (G, E, C, D) expert buffers
    xe = jnp.einsum("gtec,gtd->gecd", dispatch.astype(h.dtype), h)
    if ctx is not None:
        xe = ctx.expert_tensor(xe, expert_axis=1)
    act = act_fn(cfg.act)
    hidden = act(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) \
        * jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", hidden, p["w_down"])
    if ctx is not None:
        ye = ctx.expert_tensor(ye, expert_axis=1)
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(h.dtype), ye)
    if m.n_shared_experts:
        # shared experts run densely for every token (qwen2-moe)
        sh = p["shared"]
        g = act(h @ sh["w_gate"]) * (h @ sh["w_up"])
        y = y + g @ sh["w_down"]
    return x + y.astype(x.dtype), aux


def moe_apply_dense_oracle(p, x, cfg) -> Tuple[jax.Array, jax.Array]:
    """Reference: compute EVERY held expert for every token, weight by
    the (renormalized) top-k router probs (zero where not chosen). No
    capacity, no dropping — the oracle that ``moe_apply`` approaches as
    capacity_factor -> inf, and that ``moe_serve`` equals. O(E/k)
    overcompute; tests only."""
    m = cfg.moe
    first, held = m.held_range
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    logits = jnp.einsum("gtd,de->gte", h.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = _top_k(probs, m)
    weights = jnp.zeros_like(probs)
    weights = jax.vmap(jax.vmap(lambda w, i, v: w.at[i].set(v)))(
        weights, top_idx, top_p)                              # (G, T, E)
    weights = weights[..., first:first + held]                # held share
    act = act_fn(cfg.act)
    hidden = act(jnp.einsum("gtd,edf->gtef", h, p["w_gate"])) \
        * jnp.einsum("gtd,edf->gtef", h, p["w_up"])
    ye = jnp.einsum("gtef,efd->gted", hidden, p["w_down"])
    y = jnp.einsum("gte,gted->gtd", weights.astype(h.dtype), ye)
    if m.n_shared_experts:
        sh = p["shared"]
        g = act(h @ sh["w_gate"]) * (h @ sh["w_up"])
        y = y + g @ sh["w_down"]
    onehot = jax.nn.one_hot(top_idx, m.n_experts).sum(2)
    frac_tokens = onehot.mean(1)
    aux = m.n_experts * jnp.mean(jnp.sum(frac_tokens * probs.mean(1), -1))
    return x + y.astype(x.dtype), aux


def moe_serve(p, x, cfg, ctx=None, live=None
              ) -> Tuple[jax.Array, jax.Array]:
    """The serving path: dropless routing of x: (B, S, D) over the held
    experts. Returns (x + the held experts' part (+ shared experts),
    held pairs per held expert (E_held,) int32). ``live`` (B, S) bool
    marks the rows whose pairs are counted (every row by default); the
    other rows are computed alike, and only left out of the count.

    Under a mesh (``ctx``) the layer keeps expert parallelism
    (``_sharded_part``)."""
    m = cfg.moe
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    if ctx is None:
        y, counts = _held_part(p, h, live, cfg, *m.held_range)
    else:
        y, counts = _sharded_part(p, h, live, cfg, ctx.mesh)
    if m.n_shared_experts:
        with jax.named_scope("moe_combine"):
            sh = p["shared"]
            act = act_fn(cfg.act)
            y = y + (act(h @ sh["w_gate"]) * (h @ sh["w_up"])) \
                @ sh["w_down"]
    return x + y.astype(x.dtype), counts


def _held_part(p, h, live, cfg, first, held):
    """The held experts' part of the layer for normed rows h: (B, S, D),
    float32, and the live pairs per held expert. ``first`` may be traced
    (an expert rank's offset under ``_sharded_part``).

    Every (token, choice) pair is keyed by its held expert (pairs whose
    expert another device holds key past the last), and the pairs are
    sorted by key. A token's k choices are distinct experts, so at most
    ``min(k, held)`` of them are held: the first ``tokens * min(k,
    held)`` sorted rows hold every held pair, and that static bound
    drops nothing. One grouped matmul per projection
    (``jax.lax.ragged_dot``, groups = the held pairs per expert) reads
    each held expert's weights once and computes only the rows routed to
    it; rows past the held pairs are masked out of the combine, which
    scatter-adds the weighted rows back to their tokens in float32."""
    b, s, d = h.shape
    n, k = b * s, cfg.moe.top_k
    h = h.reshape(n, d)
    with jax.named_scope("moe_router"):
        logits = (h @ p["router"].astype(h.dtype)).astype(jnp.float32)
        top_p, top_idx = _top_k(jax.nn.softmax(logits, axis=-1), cfg.moe)
        local = top_idx.reshape(-1) - first                   # (n*k,)
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)[:n * min(k, held)]
        sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        counts = sizes if live is None else jnp.bincount(
            jnp.where(jnp.repeat(live.reshape(-1), k), key, held),
            length=held + 1)[:held].astype(jnp.int32)
        tok = order // k
        kept = mine[order]
        w = jnp.where(kept, top_p.reshape(-1)[order], 0.0)
    with jax.named_scope("moe_experts"):
        xs = h[tok]
        act = act_fn(cfg.act)
        g = act(jax.lax.ragged_dot(xs, p["w_gate"], sizes)) \
            * jax.lax.ragged_dot(xs, p["w_up"], sizes)
        ys = jax.lax.ragged_dot(g, p["w_down"], sizes)
    with jax.named_scope("moe_combine"):
        rows = jnp.where(kept[:, None], ys.astype(jnp.float32), 0.0)
        y = jnp.zeros((n, d), jnp.float32).at[tok].add(rows * w[:, None])
    return y.reshape(b, s, d), counts


def _sharded_part(p, h, live, cfg, mesh):
    """``_held_part`` under a mesh, for every expert. Each "model" shard
    holds whole experts when the axis divides their count (expert
    parallelism: shard i holds experts [i*E/m, (i+1)*E/m)), else a slice
    of every expert's width (per-expert tensor parallelism), as the
    parameter rules lay the banks out; it serves its batch shard's
    tokens against what it holds, and the shards' parts are summed over
    "model" in float32. Each part is exact, so the sum is the whole
    layer's (a share test pins this), and no token is dropped. Returns
    the part and the pairs per expert of the whole batch."""
    from repro.sharding.rules import batch_axes
    m = cfg.moe
    if not m.holds_all:
        raise ValueError(f"{cfg.name}: a mesh serves every expert; name no "
                         "held share")
    ba = batch_axes(mesh)
    n_model = mesh.shape["model"]
    n_batch = math.prod(mesh.shape[a] for a in ba)
    b, s, _ = h.shape
    rows = P(ba) if b % n_batch == 0 else \
        P(None, ba) if s % n_batch == 0 else P()
    by_expert = m.n_experts % n_model == 0
    banks = (P("model"),) * 3 if by_expert else (
        P(None, None, "model"), P(None, None, "model"), P(None, "model"))
    held = m.n_experts // n_model if by_expert else m.n_experts

    def part(q, hh, ll):
        first = jax.lax.axis_index("model") * held if by_expert else 0
        y, counts = _held_part(q, hh, ll, cfg, first, held)
        if rows != P():
            counts = jax.lax.psum(counts, ba)
        return jax.lax.psum(y, "model"), counts

    q = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
    q_specs = dict(zip(("w_gate", "w_up", "w_down"), banks), router=P())
    if live is None:
        live = jnp.ones((b, s), jnp.bool_)
    return jax.shard_map(
        part, mesh=mesh, in_specs=(q_specs, rows, rows),
        out_specs=(rows, P("model") if by_expert else P()))(
            q, h, live)
