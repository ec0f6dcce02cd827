"""Plain float32 reference of the dense GQA decoder both configurations
share, and the comparison that decides ``correct``.

Straight ``jax.numpy``: no cache, no kernels, no batching of requests,
every matmul at ``jax.default_matmul_precision("highest")`` (on a TPU a
float32 matmul otherwise runs in bf16 passes). It imports nothing of
the program. It reads the parameter tree that ``weights.make_params``
draws, which is the tree the served model reads.

The layer (Qwen2 / ChatGLM3 family): RMSNorm, GQA attention with q/k/v
bias, rotary position embedding on the first ``rope_fraction`` of each
head's dims, causal softmax, output projection, residual; RMSNorm,
SwiGLU MLP, residual; a final RMSNorm and the head (tied to the
embedding or not).

Departures from the published models, all of them conventions the
served model has and the reference therefore follows:

* the embedding output is multiplied by ``sqrt(d_model)``;
* an RMSNorm scale ``w`` is applied as ``(1 + w)``;
* rotary embedding rotates the two halves of the rotated dims
  (GPT-NeoX layout); ChatGLM3 interleaves pairs of dims instead;
* the vocabulary is padded to a multiple of 128 and the padding's
  logits are masked out.

The comparison (``gaps``): run the reference once over each
prompt with the tokens the server streamed for it, and at every
position read how far the served token's logit lies below the
reference's best. Greedy decoding picks the program's own best, so a
sound program reads 0 except where rounding flipped a near-tie, and a
wrong token reads a gap of the logits' own spread.
"""
from __future__ import annotations

import math
from functools import partial
import jax
import jax.numpy as jnp
import numpy as np

from weights import Spec

Q_BLOCK = 512       # query rows per attention block
HEAD_BLOCK = 256    # positions per block of the head's logits


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, positions, theta: float, fraction: float):
    """x: (S, H, D); rotates the first ``fraction`` of D, half against
    half."""
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], axis=-1)


def attention(q, k, v):
    """Causal GQA attention, one query block at a time. q: (S, H, D);
    k, v: (S, Hk, D)."""
    s, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    nb = -(-s // Q_BLOCK)
    pad = nb * Q_BLOCK - s
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, Q_BLOCK, h, d)
    kpos = jnp.arange(s)

    def one(i, qi):
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(d)
        sc = jnp.where(kpos[None, None] <= qpos[None, :, None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(lambda a: one(*a), (jnp.arange(nb), qb))
    return out.reshape(nb * Q_BLOCK, h, d)[:s]


def layer(spec: Spec, x, p, positions):
    a, m = p["block"], p["mlp"]
    hx = rms_norm(x, a["ln"], spec.norm_eps)
    q = jnp.einsum("sd,dhk->shk", hx, a["wq"])
    k = jnp.einsum("sd,dhk->shk", hx, a["wk"])
    v = jnp.einsum("sd,dhk->shk", hx, a["wv"])
    if spec.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q, positions, spec.rope_theta, spec.rope_fraction)
    k = rope(k, positions, spec.rope_theta, spec.rope_fraction)
    x = x + jnp.einsum("shk,hkd->sd", attention(q, k, v), a["wo"])
    hx = rms_norm(x, m["ln"], spec.norm_eps)
    g = jax.nn.silu(hx @ m["w_gate"]) * (hx @ m["w_up"])
    return x + g @ m["w_down"]


def hidden(spec: Spec, params, tokens):
    """Final-normed hidden states of one sequence. tokens: (S,)."""
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


def _head_blocks(spec: Spec, params, x, fn):
    """Apply ``fn(i, logits)`` to the head's logits HEAD_BLOCK rows at a
    time (the whole (S, vocab) array would not fit beside the model)."""
    def blk(i):
        rows = jax.lax.dynamic_slice_in_dim(x, i * HEAD_BLOCK, HEAD_BLOCK)
        return fn(i, logits_at(spec, params, rows))
    out = jax.lax.map(blk, jnp.arange(x.shape[0] // HEAD_BLOCK))
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), out)


@partial(jax.jit, static_argnums=0)
def _picks(spec: Spec, params, tokens):
    """The token the model puts first at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = hidden(spec, params, tokens)
        return _head_blocks(spec, params, x,
                            lambda i, lg: lg.argmax(-1).astype(jnp.int32))


@partial(jax.jit, static_argnums=0)
def _gaps(spec: Spec, params, tokens, targets):
    """targets: (K, S) token ids. Returns (K + 1, S): at each position
    the best logit minus the logit of each target, and last the best
    minus the second best (how near a tie the position is)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(spec, params, tokens)

        def fn(i, lg):
            t = jax.lax.dynamic_slice_in_dim(targets, i * HEAD_BLOCK,
                                             HEAD_BLOCK, axis=1)
            got = jnp.take_along_axis(lg, jnp.maximum(t, 0).T, axis=1)
            top2 = jax.lax.top_k(lg, 2)[0]
            return jnp.concatenate(
                [top2[:, :1] - got, top2[:, :1] - top2[:, 1:]], axis=1)
        return _head_blocks(spec, params, x, fn).T


def padded(prompt, served, length: int):
    """The sequence the reference reads for one request (the prompt and
    the served tokens but the last, zero-padded to ``length`` rounded up
    to HEAD_BLOCK), and the slice of positions at which the served
    tokens were due."""
    n, m = len(prompt), len(served)
    seq = list(prompt) + list(served[:-1])
    length = -(-length // HEAD_BLOCK) * HEAD_BLOCK
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} exceeds {length}")
    toks = np.zeros(length, np.int32)
    toks[:len(seq)] = seq
    return toks, slice(n - 1, n - 1 + m)


def picks(spec: Spec, params, toks) -> np.ndarray:
    return np.asarray(_picks(spec, params, jnp.asarray(toks)))


def gaps(spec: Spec, params, toks, where: slice, *targets) -> list:
    """Gap of each target sequence (ids due at the positions ``where``)
    under the reference's logits, and last the reference's own margin
    between its best and second-best token at those positions."""
    full = np.zeros((len(targets), len(toks)), np.int32)
    for k, t in enumerate(targets):
        full[k, where] = np.asarray(t)[:where.stop - where.start]
    out = np.asarray(_gaps(spec, params, jnp.asarray(toks),
                           jnp.asarray(full)))
    return [out[k, where] for k in range(len(targets) + 1)]


def round_e4m3(x):
    """Round float32 values (|x| <= 448) to the nearest float8 e4m3fn
    value, ties to even, in float32 arithmetic: 3 mantissa bits, the
    smallest normal 2**-6, subnormals in steps of 2**-9. A cast to
    ``float8_e4m3fn`` and back is not relied on: on the chip it left
    every logit of the control unchanged."""
    _, e = jnp.frexp(x)                      # |x| = m * 2**e, m in [0.5, 1)
    step = jnp.exp2((jnp.maximum(e - 1, -6) - 3).astype(jnp.float32))
    return jnp.round(x / step) * step


def quantize(params, kind: str):
    """The control's weights: every matmul weight rounded to ``kind``
    ("int8" or "fp8", one scale per output channel) and back to float32.
    ``params`` is consumed (its buffers are donated)."""
    def one(path, w):
        name = str(getattr(path[-1], "key", ""))
        if not name.startswith("w") and name not in ("embed", "lm_head"):
            return w
        w = w.astype(jnp.float32)
        # one scale per output channel: reduce over the contracted axes
        # (stacked layer weights carry the layer axis first)
        red = {"lm_head": (0,), "wo": (1, 2)}.get(name, (1,))
        amax = jnp.max(jnp.abs(w), axis=red, keepdims=True)
        if kind == "int8":
            s = amax / 127.0
            return jnp.clip(jnp.round(w / s), -127, 127) * s
        if kind == "fp8":
            s = amax / 448.0
            return round_e4m3(w / s) * s
        raise ValueError(kind)
    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(one, p),
                   donate_argnums=0)(params)

