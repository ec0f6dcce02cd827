"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode on
CPU) and the XLA production implementation are asserted allclose against
the pure-jnp oracle in ref.py."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_seq_ref
from repro.models.layers import decode_attention_xla, flash_attention_xla
from repro.models.rglru import rglru_scan_ref as rglru_assoc_ref

pytestmark = [pytest.mark.kernels, pytest.mark.slow]

KEY = jax.random.PRNGKey(7)


FA_CASES = [
    # (sq, sk, h, hk, d, causal, window, dtype)
    (64, 64, 4, 2, 32, True, 0, jnp.float32),
    (128, 128, 4, 1, 64, True, 24, jnp.float32),
    (32, 32, 2, 2, 16, False, 0, jnp.float32),
    (64, 64, 8, 4, 128, True, 0, jnp.bfloat16),
    (96, 96, 4, 4, 64, True, 32, jnp.float32),
    (256, 256, 2, 1, 128, True, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("sq,sk,h,hk,d,causal,window,dtype", FA_CASES)
def test_flash_attention_pallas_vs_ref(sq, sk, h, hk, d, causal, window,
                                       dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (2, sk, hk, d), dtype)
    v = jax.random.normal(ks[2], (2, sk, hk, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("sq,sk,h,hk,d,causal,window,dtype", FA_CASES)
def test_flash_attention_xla_vs_ref(sq, sk, h, hk, d, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (2, sk, hk, d), dtype)
    v = jax.random.normal(ks[2], (2, sk, hk, d), dtype)
    out = flash_attention_xla(q, k, v, causal=causal, window=window, chunk=32)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_xla_grads_vs_ref():
    """The custom flash VJP must match the oracle's autodiff grads."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 48, 4, 32))
    k = jax.random.normal(ks[1], (2, 48, 2, 32))
    v = jax.random.normal(ks[2], (2, 48, 2, 32))
    for causal, window in [(True, 0), (True, 12), (False, 0)]:
        f = lambda *a: jnp.sum(jnp.sin(flash_attention_xla(
            *a, causal=causal, window=window, chunk=16)))
        g = lambda *a: jnp.sum(jnp.sin(attention_ref(
            *a, causal=causal, window=window)))
        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)


DEC_CASES = [
    (2, 4, 2, 32, 64, jnp.float32),
    (3, 8, 1, 64, 128, jnp.float32),
    (1, 4, 4, 128, 256, jnp.bfloat16),
    (4, 16, 2, 64, 96, jnp.float32),
]


@pytest.mark.parametrize("b,h,hk,d,s,dtype", DEC_CASES)
def test_decode_attention_pallas_vs_ref(b, h, hk, d, s, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, s, hk, d), dtype)
    vc = jax.random.normal(ks[2], (b, s, hk, d), dtype)
    lens = jax.random.randint(ks[3], (b,), 1, s + 1)
    out = decode_attention(q, kc, vc, lens, bk=32)
    ref = decode_attention_ref(q, kc, vc, lens)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,h,hk,d,s,dtype", DEC_CASES)
def test_decode_attention_xla_vs_ref(b, h, hk, d, s, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, s, hk, d), dtype)
    vc = jax.random.normal(ks[2], (b, s, hk, d), dtype)
    lens = jax.random.randint(ks[3], (b,), 1, s + 1)
    out = decode_attention_xla(q, kc, vc, lens)
    ref = decode_attention_ref(q, kc, vc, lens)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


PAGED_CASES = [
    # (b, h, hk, d, num_blocks, block_size, nb_pages, dtype, layout)
    (2, 4, 2, 32, 9, 8, 4, jnp.float32, "random"),
    (3, 8, 1, 64, 5, 16, 2, jnp.float32, "random"),
    (1, 4, 4, 128, 17, 8, 8, jnp.bfloat16, "random"),
    (4, 8, 2, 64, 13, 16, 3, jnp.float32, "random"),
    # the served head shapes (qwen2-1.5b, chatglm3-6b, qwen3-moe) at
    # 16-row blocks, over ragged lengths: the kernel's page groups hold
    # 32, 32 and 16 pages (``chunk_pages``), so each slot spans two
    (4, 12, 2, 128, 161, 16, 40, jnp.bfloat16, "ragged"),
    (4, 32, 2, 128, 161, 16, 40, jnp.bfloat16, "ragged"),
    (4, 64, 4, 128, 97, 16, 24, jnp.bfloat16, "ragged"),
    # slots 0 and 1 share their first pages; groups of 16 pages do not
    # divide the 20 of a table
    (3, 12, 2, 128, 61, 16, 20, jnp.float32, "shared"),
]


def _paged_inputs(b, h, hk, d, n, bs, nb, dtype, layout):
    """q, pools, tables and lengths of one case. ``ragged`` lengths are
    1 (a free slot), a page boundary, one row past it, and the whole
    table; ``shared`` maps the first pages of slots 0 and 1 to the same
    physical blocks (a shared prefix)."""
    from repro.kernels.decode_attention.kernel import chunk_pages
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kp = jax.random.normal(ks[1], (n, bs, hk, d), dtype)
    vp = jax.random.normal(ks[2], (n, bs, hk, d), dtype)
    tables = jax.random.randint(ks[3], (b, nb), 0, n)
    lens = jax.random.randint(ks[4], (b,), 1, nb * bs + 1)
    if layout == "ragged":
        edge = bs * chunk_pages(bs, hk, d, nb, jnp.dtype(dtype).itemsize)
        assert edge < nb * bs
        lens = jnp.array([1, edge, edge + 1, nb * bs][:b])
    elif layout == "shared":
        tables = tables.at[1, :nb // 2].set(tables[0, :nb // 2])
        lens = jnp.maximum(lens, bs * (nb // 2) + 1)
    return q, kp, vp, tables, lens


@pytest.mark.parametrize("b,h,hk,d,n,bs,nb,dtype,layout", PAGED_CASES)
def test_paged_decode_attention_pallas_vs_ref(b, h, hk, d, n, bs, nb, dtype,
                                              layout):
    """Block-table gather path: the kernel must stream exactly the pages
    named by the table (including repeated/null physical blocks) and mask
    rows past each sequence's length."""
    q, kp, vp, tables, lens = _paged_inputs(b, h, hk, d, n, bs, nb, dtype,
                                            layout)
    out = paged_decode_attention(q, kp, vp, tables, lens)
    ref = paged_decode_attention_ref(q, kp, vp, tables, lens)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,h,hk,d,n,bs,nb,dtype,layout", PAGED_CASES)
def test_paged_gather_xla_vs_ref(b, h, hk, d, n, bs, nb, dtype, layout):
    """The XLA path off the TPU (gather pages to a contiguous view, then
    dense decode attention) equals the paged oracle."""
    from repro.models.layers import paged_gather
    q, kp, vp, tables, lens = _paged_inputs(b, h, hk, d, n, bs, nb, dtype,
                                            layout)
    out = decode_attention_xla(q, paged_gather(kp, tables),
                               paged_gather(vp, tables), lens)
    ref = paged_decode_attention_ref(q, kp, vp, tables, lens)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


SCAN_CASES = [
    (2, 64, 128), (3, 100, 96), (1, 256, 512), (2, 17, 40),
]


@pytest.mark.parametrize("B,S,D", SCAN_CASES)
def test_rglru_scan_pallas_vs_seq_ref(B, S, D):
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (B, S, D), minval=0.5, maxval=0.999)
    b = jax.random.normal(ks[1], (B, S, D)) * 0.1
    h0 = jax.random.normal(ks[2], (B, D))
    y = rglru_scan(a, b, h0, bs=32, bd=64)
    yr = rglru_scan_seq_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,S,D", SCAN_CASES)
def test_rglru_assoc_scan_vs_seq_ref(B, S, D):
    """The production associative-scan lowering equals the sequential scan."""
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (B, S, D), minval=0.5, maxval=0.999)
    b = jax.random.normal(ks[1], (B, S, D)) * 0.1
    h0 = jax.random.normal(ks[2], (B, D))
    y = rglru_assoc_ref(a, b, h0)
    yr = rglru_scan_seq_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


def test_mlstm_chunkwise_matches_decode_recurrence():
    """Chunkwise-parallel mLSTM == token-by-token recurrent form."""
    from repro.models.ssm import mlstm_chunkwise
    b, s, nh, dh = 2, 48, 2, 16
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, s, nh, dh))
    k = jax.random.normal(ks[1], (b, s, nh, dh))
    v = jax.random.normal(ks[2], (b, s, nh, dh))
    log_i = jax.random.normal(ks[3], (b, s, nh))
    log_f = -jax.nn.softplus(-jax.random.normal(ks[4], (b, s, nh)) - 1.0)
    out, (C, n, m) = mlstm_chunkwise(q, k, v, log_i, log_f, None, chunk=16)

    # sequential oracle
    import numpy as onp
    qn, kn, vn = (onp.asarray(x, onp.float64) for x in (q, k, v))
    li, lf = onp.asarray(log_i, onp.float64), onp.asarray(log_f, onp.float64)
    scale = 1.0 / onp.sqrt(dh)
    C_ = onp.zeros((b, nh, dh, dh))
    n_ = onp.zeros((b, nh, dh))
    m_ = onp.full((b, nh), -1e30)
    outs = onp.zeros((b, s, nh, dh))
    for t in range(s):
        m_new = onp.maximum(lf[:, t] + m_, li[:, t])
        decay = onp.exp(lf[:, t] + m_ - m_new)
        inw = onp.exp(li[:, t] - m_new)
        C_ = decay[..., None, None] * C_ + inw[..., None, None] \
            * onp.einsum("bhd,bhe->bhde", kn[:, t], vn[:, t])
        n_ = decay[..., None] * n_ + inw[..., None] * kn[:, t]
        qt = qn[:, t] * scale
        num = onp.einsum("bhd,bhde->bhe", qt, C_)
        den = onp.maximum(onp.abs(onp.einsum("bhd,bhd->bh", qt, n_)),
                          onp.exp(-m_new))
        outs[:, t] = num / den[..., None]
        m_ = m_new
    np.testing.assert_allclose(np.asarray(out), outs, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(C), C_, rtol=2e-4, atol=2e-4)
