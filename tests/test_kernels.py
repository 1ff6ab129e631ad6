"""Per-kernel validation: Pallas (interpret=True) + jnp fallbacks vs ref.py.

Every kernel is swept over shapes (incl. GQA group sizes, padding-forcing
lengths) and dtypes, asserting allclose against the pure-jnp oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import quantize_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas


def _qkv(key, B, T, S, H, K, D, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, T, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (B, S, K, D), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (B, S, K, D), jnp.float32).astype(dtype)
    return q, k, v


ATTN_CASES = [
    # B, T, S, H, K, D, causal, window
    (2, 16, 16, 4, 4, 8, True, 0),        # MHA causal
    (1, 16, 16, 6, 2, 16, True, 0),       # GQA rep=3
    (2, 8, 24, 4, 1, 8, True, 0),         # MQA, suffix queries (prefill)
    (1, 16, 16, 4, 2, 8, False, 0),       # bidirectional (encoder)
    (1, 32, 32, 4, 4, 8, True, 8),        # local window
    (1, 20, 20, 2, 2, 8, True, 0),        # non-multiple-of-block lengths
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_chunked_vs_ref(case, dtype):
    B, T, S, H, K, D, causal, window = case
    q, k, v = _qkv(jax.random.PRNGKey(0), B, T, S, H, K, D, dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_chunk=8, kv_chunk=8)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_pallas_interpret_vs_ref(case):
    B, T, S, H, K, D, causal, window = case
    q, k, v = _qkv(jax.random.PRNGKey(1), B, T, S, H, K, D, jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=8, block_k=8, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_pallas_block_sweep():
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 32, 32, 4, 2, 16, jnp.float32)
    want = ref.attention_ref(q, k, v, causal=True)
    for bq, bk in [(8, 8), (16, 8), (8, 16), (32, 32)]:
        got = flash_attention_pallas(q, k, v, causal=True,
                                     block_q=bq, block_k=bk, interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5,
                                   err_msg=f"block ({bq},{bk})")


SSM_CASES = [(1, 8, 4, 2), (2, 16, 8, 4), (1, 24, 6, 3)]  # B, T, I, N


@pytest.mark.parametrize("B,T,I,N", SSM_CASES)
@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_ssm_scan_vs_ref(B, T, I, N, impl):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(ks[0], (B, T, I))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, I)))
    A = -jnp.exp(jax.random.normal(ks[2], (I, N)))
    Bm = jax.random.normal(ks[3], (B, T, N))
    C = jax.random.normal(ks[4], (B, T, N))
    D = jax.random.normal(ks[5], (I,))
    if impl == "pallas":
        y, h = ssm_scan_pallas(x, dt, A, Bm, C, D, interpret=True)
    else:
        y, h = ops.ssm_scan(x, dt, A, Bm, C, D, impl="chunked", time_chunk=4)
    yr, hr = ref.ssm_scan_ref(x, dt, A, Bm, C, D)
    np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h, hr, atol=1e-4, rtol=1e-4)


def test_ssm_step_matches_scan():
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    B, T, I, N = 2, 6, 4, 3
    x = jax.random.normal(ks[0], (B, T, I))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, I)))
    A = -jnp.exp(jax.random.normal(ks[2], (I, N)))
    Bm = jax.random.normal(ks[3], (B, T, N))
    C = jax.random.normal(ks[4], (B, T, N))
    D = jax.random.normal(ks[5], (I,))
    y_ref, h_ref = ref.ssm_scan_ref(x, dt, A, Bm, C, D)
    h = jnp.zeros((B, I, N))
    ys = []
    for t in range(T):
        y, h = ops.ssm_step(x[:, t], dt[:, t], A, Bm[:, t], C[:, t], D, h)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h, h_ref, atol=1e-4, rtol=1e-4)


RGLRU_CASES = [(1, 8, 4), (2, 16, 8), (1, 13, 6), (1, 20, 6)]  # B, T, L


@pytest.mark.parametrize("B,T,L", RGLRU_CASES)
@pytest.mark.parametrize("impl", ["assoc", "pallas"])
def test_rglru_vs_ref(B, T, L, impl):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (B, T, L))
    a = jax.random.normal(ks[1], (B, T, L))
    i = jax.random.normal(ks[2], (B, T, L))
    lam = jax.random.normal(ks[3], (L,))
    if impl == "pallas":
        hs, hT = rglru_pallas(x, a, i, lam, interpret=True)
    else:
        hs, hT = ops.rglru(x, a, i, lam, impl="assoc")
    hr, hTr = ref.rglru_ref(x, a, i, lam)
    np.testing.assert_allclose(hs, hr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(hT, hTr, atol=1e-4, rtol=1e-4)


def test_rglru_step_matches_scan():
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    B, T, L = 2, 5, 4
    x = jax.random.normal(ks[0], (B, T, L))
    a = jax.random.normal(ks[1], (B, T, L))
    i = jax.random.normal(ks[2], (B, T, L))
    lam = jax.random.normal(ks[3], (L,))
    hs_ref, _ = ref.rglru_ref(x, a, i, lam)
    h = jnp.zeros((B, L))
    for t in range(T):
        _, h = ops.rglru_step(x[:, t], a[:, t], i[:, t], lam, h)
    np.testing.assert_allclose(h, hs_ref[:, -1], atol=1e-4, rtol=1e-4)


def test_rglru_h0_seeding():
    """Chunked decode continuation: h0-seeded scan == suffix of full scan."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    B, T, L = 1, 12, 4
    x = jax.random.normal(ks[0], (B, T, L))
    a = jax.random.normal(ks[1], (B, T, L))
    i = jax.random.normal(ks[2], (B, T, L))
    lam = jax.random.normal(ks[3], (L,))
    full, _ = ref.rglru_ref(x, a, i, lam)
    head, h_mid = ops.rglru(x[:, :7], a[:, :7], i[:, :7], lam)
    tail, _ = ops.rglru(x[:, 7:], a[:, 7:], i[:, 7:], lam, h0=h_mid)
    np.testing.assert_allclose(jnp.concatenate([head, tail], 1), full,
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", [(8, 16), (7, 33), (128, 256), (1, 5)])
def test_quantize_pallas_vs_ref(shape):
    x = jax.random.normal(jax.random.PRNGKey(8), shape) * 3.0
    qr, sr = ref.quantize_ref(x)
    qp, sp = quantize_pallas(x, interpret=True)
    np.testing.assert_allclose(sp, sr, atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(qr))
    back = ops.dequantize(qp, sp)
    assert float(jnp.max(jnp.abs(back - x))) <= float(sp.max()) + 1e-6


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(9), (32, 64))
    q, s = ops.quantize(x)
    err = ops.dequantize(q, s) - x
    # max error <= scale/2 per row (symmetric int8 rounding)
    assert np.all(np.abs(np.asarray(err)) <= np.asarray(s) * 0.5 + 1e-6)
