"""Per-kernel validation: Pallas (interpret=True) + jnp fallbacks vs ref.py.

Every kernel is swept over shapes (incl. GQA group sizes, padding-forcing
lengths) and dtypes, asserting allclose against the pure-jnp oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
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


FUSED_CASES = [
    # B, T, S, H, K, D, causal, window, block_q, block_k
    (2, 32, 32, 4, 4, 16, True, 0, 8, 8),      # causal: blocks skipped
    (1, 32, 32, 4, 2, 16, False, 0, 8, 8),     # bidirectional (encoder)
    (1, 16, 150, 4, 4, 16, False, 0, 8, 32),   # cross, 128 x 1,500 scaled
    (1, 20, 20, 2, 2, 8, True, 0, 8, 8),       # ragged: not a block multiple
    (1, 24, 24, 6, 2, 8, True, 0, 8, 8),       # GQA rep=3
    (1, 40, 40, 4, 1, 8, True, 12, 8, 8),      # local window, MQA
    (2, 8, 24, 4, 2, 8, True, 0, 8, 8),        # suffix queries (prefill)
    (1, 20, 20, 2, 2, 8, True, 0, None, None),  # blocks from the shapes
    (1, 16, 16, 2, 2, 192, True, 0, 8, 8),     # head_dim not a lane multiple
]


def _fused_and_ref(case, dtype, precision=None):
    """Output and vjp of the fused kernel, traced under the matmul
    ``precision`` (the default when None), and of the oracle."""
    B, T, S, H, K, D, causal, window, bq, bk = case
    q, k, v = _qkv(jax.random.PRNGKey(10), B, T, S, H, K, D, dtype)
    do = jax.random.normal(jax.random.PRNGKey(11), q.shape).astype(dtype)

    def fused(q, k, v):
        with jax.default_matmul_precision(precision):
            return flash_attention_pallas(q, k, v, causal=causal,
                                          window=window, block_q=bq,
                                          block_k=bk, interpret=True)

    def oracle(q, k, v):
        return ref.attention_ref(q, k, v, causal=causal, window=window)

    out = []
    for f in (fused, oracle):
        o, vjp = jax.vjp(f, q, k, v)
        out.append((o, *vjp(do)))
    return out


def _rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("case", FUSED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_attention_and_grads_vs_ref(case, dtype):
    """The fused kernel's output and its backward's dq, dk, dv against
    the oracle and its autodiff gradients."""
    got, want = _fused_and_ref(case, dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.dtype == w.dtype, name
        assert _rel(g, w) <= tol, (name, _rel(g, w))


@pytest.mark.parametrize("case", FUSED_CASES[:3])
def test_fused_attention_bf16_operands(case):
    """f32 inputs under a one-pass matmul precision, as the TPU's default
    runs an f32 dot: the dots take bf16 operands, the rest stays f32 and
    within bf16 rounding of the oracle."""
    got, want = _fused_and_ref(case, jnp.float32, "bfloat16")
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.dtype == jnp.float32, name
        assert 1e-4 < _rel(g, w) <= 2e-2, (name, _rel(g, w))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dq_has_no_bias_along_the_mean_key(dtype):
    """Keys with a large common part: dq stays within bf16 rounding of
    the oracle on the bf16 operands.  A softmax term di = rowsum(dO * O)
    taken from a dO or an O other than the kernels' own (an f32 dO, a
    bf16 O) leaves a bias along the mean key, about 5x this error."""
    B, T, S, H, D = 1, 128, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(15), 5)
    q = jax.random.normal(ks[0], (B, T, H, D)).astype(dtype)
    k = (jax.random.normal(ks[1], (B, S, H, D))
         + 4 * jax.random.normal(ks[4], (1, 1, H, D))).astype(dtype)
    v = (jax.random.normal(ks[2], (B, S, H, D)) + 1).astype(dtype)
    do = jax.random.normal(ks[3], (B, T, H, D)).astype(dtype)

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    _, vjp = jax.vjp(lambda q: ref.attention_ref(
        q, rounded(k), rounded(v), causal=False), rounded(q))
    want = vjp(rounded(do))[0]
    with jax.default_matmul_precision("bfloat16"):
        _, vjp = jax.vjp(lambda q: flash_attention_pallas(
            q, k, v, causal=False, block_q=32, block_k=32, interpret=True), q)
        got = np.asarray(vjp(do)[0], np.float32)
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 2e-2, err


@pytest.mark.parametrize("case", [FUSED_CASES[0], FUSED_CASES[2],
                                  FUSED_CASES[5], FUSED_CASES[6]])
def test_fused_attention_log_sum_exp_residual(case):
    """The forward's one residual besides its output: each query row's
    log-sum-exp of its visible scaled scores, f32."""
    B, T, S, H, K, D, causal, window, bq, bk = case
    q, k, v = _qkv(jax.random.PRNGKey(12), B, T, S, H, K, D, jnp.float32)
    _, bq = fa._tile(T, bq)
    _, bk = fa._tile(S, bk, fa.MAX_KV_BLOCK)
    g = fa._Geometry(T=T, S=S, bq=bq, bk=bk, causal=causal, window=window,
                     scale=D ** -0.5, rep=H // K, interpret=True,
                     dtypes=("float32",) * 3, operand="float32")
    _, (*_, lse) = fa._attend_fwd(q, k, v, g)
    kr = jnp.repeat(k, H // K, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, kr) * D ** -0.5
    qpos = jnp.arange(T)[:, None] + (S - T)
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    want = jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)
    assert lse.dtype == jnp.float32
    assert lse.shape == (B * H, 1, g.Tp)
    np.testing.assert_allclose(lse[:, 0, :T].reshape(B, H, T), want,
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("case", [FUSED_CASES[0], FUSED_CASES[4],
                                  FUSED_CASES[5], FUSED_CASES[6]])
def test_skipped_blocks_change_nothing(case, monkeypatch):
    """Visiting every block, masked, with no index clamped, gives bitwise
    the output and gradients of the run that skips hidden blocks."""
    skipping = _fused_and_ref(case, jnp.float32)[0]
    monkeypatch.setattr(fa._Geometry, "needed",
                        lambda self, qi, kj: kj * self.bk < self.S)
    monkeypatch.setattr(fa._Geometry, "kv_block", lambda self, qi, kj: kj)
    monkeypatch.setattr(fa._Geometry, "q_block", lambda self, kj, qi: qi)
    visiting = _fused_and_ref(case, jnp.float32)[0]
    for name, a, b in zip(("o", "dq", "dk", "dv"), skipping, visiting):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("case", [FUSED_CASES[0], FUSED_CASES[3],
                                  FUSED_CASES[5], FUSED_CASES[6]])
def test_index_maps_fetch_each_needed_block_once(case):
    """Along the reduction axis a block index changes only onto a needed
    block: a skipped step repeats its neighbour's index, so the pipeline
    issues no DMA for it, and every needed block keeps its own."""
    B, T, S, H, K, D, causal, window, bq, bk = case
    g = fa._Geometry(T=T, S=S, bq=bq, bk=bk, causal=causal, window=window,
                     scale=1.0, rep=H // K, interpret=True,
                     dtypes=("float32",) * 3, operand="float32")

    def fetches(indices):
        return sum(1 for i, x in enumerate(indices)
                   if i == 0 or x != indices[i - 1])

    for qi in range(g.nq):
        need = [kj for kj in range(g.nk) if bool(g.needed(qi, kj))]
        idx = [int(g.kv_block(qi, kj)) for kj in range(g.nk)]
        assert [idx[kj] for kj in need] == need
        assert fetches(idx) == max(len(need), 1)
    for kj in range(g.nk):
        need = [qi for qi in range(g.nq) if bool(g.needed(qi, kj))]
        idx = [int(g.q_block(kj, qi)) for qi in range(g.nq)]
        assert [idx[qi] for qi in need] == need
        assert fetches(idx) == max(len(need), 1)
    if causal and T == S:
        assert sum(bool(g.needed(qi, kj)) for qi in range(g.nq)
                   for kj in range(g.nk)) < g.nq * g.nk


def test_attention_dispatch_by_platform_and_shapes():
    """The fused kernel where the call runs on a TPU, alone in its mesh,
    with shapes it supports; the chunked path everywhere else."""
    gpt2 = (16, 1024, 12, 64)
    enc, cross_q = (8, 1500, 12, 64), (8, 128, 12, 64)
    assert ops.attention_impl("tpu", gpt2, gpt2, gpt2) == "pallas"
    assert ops.attention_impl("tpu", cross_q, enc, enc) == "pallas"
    assert ops.attention_impl("tpu", (1, 64, 8, 128), (1, 64, 2, 128),
                              (1, 64, 2, 128)) == "pallas"       # GQA
    assert ops.attention_impl("cpu", gpt2, gpt2, gpt2) == "chunked"
    assert ops.attention_impl("gpu", gpt2, gpt2, gpt2) == "chunked"
    assert ops.attention_impl("tpu", gpt2, gpt2, gpt2,
                              mesh_devices=4) == "chunked"
    mla_qk, mla_v = (1, 64, 16, 192), (1, 64, 16, 128)
    assert ops.attention_impl("tpu", mla_qk, mla_qk, mla_v) == "chunked"
    wide = (1, 64, 4, 512)
    assert ops.attention_impl("tpu", wide, wide, wide) == "chunked"
    assert ops.attention_impl("tpu", (1, 64, 6, 64), (1, 64, 4, 64),
                              (1, 64, 4, 64)) == "chunked"
    # f32 inputs take the fused kernel's one bf16 pass only where the
    # matmul precision asks for no more; bf16 inputs under any precision.
    for precision in (None, "default", "bfloat16"):
        assert ops.attention_impl("tpu", gpt2, gpt2, gpt2, 1, jnp.float32,
                                  precision) == "pallas"
    for precision in ("highest", "float32", "high", "tensorfloat32"):
        assert ops.attention_impl("tpu", gpt2, gpt2, gpt2, 1, jnp.float32,
                                  precision) == "chunked"
        assert ops.attention_impl("tpu", gpt2, gpt2, gpt2, 1, jnp.bfloat16,
                                  precision) == "pallas"


def test_dot_operands_follow_the_matmul_precision():
    """f32 inputs take bf16 dot operands where XLA gives an f32 dot one
    pass: the default precision on the TPU (not interpreted), a one-pass
    setting anywhere; f32 under a higher setting or interpreted on the
    CPU's default.  bf16 inputs stay bf16."""
    assert fa._operand(jnp.float32, interpret=False) == "bfloat16"
    assert fa._operand(jnp.float32, interpret=True) == "float32"
    assert fa._operand(jnp.bfloat16, interpret=False) == "bfloat16"
    with jax.default_matmul_precision("highest"):
        for interpret in (False, True):
            assert fa._operand(jnp.float32, interpret) == "float32"
            assert fa._operand(jnp.bfloat16, interpret) == "bfloat16"
    with jax.default_matmul_precision("bfloat16"):
        for interpret in (False, True):
            assert fa._operand(jnp.float32, interpret) == "bfloat16"


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_default_attention_on_cpu_is_the_chunked_path(case, dtype):
    """On the CPU the default stays the chunked path: bitwise its output
    and gradients."""
    B, T, S, H, K, D, causal, window = case
    q, k, v = _qkv(jax.random.PRNGKey(13), B, T, S, H, K, D, dtype)
    assert ops.attention_impl(jax.default_backend(), q.shape, k.shape,
                              v.shape) == "chunked"
    outs = []
    for impl in (None, "chunked"):
        def f(q, k, v, impl=impl):
            return ops.flash_attention(q, k, v, causal=causal, window=window,
                                       impl=impl)
        o, vjp = jax.vjp(f, q, k, v)
        outs.append((o, *vjp(jnp.ones_like(o))))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_backward_kernels_run_under_the_attention_scope():
    """Every op of the backward kernels carries the caller's
    ``attention`` scope and the transpose's ``transpose(`` in its HLO
    name stack, as ``bench/layers.step_scopes`` reads them."""
    from bench import layers

    q, k, v = _qkv(jax.random.PRNGKey(14), 1, 16, 16, 2, 2, 8, jnp.float32)

    def loss(q, k, v):
        with jax.named_scope("attention"):
            o = flash_attention_pallas(q, k, v, causal=True, block_q=8,
                                       block_k=8, interpret=True)
        return jnp.sum(o * o)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    scopes = layers.hlo_scopes(text)
    for kernel in ("flash_attention_dq", "flash_attention_dkv"):
        ops_of = [s for s in scopes.values() if f"/{kernel}/" in s]
        assert len(ops_of) > 10, kernel
        for s in ops_of:
            assert "attention" in layers._scope_words(s), s
            assert layers.step_part(s) == "backward", s


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
