"""Jit-ready kernel entry points used by the model substrate.

Every op has (a) a memory-efficient pure-jnp implementation that lowers on
any backend — this is what the multi-pod dry-run compiles — and (b) a
Pallas TPU kernel (``impl="pallas"``) validated in interpret mode against
:mod:`repro.kernels.ref`.  ``flash_attention`` takes its fused kernel by
default where the call runs on a TPU with shapes the kernel supports;
``interpret=True`` runs a kernel body on the CPU instead of the chip.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

_NEG_INF = -1e30


# ===========================================================================
# Flash attention (fused Pallas kernel on the TPU; chunked online softmax
# everywhere else: the dry-run / CPU path)
# ===========================================================================
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None, impl: Optional[str] = None,
                    q_chunk: int = 512, kv_chunk: int = 1024,
                    interpret: bool = False) -> jax.Array:
    """Memory-efficient attention.  q: (B,T,H,D); k,v: (B,S,K,D), H%K==0.

    The last query position is aligned with the last key position (so a
    suffix of new tokens against a longer KV prefix works for prefill).
    ``window > 0`` restricts attention to the ``window`` most recent keys
    (recurrentgemma local attention).  ``impl=None`` picks the fused
    kernel where :func:`attention_impl` says so, else ``"chunked"``.
    """
    if impl is None:
        impl = attention_impl(jax.default_backend(), q.shape, k.shape,
                              v.shape, _mesh_devices(), q.dtype,
                              jax.config.jax_default_matmul_precision)
    if impl == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale)
    if impl == "pallas":
        from repro.kernels import flash_attention as _fa
        return _fa.flash_attention_pallas(q, k, v, causal=causal,
                                          window=window, scale=scale,
                                          interpret=interpret)
    return _flash_chunked(q, k, v, causal, window, scale, q_chunk, kv_chunk)


def attention_impl(platform: str, q_shape, k_shape, v_shape,
                   mesh_devices: int = 1, dtype="bfloat16",
                   precision: Optional[str] = None) -> str:
    """``"pallas"`` (the fused kernel and its backward) where the call runs
    on a TPU with shapes the kernel supports, else ``"chunked"``: decided
    by what the call can observe, the platform, its mesh, the shapes and,
    for f32 inputs, the matmul precision the call is traced under."""
    from repro.kernels import flash_attention as _fa
    return ("pallas" if _fa.supports(platform, q_shape, k_shape, v_shape,
                                     mesh_devices, dtype, precision)
            else "chunked")


def _mesh_devices() -> int:
    """Devices of the mesh the call is traced under (1 without one)."""
    mesh = jax.sharding.get_abstract_mesh()
    return 1 if mesh.empty else mesh.size


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _flash_chunked(q, k, v, causal, window, scale, q_chunk, kv_chunk):
    B, T, H, D = q.shape
    _, S, K, _ = k.shape
    rep = H // K
    scale = scale if scale is not None else D ** -0.5
    q_chunk = min(q_chunk, T)
    kv_chunk = min(kv_chunk, S)
    nq = -(-T // q_chunk)
    nk = -(-S // kv_chunk)
    Tp, Sp = nq * q_chunk, nk * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    q4 = qp.reshape(B, nq, q_chunk, K, rep, D)
    k4 = kp.reshape(B, nk, kv_chunk, K, D)
    v4 = vp.reshape(B, nk, kv_chunk, K, D)
    offs = S - T  # global position of q row t is offs + t

    def q_block(_, qi):
        # GQA-aware: q laid out (B,Cq,K,rep,D) so K/V are never repeated
        # to H heads in HBM (§Perf iter 2: the repeat materialized rep x
        # score-sized buffers per chunk).
        qb = q4[:, qi].astype(jnp.float32)
        qpos = offs + qi * q_chunk + jnp.arange(q_chunk)    # (Cq,)

        def kv_block(state, kj):
            m, l, acc = state
            kb = k4[:, kj].astype(jnp.float32)              # (B,Ck,K,D)
            vb = v4[:, kj]                                  # (B,Ck,K,D) bf16
            logits = jnp.einsum("bqhrd,bkhd->bhrqk", qb, kb) * scale
            kpos = kj * kv_chunk + jnp.arange(kv_chunk)     # (Ck,)
            mask = kpos[None, :] < S                        # padding
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window > 0:
                mask &= kpos[None, :] > qpos[:, None] - window
            # Additive bias folds the mask into the same fusion as the max.
            logits = logits + jnp.where(mask[None, None, None], 0.0,
                                        _NEG_INF)
            new_m = jnp.maximum(m, logits.max(axis=-1))
            # p is bounded in [0,1]: bf16 halves the dominant HBM traffic
            # of the fallback path; the l/acc accumulators stay f32.
            p = jnp.exp(logits - new_m[..., None])
            corr = jnp.exp(m - new_m)
            l = l * corr + jnp.sum(p, axis=-1, dtype=jnp.float32)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhrqk,bkhd->bhrqd", p, vb,
                preferred_element_type=jnp.float32)
            return (new_m, l, acc), None

        init = (
            jnp.full((B, K, rep, q_chunk), _NEG_INF, jnp.float32),
            jnp.zeros((B, K, rep, q_chunk), jnp.float32),
            jnp.zeros((B, K, rep, q_chunk, D), jnp.float32),
        )
        # Checkpoint each kv step: without this the scan VJP STACKS every
        # chunk's O(Cq x Ck) score tensor as a residual — the whole reason
        # flash attention needs a recomputing backward (§Perf iter B4).
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_block), init,
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]        # (B,K,rep,Cq,D)
        return _, out

    _, outs = jax.lax.scan(q_block, None, jnp.arange(nq))   # (nq,B,K,rep,Cq,D)
    out = outs.reshape(nq, B, H, q_chunk, D)
    out = jnp.moveaxis(out, 0, 2).reshape(B, H, Tp, D)
    return jnp.moveaxis(out[:, :, :T], 1, 2).astype(q.dtype)  # (B,T,H,D)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     cache_len: jax.Array, *, window: int = 0,
                     scale: Optional[float] = None) -> jax.Array:
    """Single-position attention against a (possibly padded) KV cache.

    q: (B,1,H,D); k,v: (B,S,K,D); ``cache_len`` = number of valid cache
    positions (the new token's position is ``cache_len - 1``).  Direct
    einsum: per-token decode is bandwidth-bound, chunking buys nothing.
    """
    B, _, H, D = q.shape
    _, S, K, _ = k.shape
    rep = H // K
    scale = scale if scale is not None else D ** -0.5
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), rep, axis=2)
    logits = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32), kf) * scale
    kpos = jnp.arange(S)[None, None, None]
    mask = kpos < cache_len
    if window > 0:
        mask &= kpos > cache_len - 1 - window
    logits = jnp.where(mask, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p, vf)
    return out.astype(q.dtype)


# ===========================================================================
# Linear recurrences (mamba1 selective scan, RG-LRU)
# ===========================================================================
def _assoc_combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a1 * a2, a2 * b1 + b2


def ssm_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, h0: Optional[jax.Array] = None, *,
             impl: str = "chunked", time_chunk: int = 16,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Mamba1 selective scan.  Shapes as :func:`repro.kernels.ref.ssm_scan_ref`.

    ``chunked``: sequential scan over time chunks, associative scan inside
    each chunk — the (B,Tc,I,N) state tensor stays VMEM-sized.
    """
    if impl == "ref":
        return _ref.ssm_scan_ref(x, dt, A, B, C, D, h0)
    if impl == "pallas":
        from repro.kernels import ssm_scan as _ss
        return _ss.ssm_scan_pallas(x, dt, A, B, C, D, h0,
                                   interpret=interpret)
    Bt, T, I = x.shape
    N = A.shape[1]
    Tc = min(time_chunk, T)
    nt = -(-T // Tc)
    Tp = nt * Tc
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, Tp - T), (0, 0)))
    dtf = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, Tp - T), (0, 0)))
    Bf = jnp.pad(B.astype(jnp.float32), ((0, 0), (0, Tp - T), (0, 0)))
    Cf = jnp.pad(C.astype(jnp.float32), ((0, 0), (0, Tp - T), (0, 0)))
    x4 = xf.reshape(Bt, nt, Tc, I)
    dt4 = dtf.reshape(Bt, nt, Tc, I)
    B4 = Bf.reshape(Bt, nt, Tc, N)
    C4 = Cf.reshape(Bt, nt, Tc, N)

    def chunk(h, ti):
        dtc, xc = dt4[:, ti], x4[:, ti]
        dA = jnp.exp(dtc[..., None] * A[None, None])         # (Bt,Tc,I,N)
        dBx = dtc[..., None] * B4[:, ti][:, :, None, :] * xc[..., None]
        # prefix recurrence within the chunk, seeded by h
        aa, bb = jax.lax.associative_scan(_assoc_combine, (dA, dBx), axis=1)
        hs = aa * h[:, None] + bb                            # (Bt,Tc,I,N)
        y = jnp.einsum("btin,btn->bti", hs, C4[:, ti])
        return hs[:, -1], y

    h = (h0.astype(jnp.float32) if h0 is not None
         else jnp.zeros((Bt, I, N), jnp.float32))
    # Checkpoint each time chunk: the scan VJP otherwise stacks every
    # chunk's (B,Tc,I,N) dA/dBx residuals — the full O(B*T*I*N) state
    # expansion this chunked formulation exists to avoid (§Perf sweep-3).
    h, ys = jax.lax.scan(jax.checkpoint(chunk), h,
                         jnp.arange(nt))                     # ys: (nt,Bt,Tc,I)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bt, Tp, I)[:, :T]
    y = y + x.astype(jnp.float32) * D[None, None].astype(jnp.float32)
    return y.astype(x.dtype), h


def ssm_step(xt: jax.Array, dtt: jax.Array, A: jax.Array, Bt_: jax.Array,
             Ct: jax.Array, D: jax.Array, h: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """One decode step.  xt,dtt: (B,I); Bt_,Ct: (B,N); h: (B,I,N)."""
    xf, dtf = xt.astype(jnp.float32), dtt.astype(jnp.float32)
    dA = jnp.exp(dtf[..., None] * A[None])                   # (B,I,N)
    dBx = dtf[..., None] * Bt_[:, None, :] * xf[..., None]
    h = dA * h.astype(jnp.float32) + dBx
    y = jnp.einsum("bin,bn->bi", h, Ct.astype(jnp.float32))
    y = y + xf * D[None].astype(jnp.float32)
    return y.astype(xt.dtype), h


def rglru(x: jax.Array, a_gate: jax.Array, i_gate: jax.Array,
          log_lam: jax.Array, h0: Optional[jax.Array] = None, *,
          c: float = 8.0, impl: str = "chunked", time_chunk: int = 256,
          interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """RG-LRU over a sequence.  Shapes as :func:`repro.kernels.ref.rglru_ref`.

    ``chunked`` (default): sequential scan over time chunks with the
    associative scan inside each chunk, body checkpointed — a full-T
    associative scan materializes log2(T) sequence-sized f32 levels and
    its VJP saves them (§Perf sweep-3).
    """
    if impl == "ref":
        return _ref.rglru_ref(x, a_gate, i_gate, log_lam, h0, c=c)
    if impl == "pallas":
        from repro.kernels import rglru_scan as _rs
        return _rs.rglru_pallas(x, a_gate, i_gate, log_lam, h0, c=c,
                                interpret=interpret)

    def gates(xg, ag, ig, mask):
        lam = jax.nn.softplus(log_lam.astype(jnp.float32))
        log_a = -c * lam * jax.nn.sigmoid(ag.astype(jnp.float32))
        if mask is not None:
            log_a = log_a * mask          # padded steps: a=1 (identity)
        a = jnp.exp(log_a)
        mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
        inp = mult * jax.nn.sigmoid(ig.astype(jnp.float32)) * xg
        if mask is not None:
            inp = inp * mask              # padded steps: no input
        return a, inp

    B, T, L = x.shape
    xf = x.astype(jnp.float32)
    if impl == "assoc" or T <= time_chunk:
        a, inp = gates(xf, a_gate, i_gate, None)
        aa, bb = jax.lax.associative_scan(_assoc_combine, (a, inp), axis=1)
        if h0 is not None:
            hs = aa * h0.astype(jnp.float32)[:, None] + bb
        else:
            hs = bb
        return hs.astype(x.dtype), hs[:, -1]

    Tc = time_chunk
    nt = -(-T // Tc)
    Tp = nt * Tc
    pad = ((0, 0), (0, Tp - T), (0, 0))
    x4 = jnp.pad(xf, pad).reshape(B, nt, Tc, L)
    a4 = jnp.pad(a_gate, pad).reshape(B, nt, Tc, L)
    i4 = jnp.pad(i_gate, pad).reshape(B, nt, Tc, L)
    valid = (jnp.arange(Tp) < T).astype(jnp.float32).reshape(nt, Tc)

    def chunk(h, ti):
        mask = valid[ti][None, :, None]
        a, inp = gates(x4[:, ti], a4[:, ti], i4[:, ti], mask)
        aa, bb = jax.lax.associative_scan(_assoc_combine, (a, inp), axis=1)
        hs = aa * h[:, None] + bb
        return hs[:, -1], hs

    h = (h0.astype(jnp.float32) if h0 is not None
         else jnp.zeros((B, L), jnp.float32))
    h, ys = jax.lax.scan(jax.checkpoint(chunk), h, jnp.arange(nt))
    hs = jnp.moveaxis(ys, 0, 1).reshape(B, Tp, L)[:, :T]
    return hs.astype(x.dtype), h


def rglru_step(xt: jax.Array, a_gate: jax.Array, i_gate: jax.Array,
               log_lam: jax.Array, h: jax.Array, *, c: float = 8.0
               ) -> Tuple[jax.Array, jax.Array]:
    """One decode step.  xt, gates: (B,L); h: (B,L)."""
    xf = xt.astype(jnp.float32)
    lam = jax.nn.softplus(log_lam.astype(jnp.float32))
    log_a = -c * lam[None] * jax.nn.sigmoid(a_gate.astype(jnp.float32))
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    h = a * h.astype(jnp.float32) + mult * jax.nn.sigmoid(
        i_gate.astype(jnp.float32)) * xf
    return h.astype(xt.dtype), h


# ===========================================================================
# int8 quantization (gradient compression)
# ===========================================================================
def quantize(x: jax.Array, *, impl: str = "jnp", interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array]:
    if impl == "pallas":
        from repro.kernels import quantize as _qz
        return _qz.quantize_pallas(x, interpret=interpret)
    return _ref.quantize_ref(x)


def dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return _ref.dequantize_ref(q, scale, dtype)
