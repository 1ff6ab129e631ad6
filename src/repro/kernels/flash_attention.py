"""Pallas TPU flash attention (forward) with explicit VMEM tiling.

TPU adaptation notes (vs. the usual CUDA flash kernels):
* Tiles are MXU-shaped — (block_q × d) @ (d × block_k) feeds the 128×128
  systolic array, so block sizes default to multiples of 128 and the
  contraction dim is the full head_dim (head_dim ≤ 256 fits VMEM).
* The kv axis is the innermost grid dimension with "arbitrary" semantics:
  the online-softmax state (m, l, acc) lives in VMEM scratch and persists
  across sequential kv steps — the TPU grid is a sequential loop per core,
  not a CUDA thread block, so no atomics / shared-memory staging.
* GQA is handled in the index maps (kv head = q head // group), not by
  materializing repeated K/V in HBM.

Correctness is validated in interpret mode against
:func:`repro.kernels.ref.attention_ref` over shape/dtype sweeps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int, block_q: int,
               block_k: int, seq_q: int, seq_k: int, nk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = (seq_k - seq_q) + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k                              # key padding
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        o_ref[0, ...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (B,T,H,D); k,v: (B,S,K,D).  Returns (B,T,H,D).

    ``interpret=True`` runs the kernel body on the CPU for validation.
    """
    B, T, H, D = q.shape
    _, S, K, _ = k.shape
    rep = H // K
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, max(T, 8))
    block_k = min(block_k, max(S, 8))
    nq = -(-T // block_q)
    nk = -(-S // block_k)
    Tp, Sp = nq * block_q, nk * block_k

    # (B*H, T, D) query-major layout; KV stays at K heads (GQA via index map).
    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, T, D)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * K, S, D)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * K, S, D)
    qh = jnp.pad(qh, ((0, 0), (0, Tp - T), (0, 0)))
    kh = jnp.pad(kh, ((0, 0), (0, Sp - S), (0, 0)))
    vh = jnp.pad(vh, ((0, 0), (0, Sp - S), (0, 0)))

    def q_map(bh, qi, kj):
        return (bh, qi, 0)

    def kv_map(bh, qi, kj):
        b = bh // H
        h = bh % H
        return (b * K + h // rep, kj, 0)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_q=T, seq_k=S, nk=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Tp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh)
    out = out[:, :T].reshape(B, H, T, D)
    return jnp.moveaxis(out, 1, 2)
