"""Pallas TPU kernel for the mamba1 selective scan.

TPU adaptation: the CUDA selective-scan kernel keeps per-thread state in
registers and parallelizes over channels within a block; on TPU we tile
channels (I) across the parallel grid and walk time chunks sequentially
on the innermost grid axis, carrying the state in VMEM scratch as
(N × block_i), channels on the lanes.  The discretized ``exp(dt·A)`` and
``dt·B·x`` terms exist one time step at a time, so HBM traffic is O(T·I)
instead of O(T·I·N).  Inside a chunk the time loop indexes VMEM refs
with ``pl.ds``; B and C arrive transposed per chunk, (N × Tc), so each
step takes its column by a masked lane sum.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                h_ref, dt_s, x_s, y_s, *, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    dt_s[...] = dt_ref[0].astype(jnp.float32)        # (Tc, Ic)
    x_s[...] = x_ref[0].astype(jnp.float32)
    At = a_ref[...].astype(jnp.float32)              # (N, Ic)
    Bt = b_ref[0, 0].astype(jnp.float32)             # (N, Tc)
    Ct = c_ref[0, 0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, Bt.shape, 1)

    def step(t, h):
        # Column t of the (N, Tc) tiles as (N, 1): a masked lane sum,
        # since a TPU vector has no dynamic lane slice.
        sel = lane == t
        b_t = jnp.sum(jnp.where(sel, Bt, 0.0), axis=1, keepdims=True)
        c_t = jnp.sum(jnp.where(sel, Ct, 0.0), axis=1, keepdims=True)
        dt_t = dt_s[pl.ds(t, 1), :]                   # (1, Ic)
        h = jnp.exp(dt_t * At) * h + (dt_t * b_t) * x_s[pl.ds(t, 1), :]
        y_s[pl.ds(t, 1), :] = jnp.sum(h * c_t, axis=0, keepdims=True)
        return h

    h_ref[...] = jax.lax.fori_loop(0, dt_s.shape[0], step, h_ref[...])
    y_ref[0, ...] = y_s[...].astype(y_ref.dtype)

    @pl.when(ti == nt - 1)
    def _finish():
        hout_ref[0, ...] = h_ref[...]


def ssm_scan_pallas(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                    C: jax.Array, D: jax.Array,
                    h0: Optional[jax.Array] = None, *,
                    block_i: int = 256, time_chunk: int = 16,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Shapes as :func:`repro.kernels.ref.ssm_scan_ref` (h0 must be None)."""
    assert h0 is None, "pallas path starts from zero state"
    Bt, T, I = x.shape
    N = A.shape[1]
    block_i = min(block_i, I)
    time_chunk = min(time_chunk, T)
    ni = -(-I // block_i)
    nt = -(-T // time_chunk)
    Ip, Tp = ni * block_i, nt * time_chunk
    xp = jnp.pad(x, ((0, 0), (0, Tp - T), (0, Ip - I)))
    dtp = jnp.pad(dt, ((0, 0), (0, Tp - T), (0, Ip - I)))
    Ap = jnp.pad(A, ((0, Ip - I), (0, 0)))
    Bp = jnp.pad(B, ((0, 0), (0, Tp - T), (0, 0)))
    Cp = jnp.pad(C, ((0, 0), (0, Tp - T), (0, 0)))

    # Per-chunk (N, Tc) tiles of B and C: their last two block dims are
    # then whole array dims, whatever the chunk length.
    Bc = Bp.reshape(Bt, nt, time_chunk, N).transpose(0, 1, 3, 2)
    Cc = Cp.reshape(Bt, nt, time_chunk, N).transpose(0, 1, 3, 2)

    y, hT = pl.pallas_call(
        functools.partial(_ssm_kernel, nt=nt),
        grid=(Bt, ni, nt),
        in_specs=[
            pl.BlockSpec((1, time_chunk, block_i), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, time_chunk, block_i), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((N, block_i), lambda b, i, t: (0, i)),
            pl.BlockSpec((1, 1, N, time_chunk), lambda b, i, t: (b, t, 0, 0)),
            pl.BlockSpec((1, 1, N, time_chunk), lambda b, i, t: (b, t, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, time_chunk, block_i), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, N, block_i), lambda b, i, t: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, Tp, Ip), x.dtype),
            jax.ShapeDtypeStruct((Bt, N, Ip), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, block_i), jnp.float32),
            pltpu.VMEM((time_chunk, block_i), jnp.float32),
            pltpu.VMEM((time_chunk, block_i), jnp.float32),
            pltpu.VMEM((time_chunk, block_i), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, dtp, Ap.T, Bc, Cc)
    y = y[:, :T, :I] + (x.astype(jnp.float32)
                        * D[None, None].astype(jnp.float32)).astype(x.dtype)
    return y, jnp.swapaxes(hT[:, :, :I], 1, 2)
