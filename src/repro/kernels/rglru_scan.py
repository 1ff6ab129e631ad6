"""Pallas TPU kernel for the RG-LRU gated linear recurrence.

Channels tile across the parallel grid; time runs sequentially on the
innermost grid axis with the hidden state in VMEM scratch.  All gate
math is fp32 inside the kernel regardless of the I/O dtype.  The gates
of a chunk are computed at once into VMEM scratch, and the time loop
reads and writes one row of it at a time through ``pl.ds``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, ag_ref, ig_ref, lam_ref, y_ref, hout_ref, h_ref,
                  a_s, inp_s, y_s, *, c: float, nt: int, seq: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)            # (Tc, Lc)
    ag = ag_ref[0].astype(jnp.float32)
    ig = ig_ref[0].astype(jnp.float32)
    lam = jax.nn.softplus(lam_ref[...].astype(jnp.float32))   # (1, Lc)
    log_a = -c * lam * jax.nn.sigmoid(ag)                     # (Tc, Lc)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    # Padded steps past the sequence end leave the state as it is.
    pos = ti * x.shape[0] + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    valid = pos < seq
    a_s[...] = jnp.where(valid, jnp.exp(log_a), 1.0)
    inp_s[...] = jnp.where(valid, mult * jax.nn.sigmoid(ig) * x, 0.0)

    def step(t, h):
        h = a_s[pl.ds(t, 1), :] * h + inp_s[pl.ds(t, 1), :]   # (1, Lc)
        y_s[pl.ds(t, 1), :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, a_s.shape[0], step, h_ref[...])
    y_ref[0, ...] = y_s[...].astype(y_ref.dtype)

    @pl.when(ti == nt - 1)
    def _finish():
        hout_ref[0, ...] = h_ref[...]


def rglru_pallas(x: jax.Array, a_gate: jax.Array, i_gate: jax.Array,
                 log_lam: jax.Array, h0: Optional[jax.Array] = None, *,
                 c: float = 8.0, block_l: int = 256, time_chunk: int = 16,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Shapes as :func:`repro.kernels.ref.rglru_ref` (h0 must be None)."""
    assert h0 is None, "pallas path starts from zero state"
    B, T, L = x.shape
    block_l = min(block_l, L)
    time_chunk = min(time_chunk, T)
    nl = -(-L // block_l)
    nt = -(-T // time_chunk)
    Lp, Tp = nl * block_l, nt * time_chunk
    pad3 = ((0, 0), (0, Tp - T), (0, Lp - L))
    xp = jnp.pad(x, pad3)
    agp = jnp.pad(a_gate, pad3)
    igp = jnp.pad(i_gate, pad3)
    lamp = jnp.pad(log_lam, ((0, Lp - L),))[None, :]          # (1, Lp)

    y, hT = pl.pallas_call(
        functools.partial(_rglru_kernel, c=c, nt=nt, seq=T),
        grid=(B, nl, nt),
        in_specs=[
            pl.BlockSpec((1, time_chunk, block_l), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, time_chunk, block_l), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, time_chunk, block_l), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, block_l), lambda b, i, t: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, time_chunk, block_l), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, 1, block_l), lambda b, i, t: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, Lp), x.dtype),
            jax.ShapeDtypeStruct((B, 1, Lp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_l), jnp.float32),
            pltpu.VMEM((time_chunk, block_l), jnp.float32),
            pltpu.VMEM((time_chunk, block_l), jnp.float32),
            pltpu.VMEM((time_chunk, block_l), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, agp, igp, lamp)
    return y[:, :T, :L], hT[:, 0, :L]
