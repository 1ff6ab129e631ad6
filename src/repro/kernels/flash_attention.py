"""Pallas TPU flash attention: a fused forward and its own backward.

TPU adaptation notes (vs. the usual CUDA flash kernels):
* Tiles are MXU-shaped — (block_q × d) @ (d × block_k) feeds the 128×128
  systolic array, so blocks are multiples of 128 and the contraction dim is
  the full head_dim (head_dim ≤ 256 fits VMEM).
* The reduction axis is the innermost grid dimension with "arbitrary"
  semantics: the online-softmax state (m, l, acc) and the gradient
  accumulators live in VMEM scratch and persist across its sequential
  steps — the TPU grid is a sequential loop per core, not a CUDA thread
  block, so no atomics / shared-memory staging.  No score-sized tensor
  ever reaches HBM.
* GQA is handled in the index maps (kv head = q head // group), not by
  materializing repeated K/V in HBM.
* Blocks that the causal or local-window mask hides entirely are skipped:
  no compute, and their index maps repeat a needed block's index so the
  pipeline issues no DMA for them.  Only blocks that cross the diagonal,
  the window's edge or the padded end of the keys compute a mask.
* Sequences are padded to a multiple of 128 rows only (1,500 -> 1,536).

The forward writes the output and each row's log-sum-exp (f32); the
backward recomputes p = exp(s - lse) block by block in two kernels, one
for dq (over kv blocks) and one for dk, dv (over the q blocks of every
query head of the group).  Every dot's operands take one dtype with f32
accumulation: bf16 for f32 inputs where XLA would give such a dot one
bf16 pass (the default matmul precision on the TPU), else the inputs'
own (``_operand``); scores, softmax statistics, accumulators and the
output stay f32.

Correctness is validated in interpret mode against
:func:`repro.kernels.ref.attention_ref` and its gradients.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
MAX_HEAD_DIM = 256
# Blocks (tuned on a TPU v5e at head_dim 64): queries in blocks of at most
# MAX_BLOCK rows; keys in one block up to MAX_KV_BLOCK rows, else blocks of
# at most MAX_BLOCK.  Larger blocks amortize the grid's per-step cost.
MAX_BLOCK = 512
MAX_KV_BLOCK = 2048
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


# Settings of ``jax.default_matmul_precision`` that give an f32 dot one
# bf16 MXU pass on the TPU; any other asks for more passes.
DEFAULT_PRECISIONS = (None, "default", "bfloat16", "fastest")
ONE_PASS_PRECISIONS = ("bfloat16", "fastest")


def supports(platform: str, q_shape: Sequence[int], k_shape: Sequence[int],
             v_shape: Sequence[int], mesh_devices: int = 1,
             dtype="bfloat16", precision: Optional[str] = None) -> bool:
    """Whether the fused kernels take a call: a TPU, one device in the
    call's mesh (a Mosaic kernel is not partitioned automatically), equal
    q/k/v head widths of at most ``MAX_HEAD_DIM``, whole GQA groups, and
    no f32 inputs under a matmul ``precision`` above the default (the
    kernel's dots take one bf16 pass)."""
    (_, _, H, D), (_, _, K, Dk), (_, _, _, Dv) = q_shape, k_shape, v_shape
    precise = (jnp.dtype(dtype) == jnp.float32
               and precision not in DEFAULT_PRECISIONS)
    return (platform == "tpu" and mesh_devices == 1 and D == Dk == Dv
            and D <= MAX_HEAD_DIM and K > 0 and H % K == 0 and not precise)


def _operand(dtype, interpret: bool) -> str:
    """The dtype of every dot's operands, as XLA would run an f32 dot where
    the kernel runs: bf16 for f32 inputs under the default matmul precision
    on the TPU (one MXU pass), or under a one-pass setting anywhere; else
    the inputs' own (the CPU's default is f32)."""
    dtype = jnp.dtype(dtype)
    setting = jax.config.jax_default_matmul_precision
    one_pass = setting in ONE_PASS_PRECISIONS or (
        setting in DEFAULT_PRECISIONS and not interpret)
    return "bfloat16" if dtype == jnp.float32 and one_pass else dtype.name


def _tile(n: int, block: Optional[int], whole: int = MAX_BLOCK
          ) -> Tuple[int, int]:
    """(padded length, block) for ``n`` rows: pad to a multiple of 128;
    one block up to ``whole`` rows, else the largest block up to
    ``MAX_BLOCK`` that divides it (or pad to a multiple of an explicit
    ``block``)."""
    if block is not None:
        return -(-n // block) * block, block
    padded = -(-n // LANES) * LANES
    if padded <= whole:
        return padded, padded
    b = MAX_BLOCK
    while padded % b:
        b //= 2
    return padded, b


def _div(x, d: int):
    """Floor division of a non-negative static or traced int."""
    return x // d if isinstance(x, int) else lax.div(x, d)


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """Static shape of one call: sequence lengths, blocks and mask."""
    T: int
    S: int
    bq: int
    bk: int
    causal: bool
    window: int
    scale: float
    rep: int
    interpret: bool
    dtypes: Tuple[str, str, str]        # of q, k, v: their gradients'
    operand: str                        # of every dot's operands

    @property
    def Tp(self) -> int:
        return -(-self.T // self.bq) * self.bq

    @property
    def Sp(self) -> int:
        return -(-self.S // self.bk) * self.bk

    @property
    def nq(self) -> int:
        return self.Tp // self.bq

    @property
    def nk(self) -> int:
        return self.Sp // self.bk

    @property
    def offs(self) -> int:
        """Global position of query row 0: the last query aligns with the
        last key (a suffix of new tokens against a longer KV prefix)."""
        return self.S - self.T

    # --- which blocks carry any visible (query, key) pair -------------
    def needed(self, qi, kj):
        q_lo = self.offs + qi * self.bq
        ok = kj * self.bk < self.S
        if self.causal:
            ok &= kj * self.bk <= q_lo + self.bq - 1
        if self.window > 0:
            ok &= kj * self.bk + self.bk - 1 > q_lo - self.window
        return ok

    def unmasked(self, qi, kj):
        """Every pair of the block visible: no mask to compute."""
        q_lo = self.offs + qi * self.bq
        ok = (kj + 1) * self.bk <= self.S
        if self.causal:
            ok &= kj * self.bk + self.bk - 1 <= q_lo
        if self.window > 0:
            ok &= kj * self.bk > q_lo + self.bq - 1 - self.window
        return ok

    def kv_block(self, qi, kj):
        """``kj`` clamped to the kv blocks query block ``qi`` needs."""
        q_lo = self.offs + qi * self.bq
        if self.causal:
            kj = jnp.minimum(kj, _div(jnp.maximum(q_lo + self.bq - 1, 0),
                                      self.bk))
        if self.window > 0:
            kj = jnp.maximum(kj, _div(jnp.maximum(q_lo - self.window + 1, 0),
                                      self.bk))
        return jnp.clip(kj, 0, self.nk - 1)

    def q_block(self, kj, qi):
        """``qi`` clamped to the query blocks that need kv block ``kj``."""
        k_lo = kj * self.bk
        if self.causal:
            qi = jnp.maximum(qi, _div(jnp.maximum(k_lo - self.offs, 0),
                                      self.bq))
        if self.window > 0:
            qi = jnp.minimum(qi, _div(jnp.maximum(
                k_lo + self.bk - 1 - self.offs + self.window - 1, 0),
                self.bq))
        return jnp.clip(qi, 0, self.nq - 1)

    def mask(self, qi, kj, transposed: bool = False):
        """Visible pairs of block (qi, kj): (bq, bk), or (bk, bq)."""
        shape = (self.bk, self.bq) if transposed else (self.bq, self.bk)
        qdim, kdim = (1, 0) if transposed else (0, 1)
        qpos = (self.offs + qi * self.bq
                + lax.broadcasted_iota(jnp.int32, shape, qdim))
        kpos = kj * self.bk + lax.broadcasted_iota(jnp.int32, shape, kdim)
        ok = kpos < self.S
        if self.causal:
            ok &= kpos <= qpos
        if self.window > 0:
            ok &= kpos > qpos - self.window
        return ok


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A (rows, 128) lane-replicated column widened to ``n`` lanes."""
    return jnp.tile(x, (1, -(-n // LANES)))[:, :n]


def _blocks(g: _Geometry, qi, kj, body):
    """Run ``body(masked)`` on a needed block; the mask only where some
    pair of the block is hidden."""
    run, full = g.needed(qi, kj), g.unmasked(qi, kj)
    pl.when(run & full)(lambda: body(False))
    pl.when(run & jnp.logical_not(full))(lambda: body(True))


def _compiler(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


# ---------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, g: _Geometry):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        s = lax.dot_general(q_ref[0], k_ref[0], _NT,
                            preferred_element_type=jnp.float32) * g.scale
        if masked:
            s = jnp.where(g.mask(qi, kj), s, NEG_INF)
        m_prev = m_ref[...]                                  # (bq, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, g.bk))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[0], _NN,
                             preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _lanes(corr, acc_ref.shape[1]) + pv

    _blocks(g, qi, kj, body)

    @pl.when(kj == g.nk - 1)
    def _finish():
        l = l_ref[...]
        inv = 1.0 / jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_ref[...] * _lanes(inv, acc_ref.shape[1])
                    ).astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))    # (bq, 128)
        lse_ref[0] = lse.T[:1]                               # (1, bq)


def _forward(q3, k3, v3, g: _Geometry):
    """q3: (B*H, Tp, D); k3, v3: (B*K, Sp, D).
    Returns o (B*H, Tp, D) and lse (B*H, 1, Tp), both f32."""
    BH, _, D = q3.shape

    def q_map(bh, qi, kj):
        return (bh, qi, 0)

    def kv_map(bh, qi, kj):
        return (bh // g.rep, g.kv_block(qi, kj), 0)

    def lse_map(bh, qi, kj):
        return (bh, 0, qi)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, g=g),
        grid=(BH, g.nq, g.nk),
        in_specs=[pl.BlockSpec((1, g.bq, D), q_map),
                  pl.BlockSpec((1, g.bk, D), kv_map),
                  pl.BlockSpec((1, g.bk, D), kv_map)],
        out_specs=[pl.BlockSpec((1, g.bq, D), q_map),
                   pl.BlockSpec((1, 1, g.bq), lse_map)],
        out_shape=[jax.ShapeDtypeStruct((BH, g.Tp, D), jnp.float32),
                   jax.ShapeDtypeStruct((BH, 1, g.Tp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g.bq, LANES), jnp.float32),
                        pltpu.VMEM((g.bq, LANES), jnp.float32),
                        pltpu.VMEM((g.bq, D), jnp.float32)],
        compiler_params=_compiler("parallel", "parallel", "arbitrary"),
        interpret=g.interpret,
        name="flash_attention_fwd",
    )(q3, k3, v3)


# ---------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref,
               *, g: _Geometry):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        s = lax.dot_general(q_ref[0], k_ref[0], _NT,
                            preferred_element_type=jnp.float32) * g.scale
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        if masked:
            p = jnp.where(g.mask(qi, kj), p, 0.0)
        dp = lax.dot_general(do_ref[0], v_ref[0], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, 0][:, None])
        acc_ref[...] += lax.dot_general(ds.astype(k_ref.dtype), k_ref[0], _NN,
                                        preferred_element_type=jnp.float32)

    _blocks(g, qi, kj, body)

    @pl.when(kj == g.nk - 1)
    def _finish():
        dq_ref[0] = (acc_ref[...] * g.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, g: _Geometry):
    kj, i = pl.program_id(1), pl.program_id(2)
    qi = lax.rem(i, g.nq)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        # Transposed scores (bk, bq): the row statistics broadcast along
        # lanes, and dk, dv come out of plain (bk, bq) @ (bq, D) dots.
        st = lax.dot_general(k_ref[0], q_ref[0], _NT,
                             preferred_element_type=jnp.float32) * g.scale
        pt = jnp.exp(st - lse_ref[0])
        if masked:
            pt = jnp.where(g.mask(qi, kj, transposed=True), pt, 0.0)
        dv_acc[...] += lax.dot_general(pt.astype(do_ref.dtype), do_ref[0], _NN,
                                       preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[0], do_ref[0], _NT,
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0])
        dk_acc[...] += lax.dot_general(dst.astype(q_ref.dtype), q_ref[0], _NN,
                                       preferred_element_type=jnp.float32)

    _blocks(g, qi, kj, body)

    @pl.when(i == g.rep * g.nq - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * g.scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _backward(q3, k3, v3, do3, lse, di, g: _Geometry):
    BH, _, D = q3.shape
    BK = k3.shape[0]

    def q_map(bh, qi, kj):
        return (bh, qi, 0)

    def kv_map(bh, qi, kj):
        return (bh // g.rep, g.kv_block(qi, kj), 0)

    def row_map(bh, qi, kj):
        return (bh, 0, qi)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, g=g),
        grid=(BH, g.nq, g.nk),
        in_specs=[pl.BlockSpec((1, g.bq, D), q_map),
                  pl.BlockSpec((1, g.bk, D), kv_map),
                  pl.BlockSpec((1, g.bk, D), kv_map),
                  pl.BlockSpec((1, g.bq, D), q_map),
                  pl.BlockSpec((1, 1, g.bq), row_map),
                  pl.BlockSpec((1, 1, g.bq), row_map)],
        out_specs=pl.BlockSpec((1, g.bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((BH, g.Tp, D), g.dtypes[0]),
        scratch_shapes=[pltpu.VMEM((g.bq, D), jnp.float32)],
        compiler_params=_compiler("parallel", "parallel", "arbitrary"),
        interpret=g.interpret,
        name="flash_attention_dq",
    )(q3, k3, v3, do3, lse, di)

    # dk, dv: kv head ``b`` of the (B*K) axis sums over its group's query
    # heads b*rep + r and their q blocks: inner index i = r * nq + qi.
    def head_q(b, kj, i):
        return (b * g.rep + _div(i, g.nq),
                g.q_block(kj, lax.rem(i, g.nq)), 0)

    def head_row(b, kj, i):
        h, qi, _ = head_q(b, kj, i)
        return (h, 0, qi)

    def kv_own(b, kj, i):
        return (b, kj, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, g=g),
        grid=(BK, g.nk, g.rep * g.nq),
        in_specs=[pl.BlockSpec((1, g.bq, D), head_q),
                  pl.BlockSpec((1, g.bk, D), kv_own),
                  pl.BlockSpec((1, g.bk, D), kv_own),
                  pl.BlockSpec((1, g.bq, D), head_q),
                  pl.BlockSpec((1, 1, g.bq), head_row),
                  pl.BlockSpec((1, 1, g.bq), head_row)],
        out_specs=[pl.BlockSpec((1, g.bk, D), kv_own),
                   pl.BlockSpec((1, g.bk, D), kv_own)],
        out_shape=[jax.ShapeDtypeStruct((BK, g.Sp, D), g.dtypes[1]),
                   jax.ShapeDtypeStruct((BK, g.Sp, D), g.dtypes[2])],
        scratch_shapes=[pltpu.VMEM((g.bk, D), jnp.float32),
                        pltpu.VMEM((g.bk, D), jnp.float32)],
        compiler_params=_compiler("parallel", "parallel", "arbitrary"),
        interpret=g.interpret,
        name="flash_attention_dkv",
    )(q3, k3, v3, do3, lse, di)
    return dq, dk, dv


# ---------------------------------------------------------------- layout
def _heads_major(x: jax.Array, n: int, dtype: str) -> jax.Array:
    """(B, N, H, D) -> (B*H, n, D) in ``dtype``, zero-padded to ``n`` rows."""
    B, N, H, D = x.shape
    x = jnp.moveaxis(x, 2, 1).reshape(B * H, N, D).astype(dtype)
    return jnp.pad(x, ((0, 0), (0, n - N), (0, 0)))


def _seq_major(x: jax.Array, B: int, N: int) -> jax.Array:
    """(B*H, n, D) -> (B, N, H, D)."""
    BH, _, D = x.shape
    return jnp.moveaxis(x[:, :N].reshape(B, BH // B, N, D), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(q, k, v, g: _Geometry):
    return _attend_fwd(q, k, v, g)[0]


def _attend_fwd(q, k, v, g: _Geometry):
    q3, k3, v3 = (_heads_major(q, g.Tp, g.operand),
                  _heads_major(k, g.Sp, g.operand),
                  _heads_major(v, g.Sp, g.operand))
    o3, lse = _forward(q3, k3, v3, g)
    o = _seq_major(o3, q.shape[0], g.T).astype(q.dtype)
    return o, (q3, k3, v3, o3, lse)


def _attend_bwd(g: _Geometry, res, do):
    q3, k3, v3, o3, lse = res
    B, T, _, _ = do.shape
    do3 = _heads_major(do, g.Tp, g.operand)
    # di = rowsum(dO * O), the softmax's own term of ds, from the dO that
    # the kernels' dp = dO v^T takes and the f32 O, so that sum_k ds stays
    # near 0 per row.  A mismatch delta (a bf16 O, or an f32 dO against
    # the bf16 one in dp) leaves sum_k ds = -delta: a bias of dq along
    # the mean key, which the softmax's shift invariance says is absent.
    di = jnp.sum(do3.astype(jnp.float32) * o3, axis=-1)[:, None, :]
    dq3, dk3, dv3 = _backward(q3, k3, v3, do3, lse, di, g)
    return (_seq_major(dq3, B, T), _seq_major(dk3, B, g.S),
            _seq_major(dv3, B, g.S))


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B,T,H,D); k,v: (B,S,K,D).  Returns (B,T,H,D); differentiable.

    Blocks follow from the shapes (``_tile``); ``block_q`` / ``block_k``
    override them for small-shape validation.  The dots' operands follow
    ``jax.default_matmul_precision`` (``_operand``).  ``interpret=True``
    runs the kernel bodies on the CPU.
    """
    B, T, H, D = q.shape
    _, S, K, _ = k.shape
    _, bq = _tile(T, block_q)
    _, bk = _tile(S, block_k, MAX_KV_BLOCK)
    g = _Geometry(T=T, S=S, bq=bq, bk=bk, causal=causal, window=window,
                  scale=float(scale if scale is not None else D ** -0.5),
                  rep=H // K, interpret=interpret,
                  dtypes=(q.dtype.name, k.dtype.name, v.dtype.name),
                  operand=_operand(q.dtype, interpret))
    return _attend(q, k, v, g)
