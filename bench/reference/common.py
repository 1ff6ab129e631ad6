"""Plain float32 pieces shared by the references, and the weight maker.

Nothing here imports the program under test.  Every matrix product goes
through :func:`mm`, which runs at float32 ``highest`` precision unless a
lower ``lowp`` dtype is given; then both operands are rounded to it
first.  That rounding, together with parameters stored in ``lowp``, is
the lower-precision control that ``correct`` has to reject.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
          "float8_e4m3fn": jnp.float8_e4m3fn}
# Inside a jitted function XLA may drop a convert from float32 to
# bfloat16 and back (excess precision); it never drops a
# ``reduce_precision``, which rounds to the same values.  float8_e4m3fn
# has subnormals that ``reduce_precision`` would flush, and its converts
# are kept, so it rounds through its own dtype.
BITS = {"bfloat16": (8, 7)}
# One step down from each stated precision (the control's precision).
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


class Leaf(NamedTuple):
    """One parameter: its shape, stored dtype and how it is drawn."""
    shape: tuple
    dtype: str
    init: str            # "normal" | "ones" | "zeros"
    fan_in: int = 1


def init(key, layout) -> Any:
    """Weights for ``layout`` from ``key``: a normal draw scaled by
    fan_in ** -0.5 for matrices, ones and zeros for norm scales and
    biases, each leaf on its own fold of the key, stored in its dtype."""
    leaves, treedef = jax.tree.flatten(
        layout, is_leaf=lambda x: isinstance(x, Leaf))
    out = []
    for i, leaf in enumerate(leaves):
        if leaf.init == "normal":
            x = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32) * (leaf.fan_in ** -0.5)
        elif leaf.init == "ones":
            x = jnp.ones(leaf.shape, jnp.float32)
        else:
            x = jnp.zeros(leaf.shape, jnp.float32)
        out.append(x.astype(DTYPES[leaf.dtype]))
    return jax.tree.unflatten(treedef, out)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, also past 32 bits."""
    lo, hi = seed & 0x7FFFFFFF, (seed >> 31) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def audio_frames(key, batch: int, enc_len: int, d_model: int, dtype: str
                 ) -> jax.Array:
    """The stub audio frontend's frame embeddings: N(0, 0.02**2)."""
    x = jax.random.normal(key, (batch, enc_len, d_model), jnp.float32)
    return (x * 0.02).astype(DTYPES[dtype])


def padded_vocab(model: dict) -> int:
    m = model.get("pad_vocab_to", 1)
    return -(-model["vocab"] // m) * m


# ---------------------------------------------------------------- layers
def round_to(x: jax.Array, dtype: str) -> jax.Array:
    """float32 ``x`` rounded to the values ``dtype`` holds, kept float32."""
    if dtype == "float32":
        return x
    if dtype in BITS:
        e, m = BITS[dtype]
        return jax.lax.reduce_precision(x, exponent_bits=e, mantissa_bits=m)
    return x.astype(DTYPES[dtype]).astype(jnp.float32)


def make_mm(lowp: Optional[str]) -> Callable:
    def mm(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
        if lowp is not None:
            a, b = round_to(a, lowp), round_to(b, lowp)
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    return mm


def layernorm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions 0..T-1 on (B, T, H, D): the first and second
    halves of D are the two coordinates of each rotated pair."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def attention(mm, p: dict, x: jax.Array, src: jax.Array, *, causal: bool,
              rotary: Optional[float]) -> jax.Array:
    """Multi-head attention of ``x`` over ``src``; no biases."""
    q = mm("btd,dhk->bthk", x, p["wq"])
    k = mm("bsd,dhk->bshk", src, p["wk"])
    v = mm("bsd,dhk->bshk", src, p["wv"])
    if rotary is not None:
        q, k = rope(q, rotary), rope(k, rotary)
    s = mm("bthk,bshk->bhts", q, k) / math.sqrt(q.shape[-1])
    if causal:
        t, n = s.shape[-2:]
        s = jnp.where(jnp.tril(jnp.ones((t, n), bool)), s, -jnp.inf)
    o = mm("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v)
    return mm("bthk,hkd->btd", o, p["wo"])


def ffn_gelu(mm, p: dict, x: jax.Array) -> jax.Array:
    return mm("btf,fd->btd", gelu_tanh(mm("btd,df->btf", x, p["wi"])),
              p["wo"])


def layers(body: Callable, x: jax.Array, stacked: dict) -> jax.Array:
    """Apply ``body(x, layer_params)`` over the stacked layers, keeping
    only each layer's input for the backward pass."""
    def step(x, lp):
        return body(x, lp), None
    return jax.lax.scan(jax.checkpoint(step), x, stacked)[0]


def ce_sum(mm, head: jax.Array, x: jax.Array, labels: jax.Array,
           vocab: int) -> jax.Array:
    """Summed next-token cross entropy over the real vocabulary."""
    logits = mm("btd,dv->btv", x, head[:, :vocab])
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)


# ------------------------------------------------------------- training
# The reference's training state on the device, in bytes a parameter:
# float32 params, AdamW's m and v, and the gradient accumulator.
STATE_BYTES_PER_PARAM = 16


def param_count(layout) -> int:
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(
        layout, is_leaf=lambda x: isinstance(x, Leaf)))


def leaf_norms(tree) -> List[float]:
    return [float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
            for x in jax.tree.leaves(tree)]


def change_norms(params, p0: List[np.ndarray]) -> List[float]:
    """Per leaf |params - p0|, with ``p0`` the start weights on the host:
    one leaf of it on the device at a time."""
    return [leaf_norms(p - jnp.asarray(q))[0]
            for p, q in zip(jax.tree.leaves(params), p0)]


class Readings(NamedTuple):
    """What the comparison reads from three steps of a training run."""
    losses: List[float]           # loss of steps 1, 2, 3
    grad_norms: List[float]       # per leaf: step 1's gradient, unclipped
    update1_norms: List[float]    # per leaf: |params after 1 - params at 0|
    update_norms: List[float]     # per leaf: |params after 3 - params at 0|


def store(p: jax.Array, dtype: str) -> jax.Array:
    return round_to(p.astype(jnp.float32), dtype)


# Each step is a jitted call of its own, so that XLA fuses no add or
# divide into a neighbouring kernel, where it could round differently.
@partial(jax.jit, donate_argnums=0)
def accumulate(acc, g):
    """``acc + g`` leaf by leaf, into ``acc``'s buffers."""
    return jax.tree.map(jnp.add, acc, g)


@partial(jax.jit, donate_argnums=0)
def mean_over(acc, ntok):
    """``acc / ntok`` leaf by leaf, into ``acc``'s buffers."""
    return jax.tree.map(lambda g: g / ntok, acc)


def make_adamw(opt: dict, stored) -> Callable:
    """The jitted AdamW step ``(p, g, m, v, step) -> (p, m, v)``: clip by
    the global norm, moments, bias correction, decoupled decay on rank
    >= 2 leaves, ``store`` in each leaf's dtype of ``stored``.  ``p``,
    ``m`` and ``v`` are donated and the results written into their
    buffers; ``g`` has no result to go into, and its caller drops it."""
    @partial(jax.jit, donate_argnums=(0, 2, 3))
    def adamw(p, g, m, v, step):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))

        def upd(p, g, m, v, dtype):
            g = g * clip
            m = opt["b1"] * m + (1 - opt["b1"]) * g
            v = opt["b2"] * v + (1 - opt["b2"]) * g * g
            mhat = m / (1 - opt["b1"] ** step)
            vhat = v / (1 - opt["b2"] ** step)
            delta = mhat / (jnp.sqrt(vhat) + opt["eps"])
            if p.ndim >= 2:       # decoupled decay on rank >= 2 leaves
                delta = delta + opt["weight_decay"] * p
            return store(p - opt["lr"] * delta, dtype), m, v

        out = jax.tree.map(upd, p, g, m, v, stored)
        pick = [jax.tree.map(lambda _, o: o[i], p, out) for i in range(3)]
        return tuple(pick)
    return adamw


def train3(ref, model: dict, opt: dict, layout, key, rows: List[dict],
           lowp: Optional[str] = None, block_rows: int = 1) -> Readings:
    """Three AdamW steps of the reference ``ref`` from the weights that
    ``init(key, layout)`` makes, over ``rows[k]`` (the batch of step k+1:
    a dict of host arrays, one row per sequence).  ``lowp`` runs the
    control: operands and stored parameters rounded to that dtype.

    The device holds at most ``STATE_BYTES_PER_PARAM`` bytes a parameter
    besides one block's activations: the start weights stay on the host,
    each block's gradient is folded into one donated accumulator, and m
    and v wait on the host while the gradients are taken (else they and
    a block's whole gradient would be 20 bytes), coming back for the
    update, which writes into their buffers."""
    mm = make_mm(lowp)
    stored = jax.tree.map(lambda leaf: leaf.dtype, layout,
                          is_leaf=lambda x: isinstance(x, Leaf))
    if lowp is not None:
        stored = jax.tree.map(lambda d: LOWER[d], stored)

    grad_block = jax.jit(jax.value_and_grad(
        lambda p, blk: ref.loss_sum(mm, p, blk, model)))
    adamw = make_adamw(opt, stored)

    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda x, d: store(x.astype(jnp.float32), d),
                              init(key, layout), stored)
        p0 = jax.device_get(jax.tree.leaves(params))
        moments = None            # (m, v) on the host between updates
        losses, grad_norms, update1 = [], [], []
        for k, batch in enumerate(rows):
            n = next(iter(batch.values())).shape[0]
            total, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
            for r in range(0, n, block_rows):
                blk = {name: jnp.asarray(a[r:r + block_rows])
                       for name, a in batch.items()}
                ls, g = grad_block(params, blk)
                total = total + ls
                # Wait for the sum: the next block's gradient is then
                # allocated after this one is freed, not beside it.
                grads = jax.block_until_ready(accumulate(grads, g))
                del g
            ntok = n * batch["labels"].shape[1]
            grads = mean_over(grads, ntok)
            losses.append(float(total) / ntok)
            if k == 0:
                grad_norms = leaf_norms(grads)
                m = jax.tree.map(jnp.zeros_like, params)
                v = jax.tree.map(jnp.zeros_like, params)
            else:
                m, v = jax.device_put(moments)
            params, m, v = adamw(params, grads, m, v, float(k + 1))
            del grads
            if k == 0:
                update1 = change_norms(params, p0)
            moments = jax.device_get((m, v)) if k + 1 < len(rows) else None
            del m, v
        return Readings(losses, grad_norms, update1,
                        change_norms(params, p0))


def gaps(prog: Readings, ref: Readings) -> Dict[str, float]:
    """The compared numbers: relative gaps of the program's readings to
    the reference's.  A norm's gap is taken per leaf against the larger
    of that leaf's reference norm and the median leaf's, and the worst
    leaf counts.  Leaves whose reference gradient is under a thousandth
    of the median leaf's are left out of the update's gap: Adam moves
    them by round-off alone."""
    def worst(p, r, keep):
        med = float(np.median(r))
        return max(abs(a - b) / max(b, med, 1e-30)
                   for a, b, k in zip(p, r, keep) if k)

    gmed = float(np.median(ref.grad_norms))
    moved = [g >= 1e-3 * gmed for g in ref.grad_norms]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog.losses, ref.losses)),
        "grad_gap": worst(prog.grad_norms, ref.grad_norms,
                          [True] * len(moved)),
        "update1_gap": worst(prog.update1_norms, ref.update1_norms, moved),
        "update_gap": worst(prog.update_norms, ref.update_norms, moved),
    }
