"""Model FLOPs of one training step, from a configuration's shapes.

Counted: every matrix multiplication the model needs (projections,
feed-forward, output head, attention scores and their weighted sums) at
two operations per multiply-add, forward once and backward twice.  Not
counted: recomputation under activation checkpointing, padding of the
vocabulary or of attention chunks, norms, softmax and the optimizer
(elementwise work).  Causal self-attention needs only the lower
triangle of its scores, so it counts T(T+1)/2 query-key pairs.

A configuration whose blocks this count does not describe brings its
own ``train_flops_per_step`` (and ``attention_flops_per_step`` and
``attention_bytes_per_step``) in its reference module.
"""

from __future__ import annotations

from typing import Iterator, Tuple

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def _attn_proj(d: int, heads: int, kv_heads: int, head_dim: int) -> int:
    return d * (heads + 2 * kv_heads) * head_dim + heads * head_dim * d


def _head_width(model: dict) -> int:
    """Heads times head size: the width of q, k and v."""
    heads = model["n_heads"]
    return heads * (model.get("d_head") or model["d_model"] // heads)


def _attention_calls(model: dict, seq: int
                     ) -> Iterator[Tuple[int, int, int, float]]:
    """(layers, query rows, kv rows, query-key pairs) of each kind of
    attention in one sequence: the decoder's causal self-attention and,
    for an encoder-decoder, the encoder's bidirectional self-attention
    and the decoder's cross-attention over the encoder's frames."""
    layers, t = model["n_layers"], seq
    yield layers, t, t, t * (t + 1) / 2
    if model["kind"] == "encdec":
        s = model["enc_len"]
        yield model["enc_layers"], s, s, s * s
        yield layers, t, s, t * s


def attention_flops_per_row(model: dict, seq: int) -> float:
    """Forward FLOPs of attention's scores and weighted sums for one
    sequence (the work under the program's ``attention`` scope; the
    projections around it are not counted here)."""
    w = _head_width(model)
    return sum(n * 4.0 * w * pairs
               for n, _, _, pairs in _attention_calls(model, seq))


def forward_flops_per_row(model: dict, seq: int) -> float:
    """Forward FLOPs for one sequence (one row of the batch)."""
    d, heads = model["d_model"], model["n_heads"]
    kv_heads = model.get("n_kv_heads") or heads
    hd = model.get("d_head") or d // heads
    ff = (3 if model.get("ffn", "swiglu") in ("swiglu", "geglu") else 2) \
        * d * model["d_ff"]
    layers, t = model["n_layers"], seq
    f = 2.0 * t * (layers * (_attn_proj(d, heads, kv_heads, hd) + ff)
                   + d * model["vocab"])
    if model["kind"] == "encdec":
        s, enc_layers = model["enc_len"], model["enc_layers"]
        # Encoder: every projection and feed-forward on the s frames.
        f += 2.0 * s * enc_layers * (_attn_proj(d, heads, heads, hd) + ff)
        # Decoder cross-attention: q and out on the t tokens, k and v on
        # the s encoder frames (in every decoder layer).
        f += layers * (2.0 * t * 2 * d * heads * hd
                       + 2.0 * s * 2 * d * heads * hd)
    return f + attention_flops_per_row(model, seq)


def train_flops_per_step(model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: forward plus a backward of twice
    the forward, over ``batch`` rows of ``seq`` tokens."""
    return 3.0 * batch * forward_flops_per_row(model, seq)


def attention_flops_per_step(model: dict, batch: int, seq: int) -> float:
    """Attention's part of ``train_flops_per_step``: its scores and
    weighted sums, forward and backward, over the batch."""
    return 3.0 * batch * attention_flops_per_row(model, seq)


def attention_bytes_per_step(model: dict, batch: int, seq: int) -> float:
    """The least HBM traffic of attention in one training step: each
    call reads q, k and v and writes its output; its backward reads
    those four and the output's gradient and writes the gradients of q,
    k and v.  A query-side tensor has ``t`` rows and a key-side one
    ``s``, so a call moves 6 (t + s) rows of the heads' width."""
    w = _head_width(model) * ITEMSIZE[model["dtype"]]
    return batch * sum(n * 6.0 * (t + s) * w
                       for n, t, s, _ in _attention_calls(model, seq))
