"""Model FLOPs of one training step, from a configuration's shapes.

Counted: every matrix multiplication the model needs (projections,
feed-forward, output head, attention scores and their weighted sums) at
two operations per multiply-add, forward once and backward twice.  Not
counted: recomputation under activation checkpointing, padding of the
vocabulary or of attention chunks, norms, softmax and the optimizer
(elementwise work).  Causal self-attention needs only the lower
triangle of its scores, so it counts T(T+1)/2 query-key pairs.
"""

from __future__ import annotations


def _attn_proj(d: int, heads: int, kv_heads: int, head_dim: int) -> int:
    return d * (heads + 2 * kv_heads) * head_dim + heads * head_dim * d


def forward_flops_per_row(model: dict, seq: int) -> float:
    """Forward FLOPs for one sequence (one row of the batch)."""
    d, heads = model["d_model"], model["n_heads"]
    kv_heads = model.get("n_kv_heads") or heads
    hd = model.get("d_head") or d // heads
    ff = (3 if model.get("ffn", "swiglu") in ("swiglu", "geglu") else 2) \
        * d * model["d_ff"]
    layers, t = model["n_layers"], seq
    causal_pairs = t * (t + 1) / 2
    f = 2.0 * t * (layers * (_attn_proj(d, heads, kv_heads, hd) + ff)
                   + d * model["vocab"])
    f += layers * 4.0 * heads * hd * causal_pairs
    if model["kind"] == "encdec":
        s, enc_layers = model["enc_len"], model["enc_layers"]
        # Encoder: bidirectional self-attention over all s x s pairs.
        f += 2.0 * s * enc_layers * (_attn_proj(d, heads, heads, hd) + ff)
        f += enc_layers * 4.0 * heads * hd * s * s
        # Decoder cross-attention: q and out on the t tokens, k and v on
        # the s encoder frames (in every decoder layer), t x s scores.
        f += layers * (2.0 * t * 2 * d * heads * hd
                       + 2.0 * s * 2 * d * heads * hd
                       + 4.0 * heads * hd * t * s)
    return f


def train_flops_per_step(model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: forward plus a backward of twice
    the forward, over ``batch`` rows of ``seq`` tokens."""
    return 3.0 * batch * forward_flops_per_row(model, seq)
