"""Plain float32 reference of the encoder-decoder the whisper-small
configuration runs (arXiv:2212.04356).

The encoder takes the stub frontend's frame embeddings through
pre-LN bidirectional self-attention blocks and a final layer norm; each
decoder block adds cross-attention over the encoder's output between
its causal self-attention and its feed-forward.  The departures from
the published model are the configuration file's ``deviations``:
rotary positions in the encoder and the decoder self-attention (none in
cross-attention), a stub in place of the convolutional frontend, no
biases in the projections, an untied head, tanh-GELU, token embeddings
scaled by sqrt(d_model).
"""

from __future__ import annotations

import jax.numpy as jnp

from bench.reference import common as C
from bench.reference import gpt2


def layout(model: dict) -> dict:
    out = gpt2.layout(model)
    depth = model["n_layers"]
    out["blocks"]["b0"]["norm_c"] = gpt2.norm_layout(model, depth)
    out["blocks"]["b0"]["cross"] = gpt2.attn_layout(model, depth)
    out["enc_blocks"] = gpt2.block_layout(model, model["enc_layers"])
    out["enc_final_norm"] = gpt2.norm_layout(model)
    return out


def encode(mm, params: dict, frames, model: dict):
    eps, theta = model["layer_norm_epsilon"], model["rope_theta"]

    def block(x, lp):
        h = C.layernorm(lp["norm1"], x, eps)
        x = x + C.attention(mm, lp["mixer"], h, h, causal=False,
                            rotary=theta)
        h = C.layernorm(lp["norm2"], x, eps)
        return x + C.ffn_gelu(mm, lp["ffn"], h)

    x = C.layers(block, frames.astype(jnp.float32), params["enc_blocks"])
    return C.layernorm(params["enc_final_norm"], x, eps)


def loss_sum(mm, params: dict, batch: dict, model: dict):
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    enc_out = encode(mm, params, batch["frames"], model)
    return gpt2.decode(mm, params, batch, model, enc_out)
