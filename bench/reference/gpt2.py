"""Plain float32 reference of the decoder the GPT-2 configuration runs.

GPT-2 small (Radford et al. 2019) as the program builds it: pre-LN
blocks of multi-head causal self-attention and a tanh-GELU feed-forward,
a final layer norm and an untied output head.  The departures from the
published model are the configuration file's ``deviations``: rotary
positions in place of learned ones, no biases in the projections, an
untied head, token embeddings scaled by sqrt(d_model).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from bench.reference import common as C
from bench.reference.common import Leaf


def norm_layout(model: dict, depth: int = 0) -> dict:
    shape = ((depth,) if depth else ()) + (model["d_model"],)
    return {"scale": Leaf(shape, "float32", "ones"),
            "bias": Leaf(shape, "float32", "zeros")}


def attn_layout(model: dict, depth: int) -> dict:
    d, h, dt = model["d_model"], model["n_heads"], model["dtype"]
    hd = d // h
    return {"wq": Leaf((depth, d, h, hd), dt, "normal", d),
            "wk": Leaf((depth, d, h, hd), dt, "normal", d),
            "wv": Leaf((depth, d, h, hd), dt, "normal", d),
            "wo": Leaf((depth, h, hd, d), dt, "normal", h * hd)}


def block_layout(model: dict, depth: int) -> dict:
    d, f, dt = model["d_model"], model["d_ff"], model["dtype"]
    return {"norm1": norm_layout(model, depth),
            "mixer": attn_layout(model, depth),
            "norm2": norm_layout(model, depth),
            "ffn": {"wi": Leaf((depth, d, f), dt, "normal", d),
                    "wo": Leaf((depth, f, d), dt, "normal", f)}}


def layout(model: dict) -> dict:
    d, vp, dt = model["d_model"], C.padded_vocab(model), model["dtype"]
    return {"embed": {"table": Leaf((vp, d), dt, "normal", d),
                      "head": Leaf((d, vp), dt, "normal", d)},
            "final_norm": norm_layout(model),
            "blocks": {"b0": block_layout(model, model["n_layers"])}}


def decoder_block(mm, model: dict, x, lp, enc_out=None):
    eps, theta = model["layer_norm_epsilon"], model["rope_theta"]
    h = C.layernorm(lp["norm1"], x, eps)
    x = x + C.attention(mm, lp["mixer"], h, h, causal=True, rotary=theta)
    if enc_out is not None:
        h = C.layernorm(lp["norm_c"], x, eps)
        x = x + C.attention(mm, lp["cross"], h, enc_out, causal=False,
                            rotary=None)
    h = C.layernorm(lp["norm2"], x, eps)
    return x + C.ffn_gelu(mm, lp["ffn"], h)


def decode(mm, params: dict, batch: dict, model: dict, enc_out=None):
    """Summed cross entropy of the decoder over one block of rows."""
    x = params["embed"]["table"][batch["tokens"]] * math.sqrt(
        model["d_model"])
    x = C.layers(lambda x, lp: decoder_block(mm, model, x, lp, enc_out),
                 x, params["blocks"]["b0"])
    x = C.layernorm(params["final_norm"], x, model["layer_norm_epsilon"])
    return C.ce_sum(mm, params["embed"]["head"], x, batch["labels"],
                    model["vocab"])


def loss_sum(mm, params: dict, batch: dict, model: dict):
    return decode(mm, params, {k: jnp.asarray(v) for k, v in batch.items()},
                  model)
