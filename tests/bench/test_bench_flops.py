"""Model FLOPs of a training step against a count by hand."""

import pytest

from bench.flops import train_flops_per_step


def test_decoder_by_hand():
    # d=4, 2 heads of 2, d_ff=8 (gelu), vocab 10, 1 layer, seq 3, batch 2.
    m = {"kind": "decoder", "n_layers": 1, "d_model": 4, "n_heads": 2,
         "n_kv_heads": 2, "d_ff": 8, "vocab": 10, "ffn": "gelu"}
    # per token: q,k,v,o 4*16 = 64 MACs, ffn 2*32 = 64, head 40: 168 MACs
    per_token = 2 * 168
    # causal pairs 3*4/2 = 6; QK and PV: 2 heads x 2 dims x 2 flops x 2
    attn = 6 * 2 * 2 * 2 * 2
    fwd = 3 * per_token + attn
    assert train_flops_per_step(m, batch=2, seq=3) == pytest.approx(
        3 * 2 * fwd)


def test_encoder_decoder_by_hand():
    m = {"kind": "encdec", "n_layers": 1, "enc_layers": 1, "d_model": 4,
         "n_heads": 2, "n_kv_heads": 2, "d_ff": 8, "vocab": 10,
         "ffn": "gelu", "enc_len": 5}
    dec = 3 * 2 * 168 + 6 * 16
    enc = 5 * 2 * (64 + 64) + 25 * 16       # all 5x5 pairs
    cross = 3 * 2 * 32 + 5 * 2 * 32 + 3 * 5 * 16
    assert train_flops_per_step(m, batch=1, seq=3) == pytest.approx(
        3 * (dec + enc + cross))


def test_swiglu_counts_three_matrices():
    m = {"kind": "decoder", "n_layers": 1, "d_model": 4, "n_heads": 2,
         "n_kv_heads": 2, "d_ff": 8, "vocab": 10, "ffn": "gelu"}
    gated = dict(m, ffn="swiglu")
    diff = train_flops_per_step(gated, 1, 1) - train_flops_per_step(m, 1, 1)
    assert diff == pytest.approx(3 * 2 * 32)
