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


@pytest.mark.parametrize("name, want", [
    ("gpt2-small-commit", 13072311779328.0),
    ("whisper-small-session", 10155882184704.0),
])
def test_configurations_count_as_before(name, want):
    """Each cell's FLOPs per step, bit for bit what the count gave when
    the cells were measured first."""
    import json

    from bench_tiny import ROOT

    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    got = train_flops_per_step(cfg["model"], cfg["job"]["batch"],
                               cfg["job"]["seq"])
    assert got == want and got.hex() == want.hex()


def test_attention_term_by_hand():
    from bench.flops import attention_bytes_per_step, attention_flops_per_step

    dec = {"kind": "decoder", "n_layers": 1, "d_model": 4, "n_heads": 2,
           "n_kv_heads": 2, "d_ff": 8, "vocab": 10, "ffn": "gelu",
           "dtype": "float32"}
    # 6 causal pairs x 2 heads x 2 dims x 2 flops x 2 (QK and PV), x 3
    assert attention_flops_per_step(dec, batch=2, seq=3) == 3 * 2 * 96
    # q, k, v, o forward; q, k, v, o, do read and dq, dk, dv written
    # backward: 12 tensors of 3 rows x 4 wide x 4 bytes, per row
    assert attention_bytes_per_step(dec, batch=2, seq=3) == 2 * 12 * 3 * 16
    encdec = dict(dec, kind="encdec", enc_layers=1, enc_len=5,
                  dtype="bfloat16")
    pairs = 6 + 25 + 15                      # causal, encoder, cross
    assert attention_flops_per_step(encdec, 1, 3) == 3 * pairs * 16
    rows = 6 * (3 + 3) + 6 * (5 + 5) + 6 * (3 + 5)
    assert attention_bytes_per_step(encdec, 1, 3) == rows * 4 * 2
