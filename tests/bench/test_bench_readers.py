"""The per-layer readers of the program's spans, counters and scopes, on
a hand-built ``ctx``."""

import json
from types import SimpleNamespace

import pytest
from bench_tiny import ROOT

from bench import flops
from bench import run as R

PEAK = json.loads((ROOT / "bench" / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]
GPT2 = json.loads((ROOT / "bench" / "configs" / "gpt2-small-commit.json")
                  .read_text())


def _reader(name):
    return R.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def _recorded():
    from repro import telemetry

    with telemetry.recording() as rec:
        for _ in range(3):
            with telemetry.span("ckpt.save"):
                with telemetry.span("ckpt.save.write"):
                    pass
        with telemetry.span("ckpt.restore"):
            with telemetry.span("ckpt.restore.read"):
                pass
        telemetry.count("ingest.samples", 32)
        telemetry.count("ingest.queries", 32)
    return rec


def _ctx(**kw):
    base = dict(telemetry=None, scopes=None, model=GPT2["model"],
                job=GPT2["job"], ref=None, peak=PEAK)
    return SimpleNamespace(**{**base, **kw})


def test_span_readers_take_the_mean_span():
    rec = _recorded()
    writes = [(s.end_ns - s.start_ns) / 1e9 for s in rec.spans
              if s.name == "ckpt.save.write"]
    reads = [(s.end_ns - s.start_ns) / 1e9 for s in rec.spans
             if s.name == "ckpt.restore.read"]
    ctx = _ctx(telemetry=rec)
    assert _reader("save_write_s")(ctx) == pytest.approx(sum(writes) / 3)
    assert _reader("restore_read_s")(ctx) == pytest.approx(reads[0])
    assert _reader("ingest_queries_per_sample")(ctx) == 1.0


@pytest.mark.parametrize("name", ["save_write_s", "restore_read_s",
                                  "ingest_queries_per_sample", "attn_ms",
                                  "attn_roofline"])
def test_readers_with_nothing_to_read_return_none(name):
    from repro import telemetry

    with telemetry.recording() as empty:
        pass
    assert _reader(name)(_ctx()) is None
    assert _reader(name)(_ctx(telemetry=empty, scopes={"module": 1.0})) is None


def test_attention_readers():
    ctx = _ctx(scopes={"attention": 230.4, "forward": 76.3})
    assert _reader("attn_ms")(ctx) == 230.4
    args = (GPT2["model"], GPT2["job"]["batch"], GPT2["job"]["seq"])
    least = max(flops.attention_flops_per_step(*args) / 197e12,
                flops.attention_bytes_per_step(*args) / 819e9)
    got = _reader("attn_roofline")(ctx)
    assert got == pytest.approx(least / 0.2304 * 100)
    assert 0 < got < 100


def test_attn_roofline_takes_the_reference_s_own_count():
    ref = SimpleNamespace(attention_flops_per_step=lambda m, b, s: 197e12,
                          attention_bytes_per_step=lambda m, b, s: 0.0)
    ctx = _ctx(scopes={"attention": 2000.0}, ref=ref)
    assert _reader("attn_roofline")(ctx) == pytest.approx(50.0)
