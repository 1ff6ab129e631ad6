"""A configuration, a traffic mix and a per-layer metric are added as
files of their own, and the harness finds them by name."""

import json
import re
import shutil
from types import SimpleNamespace

import pytest
from bench_tiny import ROOT, TINY_MODEL, tiny_copy

from bench import run as R


def test_new_files_are_found_by_name(tmp_path, capsys):
    root = tiny_copy(tmp_path)
    cfg = json.loads((root / "bench" / "configs" / "gpt2-small-commit.json")
                     .read_text())
    cfg.update(name="throwaway-lm")
    cfg["model"]["n_layers"] = 1
    (root / "bench" / "configs" / "throwaway-lm.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "one-save.json").write_text(json.dumps(
        {"saves_at": [0.5], "fail_after_save": None, "restore_hosts": None,
         "failed_hosts": []}))
    (root / "bench" / "metrics" / "throwaway_saves.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window.save_s))\n")

    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "throwaway-lm", "source": "https://example.org/lm",
         "file": "bench/configs/throwaway-lm.json", "reduced": [],
         "why": "a test"})
    cell = "throwaway-lm.one-save"
    manifest["workloads"].append(
        {"name": cell, "config": "throwaway-lm", "traffic": "one-save",
         "chips": 1, "why": "a test"})
    manifest["per_layer"].append(
        {"name": "throwaway_saves", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "checkpoint",
         "moves": "samples_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    res = R.run(["--workload", cell, "--seed", "5", "--seconds", "1"],
                root=root, require_chip=False)
    assert res["correct"] is True, res["checks"]
    err = capsys.readouterr().err
    saves = re.search(r"saves=\[([^\]]*)\]", err).group(1)
    assert len(saves.split(",")) == 1          # the new traffic's one save

    ctx = SimpleNamespace(trace=None, telemetry=None, scopes=None,
                          window=SimpleNamespace(save_s=[0.1], spans={},
                                                 steps=0))
    assert R.read_per_layer(root, manifest, cell, ctx) == {
        "throwaway_saves": 1.0}
    assert "throwaway_saves" not in R.read_per_layer(
        root, manifest, "gpt2-small-commit.train", ctx)


def _one_step_trace(log_dir):
    """A device trace in place of the CPU's: one 10 ms train step in a
    20 ms window."""
    from bench import devtrace

    ms = 1e6
    return devtrace.Trace([[("fusion.1", 0, 10 * ms)]],
                          [[("jit_train_step(1)", 0, 10 * ms)]],
                          {"window": [(0, 20 * ms)]})


def test_a_configuration_brings_its_flops_and_span_readers(
        tmp_path, monkeypatch):
    """A configuration whose reference counts its own FLOPs, and a reader
    of one program span, run through ``run.py`` as new files alone."""
    from bench import devtrace

    root = tiny_copy(tmp_path)
    cfg = json.loads((root / "bench" / "configs" / "gpt2-small-commit.json")
                     .read_text())
    cfg.update(name="throwaway-lm", reference="throwaway")
    (root / "bench" / "configs" / "throwaway-lm.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "reference" / "throwaway.py").write_text(
        "from bench.reference.gpt2 import layout, loss_sum  # noqa: F401\n"
        "\n\ndef train_flops_per_step(model, batch, seq):\n"
        "    return 1e9 * batch\n")
    (root / "bench" / "metrics" / "throwaway_writes.py").write_text(
        "def read(ctx):\n"
        "    return float(sum(s.name == 'ckpt.save.write'\n"
        "                     for s in ctx.telemetry.spans))\n")
    (root / "bench" / "traffic" / "one-save.json").write_text(json.dumps(
        {"saves_at": [0.5], "fail_after_save": None, "restore_hosts": None,
         "failed_hosts": []}))
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flop_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))

    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "throwaway-lm", "source": "https://example.org/lm",
         "file": "bench/configs/throwaway-lm.json", "reduced": [],
         "why": "a test"})
    cell = "throwaway-lm.one-save"
    manifest["workloads"].append(
        {"name": cell, "config": "throwaway-lm", "traffic": "one-save",
         "chips": 1, "why": "a test"})
    manifest["per_layer"].append(
        {"name": "throwaway_writes", "unit": "count", "better": "lower",
         "source": "program_span", "layer": "checkpoint",
         "moves": "ckpt_save_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    monkeypatch.setattr(devtrace, "load", _one_step_trace)
    res = R.run(["--workload", cell, "--seed", str(2**31 + 3), "--seconds",
                 "1", "--trace", "1"], root=root, require_chip=False)
    assert res["correct"] is True, res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["throwaway_writes"] == 1.0        # the traffic's one save
    # The reference's 1e9 FLOPs a row over a 10 ms step at 1e12 FLOP/s.
    batch = cfg["job"]["batch"]
    assert got["train_mfu"] == pytest.approx(1e9 * batch / 0.01 / 1e12 * 100)
    assert got["ingest_queries_per_sample"] == 1.0
    assert "attn_ms" not in got and "save_write_s" not in got


def test_an_untraced_run_records_nothing_and_takes_no_hlo(
        tmp_path, monkeypatch):
    from bench.job import Job
    from repro import telemetry

    def refuse(*a, **kw):
        raise AssertionError("an untraced run recorded or took HLO")

    monkeypatch.setattr(telemetry, "recording", refuse)
    monkeypatch.setattr(Job, "step_hlo", refuse)
    root = tiny_copy(tmp_path)
    res = R.run(["--workload", "gpt2-small-commit.ckpt-restart", "--seed",
                 "4", "--seconds", "1"], root=root, require_chip=False)
    assert res["correct"] is True, res["checks"]


def test_a_configuration_brings_its_own_cpu_size(tmp_path):
    """A configuration's ``tiny`` object is applied after ``TINY_MODEL``:
    it sets a width that ``TINY_MODEL`` sets otherwise and a key that it
    does not know, and the cell runs ``correct`` at that size."""
    src = tmp_path / "src"
    src.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", src / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", src / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((src / "bench" / "configs" / "gpt2-small-commit.json")
                     .read_text())
    cfg.update(name="throwaway-lm", tiny={"d_ff": 48, "rope_theta": 500.0})
    (src / "bench" / "configs" / "throwaway-lm.json").write_text(
        json.dumps(cfg))
    manifest = json.loads((src / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "throwaway-lm", "source": "https://example.org/lm",
         "file": "bench/configs/throwaway-lm.json", "reduced": [],
         "why": "a test"})
    manifest["workloads"].append(
        {"name": "throwaway-lm.train", "config": "throwaway-lm",
         "traffic": "train", "chips": 1, "why": "a test"})
    (src / "BENCHMARK.json").write_text(json.dumps(manifest))

    (tmp_path / "tiny").mkdir()
    root = tiny_copy(tmp_path / "tiny", src)
    model = json.loads((root / "bench" / "configs" / "throwaway-lm.json")
                       .read_text())["model"]
    assert model["d_ff"] == 48 and model["rope_theta"] == 500.0
    assert model["d_model"] == TINY_MODEL["d_model"]
    res = R.run(["--workload", "throwaway-lm.train", "--seed", "6",
                 "--seconds", "1"], root=root, require_chip=False)
    assert res["correct"] is True, res["checks"]
