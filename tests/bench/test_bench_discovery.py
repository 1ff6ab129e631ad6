"""A configuration, a traffic mix and a per-layer metric are added as
files of their own, and the harness finds them by name."""

import json
import re
from types import SimpleNamespace

from bench_tiny import tiny_copy

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

    ctx = SimpleNamespace(trace=None, window=SimpleNamespace(
        save_s=[0.1], spans={}, steps=0))
    assert R.read_per_layer(root, manifest, cell, ctx) == {
        "throwaway_saves": 1.0}
    assert "throwaway_saves" not in R.read_per_layer(
        root, manifest, "gpt2-small-commit.train", ctx)
