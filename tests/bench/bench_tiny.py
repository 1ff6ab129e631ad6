"""A copy of the benchmark with every configuration cut to a CPU size."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_MODEL = {"n_layers": 2, "enc_layers": 2, "d_model": 32, "n_heads": 4,
              "n_kv_heads": 4, "d_ff": 64, "vocab": 96, "pad_vocab_to": 64,
              "enc_len": 12}
TINY_JOB = {"batch": 4, "seq": 16, "reference_rows": 2}


def tiny_copy(dest: Path, src: Path = ROOT) -> Path:
    """``BENCHMARK.json`` and ``bench/`` copied from ``src`` to ``dest``,
    with each configuration's model and batch shrunk; returns ``dest``.
    A configuration's own ``tiny`` object, applied after ``TINY_MODEL``,
    shrinks model keys that ``TINY_MODEL`` does not know."""
    shutil.copy(src / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(src / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for path in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["model"].update({k: v for k, v in TINY_MODEL.items()
                             if k in cfg["model"]})
        cfg["model"].update(cfg.get("tiny", {}))
        cfg["job"].update(TINY_JOB)
        cfg["storage"]["samples_per_host"] = 8
        path.write_text(json.dumps(cfg))
    return dest
