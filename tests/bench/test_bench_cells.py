"""Every cell of ``BENCHMARK.json`` run on the CPU at a tiny size."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
from bench_tiny import ROOT, tiny_copy

from bench import devtrace
from bench import run as R

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_and_prints_its_line(workload, tiny_root):
    res = R.run(["--workload", workload, "--seed", str(2**31 + 7),
                 "--seconds", "1"], root=tiny_root, require_chip=False)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in MANIFEST["end_to_end"]
            if R.applies(m, workload)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


def test_device_metrics_refuse_a_cpu(tiny_root):
    with pytest.raises(devtrace.NoDeviceTrace):
        R.run(["--workload", WORKLOADS[0], "--seed", "3", "--seconds", "1",
               "--trace", "1"], root=tiny_root, require_chip=False)


def test_command_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(Path("bench") / "run.py"), "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr
