"""``correct`` has to come out false for the lower-precision control and
for each fault the timed path can have, at a tiny size on the CPU.

The control is the reference put in the program's place one precision
below the configuration's (operands and stored parameters rounded);
it has to fail one of the configuration's limits.  The faults are
planted under a whole run of a cell with the chip check skipped: a step
that returns its state unchanged, half of the batch left out with the
mean taken over the rest, a token altered where the pipeline produces
it, a restored leaf altered where the restore produces it, and a
restore that hands back its template.  One chip has no exchange
between chips to leave out.
"""

import json
import sys

import jax
import numpy as np
import pytest
from bench_tiny import tiny_copy

from bench import run as R
from bench.job import Job

CONFIGS = ["gpt2-small-commit", "whisper-small-session"]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("name", CONFIGS)
def test_control_fails_a_limit(name, tiny_root):
    config = json.loads((tiny_root / "bench" / "configs" / f"{name}.json")
                        .read_text())
    traffic = json.loads((tiny_root / "bench" / "traffic" / "train.json")
                         .read_text())
    ref = R.load_module(tiny_root / "bench" / "reference"
                        / f"{config['reference']}.py")
    job = Job(config, traffic, ref, 21)
    prog = job.first_steps()
    _, first = job.ingest_check()
    gaps, _ = R.first_step_gaps(job, prog, ref, first, ("control",))
    limits = config["limits"]
    sound, control = gaps["program"], gaps["control"]
    assert all(sound[k] <= limits[k] for k in limits), sound
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("name", CONFIGS)
def test_study_reads_the_control_and_the_half_batch(name, tiny_root, capsys,
                                                    monkeypatch):
    """``bench/study.py``, which sets the limits, runs its stand-ins
    through the same reference training; each fails a limit."""
    monkeypatch.setattr(sys, "path", list(sys.path))
    study = R.load_module(tiny_root / "bench" / "study.py")
    assert study.main(["--config", name, "--seeds", "1"]) == 0
    seed, summary = [json.loads(x)
                     for x in capsys.readouterr().out.splitlines()]
    limits = json.loads((tiny_root / "bench" / "configs" / f"{name}.json")
                        .read_text())["limits"]
    assert summary["lower"] == seed["program"]
    assert all(seed["program"][k] <= limits[k] for k in limits), seed
    for stand_in in ("control", "half_batch"):
        assert any(seed[stand_in][k] > limits[k] for k in limits), seed


def _state_unchanged(monkeypatch):
    import repro.train.train_step as ts

    make = ts.make_train_step

    def broken(*a, **kw):
        step = make(*a, **kw)
        return lambda state, batch: (state, step(state, batch)[1])
    monkeypatch.setattr(ts, "make_train_step", broken)


def _half_batch(monkeypatch):
    import repro.train.train_step as ts

    make = ts.make_train_step

    def broken(*a, **kw):
        step = make(*a, **kw)

        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    monkeypatch.setattr(ts, "make_train_step", broken)


def _token_altered(monkeypatch):
    from repro.data.pipeline import TokenPipeline

    batches = TokenPipeline.batches

    def broken(self, epoch, reader_host=0):
        for i, b in enumerate(batches(self, epoch, reader_host)):
            if epoch == 0 and i == 4:
                toks = b["tokens"].at[0, 0].set(
                    (b["tokens"][0, 0] + 1) % self.cfg.vocab)
                b = {"tokens": toks, "labels": jax.numpy.roll(toks, -1, 1)}
            yield b
    monkeypatch.setattr(TokenPipeline, "batches", broken)


def _restore_altered(monkeypatch):
    from repro.checkpoint.manager import CheckpointManager

    restore = CheckpointManager.restore

    def broken(self, *a, **kw):
        tree = restore(self, *a, **kw)
        leaves, treedef = jax.tree.flatten(tree)
        leaves[0] = np.asarray(leaves[0]).copy()
        leaves[0].reshape(-1)[0] += 1
        return jax.tree.unflatten(treedef, leaves)
    monkeypatch.setattr(CheckpointManager, "restore", broken)


def _restore_template(monkeypatch):
    from repro.checkpoint.manager import CheckpointManager

    restore = CheckpointManager.restore

    def broken(self, step, template, *a, **kw):
        restore(self, step, template, *a, **kw)
        return template
    monkeypatch.setattr(CheckpointManager, "restore", broken)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered,
          "restore_altered": _restore_altered,
          "restore_template": _restore_template}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_correct_false(fault, tiny_root, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = R.run(["--workload", "gpt2-small-commit.ckpt-restart", "--seed",
                 "9", "--seconds", "1"], root=tiny_root, require_chip=False)
    assert res["correct"] is False
    failed = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    want = {"state_unchanged": "update_gap", "half_batch": "grad_gap",
            "token_altered": "ingest_mismatches",
            "restore_altered": "ckpt_mismatches",
            "restore_template": "ckpt_mismatches"}[fault]
    assert want in failed, res["checks"]
