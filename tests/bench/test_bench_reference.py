"""The plain references against the program, at a tiny size on the CPU.

Both sides run in float32 on the same weights and rows, so they differ
by float32 round-off alone: the program's attention runs an online
softmax over key chunks and the reference a plain one, and the two sum
in different orders.  Over a few hundred terms that is some 1e-7
relative; the tolerances leave ten to a hundred times that.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_tiny import TINY_JOB, TINY_MODEL, ROOT

from bench.reference import common as C
from bench.run import load_module

CONFIGS = ["gpt2-small-commit", "whisper-small-session"]


def _setup(name):
    from repro.models.config import ModelConfig

    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    model = dict(cfg["model"])
    model.update({k: v for k, v in TINY_MODEL.items() if k in model})
    model["dtype"] = "float32"
    ref = load_module(ROOT / "bench" / "reference"
                      / f"{cfg['reference']}.py")
    kw = {k: v for k, v in model.items()
          if k in ModelConfig.__dataclass_fields__}
    kw.update(dtype=jnp.float32, opt_state_dtype=jnp.float32)
    return model, ref, ModelConfig(name=name, **kw)


@pytest.mark.parametrize("name", CONFIGS)
def test_loss_and_gradients_match_the_program(name):
    from repro.train.train_step import loss_fn

    model, ref, cfg = _setup(name)
    params = C.init(jax.random.PRNGKey(3), ref.layout(model))
    b, t = TINY_JOB["batch"], TINY_JOB["seq"]
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (b, t), 0,
                                         model["vocab"]))
    batch = {"tokens": toks, "labels": np.roll(toks, -1, 1)}
    if model.get("frontend") == "audio":
        batch["frames"] = np.asarray(C.audio_frames(
            jax.random.PRNGKey(5), b, model["enc_len"], model["d_model"],
            "float32"))
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {k: jnp.asarray(v) for k, v in batch.items()}, cfg)
        total, rgrads = jax.value_and_grad(
            lambda p: ref.loss_sum(C.make_mm(None), p, batch, model))(params)
    ntok = b * t
    # Same loss to float32 round-off (see the module's docstring).
    assert float(loss) == pytest.approx(float(total) / ntok, rel=1e-5)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(rgrads)):
        r = r / ntok
        err = float(jnp.linalg.norm((g - r).ravel()))
        # Per leaf, against its own norm or, for leaves whose gradient is
        # all but zero, the largest leaf's: float32 round-off again.
        scale = max(float(jnp.linalg.norm(r.ravel())), 1e-3)
        assert err <= 1e-4 * scale


def test_reference_frames_are_the_stub_frontends():
    from repro.models.config import ModelConfig
    from repro.models.frontends import audio_frames

    cfg = ModelConfig(name="w", kind="encdec", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=4, d_ff=64, vocab=96,
                      enc_len=12, frontend="audio")
    key = jax.random.PRNGKey(11)
    mine = C.audio_frames(key, 3, 12, 32, "bfloat16")
    theirs = audio_frames(cfg, 3, key=key)
    assert mine.dtype == theirs.dtype
    assert np.array_equal(np.asarray(mine).view(np.uint16),
                          np.asarray(theirs).view(np.uint16))


def test_seed_key_takes_seeds_past_32_bits():
    a, b = C.seed_key(2**31 + 5), C.seed_key(5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(C.seed_key(2**40 + 1)),
                          np.asarray(C.seed_key(2**40 + 1)))
