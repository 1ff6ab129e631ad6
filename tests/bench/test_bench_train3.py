"""The reference's three training steps keep their readings while their
device state stays within ``STATE_BYTES_PER_PARAM`` a parameter.

``_train3_eager`` is the reference's training before its buffers were
donated: start weights on the device beside the state, each block's
gradient added eagerly, m and v held throughout, nothing donated.  It is
kept here as the oracle: the readings have to be its own, bit for bit.
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


def _train3_eager(ref, model, opt, layout, key, rows, lowp=None,
                  block_rows=1):
    mm = C.make_mm(lowp)
    stored = jax.tree.map(lambda leaf: leaf.dtype, layout,
                          is_leaf=lambda x: isinstance(x, C.Leaf))
    if lowp is not None:
        stored = jax.tree.map(lambda d: C.LOWER[d], stored)

    def store(p, dtype):
        return C.round_to(p.astype(jnp.float32), dtype)

    grad_block = jax.jit(jax.value_and_grad(
        lambda p, blk: ref.loss_sum(mm, p, blk, model)))

    @jax.jit
    def adamw(p, g, m, v, step):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))

        def upd(p, g, m, v, dtype):
            g = g * clip
            m = opt["b1"] * m + (1 - opt["b1"]) * g
            v = opt["b2"] * v + (1 - opt["b2"]) * g * g
            mhat = m / (1 - opt["b1"] ** step)
            vhat = v / (1 - opt["b2"] ** step)
            delta = mhat / (jnp.sqrt(vhat) + opt["eps"])
            if p.ndim >= 2:
                delta = delta + opt["weight_decay"] * p
            return store(p - opt["lr"] * delta, dtype), m, v

        out = jax.tree.map(upd, p, g, m, v, stored)
        return tuple(jax.tree.map(lambda _, o: o[i], p, out)
                     for i in range(3))

    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda x, d: store(x.astype(jnp.float32), d),
                              C.init(key, layout), stored)
        p0 = params
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms, update1 = [], [], []
        for k, batch in enumerate(rows):
            n = next(iter(batch.values())).shape[0]
            total, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
            for r in range(0, n, block_rows):
                blk = {name: jnp.asarray(a[r:r + block_rows])
                       for name, a in batch.items()}
                ls, g = grad_block(params, blk)
                total = total + ls
                grads = jax.tree.map(jnp.add, grads, g)
            ntok = n * batch["labels"].shape[1]
            grads = jax.tree.map(lambda g: g / ntok, grads)
            losses.append(float(total) / ntok)
            if k == 0:
                grad_norms = C.leaf_norms(grads)
            params, m, v = adamw(params, grads, m, v, float(k + 1))
            if k == 0:
                update1 = C.leaf_norms(
                    jax.tree.map(jnp.subtract, params, p0))
        update = jax.tree.map(jnp.subtract, params, p0)
        return C.Readings(losses, grad_norms, update1, C.leaf_norms(update))


def _tiny(name):
    """A configuration at the CPU size, its reference and three batches of
    rows drawn from a fixed key."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    model = dict(cfg["model"])
    model.update({k: v for k, v in TINY_MODEL.items() if k in model})
    ref = load_module(ROOT / "bench" / "reference"
                      / f"{cfg['reference']}.py")
    b, t = TINY_JOB["batch"], TINY_JOB["seq"]
    rows = []
    for k in range(3):
        toks = np.asarray(jax.random.randint(
            jax.random.PRNGKey(40 + k), (b, t), 0, model["vocab"]))
        row = {"tokens": toks, "labels": np.roll(toks, -1, 1)}
        if model.get("frontend") == "audio":
            row["frames"] = np.asarray(C.audio_frames(
                jax.random.PRNGKey(50 + k), b, model["enc_len"],
                model["d_model"], model["dtype"]))
        rows.append(row)
    return cfg, model, ref, rows


@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
@pytest.mark.parametrize("name", CONFIGS)
def test_readings_are_bitwise_the_eager_oracles(name, control):
    cfg, model, ref, rows = _tiny(name)
    args = (ref, model, cfg["job"]["optimizer"], ref.layout(model),
            jax.random.PRNGKey(7), rows)
    kw = {"lowp": C.LOWER[model["dtype"]] if control else None,
          "block_rows": TINY_JOB["reference_rows"]}
    got, want = C.train3(*args, **kw), _train3_eager(*args, **kw)
    for field in C.Readings._fields:
        assert getattr(got, field) == getattr(want, field), field


def _unaliased(compiled) -> int:
    ma = compiled.memory_analysis()
    return ma.output_size_in_bytes - ma.alias_size_in_bytes


def test_update_and_accumulation_write_into_their_donated_buffers():
    cfg, model, ref, _ = _tiny("whisper-small-session")
    layout = ref.layout(model)
    params = jax.eval_shape(lambda: C.init(jax.random.PRNGKey(0), layout))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                         params)
    stored = jax.tree.map(lambda leaf: leaf.dtype, layout,
                          is_leaf=lambda x: isinstance(x, C.Leaf))
    adamw = C.make_adamw(cfg["job"]["optimizer"], stored).lower(
        state, state, state, state, 1.0).compile()
    acc = C.accumulate.lower(state, state).compile()
    mean = C.mean_over.lower(state, 64).compile()
    # Every result is written into a donated buffer: only the results'
    # table of buffer addresses is not aliased, 8 bytes a result.
    n = len(jax.tree.leaves(state))
    assert _unaliased(adamw) <= 8 * 3 * n + 64
    assert _unaliased(acc) <= 8 * n + 64
    assert _unaliased(mean) <= 8 * n + 64


def test_start_weights_and_moments_wait_on_the_host(monkeypatch):
    cfg, model, ref, rows = _tiny("gpt2-small-commit")
    seen, put, norms = [], jax.device_put, C.change_norms

    def change_norms(params, p0):
        seen.append(p0)
        return norms(params, p0)

    def device_put(x, *a, **kw):
        seen.append(x)
        return put(x, *a, **kw)

    monkeypatch.setattr(C, "change_norms", change_norms)
    monkeypatch.setattr(jax, "device_put", device_put)
    C.train3(ref, model, cfg["job"]["optimizer"], ref.layout(model),
             jax.random.PRNGKey(7), rows, block_rows=2)
    # update1, (m, v) before steps 2 and 3, update
    assert len(seen) == 4
    for tree in seen:
        leaves = jax.tree.leaves(tree)
        assert leaves and all(type(x) is np.ndarray for x in leaves)
        assert all(x.dtype == np.float32 for x in leaves)


def test_headroom_trains_the_cut_at_a_tiny_width():
    """``bench/headroom.py`` runs the reference's training over its
    synthetic layout; at full width that layout holds the parameters
    that its measurement states."""
    from bench import headroom as H

    tiny = dict(H.MOONLIGHT_CUT, hidden=16, heads=2, qk_nope=4, qk_rope=2,
                v_head=4, kv_rank=8, dense_width=24, expert_width=8,
                experts=8, experts_held=2, expert_layers=2, vocab_rows=32)
    out = H.measure(C, tiny, 2**31 + 1)
    assert out["error"] is None and np.all(np.isfinite(out["losses"]))
    assert out["parameters"] == C.param_count(H.cut_layout(C.Leaf, tiny))
    assert C.param_count(H.cut_layout(C.Leaf, H.MOONLIGHT_CUT)) == 668_890_432
