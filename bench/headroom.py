"""Peak device memory of the reference's three training steps over the
leaf shapes of a large configuration, before its reference exists.

    python3 bench/headroom.py [--common <path of a common.py>] [--seed <n>]

Runs ``train3`` of ``bench/reference/common.py`` (or of the copy that
``--common`` names, such as a parent checkout's) over a synthetic layout
with the leaf shapes of Moonlight-16B-A3B (huggingface.co/moonshotai/
Moonlight-16B-A3B, config.json) cut to one chip of the eight that share
each layer: its dense layer and 5 of its expert layers, 8 of the 64
routed experts, both shared experts, 20,480 vocabulary rows, every width
as published.  Matrices are stored in bfloat16, norms in float32, and
the reference trains them in float32.  The loss is cheap, a sum over
leaves of tanh of one fixed row times the leaf, so that the state and
not the activations sets the peak.  Prints one JSON line: the parameter
count, the state budget of ``STATE_BYTES_PER_PARAM`` bytes a parameter,
the device's ``peak_bytes_in_use`` and, where the device ran out of
memory, the error's first line (exit code 1).  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]

# Moonlight-16B-A3B's published widths, and the cut.
MOONLIGHT_CUT = {"hidden": 2048, "heads": 16, "qk_nope": 128, "qk_rope": 64,
                 "v_head": 128, "kv_rank": 512, "dense_width": 11264,
                 "expert_width": 1408, "experts": 64, "shared_experts": 2,
                 "experts_held": 8, "expert_layers": 5, "vocab_rows": 20480}
OPT = {"lr": 4.2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1, "grad_clip": 1.0}


def cut_layout(Leaf, w: dict) -> dict:
    """The cut's leaves: the dense layer, then the expert layers stacked
    on a leading axis, as a reference scans them."""
    h, n = w["hidden"], w["heads"]

    def mat(lead, shape, fan_in):
        return Leaf(lead + shape, "bfloat16", "normal", fan_in)

    def norm(lead, d):
        return Leaf(lead + (d,), "float32", "ones")

    def mla(lead):
        return {"wq": mat(lead, (h, n, w["qk_nope"] + w["qk_rope"]), h),
                "wkv_a": mat(lead, (h, w["kv_rank"] + w["qk_rope"]), h),
                "kv_norm": norm(lead, w["kv_rank"]),
                "wkv_b": mat(lead, (w["kv_rank"], n,
                                    w["qk_nope"] + w["v_head"]),
                             w["kv_rank"]),
                "wo": mat(lead, (n, w["v_head"], h), n * w["v_head"])}

    def mlp(lead, f):
        return {"wg": mat(lead, (h, f), h), "wu": mat(lead, (h, f), h),
                "wd": mat(lead, (f, h), f)}

    e = (w["expert_layers"],)
    held = e + (w["experts_held"],)
    return {
        "embed": mat((), (w["vocab_rows"], h), h),
        "head": mat((), (h, w["vocab_rows"]), h),
        "final_norm": norm((), h),
        "dense": {"norm1": norm((), h), "mla": mla(()), "norm2": norm((), h),
                  "mlp": mlp((), w["dense_width"])},
        "moe": {"norm1": norm(e, h), "mla": mla(e), "norm2": norm(e, h),
                "router": mat(e, (h, w["experts"]), h),
                "router_bias": Leaf(e + (w["experts"],), "float32", "zeros"),
                "experts": {"wg": mat(held, (h, w["expert_width"]), h),
                            "wu": mat(held, (h, w["expert_width"]), h),
                            "wd": mat(held, (w["expert_width"], h),
                                      w["expert_width"])},
                "shared": mlp(e, w["shared_experts"] * w["expert_width"])},
    }


def loss_sum(mm, params: dict, batch: dict, model: dict):
    """A cheap loss whose gradient reaches every leaf: for each leaf as a
    (rows, last axis) matrix, tanh of a row of one value set by the
    block's labels times it, summed."""
    import jax
    import jax.numpy as jnp

    s = jnp.asarray(batch["labels"]).astype(jnp.float32).mean() * 1e-4
    total = 0.0
    for x in jax.tree.leaves(params):
        x2 = x.reshape(-1, x.shape[-1])
        u = jnp.full((1, x2.shape[0]), s / math.sqrt(x2.shape[0]))
        total = total + jnp.sum(jnp.tanh(mm("ir,rc->ic", u, x2)))
    return total


def measure(C, widths: dict, seed: int) -> dict:
    """``train3`` of the module ``C`` over the cut, and the device's peak."""
    import jax
    import numpy as np

    layout = cut_layout(C.Leaf, widths)
    n = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(
        layout, is_leaf=lambda x: isinstance(x, C.Leaf)))
    rng = np.random.default_rng(seed)
    rows = [{"labels": rng.integers(0, widths["vocab_rows"], (4, 8),
                                    dtype=np.int32)} for _ in range(3)]
    t0, losses, error = time.perf_counter(), None, None
    try:
        losses = C.train3(SimpleNamespace(loss_sum=loss_sum), {}, OPT,
                          layout, C.seed_key(seed), rows,
                          block_rows=1).losses
    except jax.errors.JaxRuntimeError as e:
        error = str(e).splitlines()[0]
    stats = jax.devices()[0].memory_stats() or {}
    return {"parameters": n,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
            "seconds": time.perf_counter() - t0, "losses": losses,
            "error": error, "device": jax.devices()[0].device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--common",
                    default=str(ROOT / "bench" / "reference" / "common.py"))
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    # libtpu logs under /tmp by default; keep them in this run's TMPDIR.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, str(ROOT))
    from bench.reference.common import STATE_BYTES_PER_PARAM
    from bench.run import load_module

    out = measure(load_module(Path(args.common)), MOONLIGHT_CUT, args.seed)
    out.update(state_budget_bytes=STATE_BYTES_PER_PARAM * out["parameters"],
               common=args.common)
    print(json.dumps(out), flush=True)
    return 0 if out["error"] is None else 1


if __name__ == "__main__":
    sys.exit(main())
