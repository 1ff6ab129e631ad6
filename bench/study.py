"""Readings from which a configuration's limits are set.

    python3 bench/study.py --config <name> --seeds <n> [--first-seed <s>]

For each seed, in one process: the program's first three steps through
the job's own call and feed (the sound runs), and against the same
reference three stand-ins put in the program's place: the control (the
reference one precision lower: operands and stored parameters rounded),
and the fault of half the batch left out, the mean taken over the rest.
A step that returns its state unchanged reads 1 on the update's gap by
definition and is not run.  Prints one JSON line per seed and a summary:
the largest program reading of each number (the lower reading) and the
smallest control and fault readings (the candidates for the upper one).
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def worst_leaves(layout, prog, want, top: int = 3) -> dict:
    """The leaves with the largest gaps of gradient and update norms."""
    import jax
    import numpy as np

    from bench.reference.common import Leaf

    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 layout, is_leaf=lambda x: isinstance(x, Leaf))[0]]
    out = {}
    for name, p, r in (("grad", prog.grad_norms, want.grad_norms),
                       ("update", prog.update_norms, want.update_norms)):
        med = float(np.median(r))
        gap = [(abs(a - b) / max(b, med, 1e-30), path, a, b)
               for a, b, path in zip(p, r, paths)]
        out[name] = sorted(gap, reverse=True)[:top]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench.run import first_step_gaps, load_module, start_jax

    jax = start_jax(ROOT)
    from bench.job import Job

    config = json.loads(
        (ROOT / "bench" / "configs" / f"{args.config}.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "train.json")
                         .read_text())
    ref = load_module(ROOT / "bench" / "reference"
                      / f"{config['reference']}.py")
    step_fn, lines = None, []
    for s in range(args.first_seed, args.first_seed + args.seeds):
        job = Job(config, traffic, ref, s, step_fn=step_fn)
        step_fn = job.step_fn
        prog = job.first_steps()
        _, first = job.ingest_check()
        peak = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        gaps, want = first_step_gaps(job, prog, ref, first,
                                     ("control", "half_batch"))
        line = {"seed": s, **gaps,
                "worst_leaves": worst_leaves(job.layout, prog, want),
                "losses": [prog.losses, want.losses],
                "peak_bytes_in_use": peak}
        print(json.dumps(line), flush=True)
        lines.append(line)
    keys = lines[0]["program"]
    summary = {"config": args.config, "seeds": len(lines),
               "lower": {k: max(x["program"][k] for x in lines) for k in keys},
               "control_min": {k: min(x["control"][k] for x in lines)
                               for k in keys},
               "half_batch_min": {k: min(x["half_batch"][k] for x in lines)
                                  for k in keys}}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"summary": summary,
                                              "seeds": lines}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
