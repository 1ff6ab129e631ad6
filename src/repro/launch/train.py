"""Production-style training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --tiny \\
        --steps 20 --batch 8 --seq 64 [--consistency session] [--mesh single]

* ``--tiny`` runs the architecture's reduced config (CPU-friendly); the
  full configs are for real accelerator meshes — their distribution is
  proven by ``repro.launch.dryrun``.
* ``--mesh single|multi`` binds the production sharding rules; it needs
  the mesh's devices (a TPU pod slice) and refuses to start without
  them.  Sharded and unsharded steps are tested equal in
  tests/test_multidevice.py.
* Checkpoints flow through the selected consistency layer with SCR
  partner redundancy; ``--fail-at`` simulates a host failure and elastic
  restart mid-run (the fault-tolerance path is exercised, not mocked).
"""

from __future__ import annotations

import argparse
import dataclasses
import statistics
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import ARCHS, get_config, tiny_config
from repro.data.pipeline import synthetic_batch
from repro.launch import mesh as M
from repro.launch.cache import use_compile_cache
from repro.models.sharding import active_rules
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import make_train_step, train_state_init


def main(argv=None) -> dict:
    """Train; returns the final ``loss``, the median ``step_s`` after the
    first (compiling) step, and ``compiles`` of the train step."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-32b", choices=sorted(ARCHS))
    ap.add_argument("--tiny", action="store_true",
                    help="reduced config (CPU-scale smoke/bring-up)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=0,
                    help="0 = use the config's setting")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "single", "multi"])
    ap.add_argument("--consistency", default="session",
                    choices=["commit", "session", "posix", "mpiio"])
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-hosts", type=int, default=4)
    ap.add_argument("--fail-at", type=int, default=0,
                    help="simulate a host failure at this step")
    args = ap.parse_args(argv)

    cfg = tiny_config(args.arch) if args.tiny else get_config(args.arch)
    if args.tiny:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    mb = args.microbatches or cfg.microbatches
    print(f"arch={cfg.name} params={cfg.params_total():,} "
          f"microbatches={mb} devices={jax.device_count()}")

    opt = AdamWConfig(lr=args.lr, state_dtype=cfg.opt_state_dtype)
    step_fn = make_train_step(cfg, opt, num_microbatches=mb)

    mesh = rules = None
    if args.mesh != "none":
        need = 512 if args.mesh == "multi" else 256
        if jax.device_count() < need:
            ap.error(f"--mesh {args.mesh} needs {need} devices, "
                     f"have {jax.device_count()}")
        mesh = M.make_production_mesh(multi_pod=args.mesh == "multi")
        rules = M.arch_rules(cfg, args.mesh == "multi")

    use_compile_cache()

    state = train_state_init(jax.random.PRNGKey(0), cfg, opt)
    mgr = CheckpointManager(model=args.consistency,
                            num_hosts=args.ckpt_hosts, partner=True)

    jitted = jax.jit(step_fn)
    step_s = []
    loss = float("nan")

    def run_steps(state, start):
        nonlocal loss
        last = start
        for i in range(start, args.steps):
            batch = synthetic_batch(jax.random.fold_in(
                jax.random.PRNGKey(7), i), cfg, args.batch, args.seq)
            t0 = time.perf_counter()
            state, metrics = jitted(state, batch)
            loss = float(metrics["loss"])        # waits for the step
            step_s.append(time.perf_counter() - t0)
            last = i + 1
            if last % 5 == 0 or last == args.steps:
                print(f"step {last:5d}  loss {loss:.4f}"
                      f"  {step_s[-1]:.3f}s/step")
            if args.ckpt_every and last % args.ckpt_every == 0:
                mgr.save(last, state)
                print(f"step {last:5d}  checkpoint saved "
                      f"({args.consistency})")
            if args.fail_at and last == args.fail_at:
                return state, last, True
        return state, last, False

    def run(state):
        start = 0
        while True:
            if mesh is not None:
                with jax.set_mesh(mesh), active_rules(rules, mesh):
                    state, start, failed = run_steps(state, start)
            else:
                state, start, failed = run_steps(state, start)
            if not failed:
                return state
            ck = max(mgr.manifests) if mgr.manifests else None
            if ck is None:
                print("[launch] failure before first checkpoint; restart "
                      "from step 0")
                continue
            print(f"[launch] host failure at step {start}; elastic "
                  f"restart from checkpoint {ck} on "
                  f"{args.ckpt_hosts - 1} hosts (partner copy)")
            state = jax.device_put(mgr.restore(
                ck, state, num_hosts_new=args.ckpt_hosts - 1,
                failed_hosts=[1]))
            start = ck
            args.fail_at = 0

    run(state)
    out = {"loss": loss, "compiles": jitted._cache_size(),
           "step_s": statistics.median(step_s[1:]) if step_s[1:] else None}
    print(f"done: {out}")
    return out


if __name__ == "__main__":
    main()
