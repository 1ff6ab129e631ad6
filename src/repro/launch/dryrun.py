import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^^ MUST precede every other import: jax locks the platform and the
# device count at first init.  The dry-run is a CPU tool: it compiles
# for 512 placeholder host devices, and its children inherit the pin, so
# none of them reaches for an accelerator.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each live cell (see ``repro.models.config.shapes_for``) this driver

1. builds the production mesh — (16,16) ("data","model") single-pod or
   (2,16,16) ("pod","data","model") multi-pod,
2. resolves the arch's logical sharding rules against it,
3. ``jax.jit(step, in_shardings, out_shardings).lower(*input_specs(...))``
   with pure ShapeDtypeStruct stand-ins (no allocation),
4. ``.compile()`` — GSPMD partitioning must succeed; failures here are
   sharding bugs in the framework,
5. prints ``memory_analysis()`` / ``cost_analysis()`` and writes a JSON
   artifact with the roofline inputs: per-device HLO dot-FLOPs and HBM
   traffic (while-loops unrolled, see :mod:`repro.launch.hlostats`),
   collective wire bytes by kind, and per-device state/cache bytes
   (proving the cell fits 16GB HBM per v5e chip).

Usage::

    python -m repro.launch.dryrun --arch starcoder2-3b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--force]
    python -m repro.launch.dryrun --list
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Tuple

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _artifact_path(arch: str, shape: str, mesh_kind: str) -> str:
    safe = arch.replace("/", "_").replace(".", "_")
    return os.path.abspath(
        os.path.join(ARTIFACT_DIR, f"{safe}__{shape}__{mesh_kind}.json"))


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------
def input_specs(cfg, cell) -> Tuple[tuple, Dict[str, Any]]:
    """Abstract (args, kwargs) for the cell's step function.

    train:    (state, batch)                      — batch = tokens/labels(+modality)
    prefill:  (params, tokens[, frames|patches])  — builds the cache
    decode:   (params, cache, tokens(B,1), index) — one new token
    """
    import jax
    import jax.numpy as jnp
    from repro.launch import mesh as M
    from repro.models.frontends import extra_inputs

    B, S = cell.global_batch, cell.seq_len
    if cell.mode == "train":
        return (M.abstract_state(cfg), M.batch_abstract(cfg, cell)), {}
    if cell.mode == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        batch.update(extra_inputs(cfg, B))
        return (M.abstract_params(cfg), batch), {}
    if cell.mode == "decode":
        return (M.abstract_params(cfg),
                M.cache_abstract(cfg, cell),
                jax.ShapeDtypeStruct((B, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32)), {}
    raise ValueError(cell.mode)


def _sharded_bytes(abstract_tree, shardings_tree, n_devices: int) -> int:
    """Max per-device bytes of a sharded abstract pytree."""
    import jax
    import numpy as np

    total = 0
    for arr, sh in zip(jax.tree.leaves(abstract_tree),
                       jax.tree.leaves(
                           shardings_tree,
                           is_leaf=lambda x: isinstance(
                               x, jax.sharding.Sharding))):
        nshards = 1
        if isinstance(sh, jax.sharding.NamedSharding):
            sizes = dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape))
            for entry in sh.spec:
                if entry is None:
                    continue
                for ax in (entry if isinstance(entry, tuple) else (entry,)):
                    nshards *= sizes[ax]
        total += int(np.prod(arr.shape) * arr.dtype.itemsize) // max(nshards, 1)
    return total


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape: str, mesh_kind: str,
             verbose: bool = True) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.launch import hlostats
    from repro.launch import mesh as M
    from repro.models.config import shapes_for
    from repro.models.sharding import active_rules
    from repro.serve.decode import make_prefill, make_serve_step
    from repro.train.train_step import make_train_step

    cfg = get_config(arch)
    cells = {c.name: c for c in shapes_for(cfg)}
    if shape not in cells:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped",
                "reason": "long_500k needs sub-quadratic decode "
                          "(full-attention arch; DESIGN.md skip list)"}
    cell = cells[shape]
    multi = mesh_kind == "multi"
    mesh = M.make_production_mesh(multi_pod=multi)
    rules = M.arch_rules(cfg, multi)
    n_dev = mesh.devices.size
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "mode": cell.mode,
        "devices": n_dev, "mesh_shape": list(mesh.devices.shape),
        "seq_len": cell.seq_len, "global_batch": cell.global_batch,
        "params_total": cfg.params_total(),
        "params_active": cfg.params_active(),
    }

    t0 = time.time()
    with jax.set_mesh(mesh), active_rules(rules, mesh):
        if cell.mode == "train":
            opt = M.opt_for(cfg)
            step = make_train_step(cfg, opt, num_microbatches=cfg.microbatches)
            state_sh = M.state_shardings(cfg, mesh, rules)
            batch_sh = M.batch_shardings(cfg, cell, mesh, rules)
            (state_ab, batch_ab), kw = input_specs(cfg, cell)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_ab, batch_ab)
            rec["state_bytes_per_device"] = _sharded_bytes(
                state_ab, state_sh, n_dev)
            rec["batch_bytes_per_device"] = _sharded_bytes(
                batch_ab, batch_sh, n_dev)
            # Tokens processed per step (for MODEL_FLOPS = 6*N*D).
            rec["tokens"] = cell.global_batch * cell.seq_len
            rec["flops_factor"] = 3  # fwd + bwd(2x)
        elif cell.mode == "prefill":
            pf = make_prefill(cfg, max_len=cell.seq_len)

            def fn(params, batch):
                extras = {k: v for k, v in batch.items() if k != "tokens"}
                return pf(params, batch["tokens"], **extras)

            params_sh = M.params_shardings(cfg, mesh, rules)
            (params_ab, batch_ab), kw = input_specs(cfg, cell)
            all_bs = M.batch_shardings(cfg, cell, mesh, rules)
            batch_sh = {k: all_bs.get(k, M.replicated(mesh))
                        for k in batch_ab}
            jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(params_ab, batch_ab)
            rec["state_bytes_per_device"] = _sharded_bytes(
                params_ab, params_sh, n_dev)
            cache_ab = M.cache_abstract(cfg, cell)
            cache_sh = M.cache_shardings(cfg, cell, mesh, rules)
            rec["cache_bytes_per_device"] = _sharded_bytes(
                cache_ab, cache_sh, n_dev)
            rec["tokens"] = cell.global_batch * cell.seq_len
            rec["flops_factor"] = 1  # fwd only
        else:  # decode
            fn = make_serve_step(cfg)
            params_sh = M.params_shardings(cfg, mesh, rules)
            cache_sh = M.cache_shardings(cfg, cell, mesh, rules)
            (params_ab, cache_ab, tok_ab, idx_ab), kw = input_specs(cfg, cell)
            tok_sh = M.batch_shardings(cfg, cell, mesh, rules)["tokens"]
            tok_sh = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(
                    *(tok_sh.spec[:1] if tok_sh.spec else (None,)), None))
            jitted = jax.jit(
                fn, in_shardings=(params_sh, cache_sh, tok_sh,
                                  M.replicated(mesh)),
                out_shardings=(None, None, cache_sh),
                donate_argnums=(1,))
            lowered = jitted.lower(params_ab, cache_ab, tok_ab, idx_ab)
            rec["state_bytes_per_device"] = _sharded_bytes(
                params_ab, params_sh, n_dev)
            rec["cache_bytes_per_device"] = _sharded_bytes(
                cache_ab, cache_sh, n_dev)
            rec["tokens"] = cell.global_batch  # one token per sequence
            rec["flops_factor"] = 1

        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    # ---- analyses ------------------------------------------------------
    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                rec.setdefault("memory_analysis", {})[attr] = int(v)
    cost = compiled.cost_analysis()
    if cost:
        rec["cost_analysis"] = {
            k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "transcendentals",
             "utilization operand 0 {}", "optimal_seconds")
        }
        rec["cost_flops_raw"] = float(cost.get("flops", 0.0))
        rec["cost_bytes_raw"] = float(cost.get("bytes accessed", 0.0))

    text = compiled.as_text()
    rec["hlo_chars"] = len(text)
    coll = hlostats.parse_collectives(text, default_group=n_dev)
    rec["collectives"] = {
        "wire_bytes_per_device": coll.wire_bytes,
        "payload_bytes": coll.payload_bytes,
        "by_kind": coll.by_kind,
        "static_count": coll.count,
        "dynamic_count": coll.dynamic_count,
    }
    hc = hlostats.parse_hlo_costs(text)
    rec["hlo_flops_per_device"] = hc["flops"]
    rec["hlo_bytes_per_device"] = hc["bytes"]
    rec["hlo_flops_raw_per_device"] = hc["flops_raw"]
    rec["status"] = "ok"

    if verbose:
        print(f"== {arch} / {shape} / {mesh_kind} "
              f"({cell.mode}, {n_dev} devices) ==")
        print(f"  lower {rec['lower_s']}s  compile {rec['compile_s']}s")
        if "memory_analysis" in rec:
            ma = rec["memory_analysis"]
            print("  memory_analysis: " + ", ".join(
                f"{k.split('_size')[0]}={v/2**30:.3f}GiB"
                for k, v in ma.items()))
        print(f"  state/device: {rec['state_bytes_per_device']/2**30:.3f}GiB"
              + (f"  cache/device: {rec['cache_bytes_per_device']/2**30:.3f}GiB"
                 if "cache_bytes_per_device" in rec else ""))
        print("  cost_analysis flops (1 while-trip): "
              f"{rec.get('cost_flops_raw', 0):.3e}")
        print("  HLO dot-FLOPs/device (unrolled): "
              f"{rec['hlo_flops_per_device']:.3e}")
        print("  HLO HBM bytes/device (unrolled): "
              f"{rec['hlo_bytes_per_device']:.3e}")
        print("  collective wire bytes/device: "
              f"{coll.wire_bytes:.3e}  by kind: "
              + json.dumps({k: f"{v:.2e}" for k, v in coll.by_kind.items()}))
    return rec


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def all_cells():
    from repro.configs.registry import ARCHS
    from repro.models.config import ALL_SHAPES
    for arch in ARCHS:
        for cell in ALL_SHAPES:
            yield arch, cell.name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell in subprocesses")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="re-run cells that already have artifacts")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args(argv)

    if args.list:
        for arch, shape in all_cells():
            print(f"{arch:24s} {shape}")
        return 0

    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        failures = []
        for arch, shape in all_cells():
            for mk in meshes:
                path = _artifact_path(arch, shape, mk)
                if os.path.exists(path) and not args.force:
                    print(f"skip (exists): {arch}/{shape}/{mk}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mk]
                print(f">>> {arch}/{shape}/{mk}", flush=True)
                t0 = time.time()
                r = subprocess.run(cmd, timeout=args.timeout)
                print(f"<<< rc={r.returncode} {time.time()-t0:.0f}s",
                      flush=True)
                if r.returncode != 0:
                    failures.append((arch, shape, mk))
        if failures:
            print("FAILED cells:", failures)
            return 1
        print("all cells done")
        return 0

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    rc = 0
    for mk in meshes:
        path = _artifact_path(args.arch, args.shape, mk)
        try:
            rec = run_cell(args.arch, args.shape, mk)
        except Exception as e:  # record the failure as an artifact too
            rec = {"arch": args.arch, "shape": args.shape, "mesh": mk,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            print(rec["traceback"], file=sys.stderr)
            rc = 1
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"artifact: {path}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
