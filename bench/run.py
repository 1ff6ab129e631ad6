"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic file are found by name
through ``BENCHMARK.json`` at the root of the checkout; a per-layer
metric is read by ``bench/metrics/<name>.py`` and a configuration's
plain reference is ``bench/reference/<reference>.py``, which may bring
the configuration's own FLOP count (``train_flops_per_step``).  Set-up
makes the weights and the data from ``--seed``, preloads the store and
runs the first three training steps (which compile); the window then
trains for ``--seconds``; after it closes, the run checks what the
window produced (the first steps against the reference, every ingested
row, every restore, the DES pricing) and prints each compared number
beside its limit, on standard error and under ``checks`` in the last
line of standard output.  ``--trace 1`` records the window with the
profiler and the program's own spans and counters, and reports the
per-layer metrics instead of the end-to-end ones.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
EXACT = ("ingest_mismatches", "ckpt_mismatches", "des_mismatches")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_file_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def read_per_layer(root: Path, manifest: dict, workload: str, ctx) -> dict:
    """Each per-layer metric of the cell, by its reader
    ``bench/metrics/<name>.py``; a reader that finds nothing to read
    returns None, and its metric is left out."""
    values = {}
    for m in manifest["per_layer"]:
        if applies(m, workload):
            v = load_module(root / "bench" / "metrics"
                            / f"{m['name']}.py").read(ctx)
            if v is not None:
                values[m["name"]] = v
    return values


def step_flops(ref, model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: the reference module's own
    ``train_flops_per_step`` where it defines one, else
    ``bench/flops.py``'s."""
    from bench import flops

    count = getattr(ref, "train_flops_per_step", flops.train_flops_per_step)
    return count(model, batch, seq)


def start_jax(root: Path):
    """Import JAX with the program and the harness of ``root`` on the
    path and the compilation cache at ``root/.jax_cache``, the one fixed
    place in the checkout where a later run finds what this one built."""
    for p in (str(root), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    # libtpu logs under /tmp by default; keep them in this run's TMPDIR.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def check_devices(jax, chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"bench: needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")


def first_step_gaps(job, prog, ref, first, stand_ins=()) -> tuple:
    """Free the job's device state, run the reference over the first three
    batches (the sample index of each row in ``first``) and return the
    gaps of the program's readings ``prog`` to it, with the reference's
    readings.  Each of ``stand_ins`` is put in the program's place and its
    gaps returned beside: ``control``, the reference one precision below
    the configuration's, and ``half_batch``, the reference on the first
    half of every batch."""
    from bench.reference import common as C

    rows = job.reference_rows(first)
    job.free()
    model, jc = job.config["model"], job.config["job"]

    def train(rows, lowp=None):
        return C.train3(ref, model, jc["optimizer"], job.layout,
                        job.weights_key, rows, lowp=lowp,
                        block_rows=jc["reference_rows"])

    want = train(rows)
    out = {"program": C.gaps(prog, want)}
    if "control" in stand_ins:
        out["control"] = C.gaps(train(rows, C.LOWER[model["dtype"]]), want)
    if "half_batch" in stand_ins:
        half = [{k: v[: len(v) // 2] for k, v in r.items()} for r in rows]
        out["half_batch"] = C.gaps(train(half), want)
    return out, want


def host_stalls(w, top: int = 5) -> str:
    """The longest intervals between step dispatches in the window and
    the garbage collector's pauses in it, for standard error."""
    gaps = sorted(((b - a, i) for i, (a, b) in
                   enumerate(zip(w.ticks, w.ticks[1:]), 1)), reverse=True)
    med = statistics.median(g for g, _ in gaps) if gaps else 0.0
    full = [p for g, p in w.gc_pauses if g == 2]
    return (f"step interval median {med!r}, longest "
            f"{[(i, round(g, 4)) for g, i in gaps[:top]]}; gc pauses "
            f"{len(w.gc_pauses)} (full {len(full)}), longest "
            f"{max((p for _, p in w.gc_pauses), default=0.0)!r}, total "
            f"{sum(p for _, p in w.gc_pauses)!r}")


def run(argv=None, root: Path = ROOT, require_chip: bool = True,
        t0: float | None = None) -> dict:
    """One run; returns the result that the last line prints."""
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    root = Path(root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if args.workload not in cells:
        ap.error(f"unknown workload {args.workload!r}; "
                 f"choose from {sorted(cells)}")
    cell = cells[args.workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    jax = start_jax(root)
    if require_chip:
        check_devices(jax, cell["chips"])
    from bench import devtrace as tr
    from bench import layers
    from bench.job import Job
    from bench.reference import common as C

    ref = load_module(root / "bench" / "reference"
                      / f"{config['reference']}.py")
    t_job = time.perf_counter()
    job = Job(config, traffic, ref, args.seed)
    t_first = time.perf_counter()
    prog = job.first_steps()
    setup_s = time.perf_counter() - t0
    phases = {"start": t_job - t0, "job": t_first - t_job,
              "first_steps": t0 + setup_s - t_first}

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        w = job.window(args.seconds, trace_dir)
        devs = jax.devices()[:cell["chips"]]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        trace = tr.load(trace_dir) if args.trace else None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t_hlo = time.perf_counter()
    if trace is not None:
        summary = tr.summarize(trace)
        scopes = layers.step_scopes(
            trace, layers.hlo_scopes(job.step_hlo()).get)
        trace = None
    else:
        summary = scopes = None

    t_check = time.perf_counter()
    ingest_bad, first = job.ingest_check()
    ckpt_bad = job.restore_check()
    des_bad, engine, des_phases = job.des_check()
    t_ref = time.perf_counter()
    gaps, want = first_step_gaps(job, prog, ref, first)
    t_done = time.perf_counter()
    ref_peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
    numbers = gaps["program"]
    numbers.update(ingest_mismatches=ingest_bad, ckpt_mismatches=ckpt_bad,
                   des_mismatches=des_bad)
    limits = {**config["limits"], **{k: 0 for k in EXACT}}
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    if summary is None:
        values = {"samples_per_s": w.samples / w.seconds, "setup_s": setup_s}
        if w.save_s:
            values["ckpt_save_s"] = statistics.fmean(w.save_s)
        if w.resume_s:
            values["resume_s"] = statistics.fmean(w.resume_s)
        wanted = manifest["end_to_end"]
    else:
        ctx = SimpleNamespace(
            workload=cell["name"], model=config["model"], job=config["job"],
            ref=ref, window=w, trace=summary, batch=job.B,
            flops_per_step=step_flops(ref, config["model"], job.B, job.seq),
            peak=json.loads((root / "bench" / "peaks.json").read_text())
            ["devices"].get(jax.devices()[0].device_kind),
            telemetry=w.telemetry, scopes=scopes)
        values = read_per_layer(root, manifest, cell["name"], ctx)
        wanted = manifest["per_layer"]
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted
               if applies(m, cell["name"]) and m["name"] in values}

    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(peaks) if None not in peaks else None}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    result = {"correct": correct, "attempted": w.steps,
              "failed": sum(v != v or v in (float("inf"), float("-inf"))
                            for v in w.losses),
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown
    result["checks"] = checks

    err = sys.stderr
    print(f"bench: {cell['name']} seed={args.seed} setup_s={setup_s!r} "
          f"window_s={w.seconds!r} steps={w.steps} saves={w.save_s!r} "
          f"restores={w.resume_s!r} compiles_in_window={w.compiles} "
          f"losses={w.losses!r} trace_s={t_check - t_hlo!r} "
          f"checks_s={t_ref - t_check!r} "
          f"reference_s={t_done - t_ref!r}", file=err)
    print(f"bench: set-up {phases!r}; window {host_stalls(w)}; "
          f"{len(gc.get_objects())} objects tracked", file=err)
    print(f"bench: host peak RSS "
          f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} "
          f"bytes; DES {des_phases} phases, vector engine ran as {engine!r}",
          file=err)
    print(f"bench: program losses {prog.losses!r}, reference "
          f"{want.losses!r}", file=err)
    n_params = C.param_count(job.layout)
    print(f"bench: device peak after the reference {ref_peaks!r} bytes, "
          f"after the window {peaks!r}; reference state budget "
          f"{C.STATE_BYTES_PER_PARAM} x {n_params} parameters = "
          f"{C.STATE_BYTES_PER_PARAM * n_params} bytes", file=err)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=err)
    return result


def main() -> int:
    result = run(sys.argv[1:], t0=T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
