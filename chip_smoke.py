"""Bring-up smoke run on one TPU chip, through the normal entry points.

    python chip_smoke.py

One process runs four phases in order, each printing its findings:

1. device    -- platform, device kind and count; anything but a TPU is a
                failure, never a fall-back to the CPU;
2. fig6      -- the simulator's DL random-read figure on the paper's full
                grid (116 KB samples, 4 procs/host, 2-16 hosts, strong and
                weak scaling, commit vs session), host only: its claims must
                pass, its points must equal ``artifacts/bench/fig6.csv``
                where that file has them, and it must touch no device;
3. dl-job    -- ``examples/train_checkpoint.py`` at its default width
                (~100M-parameter decoder): tokens ingested through the
                session layer, 20 steps, partner checkpoints every 10, host 1
                lost at step 10, restore on 3 hosts, resume, PFS flush, DES
                pricing;
4. launcher  -- ``repro.launch.train`` with whisper-small at its published
                widths in bf16, a checkpoint every 2 steps and a host
                failure at step 2.

Any failed check exits non-zero.  The last line of standard output is the
JSON object ``{"ok": true, "device": {...}}``, printed only when every
phase passed.  The compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, or to ``.jax_cache`` in the checkout.
"""

import csv
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent


def check(ok: bool, what: str) -> None:
    print(f"  check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: failed: {what}")


def device_phase(jax) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    check(dev["platform"] == "tpu", "JAX runs on a TPU")
    return dev


def fig6_phase(jax) -> None:
    from benchmarks import fig6_dl

    compiles = []

    def on_event(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    arrays_before = len(jax.live_arrays())
    t0 = time.perf_counter()
    rows = fig6_dl.run(fast=False)
    wall = time.perf_counter() - t0
    print(f"[fig6] {len(rows)} points in {wall:.3f} s wall; "
          f"{len(compiles)} device compiles, "
          f"{len(jax.live_arrays()) - arrays_before} new device arrays "
          "(host only)", flush=True)
    check(not compiles and len(jax.live_arrays()) == arrays_before,
          "fig6 used no device")
    for claim in fig6_dl.CLAIMS:
        check(claim.evaluate(rows) is True, f"fig6 claim: {claim.text}")

    def key(r):
        return (r["scaling"], int(r["hosts"]), int(r["shards"]), r["model"])

    got = {key(r): r for r in rows}
    with open(ROOT / "artifacts" / "bench" / "fig6.csv", newline="") as f:
        ref = list(csv.DictReader(f))
    same = [all(str(got[key(r)][k]) == v for k, v in r.items())
            for r in ref if key(r) in got]
    check(len(same) == len(ref) and all(same),
          f"fig6 equals the {len(ref)} points of artifacts/bench/fig6.csv")


def dl_job_phase() -> None:
    import train_checkpoint

    print("[dl-job] examples/train_checkpoint.py, default width", flush=True)
    res = train_checkpoint.main(["--steps", "20", "--ckpt-every", "10"])
    print(f"[dl-job] params={res['params']} step_s={res['step_s']!r} "
          f"peak_bytes_in_use={res['peak_bytes']} "
          f"ckpt_bw={res['ckpt_bw']!r} B/s", flush=True)
    check(math.isfinite(res["loss"]), f"loss is finite ({res['loss']!r})")
    check(res["restored_bitwise"],
          "every restored leaf is bitwise the saved one")
    check(res["compiles_before_restore"] == 1 and res["compiles"] == 1,
          "the step compiled once and the restore caused no recompile")
    check(res["ckpt_bw"] > 0, "the DES prices a checkpoint bandwidth > 0")


def launcher_phase(jax) -> None:
    from repro.launch import train

    print("[launcher] repro.launch.train --arch whisper-small", flush=True)
    res = train.main(["--arch", "whisper-small", "--steps", "6",
                      "--ckpt-every", "2", "--fail-at", "2"])
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[launcher] step_s={res['step_s']!r} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    check(math.isfinite(res["loss"]), f"loss is finite ({res['loss']!r})")
    check(res["compiles"] == 1,
          "the step compiled once and the restore caused no recompile")


def main() -> int:
    import jax

    dev = device_phase(jax)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(ROOT / "examples")]
    from repro.launch.cache import use_compile_cache

    print(f"[cache] {use_compile_cache()}", flush=True)
    fig6_phase(jax)
    dl_job_phase()
    launcher_phase(jax)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
