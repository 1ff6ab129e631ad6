"""End-to-end driver: train a ~100M decoder LM with consistency-aware
checkpointing, kill a host mid-run, and resume from the partner copy on a
DIFFERENT host count (elastic restart).

    PYTHONPATH=src python examples/train_checkpoint.py \\
        [--steps 300] [--d-model 768] [--layers 12] [--model session]

The default model is ~100M parameters (d=768, 12L, ff=3072, vocab 8192),
sized for one accelerator; on a CPU pass ``--steps 20 --d-model 256
--layers 6`` for a quick demo, the code path is identical.  Data flows
PreloadedStore -> TokenPipeline -> train_step, i.e. every training token
moved through the burst-buffer consistency layer, and checkpoints move
through CheckpointManager on the same layer.

``main(argv)`` returns what a caller checks: the final loss, whether the
restored state is bitwise the saved one, how many times the train step
compiled (before the failure and in all), the mean DES-priced
checkpoint bandwidth, the median step time after the first (compiling)
step, the parameter count and the device's peak memory.
"""

import argparse
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core.costmodel import CostModel
from repro.data.dlio import PreloadedStore
from repro.data.pipeline import TokenPipeline, make_token_samples
from repro.launch.cache import use_compile_cache
from repro.models.config import ModelConfig
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import make_train_step, train_state_init


def build_cfg(args) -> ModelConfig:
    return ModelConfig(
        name="example-lm",
        kind="decoder",
        n_layers=args.layers,
        d_model=args.d_model,
        n_heads=max(args.d_model // 64, 1),
        n_kv_heads=max(args.d_model // 128, 1),
        d_ff=4 * args.d_model,
        vocab=8192,
        dtype=jnp.float32,
        policy="dp",
    )


def _bitwise_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and x.shape == y.shape
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--model", default="session",
                    choices=["commit", "session", "posix", "mpiio"])
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--hosts", type=int, default=4)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = build_cfg(args)
    print(f"model: {cfg.params_total()/1e6:.1f}M params, "
          f"consistency={args.model}, hosts={args.hosts}")

    # ---- data: preloaded burst-buffer shards ---------------------------
    n_samples = 64
    samples = make_token_samples(jax.random.PRNGKey(0), n_samples,
                                 args.seq + 1, cfg.vocab)
    store = PreloadedStore(args.model, num_hosts=args.hosts,
                           samples_per_host=n_samples // args.hosts,
                           procs_per_host=1,
                           samples=[s.astype(np.int32) for s in samples])
    store.preload()
    pipe = TokenPipeline(store, cfg, batch_size=args.batch, seq=args.seq)

    # ---- training state + checkpoint manager ---------------------------
    opt = AdamWConfig(lr=1e-3)
    state = train_state_init(jax.random.PRNGKey(1), cfg, opt)
    step = jax.jit(make_train_step(cfg, opt))
    mgr = CheckpointManager(model=args.model, num_hosts=args.hosts,
                            partner=True, fs=store.fs)
    step_s = []

    def train_step(state, batch):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])            # waits for the step
        step_s.append(time.perf_counter() - t0)
        return state, loss

    fail_at = args.steps // 2
    i, epoch = 0, 0
    last_ckpt, saved = 0, None
    while i < fail_at:
        for batch in pipe.batches(epoch):
            state, loss = train_step(state, batch)
            i += 1
            if i % 10 == 0:
                print(f"step {i:4d}  loss {loss:.4f}  "
                      f"({step_s[-1]:.3f}s/step)")
            if i % args.ckpt_every == 0:
                mgr.save(i, state)
                last_ckpt, saved = i, state
                print(f"step {i:4d}  checkpointed (level-1, partner copy)")
            if i >= fail_at:
                break
        epoch += 1
    if last_ckpt == 0:
        mgr.save(i, state)
        last_ckpt, saved = i, state
    compiles_before_restore = step._cache_size()

    # ---- simulated failure: host 1 dies; elastic resume on hosts-1 -----
    print(f"\n*** host 1 fails at step {i}; resuming step {last_ckpt} "
          f"checkpoint on {args.hosts - 1} hosts (partner copy) ***\n")
    state = jax.device_put(mgr.restore(last_ckpt, state,
                                       num_hosts_new=args.hosts - 1,
                                       failed_hosts=[1]))
    restored_bitwise = _bitwise_equal(state, saved)
    saved = None
    print(f"restored state bitwise equal to the saved one: "
          f"{restored_bitwise}")
    i = last_ckpt

    while i < args.steps:
        for batch in pipe.batches(epoch):
            state, loss = train_step(state, batch)
            i += 1
            if i % 10 == 0:
                print(f"step {i:4d}  loss {loss:.4f}")
            if i >= args.steps:
                break
        epoch += 1

    mgr.save(args.steps, state)
    mgr.flush(args.steps)     # level-2: drain to the underlying PFS
    print(f"\nfinal loss {loss:.4f} after {i} steps "
          "(1 failure, elastic restart)")

    # ---- I/O accounting through the DES --------------------------------
    phases = CostModel().replay(store.fs.ledger)
    ck = [p for p in phases if p.name.startswith("ckpt_save")]
    bw = sum(p.io_bandwidth for p in ck) / len(ck)
    print(f"mean modeled checkpoint bandwidth: {bw/1e9:.2f} GB/s "
          f"({len(ck)} checkpoints, {args.model} consistency)")

    stats = jax.devices()[0].memory_stats() or {}
    out = {
        "loss": loss,
        "restored_bitwise": restored_bitwise,
        "compiles_before_restore": compiles_before_restore,
        "compiles": step._cache_size(),
        "ckpt_bw": bw,
        "step_s": statistics.median(step_s[1:]) if step_s[1:] else None,
        "params": cfg.params_total(),
        "peak_bytes": stats.get("peak_bytes_in_use"),
    }
    print(f"result: {out}")
    return out


if __name__ == "__main__":
    main()
