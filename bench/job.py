"""The training job that a cell drives, composed from the program's layers.

One ``Job`` is one client of the system: it preloads its data set into a
``PreloadedStore`` through the configuration's consistency layer, pulls
batches through ``TokenPipeline``, trains with the program's jitted
``train_step``, and saves and restores partner checkpoints through
``CheckpointManager``, all over one ``BaseFS``.  What a cell varies is
data: the configuration file (model, batch, storage, guarantees, limits)
and the traffic file (when to save, when a host is lost).
"""

from __future__ import annotations

import contextlib
import gc
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common as C

# Steps in flight before the loop waits for the oldest: ingest of the
# next batch overlaps the device's step, as a prefetching job does.
IN_FLIGHT = 2


@dataclass
class Window:
    """What the measured window did and took, on the host clock."""
    seconds: float = 0.0
    steps: int = 0
    samples: int = 0
    save_s: List[float] = field(default_factory=list)
    resume_s: List[float] = field(default_factory=list)
    save_rpcs: int = 0
    restore_rpcs: int = 0
    losses: List[float] = field(default_factory=list)
    compiles: int = 0
    ticks: List[float] = field(default_factory=list)
    gc_pauses: List[tuple] = field(default_factory=list)
    spans: Dict[str, List[float]] = field(default_factory=lambda:
                                          defaultdict(list))
    # The program's spans and counters (``repro.telemetry.Recorder``),
    # kept in traced windows only.
    telemetry: Optional[object] = None


class Job:
    def __init__(self, config: dict, traffic: dict, ref, seed: int,
                 step_fn=None) -> None:
        from repro.checkpoint.manager import CheckpointManager
        from repro.data.dlio import PreloadedStore
        from repro.data.pipeline import TokenPipeline
        from repro.models import transformer as T
        from repro.models.config import ModelConfig
        from repro.train.optimizer import AdamWConfig, adamw_init
        from repro.train.train_step import make_train_step

        self.config, self.traffic, self.ref, self.seed = (
            config, traffic, ref, seed)
        model, job, storage = (config["model"], config["job"],
                               config["storage"])
        fields = ModelConfig.__dataclass_fields__
        kw = {k: v for k, v in model.items() if k in fields}
        for k in ("dtype", "opt_state_dtype"):
            kw[k] = C.DTYPES[kw[k]]
        self.cfg = cfg = ModelConfig(name=config["name"], **kw)
        self.B, self.seq = job["batch"], job["seq"]
        self.opt = job["optimizer"]
        opt = AdamWConfig(state_dtype=cfg.opt_state_dtype, **self.opt)

        key = C.seed_key(seed)
        self.weights_key = jax.random.fold_in(key, 1)
        self.frames_key = jax.random.fold_in(key, 2)
        self.layout = ref.layout(model)
        _same_layout(jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(0), cfg)),
            jax.eval_shape(lambda: C.init(self.weights_key, self.layout)))

        @jax.jit
        def make_state(k):
            params = C.init(k, self.layout)
            return {"params": params, "opt": adamw_init(params, opt),
                    "step": jnp.zeros((), jnp.int32)}

        self.state = make_state(self.weights_key)

        hosts, per_host = storage["num_hosts"], storage["samples_per_host"]
        rng = np.random.default_rng(seed)
        self.samples = rng.integers(0, cfg.vocab,
                                    (hosts * per_host, self.seq + 1),
                                    dtype=np.int32)
        self.store = PreloadedStore(
            storage["consistency"], num_hosts=hosts,
            samples_per_host=per_host, procs_per_host=1,
            samples=list(self.samples))
        self.store.preload()
        self.pipe = TokenPipeline(self.store, cfg, batch_size=self.B,
                                  seq=self.seq, seed=seed)
        self.mgr = CheckpointManager(
            model=storage["consistency"], num_hosts=hosts,
            partner=storage["partner"], fs=self.store.fs)
        self.step_fn = step_fn or jax.jit(make_train_step(
            cfg, opt, num_microbatches=job.get("microbatches", 1)))
        self.epoch, self.k = 0, 0
        self.batches = self.pipe.batches(0)
        self.fed: List[Dict[str, jax.Array]] = []
        self.restore_same: List[jax.Array] = []
        self.manifest_ok: Optional[bool] = None

    # ------------------------------------------------------------ feed
    def next_batch(self) -> Dict[str, jax.Array]:
        """The next batch through ``TokenPipeline``, a new epoch's
        shuffle when one runs out, and the stub frontend's frames."""
        try:
            batch = next(self.batches)
        except StopIteration:
            self.epoch += 1
            self.batches = self.pipe.batches(self.epoch)
            batch = next(self.batches)
        self.k += 1
        self.fed.append({"tokens": batch["tokens"],
                         "labels": batch["labels"]})
        if self.cfg.frontend == "audio":
            from repro.models.frontends import audio_frames
            batch["frames"] = audio_frames(
                self.cfg, self.B,
                key=jax.random.fold_in(self.frames_key, self.k))
        return batch

    # ----------------------------------------------------- first steps
    def first_steps(self, n: int = 3) -> C.Readings:
        """Set-up's steps through the window's own call and feed: they
        compile the step and give the readings the reference checks."""
        norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])
        moved = jax.jit(lambda a, b: norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b)))
        p0 = self.state["params"]
        losses, grads, update1 = [], [], []
        for i in range(n):
            batch = self.next_batch()
            self.state, metrics = self.step_fn(self.state, batch)
            losses.append(float(metrics["loss"]))
            if i == 0:
                scale = (1 - self.opt["b1"]) * float(metrics["clip"])
                grads = [float(x) / scale
                         for x in norms(self.state["opt"]["m"])]
                update1 = [float(x)
                           for x in moved(self.state["params"], p0)]
        update = [float(x) for x in moved(self.state["params"], p0)]
        self.metrics = metrics
        self.last_batch = batch
        if self.traffic.get("fail_after_save"):
            self._same = jax.jit(_bitwise_same)
            self._same(self.state, self.state).block_until_ready()
            self._zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
            jax.block_until_ready(self._zeros(self.state))
        return C.Readings(losses, grads, update1, update)

    # ---------------------------------------------------------- window
    def window(self, seconds: float, trace_dir: Optional[str] = None
               ) -> Window:
        """Train for ``seconds`` as the traffic says; with ``trace_dir``,
        under the profiler and with the program's spans and counters
        recorded into ``Window.telemetry``."""
        from repro.core.basefs import EventKind

        w = Window()
        ledger = self.store.fs.ledger
        compiles = []

        def on_event(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        gc_start = [0.0]

        def on_gc(phase, info):
            if phase == "start":
                gc_start[0] = time.perf_counter()
            else:
                w.gc_pauses.append((info["generation"],
                                    time.perf_counter() - gc_start[0]))

        gc.callbacks.append(on_gc)

        @contextlib.contextmanager
        def span(name):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                yield
            w.spans[name].append(time.perf_counter() - t)

        saves = [f * seconds for f in self.traffic["saves_at"]]
        fail_after = self.traffic.get("fail_after_save")
        state, metrics = self.state, self.metrics
        inflight: deque = deque()
        last_save = None
        with _traced(trace_dir, w), jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            while True:
                now = time.perf_counter() - t0
                if now >= seconds:
                    break
                if len(w.save_s) < len(saves) and now >= saves[len(w.save_s)]:
                    w.losses.append(float(metrics["loss"]))
                    r0 = ledger.count(EventKind.RPC)
                    with span("ckpt_save"):
                        t = time.perf_counter()
                        last_save = (self.k, self.mgr.save(self.k, state))
                        w.save_s.append(time.perf_counter() - t)
                    w.save_rpcs += ledger.count(EventKind.RPC) - r0
                    if len(w.save_s) == fail_after:
                        # The restarted job's own state, not the saved one,
                        # is the template: a restore that hands back its
                        # template reads back zeros.
                        template = self._zeros(state)
                        r0 = ledger.count(EventKind.RPC)
                        with span("restore"):
                            t = time.perf_counter()
                            restored = jax.device_put(self.mgr.restore(
                                self.k, template,
                                num_hosts_new=self.traffic["restore_hosts"],
                                failed_hosts=self.traffic["failed_hosts"]))
                            jax.block_until_ready(restored)
                            w.resume_s.append(time.perf_counter() - t)
                        w.restore_rpcs += ledger.count(EventKind.RPC) - r0
                        self.restore_same.append(self._same(restored, state))
                        state, template = restored, None
                    continue
                with span("ingest"):
                    batch = self.next_batch()
                with span("step"):
                    if len(inflight) >= IN_FLIGHT:
                        inflight.popleft().block_until_ready()
                    state, metrics = self.step_fn(state, batch)
                    inflight.append(metrics["loss"])
                w.ticks.append(time.perf_counter() - t0)
                w.steps += 1
            w.losses.append(float(metrics["loss"]))
            jax.block_until_ready(state)
            if last_save is not None:
                self.manifest_ok = (self.mgr.read_manifest(last_save[0])
                                    == last_save[1])
            w.seconds = time.perf_counter() - t0
        jax.monitoring.unregister_event_duration_listener(on_event)
        gc.callbacks.remove(on_gc)
        w.compiles = len(compiles)
        w.samples = w.steps * self.B
        self.state, self.metrics = state, metrics
        return w

    def step_hlo(self) -> str:
        """The optimized HLO text of the compiled train step that the
        window ran: lowered with arrays like the window's own, it is
        found in the step's cache and compiles nothing."""
        return self.step_fn.lower(self.state,
                                  self.last_batch).compile().as_text()

    # -------------------------------------------------------- checking
    def ingest_check(self) -> tuple:
        """Every fed row against the preloaded samples: each row is the
        first ``seq`` tokens of one sample, its labels the row shifted by
        one (``TokenPipeline``'s next-token labels, wrapping at the end),
        and no sample twice in one epoch.  Returns the mismatches and the
        sample index of each row of the first three batches."""
        seq = self.seq
        index = {row[:seq].tobytes(): i for i, row in enumerate(self.samples)}
        per_epoch = len(self.samples) // self.B
        bad, first, seen = 0, [], set()
        for k, batch in enumerate(self.fed):
            if k % per_epoch == 0:
                seen = set()
            toks = np.asarray(batch["tokens"])
            labels = np.asarray(batch["labels"])
            bad += int(np.sum(np.any(labels != np.roll(toks, -1, 1), 1)))
            rows = []
            for row in toks:
                i = index.get(row.tobytes(), -1)
                bad += i < 0 or i in seen
                seen.add(i)
                rows.append(i)
            if k < 3:
                first.append(rows)
        return bad, first

    def restore_check(self) -> int:
        """Restored leaves that are not bitwise the saved ones, plus a
        last manifest that did not read back as it was written."""
        bad = sum(int(x) for x in self.restore_same)
        return bad + (self.manifest_ok is False)

    def des_check(self) -> tuple:
        """The ledger priced by the vector engine against the scalar
        DES: phases whose name, makespan or priced messages differ."""
        from repro.core.costmodel import CostModel

        ledger = self.store.fs.ledger
        self.store.fs.drain()
        vec = CostModel().replay(ledger, engine="vector")
        ref = CostModel().replay(ledger)
        bad = abs(len(vec) - len(ref)) + sum(
            (a.name, a.duration, a.rpc_msgs) != (b.name, b.duration, b.rpc_msgs)
            for a, b in zip(vec, ref))
        return bad, vec.engine, len(ref)

    def reference_rows(self, first: List[List[int]]) -> List[dict]:
        """The first three batches, rebuilt from the harness's own
        samples (and frames), for the reference."""
        out = []
        for k, idx in enumerate(first):
            fed = np.asarray(self.fed[k]["tokens"])
            toks = np.stack([self.samples[i, :self.seq] if i >= 0 else f
                             for i, f in zip(idx, fed)])
            rows = {"tokens": toks, "labels": np.roll(toks, -1, 1)}
            if self.cfg.frontend == "audio":
                m = self.config["model"]
                rows["frames"] = np.asarray(C.audio_frames(
                    jax.random.fold_in(self.frames_key, k + 1), self.B,
                    m["enc_len"], m["d_model"], m["dtype"]))
            out.append(rows)
        return out

    def free(self) -> None:
        """Drop every device array the job holds."""
        self.state = self.metrics = self.last_batch = None
        self.fed, self.restore_same = [], []
        gc.collect()


@contextlib.contextmanager
def _traced(trace_dir: Optional[str], w: Window):
    """The profiler and the program's recording around a window, where
    ``trace_dir`` asks for them; nothing otherwise."""
    if trace_dir is None:
        yield
        return
    from repro import telemetry

    with telemetry.recording() as rec:
        jax.profiler.start_trace(trace_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    w.telemetry = rec


def _bitwise_same(a, b) -> jax.Array:
    """Number of leaves of ``a`` whose bits differ from ``b``'s."""
    def bits(x):
        width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        return jax.lax.bitcast_convert_type(x, width)
    return sum(jnp.any(bits(x) != bits(y)).astype(jnp.int32)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _same_layout(program, harness) -> None:
    """The harness's weights must fill the program's tree exactly."""
    pa, pb = jax.tree.structure(program), jax.tree.structure(harness)
    if pa != pb:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"{pb}\nvs\n{pa}")
    for x, y in zip(jax.tree.leaves(program), jax.tree.leaves(harness)):
        if (x.shape, x.dtype) != (y.shape, y.dtype):
            raise ValueError(f"leaf {y.shape} {y.dtype} vs the program's "
                             f"{x.shape} {x.dtype}")
