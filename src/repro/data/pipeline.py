"""Token batch pipeline: PreloadedStore samples -> train_step batches.

Samples are fixed-length int32 token sequences stored as bytes in the
burst-buffer store; the pipeline assembles (tokens, labels) batches with
next-token labels.  ``synthetic_batch`` provides mesh-shardable random
batches for smoke tests and the dry-run input_specs.
"""

from __future__ import annotations

from typing import Dict, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.basefs import EventKind
from repro.data.dlio import PreloadedStore
from repro.models.config import ModelConfig


def synthetic_batch(key, cfg: ModelConfig, batch: int, seq: int
                    ) -> Dict[str, jax.Array]:
    kt, kl = jax.random.split(key)
    toks = jax.random.randint(kt, (batch, seq), 0, cfg.vocab, jnp.int32)
    out = {"tokens": toks,
           "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.frontend == "audio":
        from repro.models.frontends import audio_frames
        out["frames"] = audio_frames(cfg, batch, key=kl)
    elif cfg.frontend == "vision":
        from repro.models.frontends import vision_patches
        out["patches"] = vision_patches(cfg, batch, key=kl)
    return out


def make_token_samples(key, n: int, seq: int, vocab: int
                       ) -> List[np.ndarray]:
    """Deterministic corpus of fixed-length int32 sequences."""
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    return [rng.integers(0, vocab, size=(seq,), dtype=np.int32)
            for _ in range(n)]


class TokenPipeline:
    """Feeds train_step from a PreloadedStore, epoch by epoch.

    Every sample byte-string that reaches a batch came through the
    consistency layer (local or cross-host burst-buffer read), so data-
    ingest I/O counts appear in the store's ledger alongside training.
    """

    def __init__(self, store: PreloadedStore, cfg: ModelConfig,
                 batch_size: int, seq: int, seed: int = 0) -> None:
        self.store = store
        self.cfg = cfg
        self.B = batch_size
        self.seq = seq
        self.seed = seed

    def batches(self, epoch: int, reader_host: int = 0
                ) -> Iterator[Dict[str, jax.Array]]:
        assign = self.store.epoch_assignment(epoch, self.seed)
        flat = [i for sub in assign for i in sub]
        ledger = self.store.fs.ledger
        for b0 in range(0, len(flat) - self.B + 1, self.B):
            with telemetry.span("ingest.batch"):
                q0 = ledger.count(EventKind.RPC, "query")
                toks = []
                for idx in flat[b0 : b0 + self.B]:
                    raw = self.store.read_sample(idx, reader_host=reader_host)
                    toks.append(np.frombuffer(raw, np.int32)[: self.seq])
                tokens = jnp.asarray(np.stack(toks))
                batch = {"tokens": tokens,
                         "labels": jnp.roll(tokens, -1, axis=1)}
                telemetry.count("ingest.samples", len(toks))
                telemetry.count("ingest.queries",
                                ledger.count(EventKind.RPC, "query") - q0)
            yield batch
