"""Data-ingest + I/O case-study tests: PreloadedStore, TokenPipeline,
SCR emulation, synthetic workloads — all byte-verified through the
consistency layers (these are the paper's workloads at test scale).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs.registry import tiny_config
from repro.core.basefs import EventKind
from repro.data.dlio import PreloadedStore
from repro.data.pipeline import TokenPipeline, make_token_samples
from repro.io.scr import SCRConfig, run_scr
from repro.io.workloads import cc_r, cn_w, cs_r, run_workload, sn_w


@pytest.mark.parametrize("model", ["commit", "session", "posix", "mpiio"])
def test_preloaded_store_roundtrip(model):
    store = PreloadedStore(model, num_hosts=3, samples_per_host=8,
                           sample_bytes=256, procs_per_host=2)
    store.preload()
    stats = store.run_epoch(0)          # verify=True checks every byte
    assert stats.samples_read == 24
    assert stats.local_reads + stats.remote_reads == 24


def test_preloaded_store_query_accounting():
    qs = {}
    for model in ("commit", "session"):
        store = PreloadedStore(model, num_hosts=4, samples_per_host=8,
                               sample_bytes=128, procs_per_host=2)
        store.preload()
        qs[model] = store.run_epoch(0).queries
    assert qs["commit"] == 32           # one query per sample read
    assert qs["session"] <= 4 * 4 * 2   # <= hosts x (hosts x procs)


def test_preloaded_store_real_arrays():
    samples = [np.full((16,), i, np.int32) for i in range(12)]
    store = PreloadedStore("session", num_hosts=2, samples_per_host=6,
                           procs_per_host=1, samples=samples)
    store.preload()
    for idx in (0, 5, 6, 11):
        got = np.frombuffer(store.read_sample(idx, reader_host=1), np.int32)
        np.testing.assert_array_equal(got, samples[idx])


def test_token_pipeline_feeds_training_shapes():
    cfg = dataclasses.replace(tiny_config("starcoder2-3b"),
                              dtype=jnp.float32)
    seq = 12
    samples = make_token_samples(jax.random.PRNGKey(0), 16, seq + 1,
                                 cfg.vocab)
    store = PreloadedStore("session", num_hosts=2, samples_per_host=8,
                           procs_per_host=1,
                           samples=[s.astype(np.int32) for s in samples])
    store.preload()
    pipe = TokenPipeline(store, cfg, batch_size=4, seq=seq)
    batches = list(pipe.batches(epoch=0))
    assert len(batches) == 4
    for b in batches:
        assert b["tokens"].shape == (4, seq)
        assert b["labels"].shape == (4, seq)
        # next-token alignment
        np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                      np.asarray(b["labels"][:, :-1]))


@pytest.mark.parametrize("model", ["commit", "session"])
def test_token_pipeline_counts_samples_and_queries(model):
    """Every fed sample costs one query under either model today:
    ``read_sample`` opens a handle per sample, and under session that
    open is a ``session_open``."""
    cfg = dataclasses.replace(tiny_config("starcoder2-3b"),
                              dtype=jnp.float32)
    samples = [np.full((9,), i, np.int32) for i in range(16)]
    store = PreloadedStore(model, num_hosts=2, samples_per_host=8,
                           procs_per_host=1, samples=samples)
    store.preload()
    pipe = TokenPipeline(store, cfg, batch_size=4, seq=8)
    q0 = store.fs.ledger.count(EventKind.RPC, "query")
    with telemetry.recording() as rec:
        batches = list(pipe.batches(epoch=0))
    assert rec.counters == {
        "ingest.samples": 16,
        "ingest.queries": store.fs.ledger.count(EventKind.RPC, "query") - q0}
    assert rec.counters["ingest.queries"] / rec.counters["ingest.samples"] == 1
    assert [s.name for s in rec.spans] == ["ingest.batch"] * len(batches)


@pytest.mark.parametrize("model", ["commit", "session"])
def test_scr_checkpoint_restart_verified(model):
    cfg = SCRConfig(n=3, model=model, p=2, particles=20_000)
    res = run_scr(cfg)
    # 9 HACC arrays per surviving rank; the failed node's ranks read later
    # (spare_recover phase, excluded from restart accounting).
    assert res.verified_reads == (cfg.ranks - cfg.p) * 9
    assert res.checkpoint_bandwidth > 0
    assert res.restart_bandwidth > 0


def test_scr_session_fewer_queries_than_commit():
    q = {}
    for model in ("commit", "session"):
        res = run_scr(SCRConfig(n=3, model=model, p=2, particles=20_000))
        q[model] = res.rpc_counts["query"]
    assert q["session"] < q["commit"]


@pytest.mark.parametrize("factory", [cn_w, sn_w, cc_r, cs_r])
def test_workloads_verify_all_reads(factory):
    cfg = factory(2, 4096, "session", p=2, m=3)
    res = run_workload(cfg)
    if cfg.read_pattern:
        assert res.verified_reads == cfg.readers * cfg.m_r
    assert res.phases  # DES produced phase timings


def test_workload_ledger_consistency():
    cfg = cc_r(2, 8192, "commit", p=2, m=2)
    res = run_workload(cfg)
    # commit: one query RPC per read op.
    assert res.rpc_counts["query"] == cfg.readers * cfg.m_r
    # every write buffered once on an SSD
    ph = res.phase("write")
    assert ph.bytes_by_kind[EventKind.SSD_WRITE] == (
        cfg.writers * cfg.m_w * cfg.s)
