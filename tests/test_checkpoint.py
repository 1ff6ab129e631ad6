"""Checkpoint manager: roundtrip, partner recovery, elastic restart,
level-2 flush, and the consistency-protocol RPC accounting.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import tiny_config
from repro.core.basefs import EventKind
from repro.launch.mesh import opt_for
from repro.train.train_step import train_state_init

CFG = dataclasses.replace(tiny_config("qwen3-32b"), dtype=jnp.float32)


def _state():
    return train_state_init(jax.random.PRNGKey(0), CFG, opt_for(CFG))


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("model", ["commit", "session"])
def test_save_restore_roundtrip(model):
    state = _state()
    mgr = CheckpointManager(model=model, num_hosts=4)
    mgr.save(0, state)
    out = mgr.restore(0, state)
    _assert_tree_equal(state, out)


def test_elastic_restart_different_host_count():
    state = _state()
    mgr = CheckpointManager(model="session", num_hosts=4)
    mgr.save(3, state)
    for new_hosts in (1, 2, 3, 6, 8):
        out = mgr.restore(3, state, num_hosts_new=new_hosts)
        _assert_tree_equal(state, out)


def test_partner_recovery_single_host_failure():
    state = _state()
    mgr = CheckpointManager(model="session", num_hosts=4, partner=True)
    mgr.save(1, state)
    for failed in range(4):
        out = mgr.restore(1, state, failed_hosts=[failed])
        _assert_tree_equal(state, out)


def test_failure_without_partner_raises():
    state = _state()
    mgr = CheckpointManager(model="session", num_hosts=2, partner=False)
    mgr.save(0, state)
    with pytest.raises(RuntimeError):
        mgr.restore(0, state, failed_hosts=[0])


def test_flush_release_then_cold_restore_from_pfs():
    state = _state()
    mgr = CheckpointManager(model="commit", num_hosts=2, partner=False)
    mgr.save(7, state)
    mgr.flush(7)      # level-2: drain to the underlying PFS
    mgr.release(7)    # drop burst-buffer ownership
    out = mgr.restore(7, state)   # falls through to the PFS
    _assert_tree_equal(state, out)


def test_commit_vs_session_query_gap():
    """The paper's Fig-5 effect on real training state: commit queries per
    read, session once per (reader x file)."""
    state = _state()
    counts = {}
    for model in ("commit", "session"):
        mgr = CheckpointManager(model=model, num_hosts=4)
        mgr.save(0, state)
        q0 = mgr.fs.ledger.count(EventKind.RPC, "query")
        mgr.restore(0, state)
        counts[model] = mgr.fs.ledger.count(EventKind.RPC, "query") - q0
    assert counts["commit"] > 4 * counts["session"], counts


def test_manifest_orders_after_shards():
    """The manifest commit is the hb edge restarts rely on: it must be
    published AFTER every shard publish in the ledger order."""
    state = _state()
    mgr = CheckpointManager(model="commit", num_hosts=3, partner=False)
    mgr.save(0, state)
    attaches = [e for e in mgr.fs.ledger.events
                if e.kind is EventKind.RPC and e.rpc_type == "attach"]
    # manifest writer is client 0 and the LAST attach must be the manifest's
    assert attaches, "no attach RPCs recorded"
    assert attaches[-1].client == 0


def test_save_and_restore_spans_and_host_copies():
    state = _state()
    mgr = CheckpointManager(model="commit", num_hosts=4)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
    with telemetry.recording() as rec:
        mgr.save(5, state)
        out = mgr.restore(5, state, num_hosts_new=3, failed_hosts=[1])
    _assert_tree_equal(state, out)
    assert [(s.name, s.parent) for s in rec.spans] == [
        ("ckpt.save", None), ("ckpt.save.serialize", 0),
        ("ckpt.save.write", 0), ("ckpt.save.publish", 0),
        ("ckpt.restore", None), ("ckpt.restore.manifest", 4),
        ("ckpt.restore.read", 4), ("ckpt.restore.assemble", 4)]
    assert rec.spans[0].attrs == {"step": 5, "bytes": nbytes}
    assert rec.spans[4].attrs == {"step": 5, "hosts": 3}
    # The template's dtypes are read from the device: one full copy.
    assert rec.counters == {"ckpt.restore.d2h_bytes": nbytes}
