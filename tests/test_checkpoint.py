"""Checkpoint manager: roundtrip, partner recovery, elastic restart,
level-2 flush, and the consistency-protocol RPC accounting.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.checkpoint.manager import READER_BASE, CheckpointManager, _shard_path
from repro.checkpoint.serialization import SEP, row_partition
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
    # One host copy of each restored byte, none from the device.
    assert rec.counters == {"ckpt.restore.host_copy_bytes": nbytes}


def _restore_reference(mgr, step, template, num_hosts_new=None,
                       failed_hosts=()):
    """The restore as first written, the oracle for the one-copy restore:
    each part through ``bytes``, a ``uint8`` buffer, ``tobytes`` and a
    copy, the dtypes read from the template's values.  Its layer calls
    are the restore's own, in the same order."""
    Hn = num_hosts_new or mgr.num_hosts
    mgr.fs.ledger.mark_phase(f"ckpt_restore_{step}")
    manifest = mgr.read_manifest(step)
    handles = {}

    def get_handle(rh, src, partner):
        if (rh, src, partner) not in handles:
            fh = mgr.layer.open(
                READER_BASE + rh, _shard_path(mgr.base, step, src, partner),
                node=mgr.partner_of(src) if partner else src)
            mgr._open_session(fh)
            handles[rh, src, partner] = fh
        return handles[rh, src, partner]

    arrays = {}
    for path, meta in manifest["leaves"].items():
        shape, dtype = tuple(meta["shape"]), np.dtype(meta["dtype"])
        nrows, rowbytes = (shape[0] if shape else 1), meta["rowbytes"]
        buf = np.empty((nrows, rowbytes), np.uint8)
        for rh, (nrs, nre) in enumerate(row_partition(nrows, Hn)):
            for part in meta["parts"]:
                rs, re = part["rows"]
                lo, hi = max(rs, nrs), min(re, nre)
                if hi <= lo:
                    continue
                src = part["host"]
                fh = get_handle(rh, src, src in failed_hosts)
                mgr.layer.seek(fh, part["offset"] + (lo - rs) * rowbytes)
                data = mgr.layer.read(fh, (hi - lo) * rowbytes)
                buf[lo:hi] = np.frombuffer(bytes(data), np.uint8).reshape(
                    hi - lo, rowbytes)
        arrays[path] = np.frombuffer(buf.tobytes(), dtype).reshape(
            shape).copy()
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in flat:
        key = SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        leaves.append(arrays[key].reshape(np.shape(leaf)).astype(
            np.asarray(leaf).dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _assert_tree_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert (x.dtype, x.shape) == (y.dtype, y.shape)
        assert x.tobytes() == y.tobytes()


RESTARTS = {
    "same_hosts": dict(),
    "elastic_3": dict(num_hosts_new=3),
    "partner_copy": dict(failed_hosts=[1]),
    "pfs": dict(),
}


@pytest.mark.parametrize("restart", sorted(RESTARTS))
@pytest.mark.parametrize("model", ["commit", "session", "mpiio"])
def test_restore_matches_reference_restore(model, restart):
    """The one-copy restore hands back the reference's tree, bit for bit,
    through the same ledger of events."""
    state = _state()
    mgrs = [CheckpointManager(model=model, num_hosts=4) for _ in range(2)]
    for mgr in mgrs:
        mgr.save(4, state)
        if restart == "pfs":
            mgr.flush(4)
            mgr.release(4)
    want = _restore_reference(mgrs[0], 4, state, **RESTARTS[restart])
    got = mgrs[1].restore(4, state, **RESTARTS[restart])
    _assert_tree_bitwise(want, got)
    _assert_tree_bitwise(state, got)
    assert mgrs[0].fs.ledger.events == mgrs[1].fs.ledger.events


@pytest.mark.parametrize("hosts_new", [None, 3])
def test_restore_dtypes_and_shapes_from_template_metadata(hosts_new):
    saved = {
        "bf16": jax.random.normal(jax.random.PRNGKey(1), (5, 8),
                                  jnp.bfloat16),
        "step": jnp.asarray(7, jnp.int32),
        "few_rows": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "converted": jnp.linspace(-3.0, 3.0, 24).reshape(6, 4),
    }
    template = {k: jnp.zeros_like(v) for k, v in saved.items()}
    template["converted"] = jnp.zeros((6, 4), jnp.bfloat16)
    # Only the template's metadata may be read: its buffers are gone.
    for leaf in template.values():
        leaf.delete()
    mgr = CheckpointManager(model="commit", num_hosts=4)
    mgr.save(2, saved)
    with telemetry.recording() as rec:
        out = mgr.restore(2, template, num_hosts_new=hosts_new)
    assert rec.counters.get("ckpt.restore.d2h_bytes", 0) == 0
    assert rec.counters["ckpt.restore.host_copy_bytes"] == sum(
        x.nbytes for x in saved.values())
    want = {k: np.asarray(v) for k, v in saved.items()}
    want["converted"] = want["converted"].astype(jnp.bfloat16)
    for k, leaf in out.items():
        assert isinstance(leaf, np.ndarray)
        assert (leaf.dtype, leaf.shape) == (want[k].dtype, want[k].shape)
        assert leaf.tobytes() == want[k].tobytes()
