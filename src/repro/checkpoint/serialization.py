"""Tensor-tree <-> bytes with a layout manifest for elastic restart.

Every leaf is flattened to a C-order byte string; the manifest records
``path -> (shape, dtype, row partition)`` where rows are axis-0 slices.
Row-partitioned leaves let a restart with a *different* host count read
exactly the byte ranges it needs (possibly spanning several writers'
shard files) — the manifest is the sharding-layout contract.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

SEP = "/"


def flatten_with_paths(tree) -> List[Tuple[str, np.ndarray]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append((key, np.asarray(leaf)))
    return out


def serialize_tree(tree) -> Dict[str, np.ndarray]:
    return dict(flatten_with_paths(tree))


def tree_manifest(tree) -> Dict[str, Dict[str, Any]]:
    return {
        k: {"shape": list(v.shape), "dtype": str(v.dtype)}
        for k, v in flatten_with_paths(tree)
    }


def deserialize_tree(template, arrays: Dict[str, np.ndarray]):
    """Rebuild a pytree shaped like ``template`` from named arrays.

    Each leaf's dtype and shape come from the template's metadata, so a
    template on the device is never copied to the host; an array that
    already has them is handed back as it is.
    """
    flat = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in flat[0]:
        key = SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        if not hasattr(leaf, "dtype"):     # a Python scalar
            leaf = np.asarray(leaf)
        leaves.append(arrays[key].reshape(leaf.shape).astype(
            leaf.dtype, copy=False))
    return jax.tree_util.tree_unflatten(flat[1], leaves)


def row_partition(nrows: int, num_hosts: int) -> List[Tuple[int, int]]:
    """Contiguous row ranges per host (first hosts take the remainder)."""
    base, rem = divmod(nrows, num_hosts)
    out, start = [], 0
    for h in range(num_hosts):
        n = base + (1 if h < rem else 0)
        out.append((start, start + n))
        start += n
    return out


def manifest_to_json(manifest: Dict[str, Any]) -> bytes:
    return json.dumps(manifest, sort_keys=True).encode()


def manifest_from_json(data: bytes) -> Dict[str, Any]:
    return json.loads(data.decode())
