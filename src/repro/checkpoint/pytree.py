"""Pytree (de)serialization over the CheckpointStore.

Each leaf is one KV: key = "<name>/<step>/<leaf-path>", value = npy bytes.
Shards are mesh-shape-agnostic (full logical tensors + dtype/shape headers
in npy), so restore can reshard onto a different device count — the
elasticity requirement in DESIGN.md §6.
"""

from __future__ import annotations

import io
import json

import jax
import numpy as np

from .store import CheckpointStore


def _leaf_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]
    return keys, [l for _, l in flat], treedef


def save_pytree(store: CheckpointStore, name: str, step: int, tree,
                hot: bool = True) -> None:
    keys, leaves, _ = _leaf_paths(tree)
    for k, leaf in zip(keys, leaves):
        buf = io.BytesIO()
        np.save(buf, np.asarray(leaf))
        store.put(f"{name}/{step}/{k}", buf.getvalue(), hot=hot)
    store.put(f"{name}/{step}/__done__",
              json.dumps({"n_leaves": len(keys)}).encode(), hot=hot)
    store.flush()


def steps_available(store: CheckpointStore, name: str) -> list[int]:
    steps = set()
    for k in store.keys(prefix=f"{name}/"):
        if k.endswith("/__done__"):
            steps.add(int(k.split("/")[1]))
    return sorted(steps)


def load_pytree(store: CheckpointStore, name: str, step: int, like):
    """Restore into the structure of ``like`` (dtypes cast to match)."""
    keys, leaves, treedef = _leaf_paths(like)
    out = []
    for k, leaf in zip(keys, leaves):
        raw = store.get(f"{name}/{step}/{k}")
        arr = np.load(io.BytesIO(raw))
        want_dtype = getattr(leaf, "dtype", arr.dtype)
        if arr.dtype.kind == "V":
            # npy has no code for ml_dtypes (bfloat16, fp8): such leaves
            # come back as raw void records of the same bytes
            arr = arr.view(want_dtype)
        out.append(np.asarray(arr).astype(want_dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def drop_steps(store: CheckpointStore, name: str, keep_last: int) -> None:
    """Delete old checkpoints -> garbage for the Scavenger GC."""
    steps = steps_available(store, name)
    for s in steps[:-keep_last] if keep_last else steps:
        for k in store.keys(prefix=f"{name}/{s}/"):
            store.delete(k)
