"""Sharding-aware checkpointing: save / restore / elastic re-shard / async.

Format: one .npz of flattened leaves + a JSON manifest (paths, dtypes,
shapes, step).  Restore re-places leaves with ``jax.device_put`` against
the *current* mesh's NamedShardings, so a checkpoint written on a 16x16
mesh restores onto 2x16x16 (or a single CPU device) unchanged — this is
the elastic-scaling path.

``AsyncCheckpointer`` snapshots to host memory synchronously (cheap) and
writes to disk on a background thread, overlapping I/O with the next steps;
``wait()`` joins before the process exits.  Writes are atomic
(tmp + rename) so a preemption mid-write never corrupts the latest good
checkpoint.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Optional

import jax
import numpy as np


def _flatten(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        out[key] = np.asarray(leaf)
    return out


def save(path: str, state: Any, step: Optional[int] = None) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    arrays = _flatten(state)
    manifest = {
        "step": int(step if step is not None else 0),
        "leaves": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                   for k, v in arrays.items()},
    }
    tmp = path + ".tmp.npz"
    np.savez(tmp, **{k: v for k, v in arrays.items()})
    os.replace(tmp, path + ".npz")
    tmpm = path + ".tmp.json"
    with open(tmpm, "w") as f:
        json.dump(manifest, f)
    os.replace(tmpm, path + ".json")


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """Mesh constructor for the restore-after-fault path.

    A job restarted after a fault rebuilds its mesh on whatever topology
    survived and restores the latest checkpoint onto it: the first
    ``prod(axis_shapes)`` of ``devices`` (default: all local devices), in
    order, with Auto axis types.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = 1
    for s in axis_shapes:
        n *= int(s)
    if n > len(devices):
        raise ValueError(
            f"mesh {tuple(axis_shapes)} needs {n} devices, "
            f"only {len(devices)} available after restart")
    arr = np.array(devices[:n], dtype=object).reshape(tuple(axis_shapes))
    return jax.sharding.Mesh(arr, tuple(axis_names))


def restore(path: str, state_like: Any, mesh=None, specs=None) -> Any:
    """Restore into the structure of ``state_like``; re-shard onto ``mesh``.

    ``state_like`` may hold arrays or ShapeDtypeStructs.  When mesh+specs
    are given, leaves are placed as NamedSharding(mesh, spec) — elastic
    restore onto any device topology.
    """
    with np.load(path + ".npz") as z:
        arrays = {k: z[k] for k in z.files}
    flat, treedef = jax.tree_util.tree_flatten_with_path(state_like)
    spec_flat = None
    if specs is not None:
        spec_flat = [s for _, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))[0]]
    leaves = []
    for i, (path_k, leaf) in enumerate(flat):
        key = jax.tree_util.keystr(path_k)
        if key not in arrays:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = arrays[key]
        want_shape = tuple(leaf.shape)
        if tuple(arr.shape) != want_shape:
            raise ValueError(f"{key}: checkpoint shape {arr.shape} != {want_shape}")
        if mesh is not None and spec_flat is not None:
            sharding = jax.sharding.NamedSharding(mesh, spec_flat[i])
            leaves.append(jax.device_put(arr, sharding))
        else:
            leaves.append(jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, [l for l in leaves])


def latest_step(directory: str, prefix: str = "ckpt_") -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        if name.startswith(prefix) and name.endswith(".json"):
            try:
                steps.append(int(name[len(prefix):-len(".json")]))
            except ValueError:
                pass
    return max(steps) if steps else None


class AsyncCheckpointer:
    """Snapshot-to-host sync, write-to-disk async (one in flight)."""

    def __init__(self, directory: str, prefix: str = "ckpt_", keep: int = 3):
        self.directory = directory
        self.prefix = prefix
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def save(self, state: Any, step: int) -> None:
        self.wait()
        host_state = jax.tree.map(lambda x: np.asarray(x), state)

        def _write():
            try:
                path = os.path.join(self.directory, f"{self.prefix}{step}")
                save(path, host_state, step)
                self._gc()
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _gc(self):
        steps = sorted(s for s in (latest_step(self.directory, self.prefix),)
                       if s is not None)
        all_steps = []
        for name in os.listdir(self.directory):
            if name.startswith(self.prefix) and name.endswith(".json"):
                try:
                    all_steps.append(int(name[len(self.prefix):-len(".json")]))
                except ValueError:
                    pass
        for s in sorted(all_steps)[:-self.keep]:
            for ext in (".json", ".npz"):
                try:
                    os.remove(os.path.join(self.directory,
                                           f"{self.prefix}{s}{ext}"))
                except OSError:
                    pass
