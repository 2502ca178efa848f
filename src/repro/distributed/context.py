"""Ambient mesh context.

``shard_map``-based blocks (expert-parallel MoE, pipeline) need the Mesh at
trace time.  The launcher / step-builder installs it here so model code can
stay mesh-agnostic.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax

_state = threading.local()


def current_mesh() -> Optional[jax.sharding.Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def mesh_context(mesh: Optional[jax.sharding.Mesh]):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def data_axes(mesh: jax.sharding.Mesh) -> tuple:
    """All mesh axes that carry the batch (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def model_axis_size(mesh: Optional[jax.sharding.Mesh]) -> int:
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]
