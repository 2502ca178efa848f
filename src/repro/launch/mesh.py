"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax call.

Every builder asks for Auto axis types: ``jax.make_mesh`` defaults to
Explicit ones, under which the ``shard_map`` bodies (expert-parallel MoE,
the GPipe pipeline) and sharding constraints here do not trace.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2x16x16 = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / smoke runs): (1, N)."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))


def make_mesh_for(n_devices: int, model: int = 1):
    assert n_devices % model == 0
    return _auto_mesh((n_devices // model, model), ("data", "model"))
