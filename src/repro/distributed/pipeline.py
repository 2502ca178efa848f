"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

For multi-pod runs the cheapest cross-pod traffic is boundary activations,
not gradient all-reduces — so the ``pod`` axis can act as the pipeline
axis: stage = a contiguous block of layers, microbatches flow through a
``shard_map`` + ``ppermute`` schedule (GPipe: all-forward then all-backward,
bubble = (S-1)/(M+S-1)).

``pipelined`` wraps any per-stage function ``stage_fn(stage_params, x)``:
stage params live sharded P("pod") on their leading stage dim; x is split
into microbatches on the host side of the shard_map.  The returned function
is differentiable (jax traces through ppermute), so it drops straight into
the train step.  Used by the PP dry-run variant (launch/dryrun.py --pp) and
tests/test_pipeline.py.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipelined(stage_fn: Callable, mesh, num_microbatches: int,
              axis: str = "pod"):
    """Returns fn(stage_params, x) running S stages over the `axis`.

    stage_params: pytree with leading dim = n_stages on every leaf.
    x: (B, ...) global batch; B % num_microbatches == 0.
    """
    n_stages = mesh.shape[axis]
    other = tuple(a for a in mesh.axis_names if a != axis)

    def run(stage_params, x):
        def body(params_local, x_local):
            # params_local: this stage's params (leading dim 1) -> squeeze
            params_local = jax.tree.map(lambda p: p[0], params_local)
            stage = jax.lax.axis_index(axis)
            mb = x_local.reshape((num_microbatches,
                                  x_local.shape[0] // num_microbatches)
                                 + x_local.shape[1:])
            n_ticks = num_microbatches + n_stages - 1
            # the carry becomes pod-varying after ppermute/axis_index; the
            # zero init must be marked pod-varying too (shard_map vma rule)
            buf = jax.lax.pcast(jnp.zeros_like(mb[0]), (axis,), to="varying")
            outs = jax.lax.pcast(jnp.zeros_like(mb), (axis,), to="varying")

            def tick(carry, t):
                buf, outs = carry
                # stage 0 injects microbatch t (if any remain)
                inject = jnp.where(t < num_microbatches, t, 0)
                x_in = jnp.where(stage == 0,
                                 mb[inject].astype(buf.dtype), buf)
                y = stage_fn(params_local, x_in)
                # last stage stores result for microbatch t - (S-1)
                out_idx = jnp.clip(t - (n_stages - 1), 0, num_microbatches - 1)
                store = jnp.logical_and(stage == n_stages - 1,
                                        t >= n_stages - 1)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs, jnp.where(store, y, outs[out_idx]), out_idx, 0)
                # shift boundary activations to the next stage
                perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
                buf = jax.lax.ppermute(y, axis, perm)
                return (buf, outs), None

            (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                          jnp.arange(n_ticks))
            # broadcast final outputs from the last stage to all stages so
            # the result is replicated over the pipeline axis
            outs = jax.lax.psum(
                jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
                axis)
            return outs.reshape(x_local.shape)

        in_specs = (jax.tree.map(lambda _: P(axis), stage_params),
                    P(other if other else None))
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=P(other if other else None))(
                                 stage_params, x)

    return run
