"""GPipe pipeline (distributed/pipeline.py): the ppermute microbatch
schedule must equal sequential stage application, and be differentiable.

Subprocess with 8 fake devices (4-stage pipe x 2-way data)."""

import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.distributed.pipeline import pipelined

    S, M, B, D = 4, 8, 16, 32
    mesh = jax.make_mesh((S, 2), ("pod", "data"))

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    key = jax.random.PRNGKey(0)
    params = {
        "w": jax.random.normal(key, (S, D, D)) * 0.5,
        "b": jnp.zeros((S, D)),
    }
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    # sequential oracle
    y_ref = x
    for i in range(S):
        y_ref = stage_fn(jax.tree.map(lambda p: p[i], params), y_ref)

    run = pipelined(stage_fn, mesh, num_microbatches=M)
    y = jax.jit(run)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)

    # differentiable end to end (GPipe all-fwd/all-bwd via jax AD)
    def loss(params, x):
        return jnp.sum(run(params, x) ** 2)
    g = jax.jit(jax.grad(loss))(params, x)
    gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree.leaves(g))
    assert gn > 0 and np.isfinite(gn), gn

    # compiles on the multi-pod production mesh shape too (2 pods x 2 x 2)
    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    run3 = pipelined(stage_fn, mesh3, num_microbatches=4)
    params2 = {"w": params["w"][:2], "b": params["b"][:2]}
    lowered = jax.jit(run3).lower(params2, x)
    lowered.compile()
    print("PIPELINE_OK")
""")


def test_pipeline_matches_sequential_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"      # the child never reaches for a chip
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "PIPELINE_OK" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
