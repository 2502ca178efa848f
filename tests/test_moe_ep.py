"""Expert-parallel MoE (shard_map + all_to_all) vs the dense oracle.

Runs in a SUBPROCESS with 8 fake devices (the parent pytest process must
keep seeing 1 device — jax locks device count at first init).

With capacity_factor high enough that nothing drops, the EP path must
match the dense path to float tolerance; fp8 dispatch must match within
e4m3 quantization error.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.models import moe

    cfg = ModelConfig(
        name="moe-test", family="moe", num_layers=1, d_model=32,
        num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=128,
        pattern=("attn_moe",),
        moe=MoEConfig(num_experts=8, top_k=2, d_expert=16,
                      capacity_factor=8.0, impl="ep"),
        dtype="float32", param_dtype="float32")

    from repro.launch.mesh import make_mesh_for
    mesh = make_mesh_for(8, model=4)
    key = jax.random.PRNGKey(0)
    params = moe.init(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)

    from repro.distributed import context as dctx
    y_dense, aux_d = moe.apply_dense(params, cfg, x)
    with dctx.mesh_context(mesh):
        y_ep, aux_e = moe.apply_ep(params, cfg, x, mesh)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)

    cfg8 = cfg.with_(moe=dataclasses.replace(cfg.moe, dispatch_fp8=True))
    with dctx.mesh_context(mesh):
        y_f8, _ = moe.apply_ep(params, cfg8, x, mesh)
    err = np.abs(np.asarray(y_f8) - np.asarray(y_dense))
    scale = np.abs(np.asarray(y_dense)).mean() + 1e-6
    assert err.mean() / scale < 0.1, (err.mean(), scale)
    print("MOE_EP_OK")
""")


def test_ep_matches_dense_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"      # the child never reaches for a chip
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "MOE_EP_OK" in r.stdout, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
