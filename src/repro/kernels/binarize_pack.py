"""Pallas TPU kernel: fused sign + bitpack producer.

Turns an fp feature tile into packed sign words in one VMEM pass, so the
binarize step never round-trips an unpacked +/-1 tensor through HBM.  This
is the producer feeding xnor_matmul / binary_conv2x2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.binarize import PACK_WIDTH


def _binarize_pack_kernel(x_ref, out_ref):
    x = x_ref[...]                                    # (bm, K)
    bits = (x < 0).astype(jnp.uint32)
    bm, k = bits.shape
    bits = bits.reshape(bm, k // PACK_WIDTH, PACK_WIDTH)
    shifts = jnp.arange(PACK_WIDTH, dtype=jnp.uint32)
    out_ref[...] = jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def binarize_pack(x: jax.Array, *, bm: int = 256, interpret: bool = False) -> jax.Array:
    """(M, K) float -> (M, ceil(K/32)) uint32 packed signs (bit=1 means -1)."""
    m, k = x.shape
    kp = (-k) % PACK_WIDTH
    if kp:
        x = jnp.pad(x, ((0, 0), (0, kp)), constant_values=1.0)   # +1 -> bit 0
    bm = min(bm, m)
    mp = (-m) % bm
    if mp:
        x = jnp.pad(x, ((0, mp), (0, 0)), constant_values=1.0)
    gm = x.shape[0] // bm
    kw = x.shape[1] // PACK_WIDTH

    out = pl.pallas_call(
        _binarize_pack_kernel,
        grid=(gm,),
        in_specs=[pl.BlockSpec((bm, x.shape[1]), lambda m_: (m_, 0))],
        out_specs=pl.BlockSpec((bm, kw), lambda m_: (m_, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], kw), jnp.uint32),
        interpret=interpret,
        name="binarize_pack",
    )(x)
    return out[:m]
