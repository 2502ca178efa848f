"""Flash attention (forward) as a Pallas TPU kernel.

This is the structural fix for the dominant memory-roofline term of every
full-attention train/prefill cell (EXPERIMENTS.md §Perf): the S^2-sized
score/probability tensors never leave VMEM, so HBM traffic drops from
O(S^2 * heads) to O(S * d) — q, k, v, o only.  The JAX-level chunked
attention (models/attention.py) is the oracle and the CPU/dry-run path;
this kernel is the TPU deployment path (Pallas cannot compile on the CPU
backend — validated with interpret=True in tests/test_kernels_flash.py).

Layout: grid = (B * KH, num_q_blocks, num_k_blocks), k innermost so the
(m, l, acc) scratch carries across k-steps of one q-block (TPU grid
iteration is sequential).  GQA: the G query heads of one KV head are
folded into the q-block rows.  Causal blocks beyond the diagonal are
skipped via the index map visiting only the lower triangle... kept simple
here: masked out in-kernel (Mosaic still skips fully-masked matmuls'
writes); the block-sparse schedule is the JAX-level chunker's job.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                      bq: int, bk: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0]                       # (bq*G, d) — row r = (qoff=r//G, g=r%G)
    k = k_ref[0]                       # (bk, d)
    v = v_ref[0]                       # (bk, d)
    g = q.shape[0] // bq
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        rows = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
                + qi * bq)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * bk
        s = jnp.where(cols <= rows, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * alpha + p.sum(axis=1)
    acc_sc[...] = acc_sc[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_sc[...] /
                    jnp.maximum(l_sc[...], 1e-37)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        block_q: int = 256, block_k: int = 256,
                        scale: float | None = None,
                        interpret: bool | None = None):
    """q: (B, S, H, D); k/v: (B, S, KH, D) -> (B, S, H, D).

    The G = H // KH query heads sharing a KV head are folded into the
    q-block rows so one grid step computes a (bq*G, bk) score tile.
    """
    from repro.kernels import ops
    if interpret is None:
        interpret = ops.default_interpret()
    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)

    # query rows (qpos, g)-interleaved: row = qpos * G + g, so a block of
    # bq*G rows covers exactly q positions [i*bq, (i+1)*bq) for all G heads
    qr = q.reshape(b, s, kh, g, d).transpose(0, 2, 1, 3, 4)  # (B,KH,S,G,D)
    qr = qr.reshape(b * kh, s * g, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kh, s, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kh, s, d)

    nq, nk = s // bq, s // bk
    grid = (b * kh, nq, nk)

    out = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, bq=bq, bk=bk, causal=causal,
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g * bq, d), lambda h_, i, j: (h_, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h_, i, j: (h_, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h_, i, j: (h_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g * bq, d), lambda h_, i, j: (h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kh, g * s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq,), jnp.float32),
            pltpu.VMEM((g * bq,), jnp.float32),
            pltpu.VMEM((g * bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qr, kr, vr)

    out = out.reshape(b, kh, s, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, h, d)
