"""Pallas TPU kernel: bitpacked XNOR-popcount binary matmul.

Computes ``out[m, n] = K - 2 * popcount(a[m] ^ w[n])`` over uint32 words —
the BinarEye neuron dot product, vectorized over the TPU VPU (the MXU has no
1-bit mode; packing 32 binary channels per int32 lane gives the 32x density
that the chip gets from its XNOR gates).

Weight-stationarity (the chip's LD-once / CONV-many pattern) is expressed
through the grid order: the N (neuron) index is the *outermost* grid axis and
the weight BlockSpec depends only on it, so a weight tile is fetched to VMEM
once and stays resident while the M (activation positions) axis streams.

VMEM budget per grid step (defaults bm=bn=128, bk=64 words = 2048 channels):
  a tile 128*64*4B = 32 kB, w tile 32 kB, out tile 128*128*4B = 64 kB,
  xor broadcast intermediate bm*bn*bk*4B = 4 MB  -> fits the ~16 MB VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.binarize import PACK_WIDTH


def pack_lanes(bits):
    """(M, 32*Nw) int32 {0,1} -> (M, Nw) uint32, lane ``32j + i`` into bit
    ``i`` of word ``j``.  Mosaic cannot split the lane axis, so the MXU
    does the gather: two exact matmuls against one-hot power-of-two
    matrices build each word's low and high 16 bits (sums < 2^16 are
    exact in f32), and an int32 add (wrapping into bit 31) joins them."""
    n = bits.shape[1]
    shape = (n, n // PACK_WIDTH)
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    mine = (i >> 5) == jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    e = i & (PACK_WIDTH - 1)
    x = bits.astype(jnp.bfloat16)

    def half(lo: int):
        pow2 = jnp.where(mine & (e >= lo) & (e < lo + 16),
                         jnp.left_shift(1, e - lo), 0)
        return jnp.dot(x, pow2.astype(jnp.float32).astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    words = half(0) + jnp.left_shift(half(16), 16)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def _xnor_matmul_kernel(a_ref, w_ref, out_ref, *, k: int, nk: int):
    """Grid = (N/bn, M/bm, Kw/bk); accumulate popcounts over the k axis."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]                      # (bm, bk) uint32
    w = w_ref[...]                      # (bn, bk) uint32
    x = jnp.bitwise_xor(a[:, None, :], w[None, :, :])     # (bm, bn, bk)
    pc = jax.lax.population_count(x).astype(jnp.int32)
    out_ref[...] += jnp.sum(pc, axis=-1)

    @pl.when(kb == nk - 1)
    def _finalize():
        # dot = K - 2 * popcount(disagreements); padding words are zero on
        # both sides (pack_signs pads with +1 -> bit 0) so they contribute 0.
        out_ref[...] = jnp.int32(k) - 2 * out_ref[...]


def _xnor_matmul_pack_kernel(a_ref, w_ref, out_ref, acc_ref, *, k: int, nk: int):
    """Fused variant: sign the final sums and emit packed uint32 words.

    Accumulation runs in a VMEM scratch (the packed output words have a
    different shape/dtype than the int32 partials); the last k step
    applies ``sign(K - 2*acc)`` and packs 32 neurons per word, so a
    hidden FC layer's activations never exist unpacked outside the
    kernel.  out_ref: (bm, bn // 32) uint32.
    """
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    w = w_ref[...]
    x = jnp.bitwise_xor(a[:, None, :], w[None, :, :])
    acc_ref[...] += jnp.sum(jax.lax.population_count(x).astype(jnp.int32),
                            axis=-1)

    @pl.when(kb == nk - 1)
    def _finalize():
        s = jnp.int32(k) - 2 * acc_ref[...]               # (bm, bn) sums
        bits = (s < 0).astype(jnp.int32)                  # sign: bit=1 -> -1
        out_ref[...] = pack_lanes(bits)


@functools.partial(jax.jit, static_argnames=("k", "bm", "bn", "bk",
                                             "pack_out", "interpret", "name"))
def xnor_matmul(a_words: jax.Array, w_words: jax.Array, *, k: int,
                bm: int = 128, bn: int = 128, bk: int = 64,
                pack_out: bool = False, interpret: bool = False,
                name: str = "xnor_matmul") -> jax.Array:
    """Packed binary matmul.

    a_words: (M, Kw) uint32 packed activations (+1 -> bit0, -1 -> bit1).
    w_words: (N, Kw) uint32 packed weights.
    k:       true (unpadded) channel count; output = K - 2*popcount(xor).
    pack_out: fuse the sign activation and bit-pack along N inside the
        kernel, returning (M, N // 32) uint32 instead of (M, N) int32 —
        the stay-binary path for hidden FC layers (requires N % 32 == 0).
    Returns (M, N) int32, or (M, N // 32) uint32 when ``pack_out``.
    name:    the kernel's name in the compiled program and the profiler
             (the staged plan passes ``fc<i>``, one per layer).

    The M axis doubles as the batch axis (callers flatten (B, K) frames
    into rows), and N is the outermost grid axis, so each weight tile is
    loaded once and serves the entire batch.
    """
    m, kw = a_words.shape
    n, kw2 = w_words.shape
    assert kw == kw2, (kw, kw2)
    if pack_out:
        assert n % PACK_WIDTH == 0, (
            f"pack_out needs N % {PACK_WIDTH} == 0, got N={n}")

    bm = min(bm, m)
    bn = min(bn, n)
    if pack_out:
        # one N tile: the packed output block's lane extent must be the
        # whole N // 32 words (FC widths stay under 2048, so it is small)
        bn = n
    bk = min(bk, kw)
    # pad to tile multiples (zero words == +1 signs on both sides: no-op)
    mp, np_, kp = (-m) % bm, (-n) % bn, (-kw) % bk
    if mp or kp:
        a_words = jnp.pad(a_words, ((0, mp), (0, kp)))
    if np_ or kp:
        w_words = jnp.pad(w_words, ((0, np_), (0, kp)))
    gm, gn, gk = a_words.shape[0] // bm, w_words.shape[0] // bn, a_words.shape[1] // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda n_, m_, k_: (m_, k_)),   # activations stream
        pl.BlockSpec((bn, bk), lambda n_, m_, k_: (n_, k_)),   # weights: loop-invariant in m_
    ]
    if pack_out:
        out = pl.pallas_call(
            functools.partial(_xnor_matmul_pack_kernel, k=k, nk=gk),
            grid=(gn, gm, gk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn // PACK_WIDTH),
                                   lambda n_, m_, k_: (m_, n_)),
            out_shape=jax.ShapeDtypeStruct(
                (a_words.shape[0], w_words.shape[0] // PACK_WIDTH),
                jnp.uint32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
            interpret=interpret,
            name=name,
        )(a_words, w_words)
        return out[:m, :n // PACK_WIDTH]

    out = pl.pallas_call(
        functools.partial(_xnor_matmul_kernel, k=k, nk=gk),
        grid=(gn, gm, gk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda n_, m_, k_: (m_, n_)),
        out_shape=jax.ShapeDtypeStruct((a_words.shape[0], w_words.shape[0]), jnp.int32),
        interpret=interpret,
        name=name,
    )(a_words, w_words)
    return out[:m, :n]
