"""Pallas TPU kernel: the chip's F x C x 2x2 stride-1 binary convolution.

BinarEye's neuron array convolves a full (W x H) feature map with 2x2
binary kernels, holding all weights resident (local flip-flops) while the
2x2 window slides.  The TPU mapping: one grid step owns a tile of F output
channels (= a group of neurons); its packed weights live in VMEM for the
whole spatial sweep, and the *entire* feature map is VMEM-resident too
(chip feature maps are <= 32x32x256b = 32 kB packed -- the "all memory on
chip" property transfers directly to VMEM).

The 2x2 conv is computed as 4 shifted XNOR-popcount contractions -- no
im2col buffer, mirroring the chip's reuse of 2 of the 4 feature bits from
the previous step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def accumulate_tap_popcounts(a, w, h: int, wd: int) -> jax.Array:
    """The 2x2 conv as 4 shifted XNOR-popcount contractions.

    a: (bb, H, W, Cw) uint32 packed maps; w: (bf, 4, Cw) packed taps,
    (dy, dx) row-major.  Returns (bb, H-1, W-1, bf) int32 popcounts of
    disagreeing bits — shared by the unfused and fused conv kernels.
    """
    acc = jnp.zeros((a.shape[0], h - 1, wd - 1, w.shape[0]), jnp.int32)
    for dy in range(2):
        for dx in range(2):
            patch = a[:, dy:dy + h - 1, dx:dx + wd - 1, :]      # (bb,H-1,W-1,Cw)
            tap = w[:, 2 * dy + dx, :]                          # (bf, Cw)
            x = jnp.bitwise_xor(patch[:, :, :, None, :],
                                tap[None, None, None, :, :])
            acc += jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)
    return acc


def _binary_conv2x2_kernel(a_ref, w_ref, out_ref, *, k4: int, h: int, w: int):
    """a_ref: (bb, H, W, Cw) uint32; w_ref: (bf, 4, Cw); out_ref: (bb, H-1, W-1, bf)."""
    acc = accumulate_tap_popcounts(a_ref[...], w_ref[...], h, w)
    out_ref[...] = jnp.int32(k4) - 2 * acc


@functools.partial(jax.jit, static_argnames=("c", "bf", "bb", "interpret"))
def binary_conv2x2(a_words: jax.Array, w_words: jax.Array, *, c: int,
                   bf: int = 64, bb: int = 8,
                   interpret: bool = False) -> jax.Array:
    """Packed 2x2 stride-1 VALID binary conv, batched through the grid.

    a_words: (H, W, Cw) or (B, H, W, Cw) uint32 packed feature map(s).
    w_words: (F, 4, Cw) uint32 packed weights, tap order (dy, dx) row-major.
    c:       true channel count (k per tap); total dot length = 4*c.
    Returns (H-1, W-1, F) / (B, H-1, W-1, F) int32.

    Batch rides the grid in frame tiles of ``bb`` (F tiles outermost), so
    each weight tile is fetched once and stays VMEM-resident while every
    frame in the batch streams past it — no per-image ``vmap`` retracing
    the kernel.
    """
    squeeze = a_words.ndim == 3
    if squeeze:
        a_words = a_words[None]
    b, h, w, kw = a_words.shape
    f, taps, kw2 = w_words.shape
    assert taps == 4 and kw == kw2, (w_words.shape, a_words.shape)

    bf = min(bf, f)
    fp = (-f) % bf
    if fp:
        w_words = jnp.pad(w_words, ((0, fp), (0, 0), (0, 0)))
    gf = w_words.shape[0] // bf

    bb = min(bb, b)
    bp = (-b) % bb
    if bp:
        a_words = jnp.pad(a_words, ((0, bp), (0, 0), (0, 0), (0, 0)))
    gb = a_words.shape[0] // bb

    out = pl.pallas_call(
        functools.partial(_binary_conv2x2_kernel, k4=4 * c, h=h, w=w),
        grid=(gf, gb),
        in_specs=[
            pl.BlockSpec((bb, h, w, kw), lambda f_, b_: (b_, 0, 0, 0)),
            pl.BlockSpec((bf, 4, kw), lambda f_, b_: (f_, 0, 0)),  # stationary
        ],
        out_specs=pl.BlockSpec((bb, h - 1, w - 1, bf),
                               lambda f_, b_: (b_, 0, 0, f_)),
        out_shape=jax.ShapeDtypeStruct(
            (a_words.shape[0], h - 1, w - 1, w_words.shape[0]), jnp.int32),
        interpret=interpret,
        name="binary_conv2x2",
    )(a_words, w_words)
    out = out[:b, :, :, :f]
    return out[0] if squeeze else out
