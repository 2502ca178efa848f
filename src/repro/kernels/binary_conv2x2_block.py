"""Pallas TPU kernel: fused conv -> threshold -> pool -> repack, all packed.

BinarEye's defining property is that feature maps never leave the chip:
every layer consumes binary data and produces binary data, with no wide
intermediate ever crossing a memory boundary.  This kernel keeps that on
TPU: one grid step computes the 2x2 XNOR-popcount convolution for a tile
of F output neurons, applies the folded integer threshold comparator
(``tau``/``flip``) on the in-register sums, optionally performs the
chip's streamed 2x2/2 max-pool *in the sign domain* (max over +/-1 ==
AND of sign bits, since bit=1 encodes -1), and writes re-packed uint32
words.  Only packed bits ever touch HBM.

**Lane layout.**  Inside the kernels a feature map is a ``(Cw, P)``
array: packed channel words on sublanes and map positions on lanes, with
position ``p = y * row + x`` on the input frame's grid (``row`` = the
frame width, ``P`` rounded up to whole 128-lane vregs).  A 2x2 tap is a
lane shift of the whole map by ``0, d, d*row, d*row + d``, where ``d`` is
the map's dilation: pooling never compacts the map, it only doubles the
stride at which the valid positions sit.  Positions outside the valid
region hold garbage that no valid output ever reads, so the body needs
no masks, no unaligned sublane slices and no reshapes that split lanes —
everything Mosaic lowers is a 2-D broadcast, a lane roll, a popcount, or
a sublane reduction.  :func:`lane_map` / :func:`unlane_map` convert the
staged path's ``(B, H, W, Cw)`` HBM layout at the kernel boundary.

Batch is a grid axis rather than a ``jax.vmap``: the grid is (F tiles,
frame tiles) with F outermost, so a weight tile is fetched to VMEM once
and stays resident while the whole batch streams through it — the chip's
LD-once / CONV-many schedule extended over frames.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.binarize import PACK_WIDTH

LANES = 128
# Scoped-VMEM cap for the conv kernels (v5e holds 128 MiB per core; the
# compiler's default scope of 16 MiB is too small for S=1 maps).
VMEM_LIMIT = 64 * 1024 * 1024


def compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def lanes_for(h: int, w: int) -> int:
    """Lane extent P of an h x w map: positions rounded up to whole vregs."""
    return -(-(h * w) // LANES) * LANES


def shift_left(x, o: int):
    """``out[..., p] = x[..., p + o]`` along lanes (wrapping): a static
    lane rotation.  The wrapped lanes land only on positions no valid
    output reads."""
    return x if o == 0 else jnp.roll(x, -o, axis=-1)


def pack_rows(bits):
    """(32*Rw, P) int32 {0,1} -> (Rw, P) uint32: row ``32j + i`` becomes
    bit ``i`` of word ``j`` (the LSB-first order of ``pack_signs``).  The
    distinct powers of two sum without carries, so an int32 sum (wrapping
    into bit 31) is the bitwise OR Mosaic has no unsigned reduction for."""
    r, p = bits.shape
    sh = jax.lax.broadcasted_iota(jnp.int32, (1, PACK_WIDTH, 1), 1)
    words = jnp.sum(bits.reshape(r // PACK_WIDTH, PACK_WIDTH, p) << sh, axis=1)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def unpack_rows(words):
    """Inverse of :func:`pack_rows`: (Rw, P) uint32 -> (32*Rw, P) int32."""
    sh = jax.lax.broadcasted_iota(jnp.uint32, (PACK_WIDTH, 1), 0)
    return jnp.concatenate(
        [((words[j:j + 1, :] >> sh) & 1).astype(jnp.int32)
         for j in range(words.shape[0])], axis=0)


def neuron_rows(w_words, tau, flip) -> jax.Array:
    """Per-neuron records, one 128-lane row each: the neuron's packed taps
    (column ``t*Cw + c`` = word c of tap t = 2*dy + dx), then its int32
    comparator threshold and direction (bitcast), then zeros — the
    chip's per-neuron weight + threshold registers, in one aligned slab
    a kernel slices 32 neurons at a time.  (F, 4, Cw) -> (F, 128) uint32.
    """
    f, taps, kw = w_words.shape
    assert 4 * kw + 2 <= LANES, kw
    cols = [w_words.reshape(f, taps * kw),
            jax.lax.bitcast_convert_type(
                tau.astype(jnp.int32), jnp.uint32).reshape(f, 1),
            flip.astype(jnp.uint32).reshape(f, 1)]
    return jnp.pad(jnp.concatenate(cols, axis=1),
                   ((0, 0), (0, LANES - 4 * kw - 2)))


def conv_block_body(a, nrow_ref, *, k4: int, cw: int, ww: int, row: int,
                    dil: int, pool: bool, f0=0, nw: int = 1) -> jax.Array:
    """The fused layer body: conv -> threshold -> pool -> repack.  Shared
    by the staged per-layer kernel below and the whole-network megakernel
    (``kernels.megakernel``), so both paths run the identical arithmetic
    and stay bit-exact against each other.

    a:        (Cw_in, P) uint32 packed input map in the lane layout.
    nrow_ref: (F, 128) uint32 :func:`neuron_rows` slab (a ref) with
              ``ww`` words per tap.
    cw:       channel words per tap actually read (<= Cw_in, <= ww).
    row, dil: the map's row stride and dilation on the lane axis.
    f0, nw:   compute neurons [f0, f0 + 32*nw) — nw packed output words.
    Returns (nw, P) uint32 packed output words (dilation ``2*dil`` when
    ``pool``, else ``dil``).  The neurons run 32 at a time (one output
    word per loop step), which bounds both the live sums and the code the
    compiler emits to one (32, P) word row.
    """
    taps = [shift_left(a, off)
            for off in (0, dil, dil * row, dil * row + dil)]
    which = jax.lax.broadcasted_iota(jnp.int32, (nw, 1), 0)

    def word(j, out):
        base = pl.multiple_of(f0 + j * PACK_WIDTH, PACK_WIDTH)
        w = nrow_ref[pl.ds(base, PACK_WIDTH), :]                 # (32, 128)
        tau = jax.lax.bitcast_convert_type(w[:, 4 * ww:4 * ww + 1], jnp.int32)
        flip = w[:, 4 * ww + 1:4 * ww + 2].astype(jnp.int32)
        acc = None
        for t, sa in enumerate(taps):
            for c in range(cw):
                x = jax.lax.population_count(
                    w[:, t * ww + c:t * ww + c + 1] ^ sa[c:c + 1, :])
                x = x.astype(jnp.int32)
                acc = x if acc is None else acc + x
        s = jnp.int32(k4) - 2 * acc                             # integer sums
        # folded comparator, in-register: output is +1 iff (s >= tau) XOR
        # flip; under the bit=1 <=> -1 convention the sign bit is its
        # negation.
        ge = (s >= tau).astype(jnp.int32)
        bits = jnp.int32(1) - jnp.bitwise_xor(ge, flip)
        if pool:
            # streamed 2x2/2 max-pool in the sign domain: max over +/-1 ==
            # any +1 in the window == AND of the (negative-sign) bits.
            bits = (bits & shift_left(bits, dil)
                    & shift_left(bits, dil * row)
                    & shift_left(bits, dil * row + dil))
        return jnp.where(which == j, pack_rows(bits), out)

    return jax.lax.fori_loop(0, nw, word,
                             jnp.zeros((nw, a.shape[-1]), jnp.uint32))


def _conv_block_kernel(a_ref, nrow_ref, out_ref, *, k4: int, cw: int,
                       row: int, pool: bool, bb: int):
    """One (f-tile, frame-tile) grid step.

    a_ref:    (bb, Cw, P) uint32 packed input maps (a tile of frames).
    nrow_ref: (bf, 128)   uint32 neuron records (:func:`neuron_rows`).
    out_ref:  (1, bb, bf // 32, P) uint32 packed output words.
    """
    def frame(b, carry):
        out_ref[0, b] = conv_block_body(
            a_ref[b], nrow_ref, k4=k4, cw=cw, ww=cw, row=row, dil=1,
            pool=pool, nw=out_ref.shape[2])
        return carry

    jax.lax.fori_loop(0, bb, frame, 0)


def lane_map(a_words: jax.Array) -> jax.Array:
    """(B, H, W, Cw) packed maps -> (B, Cw, P) lane layout (row = W)."""
    b, h, w, kw = a_words.shape
    x = a_words.transpose(0, 3, 1, 2).reshape(b, kw, h * w)
    return jnp.pad(x, ((0, 0), (0, 0), (0, lanes_for(h, w) - h * w)))


def valid_lanes(ho: int, wo: int, row: int, dil: int) -> np.ndarray:
    """Lane indices of an ho x wo map at dilation ``dil``, row-major."""
    y, x = np.meshgrid(np.arange(ho), np.arange(wo), indexing="ij")
    return (dil * (y * row + x)).reshape(-1)


def unlane_map(x: jax.Array, ho: int, wo: int, row: int,
               dil: int) -> jax.Array:
    """(B, Fw, P) lane layout -> (B, ho, wo, Fw) packed maps."""
    b, fw, _ = x.shape
    x = x[:, :, valid_lanes(ho, wo, row, dil)]
    return x.reshape(b, fw, ho, wo).transpose(0, 2, 3, 1)


@functools.partial(jax.jit,
                   static_argnames=("c", "pool", "bf", "bb", "interpret"))
def binary_conv2x2_block(a_words: jax.Array, w_words: jax.Array,
                         tau: jax.Array, flip: jax.Array, *, c: int,
                         pool: bool = False, bf: int = 256, bb: int = 8,
                         interpret: bool = False) -> jax.Array:
    """Fused packed conv layer: packed words in, packed words out.

    a_words: (B, H, W, Cw) uint32 packed input feature maps (C channels).
    w_words: (F, 4, Cw) uint32 packed weights, tap order (dy, dx) row-major.
    tau:     (F,) int32 folded integer thresholds (s >= tau fires).
    flip:    (F,) comparator direction (gamma < 0), bool or int.
    c:       true channel count per tap; total dot length = 4*c.
    pool:    apply the streamed 2x2 stride-2 max-pool before repacking.
    bf, bb:  neuron / frame tile sizes.  The dominant live value per
             frame is the int32 accumulator bf*P*4 B (1 MB at the worst
             chip shape: 32x32 map, bf=256); frames run one at a time
             through it, so ``bb`` only sets the DMA granule.
    Returns (B, Ho, Wo, F // 32) uint32 — Ho = (H-1)//2 if pool else H-1.
    """
    b, h, w, kw = a_words.shape
    f, taps, kw2 = w_words.shape
    assert taps == 4 and kw == kw2, (w_words.shape, a_words.shape)
    assert f % PACK_WIDTH == 0, (
        f"fused packed output needs F % {PACK_WIDTH} == 0, got F={f}")

    bf = min(bf, f)
    bf = -(-bf // PACK_WIDTH) * PACK_WIDTH     # round up to whole words
    fp = (-f) % bf
    if fp:                                     # pad F to the tile multiple;
        w_words = jnp.pad(w_words, ((0, fp), (0, 0), (0, 0)))
        tau = jnp.pad(tau, (0, fp))            # padded words trimmed below
        flip = jnp.pad(flip, (0, fp))
    nrows = neuron_rows(w_words, tau, flip)
    gf = w_words.shape[0] // bf

    bb = min(bb, b)
    bp = (-b) % bb
    if bp:                                     # pad the batch to the frame
        a_words = jnp.pad(a_words, ((0, bp), (0, 0), (0, 0), (0, 0)))
    gb = a_words.shape[0] // bb                # tile; extra frames trimmed
    a = lane_map(a_words)
    p = a.shape[-1]
    bfw = bf // PACK_WIDTH

    out = pl.pallas_call(
        functools.partial(_conv_block_kernel, k4=4 * c, cw=kw, row=w,
                          pool=pool, bb=bb),
        grid=(gf, gb),                          # F outermost: weights stay
        in_specs=[                              # resident across the batch
            pl.BlockSpec((bb, kw, p), lambda f_, b_: (b_, 0, 0)),
            pl.BlockSpec((bf, LANES), lambda f_, b_: (f_, 0)),
        ],
        out_specs=pl.BlockSpec((1, bb, bfw, p),
                               lambda f_, b_: (f_, b_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gf, a.shape[0], bfw, p), jnp.uint32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(a, nrows)
    out = out.transpose(1, 0, 2, 3).reshape(a.shape[0], gf * bfw, p)
    out = out[:b, :f // PACK_WIDTH]
    if pool:
        return unlane_map(out, (h - 1) // 2, (w - 1) // 2, w, 2)
    return unlane_map(out, h - 1, w - 1, w, 1)
