"""Binarization primitives: sign/STE, bitpacking, BatchNorm->threshold folding.

This is the numerical heart of the BinarEye reproduction.  A BinaryNet
constrains weights and activations to {-1, +1} (Hubara et al., 2016).  The
chip evaluates the dot product of two +/-1 vectors of length K as

    dot(a, w) = K - 2 * popcount(xor(pack(a), pack(w)))

because xor of sign-bits counts the number of disagreeing positions.  We
adopt the convention  +1 -> bit 0,  -1 -> bit 1  (i.e. the bit is the sign
bit), so ``xor`` marks positions where the product is -1.

Training uses the straight-through estimator (STE): forward = sign(x),
backward = identity clipped to |x| <= 1 (the BinaryNet "hard tanh" STE).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PACK_WIDTH = 32  # binary channels per uint32 lane
_PACK_DTYPE = jnp.uint32


# ---------------------------------------------------------------------------
# Sign + straight-through estimator
# ---------------------------------------------------------------------------

@jax.custom_vjp
def ste_sign(x: jax.Array) -> jax.Array:
    """sign(x) in {-1, +1} with the BinaryNet straight-through gradient."""
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _ste_sign_fwd(x):
    return ste_sign(x), x


def _ste_sign_bwd(x, g):
    # dL/dx = dL/dy * 1{|x| <= 1}   (hard-tanh STE)
    return (g * (jnp.abs(x) <= 1.0).astype(g.dtype),)


ste_sign.defvjp(_ste_sign_fwd, _ste_sign_bwd)


def hard_sign(x: jax.Array) -> jax.Array:
    """Non-differentiable sign in {-1, +1} (ties -> +1)."""
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Bitpacking:  +/-1 (or {0,1} sign bits) <-> uint32 words
# ---------------------------------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def pack_signs(x: jax.Array, axis: int = -1) -> jax.Array:
    """Pack a +/-1 array into uint32 along ``axis`` (bit=1 means -1).

    The packed axis length becomes ceil(K / 32); K is padded with +1 (bit 0)
    so padding never flips an xor and popcount sees zeros there.
    """
    axis = axis % x.ndim
    k = x.shape[axis]
    kp = _round_up(k, PACK_WIDTH)
    if kp != k:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, kp - k)
        x = jnp.pad(x, pad, constant_values=1.0)  # +1 -> bit 0
    # move pack axis last; -1 -> bit 1
    words = pack_bit_lanes(jnp.moveaxis(x, axis, -1) < 0)
    return jnp.moveaxis(words, -1, axis)


def pack_bit_lanes(bits: jax.Array) -> jax.Array:
    """Pack a (..., K) array of {0,1} sign bits into (..., K//32) uint32.

    The shared packing idiom for code that already *has* sign bits (the
    packed thermometer encoder, :func:`pack_signs`) — LSB-first lane
    order.  K must be a multiple of 32.  The distinct powers of two sum
    without carries, so a signed sum (wrapping into bit 31) is the
    bitwise OR; TPU compilers have no unsigned integer reduction.
    """
    k = bits.shape[-1]
    assert k % PACK_WIDTH == 0, k
    lanes = bits.astype(jnp.int32).reshape(
        bits.shape[:-1] + (k // PACK_WIDTH, PACK_WIDTH))
    shifts = jnp.arange(PACK_WIDTH, dtype=jnp.int32)
    words = jnp.sum(lanes << shifts, axis=-1, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(words, _PACK_DTYPE)


def unpack_signs(words: jax.Array, k: int, axis: int = -1,
                 dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`pack_signs`; returns +/-1 of length ``k``."""
    axis = axis % words.ndim
    words = jnp.moveaxis(words, axis, -1)
    shifts = jnp.arange(PACK_WIDTH, dtype=_PACK_DTYPE)
    bits = (words[..., None] >> shifts) & _PACK_DTYPE(1)
    flat = bits.reshape(words.shape[:-1] + (words.shape[-1] * PACK_WIDTH,))
    signs = jnp.where(flat == 1, -1.0, 1.0).astype(dtype)[..., :k]
    return jnp.moveaxis(signs, -1, axis)


def xnor_dot_popcount(a_words: jax.Array, w_words: jax.Array, k: int) -> jax.Array:
    """Binary dot product from packed words: ``K - 2*popcount(a ^ w)``.

    a_words: (..., Kw) uint32;  w_words: (..., Kw) uint32 broadcastable.
    Returns int32 dot product of the underlying +/-1 vectors of length k.
    """
    x = jnp.bitwise_xor(a_words, w_words)
    pc = jax.lax.population_count(x).astype(jnp.int32)
    return jnp.int32(k) - 2 * jnp.sum(pc, axis=-1)


def thermometer_pack(images: jax.Array, bits: int, cin: int,
                     channels: int) -> jax.Array:
    """Thermometer-encode integer pixels straight into packed uint32 words.

    The chip's IO layer arithmetic for the staged pipeline
    (``neuron_array.thermometer_encode_packed``) and the host-side gate
    references; the megakernel's in-kernel encode
    (``megakernel.thermometer_lanes``) repeats the same float32 ops on
    the kernel's lane layout, and the tests hold the two bit-exact.
    Plane i of color c is -1 (bit 1) exactly when ``x_c < t_i``; leftover
    planes are constant +1 bias (bit 0).  ``channels`` must be a
    multiple of 32.  (..., H, W, cin) int -> (..., H, W, channels//32).
    """
    assert channels % PACK_WIDTH == 0, channels
    lead = images.shape[:-1]
    per = channels // cin
    levels = 2 ** bits
    t = (jnp.arange(per, dtype=jnp.float32) + 0.5) * (levels / per)
    neg = (images.astype(jnp.float32)[..., None] < t).astype(_PACK_DTYPE)
    neg = neg.reshape(lead + (cin * per,))
    pad = channels - cin * per
    if pad:                                              # +1 bias -> bit 0
        neg = jnp.concatenate(
            [neg, jnp.zeros(lead + (pad,), neg.dtype)], axis=-1)
    return pack_bit_lanes(neg)


# ---------------------------------------------------------------------------
# BatchNorm -> threshold folding (the chip's binary comparator)
# ---------------------------------------------------------------------------

def fold_bn_to_threshold(gamma, beta, mean, var, eps: float = 1e-5):
    """Fold BatchNorm + sign into an integer threshold on the popcount sum.

    sign(gamma * (s - mean)/sqrt(var+eps) + beta) ==
        (s >= tau)  if gamma > 0  else  (s <= tau),
    with tau = mean - beta*sqrt(var+eps)/gamma.

    Returns (tau, flip) where flip==True encodes the gamma<0 direction.
    The chip stores exactly this comparator threshold per neuron.
    """
    std = jnp.sqrt(var + eps)
    tau = mean - beta * std / gamma
    flip = gamma < 0
    return tau, flip


def threshold_activation(s: jax.Array, tau: jax.Array, flip: jax.Array) -> jax.Array:
    """Apply the folded comparator: +/-1 output."""
    ge = s >= tau
    out = jnp.where(jnp.logical_xor(ge, flip), 1.0, -1.0)
    return out.astype(jnp.float32)


def threshold_to_int(tau: jax.Array) -> jax.Array:
    """Quantize the folded float threshold to the int32 the chip stores.

    The conv sums ``s`` are integers (bounded by +/-4*C <= 1024, exactly
    representable in fp32), so ``s >= tau``  <=>  ``s >= ceil(tau)`` and
    the comparator needs only an integer register per neuron — this is
    the deployment form of the BN fold.  Inf thresholds (a neuron stuck
    off/on) saturate to the int32 range, preserving the always/never-fire
    behaviour for any reachable ``s``.
    """
    lo, hi = jnp.float32(-2**31), jnp.float32(2**31 - 256)
    return jnp.clip(jnp.ceil(tau), lo, hi).astype(jnp.int32)
