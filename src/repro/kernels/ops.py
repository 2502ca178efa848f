"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels lower through Mosaic; on the CPU backend they run
with ``interpret=True`` (Pallas emulates the kernel body with XLA ops,
which checks results but not what Mosaic accepts).
``default_interpret()`` picks, and refuses any other backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import binarize
from repro.kernels import binarize_pack as _bp
from repro.kernels import binary_conv2x2 as _bc
from repro.kernels import binary_conv2x2_block as _bcb
from repro.kernels import megakernel as _mk
from repro.kernels import xnor_matmul as _xm


def default_interpret() -> bool:
    """Interpret mode exactly on the CPU backend, Mosaic on a TPU; any
    other backend is an error, never a silent fallback."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels need a TPU (or the CPU backend in "
                       f"interpret mode), got backend {backend!r}")


def pack(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Fused sign+pack for a (..., K) float array -> (..., ceil(K/32)) uint32."""
    if interpret is None:
        interpret = default_interpret()
    lead = x.shape[:-1]
    flat = x.reshape((-1, x.shape[-1]))
    out = _bp.binarize_pack(flat, interpret=interpret)
    return out.reshape(lead + (out.shape[-1],))


def xnor_matmul(a_words: jax.Array, w_words: jax.Array, k: int, *,
                interpret: bool | None = None, **tiles) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    return _xm.xnor_matmul(a_words, w_words, k=k, interpret=interpret, **tiles)


def binary_conv2x2(a_words: jax.Array, w_words: jax.Array, c: int, *,
                   interpret: bool | None = None, **tiles) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    return _bc.binary_conv2x2(a_words, w_words, c=c, interpret=interpret, **tiles)


def binary_conv2x2_block(a_words: jax.Array, w_words: jax.Array,
                         tau: jax.Array, flip: jax.Array, c: int, *,
                         pool: bool = False, interpret: bool | None = None,
                         **tiles) -> jax.Array:
    """Fused packed conv layer: conv -> integer threshold -> pool -> repack.

    (B, H, W, Cw) uint32 in, (B, Ho, Wo, F//32) uint32 out — the
    feature map never leaves the bit-packed domain.
    """
    if interpret is None:
        interpret = default_interpret()
    return _bcb.binary_conv2x2_block(a_words, w_words, tau, flip, c=c,
                                     pool=pool, interpret=interpret, **tiles)


def megakernel_forward(image, frames: jax.Array, *, spec, bb: int = 8,
                       ft: int = 0,
                       interpret: bool | None = None) -> jax.Array:
    """Whole-network VMEM-resident inference: raw frames -> int32 logits.

    One ``pallas_call`` runs every stage of the compiled plan (``spec``
    from ``InferencePlan.mega``) with the full weight image resident in
    VMEM, feature maps in VMEM scratch and frame tiles of ``bb``
    double-buffered through the grid — no HBM traffic between layers.
    ``ft`` f-tiles each conv layer's F axis (0 = all F per chunk).
    """
    if interpret is None:
        interpret = default_interpret()
    return _mk.megakernel_forward(image, frames, spec=spec, bb=bb, ft=ft,
                                  interpret=interpret)


def composite_forward(image, frames, *, spec, bb: int = 8, ft=0,
                      interpret: bool | None = None):
    """Shared-array multi-program inference: one ``pallas_call`` runs
    every member of a composite (programs whose S-modes tile the array
    exactly) on its own frame stream against the composite weight image.

    ``frames`` is a tuple of per-member (B, H, W, Cin) batches; returns a
    tuple of per-member (B, classes) int32 logits.  ``ft`` may be a
    tuple with one f-tile per member group (``member_groups`` order).
    See ``interpreter.pack_programs`` for building ``image``/``spec``.
    """
    if interpret is None:
        interpret = default_interpret()
    return _mk.composite_forward(image, tuple(frames), spec=spec, bb=bb,
                                 ft=ft, interpret=interpret)


def cascade_forward(image, frames, ctrl, *, spec, bb: int = 8, rb: int = 0,
                    ft=0, check_every: int = 1, positive_class: int = 1,
                    interpret: bool | None = None):
    """Fused detector->recognizer cascade in one resident ``pallas_call``:
    the detector screens every frame tile, the escalation mask (integer
    logit margin vs the ``ctrl`` threshold) is computed in-kernel, and
    the recognizer drains only the escalated lanes through the bounded
    drain loop.  Returns (det_logits, rec_logits, queue, counts) — see
    ``megakernel.cascade_forward`` for the compacted layout and
    ``interpreter.pack_cascade`` for building ``image``/``spec``.
    """
    if interpret is None:
        interpret = default_interpret()
    return _mk.cascade_forward(image, frames, ctrl, spec=spec, bb=bb, rb=rb,
                               ft=ft, check_every=check_every,
                               positive_class=positive_class,
                               interpret=interpret)


def delta_forward(image, frames, last, llog, ctrl, *, spec, bb: int = 8,
                  rb: int = 0, ft=0, check_every: int = 1,
                  interpret: bool | None = None):
    """Delta-gated whole-network inference in one resident ``pallas_call``:
    each frame tile is thermometer-packed in-kernel and popcount-XORed
    against the resident last-frame words; lanes whose packed Hamming
    distance reaches the ``ctrl`` threshold compact into the change queue
    and recompute through the bounded drain loop, while skipped lanes
    emit their cached logits.  Returns (logits, new_last, queue, counts,
    deltas) — see ``megakernel.delta_forward`` for the state contract and
    ``interpreter.pack_delta`` for building ``image``/``spec``.
    """
    if interpret is None:
        interpret = default_interpret()
    return _mk.delta_forward(image, frames, last, llog, ctrl, spec=spec,
                             bb=bb, rb=rb, ft=ft, check_every=check_every,
                             interpret=interpret)


def member_groups(spec):
    """A composite spec's sub-array groups (members with shape-identical
    IO+conv chains stack into one fused conv); per-group ``ft`` tuples
    index groups in this order."""
    return _mk.member_groups(spec)


def binary_linear(x: jax.Array, w_signs: jax.Array, *,
                  interpret: bool | None = None) -> jax.Array:
    """End-to-end W1A1 linear for inference: float x, +/-1 weights.

    x: (..., K) float;  w_signs: (N, K) in {-1,+1}.  Returns (..., N) int32
    (the exact binary dot products; caller applies threshold / scale).
    """
    k = x.shape[-1]
    lead = x.shape[:-1]
    a_words = pack(x.reshape((-1, k)), interpret=interpret)
    w_words = binarize.pack_signs(w_signs, axis=-1)
    out = xnor_matmul(a_words, w_words, k, interpret=interpret)
    return out.reshape(lead + (w_signs.shape[0],))
