"""Plain reference of a BinarEye program: +/-1 values in jax.numpy, no kernels.

The layer list comes from the configuration file, never from the program
under test.  Layer equations (arXiv:1804.05554, Sec. II; the ISA the
configurations spell out):

* ``io``: thermometer code.  Colour ``c`` of an integer pixel ``x`` in
  ``[0, 2**bits)`` gives ``per = channels // in_channels`` planes; plane
  ``i`` is +1 when ``x >= (i + 0.5) * 2**bits / per`` and -1 otherwise.
  Planes are colour-major (``c * per + i``); the ``channels - in_channels
  * per`` planes left over are +1.
* ``conv``: ``s[b, y, x, f] = sum_{dy, dx, c} a[b, y+dy, x+dx, c] *
  w[f, dy, dx, c]`` (2x2, stride 1, valid positions only), then the
  comparator ``a' = +1 if (s >= tau[f]) != flip[f] else -1``, then the
  optional 2x2 stride-2 max-pool (an odd trailing row or column is
  dropped).
* ``fc``: ``s[b, n] = sum_k a[b, k] * w[n, k]`` with ``k`` over the
  row-major ``(y, x, f)`` flatten; a hidden layer gives ``+1 if s >= 0
  else -1``, the final layer's sums are the logits.

``labels = argmax(logits)`` (the first maximum).

``acc`` picks the arithmetic of the sums: ``"float32"`` at the highest
matmul precision (exact: every sum is an integer of at most 1,024 in
magnitude), and the lower precisions the control is computed in:
``"int8"`` (sums wrap to 8-bit two's complement, as an 8-bit accumulator
would) and ``"bfloat16"`` (products summed into bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ACCS = ("float32", "bfloat16", "int8")


def _geometry(layers):
    """(layer, in_h, in_w, in_c) for every layer of the list."""
    out, h, w, c = [], None, None, None
    for ly in layers:
        if ly["op"] == "io":
            out.append((ly, ly["height"], ly["width"], ly["in_channels"]))
            h, w, c = ly["height"], ly["width"], ly["channels"]
        elif ly["op"] == "conv":
            if (ly["height"], ly["width"]) != (h, w):
                raise ValueError(f"conv expects {ly['height']}x{ly['width']}, "
                                 f"the layers before give {h}x{w}")
            out.append((ly, h, w, c))
            h, w, c = h - 1, w - 1, ly["features"]
            if ly["maxpool"]:
                h, w = h // 2, w // 2
        elif ly["op"] == "fc":
            out.append((ly, 1, 1, ly["in_features"]))
            h = w = 1
            c = ly["out_features"]
        else:
            raise ValueError(f"unknown layer op {ly['op']!r}")
    return out


def thermometer(frames, bits: int, channels: int):
    """(B, H, W, C_in) integer pixels -> (B, H, W, channels) +/-1 float32."""
    b, h, w, cin = frames.shape
    per = channels // cin
    t = (jnp.arange(per, dtype=jnp.float32) + 0.5) * (2 ** bits / per)
    planes = jnp.where(frames.astype(jnp.float32)[..., None] >= t, 1.0, -1.0)
    planes = planes.reshape(b, h, w, cin * per)
    pad = channels - cin * per
    if pad:
        planes = jnp.concatenate(
            [planes, jnp.ones((b, h, w, pad), jnp.float32)], axis=-1)
    return planes


def _wrap_int8(s):
    """An 8-bit two's complement accumulator: the exact sum modulo 256."""
    return jnp.mod(s + 128.0, 256.0) - 128.0


def _contract(spec: str, a, w, acc: str):
    if acc == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.bfloat16)
    return jnp.einsum(spec, a, w, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def conv_sums(a, w, acc: str = "float32"):
    """a: (B, H, W, C) +/-1; w: (F, 2, 2, C) +/-1 -> (B, H-1, W-1, F)."""
    h, wd = a.shape[1], a.shape[2]
    s = None
    for dy in range(2):
        for dx in range(2):
            part = _contract("byxc,fc->byxf",
                             a[:, dy:h - 1 + dy, dx:wd - 1 + dx, :],
                             w[:, dy, dx, :], acc)
            s = part if s is None else s + part
    s = s.astype(jnp.float32)
    return _wrap_int8(s) if acc == "int8" else s


def fc_sums(a, w, acc: str = "float32"):
    s = _contract("bk,nk->bn", a, w, acc).astype(jnp.float32)
    return _wrap_int8(s) if acc == "int8" else s


def comparator(s, tau, flip):
    return jnp.where((s >= tau) != flip, 1.0, -1.0)


def maxpool(a):
    b, h, w, c = a.shape
    h2, w2 = h // 2, w // 2
    a = a[:, :2 * h2, :2 * w2, :].reshape(b, h2, 2, w2, 2, c)
    return a.max(axis=(2, 4))


def forward(weights, layers, frames, acc: str = "float32"):
    """(logits float32 (B, classes), labels int32 (B,))."""
    if acc not in ACCS:
        raise ValueError(f"acc must be one of {ACCS}, got {acc!r}")
    ci = fi = 0
    a = None
    for ly, *_ in _geometry(layers):
        if ly["op"] == "io":
            a = thermometer(frames, ly["bits"], ly["channels"])
        elif ly["op"] == "conv":
            p = weights["conv"][ci]
            a = comparator(conv_sums(a, p["w"], acc), p["tau"], p["flip"])
            if ly["maxpool"]:
                a = maxpool(a)
            ci += 1
        else:
            if a.ndim == 4:
                a = a.reshape(a.shape[0], -1)
            s = fc_sums(a, weights["fc"][fi]["w"], acc)
            a = s if ly["final"] else jnp.where(s >= 0, 1.0, -1.0)
            fi += 1
    return a, jnp.argmax(a, axis=-1).astype(jnp.int32)


def init(layers, key, calib):
    """Seeded +/-1 weights with calibrated thresholds (traceable).

    Every weight is an independent fair sign and every comparator
    direction a fair coin.  Each conv threshold is the ceiling of the
    layer's mean sum, per feature, over the calibration frames ``calib``
    — the batch-norm statistics a trained network would fold into its
    comparators — so every layer's activations stay near balanced.
    """
    convs, fcs = [], []
    a = None
    for ly, _h, _w, in_c in _geometry(layers):
        if ly["op"] == "io":
            a = thermometer(calib, ly["bits"], ly["channels"])
        elif ly["op"] == "conv":
            key, kw, kf = jax.random.split(key, 3)
            f = ly["features"]
            w = jax.random.rademacher(kw, (f, 2, 2, in_c), jnp.float32)
            flip = jax.random.bernoulli(kf, 0.5, (f,))
            s = conv_sums(a, w)
            tau = jnp.ceil(jnp.mean(s, axis=(0, 1, 2)))
            convs.append(dict(w=w, tau=tau, flip=flip))
            a = comparator(s, tau, flip)
            if ly["maxpool"]:
                a = maxpool(a)
        else:
            key, kw = jax.random.split(key)
            w = jax.random.rademacher(
                kw, (ly["out_features"], ly["in_features"]), jnp.float32)
            fcs.append(dict(w=w))
            if a.ndim == 4:
                a = a.reshape(a.shape[0], -1)
            s = fc_sums(a, w)
            a = s if ly["final"] else jnp.where(s >= 0, 1.0, -1.0)
    return {"conv": convs, "fc": fcs}
