"""Binary operations and bytes of the served work, and the chip's peaks.

Counted from a configuration's layer list, so the numbers are the
program's work whatever implements it:

* operations: a +/-1 multiply-accumulate is 2 binary operations,
  counted on valid output positions only — a conv layer on an ``h x w``
  map computes ``(h-1) x (w-1)`` positions of ``F x C x 2 x 2`` MACs
  (the paper's convention: layer 1 of cifar9 at S=1 is 504 M operations,
  the whole network 2.013 G).  The thermometer code is not counted.
* bytes, the least a dispatch has to move through HBM: each frame in at
  the program's input precision (``bits`` per colour sample), its int32
  logits out, and the weight image once per dispatch (packed +/-1
  weights, an int32 threshold and an int32 direction per conv neuron).
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def _walk(layers):
    h = w = c = None
    for ly in layers:
        if ly["op"] == "io":
            h, w, c = ly["height"], ly["width"], ly["channels"]
            yield ly, h, w, c
        elif ly["op"] == "conv":
            yield ly, h, w, c
            h, w, c = h - 1, w - 1, ly["features"]
            if ly["maxpool"]:
                h, w = h // 2, w // 2
        else:
            yield ly, 1, 1, ly["in_features"]


def layer_ops(layers):
    """(op, binary operations per frame) for every conv and fc layer."""
    out = []
    for ly, h, w, c in _walk(layers):
        if ly["op"] == "conv":
            out.append(("conv", ly["features"] * c * 4 * 2 * (h - 1) * (w - 1)))
        elif ly["op"] == "fc":
            out.append(("fc", 2 * ly["in_features"] * ly["out_features"]))
    return out


def frame_ops(layers) -> int:
    return sum(n for _op, n in layer_ops(layers))


def conv_ops(layers) -> int:
    return sum(n for op, n in layer_ops(layers) if op == "conv")


def frame_bytes(layers) -> float:
    """Frame in at its input precision plus int32 logits out."""
    io, last = layers[0], layers[-1]
    pixels = io["height"] * io["width"] * io["in_channels"]
    return pixels * io["bits"] / 8 + 4 * last["out_features"]


def weight_bytes(layers) -> float:
    """Packed weight image: 1 bit a weight, 8 bytes a conv neuron."""
    total = 0.0
    for ly, _h, _w, c in _walk(layers):
        if ly["op"] == "conv":
            total += ly["features"] * 4 * c / 8 + 8 * ly["features"]
        elif ly["op"] == "fc":
            total += ly["in_features"] * ly["out_features"] / 8
    return total


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{os.path.basename(path)} (have {sorted(table)})")
    return table[device_kind]


def least_time_s(ops: float, nbytes: float, peaks: dict):
    """(seconds, bound): the least time one chip needs for ``ops`` binary
    operations and ``nbytes`` of HBM traffic, and which of the two sets
    it.  A +/-1 MAC is an int8 MAC on the MXU, so binary operations run
    against the int8 peak."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
