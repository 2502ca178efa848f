"""Plain reference of a BinarEye detector -> recognizer cascade.

Each stage is a plain program reference (``binary_cnn.py``: +/-1 values
in ``jax.numpy``, every sum through its einsums at the highest matmul
precision); this module adds only the escalation rule (arXiv:1804.05554,
Sec. IV: a cheap detector screens every frame and wakes the recogniser
for the frames it passes):

* the detector runs on every frame;
* its margin is the positive-class logit minus the best other logit;
* a frame escalates where ``margin >= thr``, and then takes the
  recogniser's label and logits; every other frame keeps the detector's.

The weights of each stage come from ``init`` and are run by ``forward``,
re-exported here so that a configuration names this one reference.  It
imports nothing of the program under test.
"""

from __future__ import annotations

import importlib.util
import os

import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_ref_binary_cnn_stage",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "binary_cnn.py"))
binary_cnn = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(binary_cnn)

ACCS = binary_cnn.ACCS
init = binary_cnn.init
forward = binary_cnn.forward


def margins(logits, positive_class: int = 1):
    """Positive-class logit minus the best other logit, per row."""
    others = jnp.delete(logits, positive_class, axis=-1,
                        assume_unique_indices=True)
    return logits[:, positive_class] - others.max(axis=-1)


def cascade(weights, layers, frames, thr, *, detector: str, recognizer: str,
            positive_class: int = 1, acc: str = "float32"):
    """The cascade's answer for every frame: a dict of ``margin``,
    ``escalated``, ``detector_label``, ``label`` and ``logits`` (those of
    the stage that answered).  ``weights`` and ``layers`` are keyed by
    stage name; ``thr`` is the escalation threshold on the margin.  Both
    stages must give the same number of classes."""
    det_logits, det_labels = forward(weights[detector], layers[detector],
                                     frames, acc)
    rec_logits, rec_labels = forward(weights[recognizer], layers[recognizer],
                                     frames, acc)
    if det_logits.shape != rec_logits.shape:
        raise ValueError(f"the stages give {det_logits.shape[-1]} and "
                         f"{rec_logits.shape[-1]} classes; the cascade's "
                         "answers need one width")
    m = margins(det_logits, positive_class)
    esc = m >= thr
    return {"margin": m, "escalated": esc, "detector_label": det_labels,
            "label": jnp.where(esc, rec_labels, det_labels),
            "logits": jnp.where(esc[:, None], rec_logits, det_logits)}
