"""A ``ChipServer`` serving the configuration's two programs as the
paper's always-on cascade (``CascadePipeline``): the detector screens
every frame, and the recognizer answers the frames whose detector margin
reaches the threshold.

Cell keys: ``server.batch`` (detector frames a dispatch) and
``server.policy``; ``cascade.detector``, ``cascade.recognizer``,
``cascade.positive_class`` and ``cascade.escalation_share``.  The
threshold is set per seed in set-up (:func:`threshold_for_share`): the
integer threshold at which the share of bank frames whose *reference*
detector margin reaches it lies nearest ``escalation_share``, so the
work a frame costs does not wander with the seeded weights.  The
realised share is printed.  ``cascade.fused`` (default true; no cell
sets it) serves the host cascade instead, for comparison runs.
Everything else is left to the server's defaults.
"""

from __future__ import annotations

import sys

import numpy as np

import harness


def threshold_for_share(margins, share: float):
    """(threshold, realised share): the integer ``thr`` for which the
    share of ``margins`` at or above it lies nearest ``share``; between
    two equally near, the higher (cheaper) threshold."""
    m = np.sort(np.asarray(margins, dtype=np.int64))
    cands = np.unique(m)
    shares = (len(m) - np.searchsorted(m, cands, side="left")) / len(m)
    dist = np.abs(shares - share)
    i = int(np.flatnonzero(dist == dist.min())[-1])
    return int(cands[i]), float(shares[i])


def reference_cascade(h: harness.Harness, opts: dict, thr: int,
                      indices: np.ndarray, acc: str = "float32",
                      block: int = harness.REF_BLOCK) -> dict:
    """The plain reference cascade over bank frames ``indices``, in
    blocks of ``block`` frames on the first device: its answer columns
    (``binary_cascade.cascade``) as host arrays."""
    import jax
    import jax.numpy as jnp
    kw = dict(detector=opts["detector"], recognizer=opts["recognizer"],
              positive_class=int(opts.get("positive_class", 1)), acc=acc)
    fn = jax.jit(lambda w, x: h.ref.cascade(w, h.layers, x, thr, **kw))
    parts = []
    with jax.default_device(h.devices[0]):
        for s in range(0, len(indices), block):
            blk = indices[s:s + block]
            pad = block - len(blk)
            x = h.bank[np.concatenate([blk, np.repeat(blk[-1:], pad)])]
            out = fn(h.weights, jnp.asarray(x))
            parts.append({k: np.asarray(v)[:len(blk)]
                          for k, v in out.items()})
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


class Target:
    def __init__(self, h: harness.Harness, system: bool = True):
        from repro.serving import CascadePipeline, ChipServer
        opts = h.cell["cascade"]
        self.opts = opts
        self.det, self.rec = opts["detector"], opts["recognizer"]
        pc = int(opts.get("positive_class", 1))
        logits, _ = harness.reference_outputs(h, self.det,
                                              np.arange(len(h.bank)))
        self.thr, self.share = threshold_for_share(
            h.ref.margins(logits, pc), float(opts["escalation_share"]))
        print(f"set-up: threshold {self.thr} escalates {100 * self.share:.4f}"
              f"% of the {len(h.bank)} bank frames (asked "
              f"{100 * float(opts['escalation_share']):g}%)",
              file=sys.stderr)
        if not system:                 # the control: no system under test
            return
        srv = h.cell["server"]
        kw = dict(batch=int(srv["batch"]), policy=srv.get("policy", "static"))
        if h.interpret is not None:
            kw["interpret"] = h.interpret
        names = (self.det, self.rec)
        self.server = ChipServer({n: h.programs[n] for n in names},
                                 {n: h.artifacts[n] for n in names}, **kw)
        self.casc = CascadePipeline(self.server, self.det, self.rec,
                                    positive_class=pc, margin=float(self.thr),
                                    fused=bool(opts.get("fused", True)))
        self.batch = self.server.batch

    def warm(self, h: harness.Harness) -> None:
        """Two full batches: the fused unit at the batch, or both host
        lanes (the drain flushes the recognizer's partial batch)."""
        for k in range(2 * self.batch):
            self.casc.submit(h.frame(k)[1])
        self.casc.drain()
        self.server.reset_stats()

    # -- the loops' surface ---------------------------------------------------

    def capacity(self) -> int:
        return self.batch

    def pending(self) -> int:
        return len(self.server.queue)

    def begin_window(self, t0: float) -> None:
        self.server.reset_stats()

    def submit(self, frame) -> int:
        return self.casc.submit(frame)

    def step(self):
        return self.casc.step()

    def flush(self):
        return self.casc.drain()

    def counters(self) -> dict:
        st = self.server.stats()
        return {"dispatches": st.dispatches, "served": st.total_served,
                "padded": sum(st.padded.values())}

    def close(self) -> None:
        self.server.close()
        self.server = self.casc = None

    # -- the check ------------------------------------------------------------

    @staticmethod
    def columns(answers) -> dict:
        """The cascade's answers (``CascadeResult``s) as columns."""
        n = len(answers)
        return {"key": np.array([a.rid for a in answers], dtype=np.int64),
                "escalated": np.array([a.escalated for a in answers],
                                      dtype=bool),
                "detector_label": np.array([a.detector_label
                                            for a in answers],
                                           dtype=np.int64),
                "label": np.array([a.label for a in answers], dtype=np.int64),
                "logits": np.array([np.asarray(a.logits) for a in answers],
                                   dtype=np.float64).reshape(n, -1)}

    def compare(self, h: harness.Harness, cols, idx) -> dict:
        """Each answer against the reference cascade on its own frame:
        escalations, detector labels, labels, and the widest logit gap
        (the logits of the stage that answered)."""
        uniq, inv = np.unique(idx, return_inverse=True)
        ref = reference_cascade(h, self.opts, self.thr, uniq)
        lg = np.asarray(cols["logits"], dtype=np.float64)
        gap = np.abs(lg - ref["logits"][inv]).max() if len(idx) else 0.0

        def differ(col):
            return int((cols[col] != ref[col][inv]).sum())
        return {"escalation_mismatch": (differ("escalated"), 0),
                "detector_label_mismatch": (differ("detector_label"), 0),
                "label_mismatch": (differ("label"), 0),
                "logit_gap": (float(gap), 0)}

    def control_columns(self, h: harness.Harness, acc: str):
        """The reference cascade in the system's place, its sums in
        ``acc``, over the whole bank: (columns, bank indices)."""
        idx = np.arange(len(h.bank))
        ref = reference_cascade(h, self.opts, self.thr, idx, acc)
        cols = {k: ref[k] for k in ("escalated", "detector_label", "label",
                                    "logits")}
        cols["key"] = idx
        return cols, idx

    def work(self, h: harness.Harness, answers) -> dict:
        """Frames of each program computed for these answers: the
        detector on every frame, the recognizer on the escalated ones."""
        return {self.det: len(answers),
                self.rec: sum(1 for a in answers if a.escalated)}
