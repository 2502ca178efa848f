"""Cascaded always-on pipelines: routing, bit-exactness, energy billing.

The acceptance property: the cascade's final labels are bit-exact vs the
stage that produced them — every escalated frame's label equals the
recognizer's offline forward on that exact frame, every non-escalated
frame's label equals the detector's — and the energy bill composes
``det + rate * rec`` so the cascade beats recognizing every frame
whenever the escalation rate is below ``1 - det/rec``.
"""

import jax
import numpy as np
import pytest

from repro.core.chip import energy, interpreter, networks
from repro.serving import CascadePipeline, ChipServer


def _frames(program, n, seed=0):
    io = program.instrs[0]
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n, io.height, io.width, io.in_channels),
        0, 2 ** io.bits))


def _artifact(program, seed=0):
    params = interpreter.init_params(jax.random.PRNGKey(seed), program)
    return interpreter.fold_params(params, program, packed=True)


def _offline(program, packed, frames):
    plan = interpreter.compile_plan(program)
    logits, labels = plan.forward(packed, np.asarray(frames), interpret=True)
    return np.asarray(logits), np.asarray(labels)


@pytest.fixture(scope="module")
def cascade_setup():
    """A cheap 2-class detector and a 5-class recognizer sharing the
    mnist5 frame geometry (the fast stand-in for the paper's
    face-detect -> owner-recognition pair; the bench runs the real
    cifar9 S=4 -> S=1 pair)."""
    det = networks.mnist5(classes=2)
    rec = networks.mnist5(classes=5)
    arts = {"det": _artifact(det, seed=1), "rec": _artifact(rec, seed=2)}
    frames = _frames(det, 7, seed=3)
    det_logits, det_labels = _offline(det, arts["det"], frames)
    rec_logits, rec_labels = _offline(rec, arts["rec"], frames)
    return (det, rec, arts, frames,
            (det_logits, det_labels), (rec_logits, rec_labels))


def _server(det, rec, arts, **kw):
    return ChipServer({"det": det, "rec": rec}, arts, batch=2,
                      interpret=True, **kw)


def test_cascade_labels_bit_exact_per_stage(cascade_setup):
    """Escalated frames carry the recognizer's offline label, everything
    else the detector's — and the escalation decision itself matches the
    offline logit-margin rule frame by frame."""
    det, rec, arts, frames, (dl, dlab), (rl, rlab) = cascade_setup
    margins = dl[:, 1] - dl[:, 0]
    casc = CascadePipeline(_server(det, rec, arts), "det", "rec",
                           positive_class=1, margin=0.0)
    rids = casc.submit_many(frames)
    assert rids == list(range(len(frames)))
    results = sorted(casc.drain(), key=lambda c: c.rid)
    assert len(results) == len(frames)
    for i, c in enumerate(results):
        want_escalate = bool(margins[i] >= 0.0)
        assert c.escalated == want_escalate, i
        assert c.detector_label == dlab[i]
        assert c.detector_margin == pytest.approx(margins[i])
        if c.escalated:
            assert c.label == rlab[i]
            np.testing.assert_array_equal(c.logits, rl[i])
        else:
            assert c.label == dlab[i]
            np.testing.assert_array_equal(c.logits, dl[i])
    assert casc.escalated == sum(1 for c in results if c.escalated)


def test_cascade_margin_extremes(cascade_setup):
    """margin=-inf escalates every frame (labels == recognizer offline,
    the 'recognizer on every frame it escalates' oracle); margin=+inf
    escalates none (labels == detector offline)."""
    det, rec, arts, frames, (_, dlab), (_, rlab) = cascade_setup
    casc = CascadePipeline(_server(det, rec, arts), "det", "rec",
                           margin=float("-inf"))
    casc.submit_many(frames)
    res = sorted(casc.drain(), key=lambda c: c.rid)
    assert all(c.escalated for c in res)
    np.testing.assert_array_equal(np.array([c.label for c in res]), rlab)

    casc = CascadePipeline(_server(det, rec, arts), "det", "rec",
                           margin=float("inf"))
    casc.submit_many(frames)
    res = sorted(casc.drain(), key=lambda c: c.rid)
    assert not any(c.escalated for c in res)
    np.testing.assert_array_equal(np.array([c.label for c in res]), dlab)


def test_cascade_with_prefetch_and_step_interleaving(cascade_setup):
    """The cascade composes with the depth-k submission pipeline and
    incremental step()/submit() interleaving: same final label set."""
    det, rec, arts, frames, _, _ = cascade_setup
    runs = {}
    for prefetch in (0, 2):
        casc = CascadePipeline(_server(det, rec, arts, prefetch=prefetch),
                               "det", "rec")
        got = []
        for f in frames:
            casc.submit(f)
            got.extend(casc.step())
        got.extend(casc.drain())
        casc.server.close()
        runs[prefetch] = sorted((c.rid, c.label, c.escalated) for c in got)
    assert runs[0] == runs[2]
    assert len(runs[0]) == len(frames)


def test_cascade_report_math(cascade_setup):
    """The bill composes det + rate*rec (+ padding) and the savings
    ratio is measured against recognizing every frame."""
    det, rec, arts, frames, _, _ = cascade_setup
    server = _server(det, rec, arts)
    casc = CascadePipeline(server, "det", "rec", margin=float("-inf"))
    casc.submit_many(frames)
    casc.drain()
    stats = server.stats()
    rep = casc.report()
    det_uj = energy.analyze_net(det).i2l_energy_per_inference * 1e6
    rec_uj = energy.analyze_net(rec).i2l_energy_per_inference * 1e6
    want = ((len(frames) + stats.padded["det"]) * det_uj
            + (len(frames) + stats.padded["rec"]) * rec_uj) / len(frames)
    assert rep.uj_per_frame == pytest.approx(want)
    assert rep.uj_per_frame_recognizer_only == pytest.approx(rec_uj)
    assert rep.escalation_rate == 1.0
    assert rep.savings == pytest.approx(rec_uj / want)
    # ignoring padding: the pure det + rate*rec composition
    rep_np = casc.report(include_padding=False)
    assert rep_np.uj_per_frame == pytest.approx(det_uj + rec_uj)


def test_cascade_billing_ragged_drain(cascade_setup):
    """Launch-ledger billing across a ragged drain: the trailing partial
    recognizer batch's padding is billed exactly once, the server-wide
    invariant ``billed == served + padded`` holds, and the escalation
    rate's denominator is the frames served (not the padded slots)."""
    det, rec, arts, frames, (dl, _), _ = cascade_setup
    margins = dl[:, 1] - dl[:, 0]
    # a margin escalating an ODD count (batch=2 -> ragged remainder)
    margin = float(np.sort(margins)[-3])       # top-3 escalate, 3 = 2 + 1
    server = _server(det, rec, arts)
    casc = CascadePipeline(server, "det", "rec", margin=margin)
    casc.submit_many(frames)
    casc.drain()
    stats = server.stats()
    assert stats.billed == (sum(stats.served.values())
                            + sum(stats.padded.values()))
    assert stats.served["det"] == 7 and stats.padded["det"] == 1
    assert stats.served["rec"] == 3 and stats.padded["rec"] == 1
    rep = casc.report()
    det_uj = energy.analyze_net(det).i2l_energy_per_inference * 1e6
    rec_uj = energy.analyze_net(rec).i2l_energy_per_inference * 1e6
    assert rep.frames == 7 and rep.escalated == 3
    assert rep.escalation_rate == pytest.approx(3 / 7)
    assert rep.uj_per_frame == pytest.approx(
        (8 * det_uj + 4 * rec_uj) / 7)


@pytest.mark.parametrize("prefetch", [0, 1])
def test_cascade_report_midstream_never_bills_queued(cascade_setup,
                                                     prefetch):
    """A mid-stream report bills only what hit the array: frames still
    queued on the detector — or escalations deferred awaiting a full
    recognizer batch — are absent from the bill until dispatched.  At
    depth 1 the step has launched the next detector batch too."""
    det, rec, arts, frames, _, _ = cascade_setup
    server = _server(det, rec, arts, prefetch=prefetch)
    casc = CascadePipeline(server, "det", "rec", margin=float("-inf"))
    casc.submit_many(frames[:5])
    casc.step()                               # one det dispatch of 2
    rep = casc.report()                       # (and one in flight at
    assert rep.frames == 2 * (1 + prefetch)   # depth 1); the rest queued
    # both frames escalated but the recognizer batch is still deferred
    assert casc.escalated == 2 and rep.escalated == 0
    casc.drain()
    assert casc.report().frames == 5
    assert casc.report().escalated == 5
    server.close()


def test_cascade_report_paper_pair_beats_recognizer_only():
    """The paper's pair (0.92 uJ/f S=4 detector -> 14.4 uJ/f S=1
    recognizer): at any escalation rate below 1 - det/rec the cascade
    bill is strictly below running the recognizer on every frame."""
    det, rec = networks.face_detector(), networks.owner_detector()
    rep = energy.cascade_report(det, rec, frames=100, escalated=20)
    # the calibrated model lands within its documented ~7% validation
    # band of the paper's published points
    assert rep.detector_uj == pytest.approx(0.92, rel=0.07)
    assert rep.recognizer_uj == pytest.approx(14.4, rel=0.07)
    assert rep.uj_per_frame < rep.uj_per_frame_recognizer_only
    assert rep.savings > 1.0
    # break-even boundary: rate just under 1 - det/rec still saves
    rate = 1 - rep.detector_uj / rep.recognizer_uj
    almost = energy.cascade_report(det, rec, frames=1000,
                                   escalated=int(rate * 1000) - 1)
    assert almost.savings > 1.0
    with pytest.raises(ValueError, match="exceeds"):
        energy.cascade_report(det, rec, frames=5, escalated=6)


def test_cascade_coexists_with_other_server_lanes(cascade_setup):
    """The cascade shares its server with unrelated resident lanes:
    their results pass through to ``other_results`` instead of crashing
    or corrupting cascade state."""
    det, rec, arts, frames, _, _ = cascade_setup
    other = networks.mnist5(classes=7)
    server = ChipServer(
        {"det": det, "rec": rec, "other": other},
        {**arts, "other": _artifact(other, seed=9)}, batch=2,
        interpret=True)
    other_frames = _frames(other, 3, seed=8)
    oracle = _offline(other, _artifact(other, seed=9), other_frames)[1]
    casc = CascadePipeline(server, "det", "rec")
    casc.submit_many(frames)
    other_rids = server.submit_many("other", other_frames)
    results = casc.drain()
    assert len(results) == len(frames)
    got = {r.rid: r.label for r in casc.other_results}
    assert sorted(got) == other_rids
    np.testing.assert_array_equal(
        np.array([got[r] for r in other_rids]), oracle)


def test_cascade_rejects_family_stage(cascade_setup):
    """Family lanes can't be cascade stages (the energy bill is per
    stage program, and the controller may swap variants)."""
    det, rec, arts, frames, _, _ = cascade_setup
    rec2 = networks.mnist5(classes=5)
    server = ChipServer(
        {"det": det, "rec": rec, "rec2": rec2},
        {**arts, "rec2": _artifact(rec2, seed=6)}, batch=2,
        interpret=True, policy="operating-point",
        families={"fam": ("rec", "rec2")})
    with pytest.raises(ValueError, match="family"):
        CascadePipeline(server, "det", "fam")


def test_cascade_guards(cascade_setup):
    det, rec, arts, frames, _, _ = cascade_setup
    server = _server(det, rec, arts)
    with pytest.raises(KeyError, match="not resident"):
        CascadePipeline(server, "det", "ghost")
    with pytest.raises(ValueError, match="distinct"):
        CascadePipeline(server, "det", "det")
    cifar = networks.cifar9(4, classes=2)
    mixed = ChipServer({"det": det, "wide": cifar},
                       {"det": arts["det"], "wide": _artifact(cifar)},
                       batch=2, interpret=True)
    with pytest.raises(ValueError, match="geometry"):
        CascadePipeline(mixed, "det", "wide")
