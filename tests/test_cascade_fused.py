"""Fused in-kernel cascade, kernel and plan: bit-exactness vs the host
escalation rule.

ONE dispatch runs the detector over every frame tile, computes the
escalation mask (positive-class logit margin vs threshold) *inside* the
kernel, and drains the recognizer over escalated lanes only through
bounded-iteration control flow — and the answers are bit-identical to
the offline stage oracles on every escalated frame for every margin,
batch raggedness, drain schedule and REGISTRY det/rec pair.  The served
path (``ChipServer`` + ``CascadePipeline``) is tested in
``test_cascade_fused_serving.py``, which shares this module's setup.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.chip import interpreter, isa, networks
from repro.serving import margins_of
from test_fold_pack_property import _random_bn_params, random_program


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


# margins covering both extremes, a fractional value (exercises the
# ceil in margin_ctrl), zero and interior thresholds
MARGINS = (float("-inf"), -3.5, 0.0, 1.0, 7.0, float("inf"))


@pytest.fixture(scope="module")
def fused_setup():
    det = networks.mnist5(classes=2)
    rec = networks.mnist5(classes=5)
    progs = {"det": det, "rec": rec}
    arts = {"det": _artifact(det, seed=1), "rec": _artifact(rec, seed=2)}
    frames = _frames(det, 7, seed=3)
    plan, image = interpreter.pack_cascade(
        progs, arts, detector="det", recognizer="rec")
    dl, dlab = _offline(det, arts["det"], frames)
    rl, rlab = _offline(rec, arts["rec"], frames)
    return (det, rec, progs, arts, frames, plan, image,
            (dl, dlab), (rl, rlab))


def _check_fused(plan, image, frames, dl, rl, margin, **kw):
    """One fused dispatch vs the host escalation rule + offline oracles:
    det logits exact, queue == the host mask's indices (ascending),
    counts[0] == the mask popcount, rec rows == the offline recognizer
    on exactly the escalated frames."""
    ctrl = plan.margin_ctrl(margin, len(frames))
    d, dlb, r, rlb, q, cnt = plan.forward_fused(
        image, jnp.asarray(frames), ctrl, interpret=True, **kw)
    d, r, q, cnt = (np.asarray(d), np.asarray(r), np.asarray(q),
                    np.asarray(cnt))
    host_mask = margins_of(dl, plan.positive_class) >= margin
    exp_q = np.nonzero(host_mask)[0]
    np.testing.assert_array_equal(d, dl)
    np.testing.assert_array_equal(np.asarray(dlb), np.argmax(dl, axis=1))
    assert int(cnt[0]) == len(exp_q)
    assert int(cnt[1]) >= int(cnt[0])      # drain chunks may pad, never drop
    np.testing.assert_array_equal(q[:len(exp_q)], exp_q)
    np.testing.assert_array_equal(r[:len(exp_q)], rl[exp_q])
    np.testing.assert_array_equal(np.asarray(rlb)[:len(exp_q)],
                                  np.argmax(rl[exp_q], axis=1))


def test_forward_fused_bit_exact_vs_oracles(fused_setup):
    """Plan-level fused dispatch vs the offline stage oracles at every
    margin, on a ragged batch with ragged drain chunks."""
    det, rec, progs, arts, frames, plan, image, (dl, _), (rl, _) = fused_setup
    for margin in MARGINS:
        _check_fused(plan, image, frames, dl, rl, margin,
                     bb=3, rb=2, check_every=2)


def test_fused_schedule_invariance(fused_setup):
    """bb/rb/check_every are pure schedule knobs: every setting yields
    the identical escalation queue and logits."""
    det, rec, progs, arts, frames, plan, image, (dl, _), (rl, _) = fused_setup
    for bb, rb, ce in ((1, 1, 1), (4, 4, 3), (2, 1, 5), (7, 3, 2)):
        _check_fused(plan, image, frames, dl, rl, 0.0,
                     bb=bb, rb=rb, check_every=ce)


def test_fused_padding_never_escalates(fused_setup):
    """The batch-pad lanes (gidx >= n_real) are masked out of the
    escalation even at margin=-inf, where every *real* frame escalates."""
    det, rec, progs, arts, frames, plan, image, (dl, _), (rl, _) = fused_setup
    five = frames[:5]                    # bb=4 -> bpad=8, 3 pad lanes
    ctrl = plan.margin_ctrl(float("-inf"), 5)
    *_, q, cnt = plan.forward_fused(image, jnp.asarray(five), ctrl,
                                    interpret=True, bb=4, rb=2)
    assert int(np.asarray(cnt)[0]) == 5
    np.testing.assert_array_equal(np.asarray(q)[:5], np.arange(5))


def test_margin_ctrl_bit_exactness():
    """The int32 fold of the host float rule: for integer margins m,
    m >= margin  <=>  m >= ceil(margin); +/-inf map to unreachable
    sentinels; NaN is rejected."""
    mc = interpreter.CascadePlan.margin_ctrl
    assert int(mc(0.0, 3)[0, 0]) == 0
    assert int(mc(0.2, 3)[0, 0]) == 1
    assert int(mc(-0.2, 3)[0, 0]) == 0
    assert int(mc(float("-inf"), 3)[0, 0]) == -(2 ** 31)
    assert int(mc(float("inf"), 3)[0, 0]) == 2 ** 31 - 1
    assert int(mc(1e300, 3)[0, 0]) == 2 ** 31 - 1      # finite clamp
    assert int(mc(0.0, 9)[0, 1]) == 9                  # n_real rides along
    with pytest.raises(ValueError, match="NaN"):
        mc(float("nan"), 3)
    # the equivalence itself, on a grid spanning both signs
    for m in range(-5, 6):
        for margin in np.linspace(-5.5, 5.5, 45):
            thr = int(mc(float(margin), 1)[0, 0])
            assert (m >= margin) == (m >= thr), (m, margin)


def test_fused_positive_class_zero_bit_exact(fused_setup):
    """positive_class=0 flips which logit is 'positive': the fused mask
    still matches the host rule exactly."""
    det, rec, progs, arts, frames, plan0, image0, (dl, _), (rl, _) = \
        fused_setup
    plan, image = interpreter.pack_cascade(
        progs, arts, detector="det", recognizer="rec", positive_class=0)
    _check_fused(plan, image, frames, dl, rl, 0.0, bb=3, rb=2)


def test_pack_cascade_guards():
    det = networks.mnist5(classes=2)
    rec = networks.mnist5(classes=5)
    wide = networks.cifar9(4, classes=2)
    arts = {"det": _artifact(det, 1), "rec": _artifact(rec, 2),
            "wide": _artifact(wide, 3)}
    progs = {"det": det, "rec": rec, "wide": wide}
    with pytest.raises(isa.ProgramError, match="distinct"):
        interpreter.pack_cascade(progs, arts, detector="det",
                                 recognizer="det")
    with pytest.raises(KeyError, match="missing"):
        interpreter.pack_cascade(progs, arts, detector="det",
                                 recognizer="ghost")
    with pytest.raises(isa.ProgramError, match="geometry"):
        interpreter.pack_cascade(progs, arts, detector="det",
                                 recognizer="wide")
    with pytest.raises(isa.ProgramError, match="positive_class"):
        interpreter.pack_cascade(progs, arts, detector="det",
                                 recognizer="rec", positive_class=2)


def test_pack_programs_exact_tiling_gate():
    """pack_programs still rejects non-tiling multi-program packs by
    default; the cascade's exact_tiling=False escape hatch admits
    sequential-phase pairs whose S-modes oversubscribe the array."""
    det = networks.face_detector()                   # S=4 -> 64 channels
    rec = networks.REGISTRY["cifar9_s1"]()           # S=1 -> 256 channels
    progs = {"det": det, "rec": rec}
    arts = {"det": _artifact(det, 1), "rec": _artifact(rec, 2)}
    with pytest.raises(isa.ProgramError, match="tile"):
        interpreter.pack_programs(progs, arts)
    cplan, _ = interpreter.pack_programs(progs, arts, exact_tiling=False)
    assert len(cplan.programs) == 2


def test_fused_serve_fn_rejects_multi_device_mesh(fused_setup):
    """The in-kernel escalation queue is batch-global, so the fused
    dispatch refuses to shard over a multi-device mesh."""
    plan = fused_setup[5]
    fake_mesh = types.SimpleNamespace(devices=np.zeros((2,)))
    with pytest.raises(ValueError, match="multi-device"):
        plan.make_serve_fn(mesh=fake_mesh)


# ---------------------------------------------------------------------------
# Property: fused mask == host margin rule over random programs
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(s_det=st.sampled_from([2, 4]),
       s_rec=st.sampled_from([1, 2, 4]),
       seed=st.integers(0, 10 ** 6),
       margin_kind=st.sampled_from(
           ["neginf", "posinf", "zero", "median", "frac"]),
       n=st.integers(1, 6),
       bb=st.integers(1, 3))
def test_fused_mask_matches_host_property(s_det, s_rec, seed, margin_kind,
                                          n, bb):
    """Over random valid programs x margins x ragged batches: the fused
    kernel's escalation queue is exactly the host `margin >= thr` rule's
    index set, and every escalated lane carries the offline recognizer's
    logits.  Same seed -> same IO geometry for any S (the generator
    draws frame geometry before the S-dependent layers), so every
    (s_det, s_rec) pair is cascade-compatible."""
    det = random_program(s_det, seed)
    rec = random_program(s_rec, seed)
    arts = {
        "det": interpreter.fold_params(
            _random_bn_params(det, seed + 10), det, packed=True),
        "rec": interpreter.fold_params(
            _random_bn_params(rec, seed + 20), rec, packed=True),
    }
    ncd = det.instrs[-1].out_features
    pc = seed % ncd
    plan, image = interpreter.pack_cascade(
        {"det": det, "rec": rec}, arts, detector="det", recognizer="rec",
        positive_class=pc)
    frames = _frames(det, n, seed=seed + 30)
    dl, _ = _offline(det, arts["det"], frames)
    rl, _ = _offline(rec, arts["rec"], frames)
    margins = margins_of(dl, pc)
    margin = {"neginf": float("-inf"), "posinf": float("inf"),
              "zero": 0.0, "median": float(np.median(margins)),
              "frac": float(np.median(margins)) - 0.5}[margin_kind]
    _check_fused(plan, image, frames, dl, rl, margin,
                 bb=bb, rb=2, check_every=2)


# ---------------------------------------------------------------------------
# Every REGISTRY det/rec pair (acceptance criterion)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _reg_prog(name):
    return networks.REGISTRY[name]()


@functools.lru_cache(maxsize=None)
def _reg_art(name):
    return _artifact(_reg_prog(name), seed=hash(name) % 1000)


@functools.lru_cache(maxsize=None)
def _reg_offline(name):
    prog = _reg_prog(name)
    return _offline(prog, _reg_art(name), _frames(prog, 4, seed=11))


def _registry_pairs():
    names = sorted(networks.REGISTRY)
    geom = {}
    for n in names:
        io = _reg_prog(n).instrs[0]
        geom[n] = (io.height, io.width, io.in_channels, io.bits)
    return [(a, b) for a in names for b in names
            if a != b and geom[a] == geom[b]
            and _reg_prog(a).instrs[-1].out_features >= 2]


@pytest.mark.slow
@pytest.mark.parametrize("det_name,rec_name", _registry_pairs())
def test_fused_registry_pairs(det_name, rec_name):
    """Acceptance: the fused cascade is bit-exact vs the host cascade
    and the offline recognizer oracle for every geometry-compatible
    ordered REGISTRY pair — including the oversubscribed S=4 -> S=1
    paper pair (sequential phases need no exact tiling)."""
    det, rec = _reg_prog(det_name), _reg_prog(rec_name)
    arts = {det_name: _reg_art(det_name), rec_name: _reg_art(rec_name)}
    plan, image = interpreter.pack_cascade(
        {det_name: det, rec_name: rec}, arts,
        detector=det_name, recognizer=rec_name)
    frames = _frames(det, 4, seed=11)
    dl, _ = _reg_offline(det_name)
    rl, _ = _reg_offline(rec_name)
    # a margin that splits the batch when possible: the median margin
    margin = float(np.median(margins_of(dl)))
    _check_fused(plan, image, frames, dl, rl, margin, bb=4, rb=2)
