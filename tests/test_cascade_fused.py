"""Fused in-kernel cascade: bit-exactness vs the host escalation rule.

The PR's acceptance property: ONE composite dispatch runs the detector
over every frame tile, computes the escalation mask (positive-class
logit margin vs threshold) *inside* the kernel, and drains the
recognizer over escalated lanes only through bounded-iteration control
flow — and the answers are bit-identical to the host-side cascade (and
to the offline recognizer oracle on every escalated frame) for every
margin, batch raggedness, drain schedule and REGISTRY det/rec pair.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.chip import interpreter, isa, networks
from repro.kernels import cache as warmcache
from repro.serving import (CascadePipeline, ChipServer, margins_of,
                           telemetry)
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


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_fused_pipeline_matches_host_for_every_margin(fused_setup, prefetch):
    """The serving path: CascadePipeline(fused=True), stepped by
    ChipServer.step at every pipeline depth, finalizes the same labels,
    escalation flags, margins and logits as the host cascade and as the
    per-stage oracles at every margin — and the padding-free energy
    bills agree."""
    det, rec, progs, arts, frames, _, _, (dl, dlab), (rl, rlab) = \
        fused_setup
    for margin in MARGINS:
        runs = {}
        for fused in (False, True):
            server = ChipServer(progs, arts, batch=2, interpret=True,
                                prefetch=prefetch)
            casc = CascadePipeline(server, "det", "rec", margin=margin,
                                   fused=fused)
            casc.submit_many(frames)
            res = sorted(casc.drain(), key=lambda c: c.rid)
            assert len(res) == len(frames)
            runs[fused] = (res, casc.report(include_padding=False),
                           casc.escalated)
            server.close()
        host, fusedr = runs[False][0], runs[True][0]
        for h, f in zip(host, fusedr):
            assert (h.rid, h.label, h.escalated, h.detector_label) == \
                   (f.rid, f.label, f.escalated, f.detector_label), margin
            assert h.detector_margin == pytest.approx(f.detector_margin)
            np.testing.assert_array_equal(h.logits, f.logits)
        assert runs[False][2] == runs[True][2]
        assert runs[False][1].uj_per_frame == pytest.approx(
            runs[True][1].uj_per_frame)
        esc = margins_of(dl) >= margin
        np.testing.assert_array_equal([f.escalated for f in fusedr], esc)
        np.testing.assert_array_equal([f.detector_label for f in fusedr],
                                      dlab)
        np.testing.assert_array_equal([f.label for f in fusedr],
                                      np.where(esc, rlab, dlab))
        for f, e, d, r in zip(fusedr, esc, dl, rl):
            np.testing.assert_array_equal(f.logits, r if e else d)


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_fused_pipeline_margin_extremes(fused_setup, prefetch):
    """-inf escalates everything (labels == recognizer offline), +inf
    nothing (labels == detector offline) — through the fused path."""
    det, rec, progs, arts, frames, _, _, (_, dlab), (_, rlab) = fused_setup
    for margin, oracle, want_esc in ((float("-inf"), rlab, True),
                                     (float("inf"), dlab, False)):
        server = ChipServer(progs, arts, batch=2, interpret=True,
                            prefetch=prefetch)
        casc = CascadePipeline(server, "det", "rec", margin=margin,
                               fused=True)
        casc.submit_many(frames)
        res = sorted(casc.drain(), key=lambda c: c.rid)
        assert all(c.escalated == want_esc for c in res)
        np.testing.assert_array_equal(
            np.array([c.label for c in res]), oracle)
        assert casc.fused_dispatches == 4          # 7 frames / batch 2
        server.close()


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_fused_billing_invariant_and_kernel_slots(fused_setup, prefetch):
    """Fused dispatches keep the server's launch-ledger invariant
    (billed == served + padded over every lane) and bill the recognizer
    on the kernel-reported slot count: escalated frames plus the drain
    chunks' padding, never less than the escalations."""
    det, rec, progs, arts, frames, *_ = fused_setup
    server = ChipServer(progs, arts, batch=2, interpret=True,
                        prefetch=prefetch)
    casc = CascadePipeline(server, "det", "rec", margin=0.0, fused=True)
    casc.submit_many(frames)
    casc.drain()
    stats = server.stats()
    assert server._billed == (sum(stats.served.values())
                              + sum(stats.padded.values()))
    assert stats.served["det"] == len(frames)
    assert stats.served["rec"] == casc.escalated
    assert stats.padded["rec"] >= 0
    rep = casc.report()
    assert rep.frames == len(frames)
    assert rep.escalated == casc.escalated
    server.close()


def _kernel_books(plan, image, frames, batch, margin):
    """Each fused dispatch of ``frames`` in batches of ``batch`` (padded
    with the last real frame, as the executor pads), run through the
    plan directly: [(real frames, escalated, recognizer slots)]."""
    out = []
    for s in range(0, len(frames), batch):
        real = frames[s:s + batch]
        pad = np.concatenate([real, np.repeat(real[-1:],
                                              batch - len(real), axis=0)])
        *_, cnt = plan.forward_fused(image, jnp.asarray(pad),
                                     plan.margin_ctrl(margin, len(real)),
                                     interpret=True)
        cnt = np.asarray(cnt)
        out.append((len(real), int(cnt[0]), int(cnt[1])))
    return out


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_fused_dispatches_run_through_the_executor(fused_setup, prefetch):
    """A fused dispatch is an ordinary server step: one ``serve.put``,
    ``serve.launch``, ``serve.wait`` (and, without the fetch thread,
    ``serve.fetch``) and one per-step row each; on a backlog every
    launch after the first runs ahead at depth >= 1; the cascade
    counters total the kernel's counts, dispatch by dispatch; and the
    bill after the drain is the kernel's: the detector on every batch
    slot, the recognizer on the slots it computed."""
    det, rec, progs, arts, frames, plan, image, *_ = fused_setup
    batch, margin = 2, 0.0
    rec_t = telemetry.Recorder()
    server = ChipServer(progs, arts, batch=batch, interpret=True,
                        prefetch=prefetch, telemetry=rec_t)
    casc = CascadePipeline(server, "det", "rec", margin=margin, fused=True)
    casc.submit_many(frames)
    assert len(casc.drain()) == len(frames)
    books = _kernel_books(plan, image, frames, batch, margin)
    n = len(books)
    snap = rec_t.snapshot()
    spans = snap["spans"]
    for name in ("serve.put", "serve.launch", "serve.wait"):
        assert spans[name]["count"] == n, name
    assert spans.get("serve.fetch", {"count": 0})["count"] == (
        n if prefetch < 2 else 0)
    assert snap["steps"]["rows"] == n
    assert snap["counters"].get("serve.ahead", 0) == (n - 1 if prefetch
                                                      else 0)
    compiles = [e for e in snap["events"] if e["name"] == "serve.compile"]
    assert [e["variants"] for e in compiles] == ["det,rec"]
    esc = sum(e for _n, e, _s in books)
    slots = sum(s for _n, _e, s in books)
    assert snap["counters"]["cascade.escalated"] == esc == casc.escalated
    assert snap["counters"]["cascade.rec_slots"] == slots
    for name in ("cascade.escalated", "cascade.rec_slots"):
        ids = sorted(e["dispatch"] for e in snap["events"]
                     if e["name"] == name)
        assert ids == list(range(n)), name
    st = server.stats()
    assert st.served == {"det": len(frames), "rec": esc}
    assert st.padded == {"det": n * batch - len(frames), "rec": slots - esc}
    assert server._billed == n * batch + slots
    sd, sr = det.s, rec.s
    assert st.array_utilization == pytest.approx(np.mean(
        [(batch / sd + s / sr) / (batch + s) for _n, _e, s in books]))
    assert server.policy.variant_dispatches == {"det": n, "rec": 0}
    server.close()


def test_fused_midstream_report_bills_finished_dispatches(fused_setup):
    """At depth 1 the first step launches two fused dispatches and
    answers one: the bill holds that one only, since a cascade's bill is
    the kernel's count and is written when the dispatch finishes."""
    det, rec, progs, arts, frames, *_ = fused_setup
    server = ChipServer(progs, arts, batch=2, interpret=True, prefetch=1)
    casc = CascadePipeline(server, "det", "rec", fused=True)
    casc.submit_many(frames)
    assert len(casc.step()) == 2
    assert server.executor.inflight_frames() == 2
    rep = casc.report()
    assert rep.frames == 2
    assert server.stats().served["det"] == 2
    casc.drain()
    assert casc.report().frames == len(frames)
    server.close()


def test_fused_margin_change_reaches_later_dispatches(fused_setup):
    """Setting the margin (``calibrate`` does) rebinds the server's
    cascade route: the next dispatches escalate at the new margin."""
    det, rec, progs, arts, frames, _, _, (dl, _), _ = fused_setup
    server = ChipServer(progs, arts, batch=2, interpret=True, prefetch=0)
    casc = CascadePipeline(server, "det", "rec", margin=float("inf"),
                           fused=True)
    casc.margin = float("-inf")
    casc.submit_many(frames)
    assert all(c.escalated for c in casc.drain())
    server.close()


def test_fused_cascade_refuses_shared_array_lanes(fused_setup):
    """A fused cascade dispatches its detector lane solo: a lane in a
    shared-array group is refused."""
    det, _rec, progs, arts, *_ = fused_setup
    # four S=4 programs tile the array: one shared group of every lane
    progs = {**progs, "x": det, "y": det}
    arts = {**arts, "x": arts["det"], "y": arts["det"]}
    server = ChipServer(progs, arts, batch=2, interpret=True, shared=True)
    assert server.shared_groups
    with pytest.raises(ValueError, match="shared-array"):
        CascadePipeline(server, "det", "rec", fused=True)
    server.close()


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_fused_drain_answers_a_second_lane(fused_setup, prefetch):
    """The fused drain serves the server's other lanes until nothing is
    owed: with a pipeline the step that answers one of their dispatches
    may launch the last one and empty the queue, and that dispatch is
    still answered."""
    det, rec, progs, arts, frames, *_ = fused_setup
    other = networks.mnist5(classes=7)
    oart = _artifact(other, seed=9)
    server = ChipServer({**progs, "other": other}, {**arts, "other": oart},
                        batch=2, interpret=True, prefetch=prefetch)
    other_frames = _frames(other, 4, seed=8)       # two whole batches
    oracle = _offline(other, oart, other_frames)[1]
    casc = CascadePipeline(server, "det", "rec", fused=True)
    casc.submit_many(frames)
    other_rids = server.submit_many("other", other_frames)
    assert len(casc.drain()) == len(frames)
    got = {r.rid: r.label for r in casc.other_results}
    assert sorted(got) == other_rids
    np.testing.assert_array_equal([got[r] for r in other_rids], oracle)
    assert server.owed() == 0
    server.close()


def test_fused_warm_cache_and_positive_class_key(fused_setup):
    """The fused dispatch routes through the warm-start cache: a second
    pipeline over the same pair warm-starts (cache hit), while a
    different positive_class compiles its own fn (the escalation mask is
    traced against the class index)."""
    det, rec, progs, arts, frames, *_ = fused_setup
    servers = [ChipServer(progs, arts, batch=2, interpret=True)
               for _ in range(3)]
    try:
        warmcache.invalidate()
        CascadePipeline(servers[0], "det", "rec", fused=True)
        s0 = warmcache.stats()
        assert s0["misses"] >= 1
        CascadePipeline(servers[1], "det", "rec", fused=True)
        s1 = warmcache.stats()
        assert s1["hits"] == s0["hits"] + 1          # warm-started
        assert s1["misses"] == s0["misses"]
        CascadePipeline(servers[2], "det", "rec", fused=True,
                        positive_class=0)
        s2 = warmcache.stats()
        assert s2["misses"] == s1["misses"] + 1      # new trace
    finally:
        for s in servers:
            s.close()
        warmcache.invalidate()


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
