"""Delta-gated always-on video: kernel gate vs host popcount reference.

Covers the full temporal stack:

* the in-kernel skip mask (change queue + counts + per-lane deltas)
  equals the host popcount rule over random programs x thresholds x
  ragged batches x tile schedules (hypothesis property);
* at threshold 0 / -inf the gated path is bit-exact vs the plain
  megakernel — fast subset here, every REGISTRY program under
  ``@pytest.mark.slow``;
* skipped lanes emit exactly the label they last served, and state
  reset (scene change) forces a full recompute;
* ``TemporalPipeline`` billing, reporting, activity-coupled
  downshifting, and threshold calibration;
* ``video_trace`` determinism and its pixel-exact changed mask.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import binarize
from repro.core.chip import energy, interpreter, networks
from repro.serving import ChipServer
from repro.serving import temporal as tmp
from repro.serving.traffic import video_trace

from test_fold_pack_property import _random_bn_params, random_program


def _frames(program, n, seed=0):
    io = program.instrs[0]
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n, io.height, io.width, io.in_channels),
        0, 2 ** io.bits))


def _artifact(program, seed=0):
    params = interpreter.init_params(jax.random.PRNGKey(seed), program)
    return interpreter.fold_params(params, program, packed=True)


def _pack(program, frames):
    io = program.instrs[0]
    return np.asarray(binarize.thermometer_pack(
        jnp.asarray(frames, jnp.int32), io.bits, io.in_channels,
        io.channels))


def _host_deltas(packed, last):
    """Per-lane packed Hamming distance — the gate's host reference."""
    x = np.ascontiguousarray(np.bitwise_xor(packed, np.asarray(last)))
    return np.unpackbits(
        x.view(np.uint8).reshape(len(x), -1), axis=1).sum(axis=1)


# thresholds covering both sentinels, zero (= plain megakernel), a
# fractional value (exercises the ceil in delta_ctrl) and interior ones
THRESHOLDS = (float("-inf"), 0.0, 1.0, 2.5, 64.0, float("inf"))


@pytest.fixture(scope="module")
def delta_setup():
    prog = networks.mnist5()
    art = _artifact(prog, seed=1)
    dplan, image = interpreter.pack_delta(prog, art, name="mnist5")
    frames = _frames(prog, 5, seed=3)
    plan = interpreter.compile_plan(prog)
    ml, mlab = plan.forward_mega(image, frames, interpret=True)
    return prog, art, dplan, image, frames, np.asarray(ml), np.asarray(mlab)


def _gated(dplan, image, frames, last, llog, thr, n_real, **kw):
    ctrl = interpreter.DeltaPlan.delta_ctrl(thr, n_real)
    out = dplan.forward_delta(image, jnp.asarray(frames, jnp.int32),
                              last, llog, ctrl, interpret=True, **kw)
    return [np.asarray(o) for o in out]


# ---------------------------------------------------------------------------
# Bit-exactness vs the plain megakernel (threshold 0 / cold -inf)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("thr", [float("-inf"), 0.0])
@pytest.mark.parametrize("bb,rb", [(2, 2), (5, 1), (3, 4)])
def test_threshold_zero_matches_megakernel(delta_setup, thr, bb, rb):
    """With the gate open (cold -inf, or 0 against warm state) every
    live lane recomputes and logits/labels equal the plain megakernel
    bit for bit, for every tile schedule."""
    prog, _, dplan, image, frames, ml, mlab = delta_setup
    last, llog = dplan.init_state(len(frames))
    lg, lb, nl, nllog, queue, counts, _ = _gated(
        dplan, image, frames, last, llog, thr, len(frames), bb=bb, rb=rb)
    assert np.array_equal(lg, ml)
    assert np.array_equal(lb, mlab)
    assert counts[0] == len(frames)
    assert list(queue[:counts[0]]) == list(range(len(frames)))
    # warmed state: the packed current frames + the fresh logits
    assert np.array_equal(nl, _pack(prog, frames))
    assert np.array_equal(nllog.astype(np.float32), ml)


def test_skipped_lanes_serve_cached_labels(delta_setup):
    """Dispatch 2 re-sends the same frames at threshold 1: everything
    skips and the served labels are exactly dispatch 1's; perturbing one
    frame recomputes only that lane."""
    prog, _, dplan, image, frames, ml, mlab = delta_setup
    n = len(frames)
    last, llog = dplan.init_state(n)
    _, _, last, llog, _, _, _ = [
        jnp.asarray(o) for o in _gated(dplan, image, frames, last, llog,
                                       float("-inf"), n, bb=2, rb=2)]
    # identical frames: all deltas 0, nothing recomputes, cache serves
    lg, lb, nl, nllog, queue, counts, deltas = _gated(
        dplan, image, frames, last, llog, 1.0, n, bb=2, rb=2)
    assert counts[0] == 0 and np.all(deltas == 0)
    assert np.array_equal(lg, ml) and np.array_equal(lb, mlab)
    # one changed frame: exactly that lane recomputes, fresh answer
    # merges over the cache
    bumped = frames.copy()
    bumped[2] = (bumped[2] + 1) % (2 ** prog.instrs[0].bits)
    lg2, lb2, _, _, queue2, counts2, deltas2 = _gated(
        dplan, image, bumped, jnp.asarray(nl), jnp.asarray(nllog),
        1.0, n, bb=2, rb=2)
    assert counts2[0] == 1 and queue2[0] == 2 and deltas2[2] > 0
    plan = interpreter.compile_plan(prog)
    ml2, _ = plan.forward_mega(image, bumped, interpret=True)
    expect = ml.copy()
    expect[2] = np.asarray(ml2)[2]
    assert np.array_equal(lg2, expect)


def test_ragged_batch_masks_padding_lanes(delta_setup):
    """Padding lanes (index >= n_real) never enter the change queue even
    at -inf, and their cached state passes through untouched."""
    _, _, dplan, image, frames, ml, _ = delta_setup
    n = len(frames)
    last, llog = dplan.init_state(n)
    _, _, _, _, queue, counts, _ = _gated(
        dplan, image, frames, last, llog, float("-inf"), 3, bb=2, rb=2)
    assert counts[0] == 3
    assert list(queue[:3]) == [0, 1, 2]


# ---------------------------------------------------------------------------
# delta_ctrl folding
# ---------------------------------------------------------------------------

def test_delta_ctrl_folding():
    c = lambda t: int(interpreter.DeltaPlan.delta_ctrl(t, 7)[0, 0])
    assert c(float("-inf")) == -(2 ** 31)
    assert c(float("inf")) == 2 ** 31 - 1
    assert c(0.0) == 0
    assert c(2.5) == 3          # ceil: d >= 2.5 <=> d >= 3 for integer d
    assert c(-3.5) == -3
    assert int(interpreter.DeltaPlan.delta_ctrl(1.0, 7)[0, 1]) == 7
    with pytest.raises(ValueError):
        interpreter.DeltaPlan.delta_ctrl(float("nan"), 7)


def test_serve_fn_rejects_multi_device_mesh(delta_setup):
    _, _, dplan, *_ = delta_setup
    mesh = types.SimpleNamespace(devices=np.zeros((2,)))
    with pytest.raises(ValueError, match="does not shard"):
        dplan.make_serve_fn(mesh=mesh)


# ---------------------------------------------------------------------------
# The gate property: kernel skip mask == host popcount rule
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(s=st.sampled_from([1, 2, 4]), seed=st.integers(0, 10 ** 6),
       thr_i=st.integers(0, len(THRESHOLDS) - 1),
       n_real_off=st.integers(0, 3),
       bb=st.integers(1, 5), rb=st.integers(1, 5))
def test_gate_matches_host_popcount(s, seed, thr_i, n_real_off, bb, rb):
    """Over random programs, thresholds, ragged batches and tile
    schedules: the kernel's change queue, counts, per-lane deltas, state
    advance and merged logits all equal the host popcount-gate rule."""
    prog = random_program(s, seed)
    params = _random_bn_params(prog, seed + 1)
    art = interpreter.fold_params(params, prog, packed=True)
    dplan, image = interpreter.pack_delta(prog, art)
    n = 5
    n_real = n - n_real_off
    thr = THRESHOLDS[thr_i]
    frames = _frames(prog, n, seed=seed + 2)
    # warm, *distinct* state: packed codes of different frames + integer
    # logits, so interior thresholds split the batch nontrivially
    prev = _frames(prog, n, seed=seed + 3)
    last = jnp.asarray(_pack(prog, prev))
    llog = jnp.asarray(
        jax.random.randint(jax.random.PRNGKey(seed + 4),
                           (n, dplan.classes), -50, 50), jnp.int32)
    lg, lb, nl, nllog, queue, counts, deltas = _gated(
        dplan, image, frames, last, llog, thr, n_real, bb=bb, rb=rb)
    packed = _pack(prog, frames)
    d_host = _host_deltas(packed, last)
    thr_int = int(interpreter.DeltaPlan.delta_ctrl(thr, n_real)[0, 0])
    live = np.arange(n) < n_real
    mask = (d_host >= thr_int) & live
    assert np.array_equal(deltas, np.where(live, d_host, 0))
    assert counts[0] == mask.sum()
    assert list(queue[:counts[0]]) == list(np.flatnonzero(mask))
    assert counts[1] >= counts[0]          # drain-chunk padding only adds
    # reference advances only where the gate fired
    assert np.array_equal(nl, np.where(mask[:, None, None, None],
                                       packed, np.asarray(last)))
    plan = interpreter.compile_plan(prog)
    ml, _ = plan.forward_mega(image, frames, interpret=True)
    expect = np.where(mask[:, None], np.asarray(ml),
                      np.asarray(llog, np.float32))
    assert np.array_equal(lg, expect)
    assert np.array_equal(lb, expect.argmax(-1))


# ---------------------------------------------------------------------------
# Every REGISTRY program (acceptance criterion)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _reg_prog(name):
    return networks.REGISTRY[name]()


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(networks.REGISTRY))
def test_registry_threshold_zero_bit_exact(name):
    """Acceptance: at threshold 0 the gated path equals the plain
    megakernel bit for bit on every REGISTRY program."""
    prog = _reg_prog(name)
    art = _artifact(prog, seed=hash(name) % 1000)
    dplan, image = interpreter.pack_delta(prog, art, name=name)
    frames = _frames(prog, 4, seed=11)
    prev = _frames(prog, 4, seed=12)
    last = jnp.asarray(_pack(prog, prev))
    llog = jnp.zeros((4, dplan.classes), jnp.int32)
    lg, lb, *_ = _gated(dplan, image, frames, last, llog, 0.0, 4,
                        bb=2, rb=2)
    plan = interpreter.compile_plan(prog)
    ml, mlab = plan.forward_mega(image, frames, interpret=True)
    assert np.array_equal(lg, np.asarray(ml))
    assert np.array_equal(lb, np.asarray(mlab))


# ---------------------------------------------------------------------------
# TemporalPipeline: serving, billing, reset, calibration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pipe_setup():
    prog = networks.mnist5()
    art = _artifact(prog, seed=1)
    io = prog.instrs[0]
    trace = video_trace((io.height, io.width, io.in_channels), 6,
                        streams=4, seed=3, change_rate=0.3,
                        levels=2 ** io.bits)
    plan = interpreter.compile_plan(prog)
    flat = trace.frames.reshape((-1,) + trace.frames.shape[2:])
    _, oracle = plan.forward(interpreter.ensure_packed(art), flat,
                             interpret=True)
    return prog, art, trace, np.asarray(oracle)


def _serve(prog, art, trace, **kw):
    srv = ChipServer({"m": prog}, {"m": art}, batch=trace.streams,
                     interpret=True)
    pipe = tmp.TemporalPipeline(srv, "m", rb=1, **kw)
    for t in range(len(trace)):
        for s in range(trace.streams):
            pipe.submit(trace.frames[t, s])
    return srv, pipe, pipe.drain()


def test_pipeline_agreement_and_billing(pipe_setup):
    """At threshold 1 (skip only bit-identical packed frames) the gated
    labels equal ungated inference exactly; the server ledger bills only
    computed slots and stays consistent; the temporal report accounts
    every frame."""
    prog, art, trace, oracle = pipe_setup
    srv, pipe, res = _serve(prog, art, trace, threshold=1.0)
    got = np.array([r.label for r in sorted(res, key=lambda r: r.rid)])
    assert np.array_equal(got, oracle)
    n_frames = len(trace) * trace.streams
    assert pipe.frames == n_frames
    assert pipe.computed + pipe.skipped == n_frames
    # the trace's pixel-exact changed mask lower-bounds nothing — it IS
    # the compute set at threshold 1 (identical pixels <=> identical
    # packed codes <=> delta 0)
    assert pipe.computed == int(trace.changed.sum())
    stats = srv.stats()     # serve_report asserts billed == served+padded
    assert stats.served["m"] == pipe.computed
    rep = pipe.report()
    assert rep.frames == n_frames
    assert rep.skipped == pipe.skipped
    assert rep.skip_ratio == pytest.approx(pipe.skip_ratio)
    assert rep.uj_per_frame < rep.uj_per_frame_ungated
    assert rep.savings == pytest.approx(
        rep.uj_per_frame_ungated / rep.uj_per_frame)
    # per-result metadata is consistent
    assert sum(r.computed for r in res) == pipe.computed
    assert all(r.delta == 0 for r in res if not r.computed)


def test_pipeline_gate_off_matches_ungated(pipe_setup):
    """-inf threshold recomputes every frame: zero skips, served labels
    ungated, report degenerates to the ungated bill plus delta toll."""
    prog, art, trace, oracle = pipe_setup
    _, pipe, res = _serve(prog, art, trace, threshold=float("-inf"))
    got = np.array([r.label for r in sorted(res, key=lambda r: r.rid)])
    assert np.array_equal(got, oracle)
    assert pipe.skipped == 0
    assert pipe.report().skip_ratio == 0.0


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_pipeline_drain_answers_a_second_lane(pipe_setup, prefetch):
    """The temporal drain serves the server's other lanes until nothing
    is owed: with a pipeline the step that answers one of their
    dispatches may launch the last one and empty the queue, and that
    dispatch is still answered."""
    prog, art, trace, oracle = pipe_setup
    other = networks.mnist5(classes=7)
    oart = _artifact(other, seed=9)
    batch = trace.streams
    srv = ChipServer({"m": prog, "other": other}, {"m": art, "other": oart},
                     batch=batch, interpret=True, prefetch=prefetch)
    pipe = tmp.TemporalPipeline(srv, "m", threshold=1.0, rb=1)
    for t in range(2):
        for s in range(trace.streams):
            pipe.submit(trace.frames[t, s])
    other_frames = _frames(other, 2 * batch, seed=8)  # two whole batches
    other_rids = srv.submit_many("other", other_frames)
    res = pipe.drain()
    got = np.array([r.label for r in sorted(res, key=lambda r: r.rid)])
    assert np.array_equal(got, oracle[:2 * trace.streams])
    plan = interpreter.compile_plan(other)
    _, want = plan.forward(interpreter.ensure_packed(oart), other_frames,
                           interpret=True)
    by_rid = {r.rid: r.label for r in pipe.other_results}
    assert sorted(by_rid) == other_rids
    np.testing.assert_array_equal([by_rid[r] for r in other_rids],
                                  np.asarray(want))
    assert srv.owed() == 0


def test_pipeline_reset_recomputes(pipe_setup):
    """reset() drops the resident state: the next dispatch recomputes
    every stream even when frames did not change."""
    prog, art, trace, _ = pipe_setup
    srv = ChipServer({"m": prog}, {"m": art}, batch=trace.streams,
                     interpret=True)
    pipe = tmp.TemporalPipeline(srv, "m", threshold=1.0, rb=1)
    frame0 = trace.frames[0]
    for s in range(trace.streams):
        pipe.submit(frame0[s])
    pipe.drain()
    for s in range(trace.streams):      # identical frames: all skip
        pipe.submit(frame0[s])
    res = pipe.drain()
    assert not any(r.computed for r in res)
    pipe.reset()
    for s in range(trace.streams):      # still identical, but state is gone
        pipe.submit(frame0[s])
    res = pipe.drain()
    assert all(r.computed for r in res)
    assert pipe.activity == 1.0


def test_pipeline_calibrate_adopts_threshold(pipe_setup):
    prog, art, trace, _ = pipe_setup
    srv = ChipServer({"m": prog}, {"m": art}, batch=trace.streams,
                     interpret=True)
    pipe = tmp.TemporalPipeline(srv, "m", rb=1)
    thr = pipe.calibrate(trace.frames, target_agreement=1.0)
    assert thr == pipe.threshold >= 1.0


def test_pipeline_validation():
    prog = networks.mnist5()
    art = _artifact(prog)
    srv = ChipServer({"m": prog}, {"m": art}, batch=2, interpret=True)
    with pytest.raises(KeyError):
        tmp.TemporalPipeline(srv, "nope")
    with pytest.raises(ValueError):
        tmp.TemporalPipeline(srv, "m", threshold=float("nan"))
    with pytest.raises(ValueError):
        tmp.TemporalPipeline(srv, "m", activity_alpha=0.0)


def test_family_lane_needs_operating_point_policy():
    fam = {n: _reg_prog(n) for n in networks.FAMILIES["cifar10"]}
    arts = {n: _artifact(p, seed=5) for n, p in fam.items()}
    srv = ChipServer(fam, arts, batch=2, interpret=True,
                     families={"cifar10": tuple(fam)}, policy="continuous")
    with pytest.raises(ValueError, match="OperatingPointPolicy"):
        tmp.TemporalPipeline(srv, "cifar10")


def test_activity_downshifts_quiet_scene():
    """A quiet activity signal downshifts the operating point one step
    below what budget and backlog alone would pick."""
    fam = {n: _reg_prog(n) for n in networks.FAMILIES["cifar10"]}
    arts = {n: _artifact(p, seed=5) for n, p in fam.items()}
    srv = ChipServer(fam, arts, batch=2, interpret=True,
                     families={"cifar10": tuple(fam)},
                     policy="operating-point")
    pol = srv.policy
    order = pol.variant_order("cifar10")
    busy = pol.choose("cifar10", 0, 2)
    assert busy == order[0]
    pol.set_activity("cifar10", 0.1)        # below activity_low
    quiet = pol.choose("cifar10", 0, 2)
    assert quiet == order[1]
    pol.set_activity("cifar10", 0.9)        # active again: back to the top
    assert pol.choose("cifar10", 0, 2) == order[0]
    with pytest.raises(KeyError):
        pol.set_activity("nope", 0.5)
    with pytest.raises(ValueError):
        pol.set_activity("cifar10", 1.5)


# ---------------------------------------------------------------------------
# energy.temporal_report
# ---------------------------------------------------------------------------

def test_temporal_report_arithmetic():
    prog = networks.mnist5()
    rep = energy.temporal_report(prog, frames=100, computed=25,
                                 computed_padded=5)
    full = energy.analyze_net(prog, energy.F_EMIN)
    full_uj = full.i2l_energy_per_inference * 1e6
    assert rep.skipped == 75 and rep.skip_ratio == pytest.approx(0.75)
    assert rep.full_uj == pytest.approx(full_uj)
    assert rep.delta_uj < full_uj           # the toll must undercut full
    assert rep.uj_per_frame == pytest.approx(
        rep.delta_uj + 30 * full_uj / 100)
    assert rep.uj_per_frame < rep.uj_per_frame_ungated == pytest.approx(
        full_uj)
    assert rep.savings == pytest.approx(
        rep.uj_per_frame_ungated / rep.uj_per_frame)
    with pytest.raises(ValueError):
        energy.temporal_report(prog, frames=10, computed=11)
    with pytest.raises(ValueError):
        energy.temporal_report(prog, frames=10, computed=5,
                               computed_padded=-1)


# ---------------------------------------------------------------------------
# video_trace content generation
# ---------------------------------------------------------------------------

def test_video_trace_deterministic_and_changed_mask():
    a = video_trace((8, 8, 1), 10, streams=3, seed=7, change_rate=0.4,
                    scene_change_every=4, levels=16)
    b = video_trace((8, 8, 1), 10, streams=3, seed=7, change_rate=0.4,
                    scene_change_every=4, levels=16)
    assert np.array_equal(a.frames, b.frames)
    assert np.array_equal(a.changed, b.changed)
    assert a.frames.shape == (10, 3, 8, 8, 1)
    assert a.frames.min() >= 0 and a.frames.max() < 16
    # the changed mask is pixel-exact ground truth
    for t in range(1, 10):
        for s in range(3):
            assert a.changed[t, s] == (
                not np.array_equal(a.frames[t, s], a.frames[t - 1, s]))
    assert a.changed[0].all()               # first frames always "change"
    assert 0.0 < a.change_ratio < 1.0
    c = video_trace((8, 8, 1), 10, streams=3, seed=8, change_rate=0.0)
    assert not c.changed[1:].any()          # static scene stays static


# ---------------------------------------------------------------------------
# Threshold calibration
# ---------------------------------------------------------------------------

def test_simulate_gate_reference_rule():
    """The host simulator's reference advances only on recompute."""
    packed = np.zeros((4, 1, 1, 1, 1), np.uint32)
    packed[1] = 3        # 2 bits away from frame 0
    packed[2] = 3        # identical to frame 1
    packed[3] = 0        # back to frame 0's code, 2 bits from frame 2
    rec, ref = tmp.simulate_gate(packed, 2.0)
    assert rec[:, 0].tolist() == [True, True, False, True]
    assert ref[:, 0].tolist() == [0, 1, 1, 3]


def test_calibrate_threshold_meets_agreement(pipe_setup):
    prog, art, trace, oracle = pipe_setup
    thr = tmp.calibrate_delta_threshold(trace.frames, 0.95, program=prog,
                                        artifact=art, interpret=True)
    _, packed = tmp._packed_streams(trace.frames, prog)
    _, ref = tmp.simulate_gate(packed, thr)
    o = oracle.reshape(len(trace), trace.streams)
    emitted = o[ref, np.arange(trace.streams)[None, :]]
    assert (emitted == o).mean() >= 0.95
    # a perfect target still terminates (threshold 1 is always exact)
    thr1 = tmp.calibrate_delta_threshold(trace.frames, 1.0, program=prog,
                                         artifact=art, interpret=True)
    assert thr1 >= 1.0
    with pytest.raises(ValueError):
        tmp.calibrate_delta_threshold(trace.frames, 0.0, program=prog,
                                      artifact=art)


def test_threshold_for_skip(pipe_setup):
    prog, _, trace, _ = pipe_setup
    thr = tmp.threshold_for_skip(trace.frames, 0.3, program=prog)
    _, packed = tmp._packed_streams(trace.frames, prog)
    rec, _ = tmp.simulate_gate(packed, thr)
    assert 1.0 - rec.mean() >= 0.3
    with pytest.raises(ValueError, match="unreachable"):
        # cold frames always compute: skip ratio can't hit 0.99 in 6 steps
        tmp.threshold_for_skip(trace.frames, 0.99, program=prog)
    with pytest.raises(ValueError):
        tmp.threshold_for_skip(trace.frames, 1.0, program=prog)
