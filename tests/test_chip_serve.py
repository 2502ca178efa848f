"""Chip-tier serving: equivalence + property suite.

Locks down the serving subsystem three ways:

1. **Equivalence** — for every ``networks.REGISTRY`` program, labels and
   logits served through :class:`ChipServer` (static batches, padding,
   queue scheduling) are bit-exact vs the offline ``InferencePlan``
   forward over the same frames — on 1 device AND on a
   ``jax.device_count()``-device serving mesh (run CI with
   ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to make the
   mesh path a real 4-way frame scatter; on a plain CPU host it degrades
   to 1 device and must still be bit-exact).
2. **Scheduler properties** (hypothesis) — exactly-once delivery,
   per-program FIFO order, single-program batches, and round-robin
   fairness (no lane starves while backlogged) under random submission /
   dispatch interleavings.
3. **Billing** — padding slots are billed as burned energy, and the
   multi-program chip bill composes per-program NetReports sanely.
"""

import random
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.chip import energy, interpreter, networks
from repro.distributed import sharding
from repro.serving import (ChipServer, FrameQueue, FrameRequest,
                           bursty_trace)


# ---------------------------------------------------------------------------
# Helpers / fixtures
# ---------------------------------------------------------------------------

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
    logits, labels = plan.forward(packed, jnp.asarray(frames),
                                  interpret=True)
    return np.asarray(logits), np.asarray(labels)


@pytest.fixture(scope="module")
def mnist_setup():
    program = networks.mnist5()
    packed = _artifact(program, seed=3)
    frames = _frames(program, 9, seed=11)
    logits, labels = _offline(program, packed, frames)
    return program, packed, frames, logits, labels


# ---------------------------------------------------------------------------
# 1. Equivalence: served == offline, single- and multi-device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(networks.REGISTRY))
def test_served_bit_exact_vs_offline_plan(name):
    """Every registry program: ChipServer (static batch 2, with one padded
    slot) serves bit-identical labels/logits to the offline plan — through
    the plain path and through a jax.device_count()-device serving mesh."""
    program = networks.REGISTRY[name]()
    packed = _artifact(program)
    frames = _frames(program, 3, seed=7)          # 3 % 2 -> padding too
    logits_ref, labels_ref = _offline(program, packed, frames)

    mesh = sharding.serve_mesh()
    ndev = mesh.devices.size
    for m, batch in ((None, 2), (mesh, 2 * ndev)):
        server = ChipServer({name: program}, {name: packed},
                            batch=batch, mesh=m, interpret=True)
        rids = server.submit_many(name, frames)
        results = server.drain()
        assert [r.rid for r in results] == rids   # arrival order preserved
        np.testing.assert_array_equal(
            np.array([r.label for r in results]), labels_ref)
        np.testing.assert_array_equal(
            np.stack([r.logits for r in results]), logits_ref)
        assert server.queue.pending() == 0


def test_sharded_server_matches_unsharded(mnist_setup):
    """Mesh path vs plain path on the same artifact: identical results,
    whatever jax.device_count() is (1 on a plain CPU host, 4 in CI)."""
    program, packed, frames, logits_ref, labels_ref = mnist_setup
    mesh = sharding.serve_mesh()
    batch = 2 * mesh.devices.size
    plain = ChipServer({"m": program}, {"m": packed}, batch=batch,
                       interpret=True)
    shard = ChipServer({"m": program}, {"m": packed}, batch=batch,
                       mesh=mesh, interpret=True)
    for server in (plain, shard):
        server.submit_many("m", frames)
    res_p, res_s = plain.drain(), shard.drain()
    assert [r.label for r in res_p] == [r.label for r in res_s]
    np.testing.assert_array_equal(np.stack([r.logits for r in res_p]),
                                  np.stack([r.logits for r in res_s]))
    np.testing.assert_array_equal(
        np.array([r.label for r in res_s]), labels_ref)


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_donated_frames_serve_fn_matches(mnist_setup):
    """The donated/streamed-buffer entry point is numerically identical
    (donation is a no-op on backends without buffer reuse — CPU warns)."""
    program, packed, frames, logits_ref, labels_ref = mnist_setup
    plan = interpreter.compile_plan(program)
    fn = plan.make_serve_fn(donate_frames=True, interpret=True)
    logits, labels = fn(packed, jnp.asarray(frames))
    np.testing.assert_array_equal(np.asarray(logits), logits_ref)
    np.testing.assert_array_equal(np.asarray(labels), labels_ref)


def test_scatter_frames_divisibility():
    """Indivisible batches are rejected on a multi-device mesh; any batch
    divides a 1-device mesh and scatters as a plain placement."""
    mesh = sharding.serve_mesh()
    n = mesh.devices.size
    if n > 1:
        with pytest.raises(ValueError, match="not divisible"):
            sharding.scatter_frames(mesh, jnp.zeros((n + 1, 4, 4, 1)))
    placed = sharding.scatter_frames(mesh, jnp.zeros((2 * n, 4, 4, 1)))
    assert placed.sharding.mesh.axis_names == (sharding.SERVE_AXIS,)


def test_server_guards():
    program = networks.mnist5()
    packed = _artifact(program)
    with pytest.raises(ValueError, match="!="):
        ChipServer({"a": program}, {"b": packed})
    with pytest.raises(ValueError, match="batch"):
        ChipServer({"a": program}, {"a": packed}, batch=0)
    server = ChipServer({"a": program}, {"a": packed}, batch=2,
                        interpret=True)
    with pytest.raises(ValueError, match="shape"):
        server.submit("a", np.zeros((3, 3, 1), np.int32))
    with pytest.raises(KeyError, match="not resident"):
        server.submit("ghost", np.zeros((14, 14, 1), np.int32))
    with pytest.raises(KeyError):
        server.queue.submit(FrameRequest(rid=0, program="ghost", frame=None))


def test_prefetch_serves_identical_results(multi_setup):
    """prefetch=True (stage batch N+1 while N runs) returns the exact
    result stream of the synchronous server: same labels/logits, same
    dispatch indices, same padding bill — the overlap is pure host-side
    pipelining, dispatch order never changes."""
    progs, arts = multi_setup
    frames = {n: _frames(p, 5, seed=30 + i)
              for i, (n, p) in enumerate(progs.items())}
    runs = {}
    for prefetch in (False, True):
        server = ChipServer(progs, arts, batch=2, interpret=True,
                            prefetch=prefetch)
        for i in range(5):
            for n in progs:
                server.submit(n, frames[n][i])
        runs[prefetch] = (server.drain(), server.stats())
    (res_s, stats_s), (res_p, stats_p) = runs[False], runs[True]
    assert [(r.rid, r.program, r.label, r.dispatch) for r in res_s] == \
           [(r.rid, r.program, r.label, r.dispatch) for r in res_p]
    for a, b in zip(res_s, res_p):
        np.testing.assert_array_equal(a.logits, b.logits)
    assert stats_s.served == stats_p.served
    assert stats_s.padded == stats_p.padded
    assert stats_s.dispatches == stats_p.dispatches


def test_prefetch_interleaved_with_submission(mnist_setup):
    """step()-at-a-time with new frames arriving between steps: every
    frame is still served exactly once, in arrival order per program."""
    program, packed, frames, _, labels_ref = mnist_setup
    server = ChipServer({"m": program}, {"m": packed}, batch=2,
                        interpret=True, prefetch=True)
    got = []
    for i in range(len(frames)):
        server.submit("m", frames[i])
        got.extend(server.step())
    got.extend(server.drain())
    assert [r.rid for r in got] == list(range(len(frames)))
    np.testing.assert_array_equal(np.array([r.label for r in got]),
                                  labels_ref)


def test_prefetch_depth_k_serves_identical_results(multi_setup):
    """prefetch=k for any depth (incl. deeper than the queue) returns the
    exact synchronous result stream — depth-k pipelining is pure overlap,
    dispatch order and billing never change — and runs no thread of its
    own: no ``serve-fetch`` thread exists at any depth."""
    progs, arts = multi_setup
    frames = {n: _frames(p, 7, seed=40 + i)
              for i, (n, p) in enumerate(progs.items())}
    runs = {}
    for depth in (0, 1, 2, 3, 16):
        server = ChipServer(progs, arts, batch=2, interpret=True,
                            prefetch=depth)
        for i in range(7):
            for n in progs:
                server.submit(n, frames[n][i])
        results = server.step()
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("serve-fetch")], depth
        results += server.drain()
        stats = server.stats()
        runs[depth] = ([(r.rid, r.program, r.label, r.dispatch)
                        for r in results],
                       stats.served, stats.padded, stats.dispatches)
    first = runs[0]
    for depth, run in runs.items():
        assert run == first, f"depth {depth} diverged"


def test_prefetch_depth_k_interleaved_with_submission(mnist_setup):
    """Depth-3 pipeline with frames arriving between steps: every frame
    served exactly once, in arrival order."""
    program, packed, frames, _, labels_ref = mnist_setup
    server = ChipServer({"m": program}, {"m": packed}, batch=2,
                        interpret=True, prefetch=3)
    got = []
    for i in range(len(frames)):
        server.submit("m", frames[i])
        got.extend(server.step())
    got.extend(server.drain())
    assert [r.rid for r in got] == list(range(len(frames)))
    np.testing.assert_array_equal(np.array([r.label for r in got]),
                                  labels_ref)


def test_prefetch_defaults_to_depth_one():
    """A server pipelines one dispatch ahead unless told otherwise;
    ``prefetch=0`` keeps every dispatch synchronous."""
    program = networks.mnist5()
    packed = _artifact(program)
    assert ChipServer({"m": program}, {"m": packed},
                      interpret=True).prefetch == 1
    sync = ChipServer({"m": program}, {"m": packed}, interpret=True,
                      prefetch=0)
    assert sync.prefetch == 0 and sync.executor.prefetch == 0


def test_prefetch_bool_is_depth_one():
    """Back-compat: prefetch=True means a depth-1 pipeline."""
    program = networks.mnist5()
    packed = _artifact(program)
    server = ChipServer({"m": program}, {"m": packed}, batch=2,
                        interpret=True, prefetch=True)
    assert server.prefetch == 1
    with pytest.raises(ValueError, match="prefetch"):
        ChipServer({"m": program}, {"m": packed}, prefetch=-1)


def test_megakernel_server_matches_staged(mnist_setup):
    """megakernel=True serving (weight image resident, zero inter-layer
    HBM) is bit-exact vs the staged server — with and without prefetch."""
    program, packed, frames, logits_ref, labels_ref = mnist_setup
    for prefetch in (False, True):
        server = ChipServer({"m": program}, {"m": packed}, batch=2,
                            interpret=True, megakernel=True,
                            prefetch=prefetch)
        server.submit_many("m", frames)
        results = server.drain()
        np.testing.assert_array_equal(
            np.array([r.label for r in results]), labels_ref)
        np.testing.assert_array_equal(
            np.stack([r.logits for r in results]), logits_ref)


# ---------------------------------------------------------------------------
# 2. Scheduler properties (pure Python, no device work)
# ---------------------------------------------------------------------------

def _simulate(n_lanes, n_reqs, capacity, seed):
    """Random interleaving of submissions and dispatches; returns the
    dispatch trace [(lane, [rids], pending_before_dict)] and all rids."""
    rng = random.Random(seed)
    lanes = [f"p{i}" for i in range(n_lanes)]
    q = FrameQueue(lanes)
    rid = 0
    trace = []
    to_submit = n_reqs
    while to_submit or len(q):
        if to_submit and (rng.random() < 0.6 or not len(q)):
            lane = rng.choice(lanes)
            q.submit(FrameRequest(rid=rid, program=lane, frame=None))
            rid += 1
            to_submit -= 1
        else:
            before = {l: q.pending(l) for l in lanes}
            got = q.next_batch(capacity)
            assert got is not None
            name, reqs = got
            trace.append((name, [r.rid for r in reqs], before))
    assert q.next_batch(capacity) is None         # drained
    return trace, list(range(rid))


@settings(max_examples=25, deadline=None)
@given(n_lanes=st.integers(1, 4), n_reqs=st.integers(0, 40),
       capacity=st.integers(1, 5), seed=st.integers(0, 2 ** 16))
def test_queue_drain_exactly_once_property(n_lanes, n_reqs, capacity, seed):
    """Any submission/dispatch interleaving: every request is served
    exactly once, batches are single-program and <= capacity, and each
    lane's rids come out in FIFO order."""
    trace, all_rids = _simulate(n_lanes, n_reqs, capacity, seed)
    served = [r for (_, rids, _) in trace for r in rids]
    assert sorted(served) == all_rids             # exactly once, none lost
    assert all(len(rids) <= capacity and rids == sorted(rids)
               for (_, rids, _) in trace)
    per_lane = {}
    for name, rids, _ in trace:
        per_lane.setdefault(name, []).extend(rids)
    for name, rids in per_lane.items():
        assert rids == sorted(rids)               # per-lane FIFO


@settings(max_examples=25, deadline=None)
@given(n_lanes=st.integers(2, 4), n_reqs=st.integers(8, 40),
       capacity=st.integers(1, 3), seed=st.integers(0, 2 ** 16))
def test_round_robin_fairness_property(n_lanes, n_reqs, capacity, seed):
    """No starvation: a lane that was non-empty before some dispatch is
    itself dispatched within the next n_lanes dispatches (or the trace
    ends first) — the round-robin pointer can't pass over a waiting lane."""
    trace, _ = _simulate(n_lanes, n_reqs, capacity, seed)
    for i, (_, _, before) in enumerate(trace):
        waiting = [l for l, p in before.items() if p > 0]
        window = [name for (name, _, _) in trace[i:i + n_lanes]]
        for lane in waiting:
            if len(window) == n_lanes:            # full window available
                assert lane in window, (
                    f"lane {lane} waited non-empty through dispatches "
                    f"{i}..{i + n_lanes - 1}: {window}")


def test_round_robin_cycles_under_backlog():
    """All lanes backlogged -> dispatch order is a strict rotation."""
    lanes = ["a", "b", "c"]
    q = FrameQueue(lanes)
    for rid in range(12):
        q.submit(FrameRequest(rid=rid, program=lanes[rid % 3], frame=None))
    order = [q.next_batch(1)[0] for _ in range(12)]
    assert order == ["a", "b", "c"] * 4


def test_queue_skips_empty_lanes():
    q = FrameQueue(["a", "b"])
    q.submit(FrameRequest(rid=0, program="b", frame=None))
    name, reqs = q.next_batch(4)
    assert name == "b" and [r.rid for r in reqs] == [0]
    assert q.next_batch(4) is None


# ---------------------------------------------------------------------------
# 2b. FrameQueue under bursty admission (MMPP traces, variable-size takes)
# ---------------------------------------------------------------------------

def _bursty_simulate(lanes, n_reqs, seed, *, weights=None, max_take=5):
    """Admission driven by a seeded MMPP arrival trace (lane tags and
    timestamps from ``bursty_trace``), dispatches at a random VARIABLE
    size each time — the continuous-batching admission pattern.  Returns
    the dispatch trace [(lane, [rids], pending_before)]."""
    arr = bursty_trace(lanes, rate=200.0, n=n_reqs, seed=seed,
                       weights=weights)
    rng = random.Random(seed)
    q = FrameQueue(lanes)
    i = 0
    trace = []
    while i < len(arr) or len(q):
        if i < len(arr) and (rng.random() < 0.6 or not len(q)):
            q.submit(FrameRequest(rid=i, program=arr.lane[i], frame=None,
                                  t_submit=1.0 + float(arr.t[i])))
            i += 1
        else:
            before = {l: q.pending(l) for l in lanes}
            got = q.next_batch(rng.randint(1, max_take))
            assert got is not None
            trace.append((got[0], [r.rid for r in got[1]], before))
    assert q.next_batch(max_take) is None             # drained
    return arr, trace


@settings(max_examples=20, deadline=None)
@given(n_lanes=st.integers(2, 4), n_reqs=st.integers(8, 48),
       seed=st.integers(0, 2 ** 16))
def test_queue_fifo_under_bursty_variable_size_dispatches(n_lanes, n_reqs,
                                                          seed):
    """Bursty admission + variable-size dispatches: every request served
    exactly once and each lane's frames leave in exactly their arrival
    order — FIFO survives the dispatch size changing under the window."""
    lanes = [f"p{i}" for i in range(n_lanes)]
    arr, trace = _bursty_simulate(lanes, n_reqs, seed)
    served = [r for (_, rids, _) in trace for r in rids]
    assert sorted(served) == list(range(n_reqs))      # exactly once
    per_lane = {}
    for name, rids, _ in trace:
        per_lane.setdefault(name, []).extend(rids)
    for name, rids in per_lane.items():
        want = [j for j in range(n_reqs) if arr.lane[j] == name]
        assert rids == want                           # per-lane FIFO


@settings(max_examples=20, deadline=None)
@given(n_reqs=st.integers(16, 60), seed=st.integers(0, 2 ** 16))
def test_trickle_lane_never_starves_behind_burst_lane(n_reqs, seed):
    """One high-rate lane (92% of arrivals) and one trickle lane: the
    round-robin pointer still serves the trickle lane within 2 dispatches
    of it becoming backlogged, whatever the burst state does."""
    arr, trace = _bursty_simulate(["burst", "trickle"], n_reqs, seed,
                                  weights=[0.92, 0.08])
    n_lanes = 2
    for i, (_, _, before) in enumerate(trace):
        window = [name for (name, _, _) in trace[i:i + n_lanes]]
        if len(window) < n_lanes:
            continue
        for lane, pending in before.items():
            if pending > 0:
                assert lane in window, (
                    f"lane {lane} ({pending} pending) starved at dispatch "
                    f"{i}: window {window}")


@settings(max_examples=20, deadline=None)
@given(n_lanes=st.integers(1, 4), cap=st.integers(2, 6),
       n_reqs=st.integers(4, 40), seed=st.integers(0, 2 ** 16))
def test_drain_completeness_with_ragged_final_batches(n_lanes, cap, n_reqs,
                                                      seed):
    """Submit a whole bursty trace, then drain at a fixed capacity: every
    lane empties completely, and a lane whose count doesn't divide the
    capacity ends on exactly its ragged remainder — no frame is stranded
    waiting for a full batch."""
    lanes = [f"p{i}" for i in range(n_lanes)]
    arr = bursty_trace(lanes, rate=200.0, n=n_reqs, seed=seed)
    q = FrameQueue(lanes)
    for i in range(len(arr)):
        q.submit(FrameRequest(rid=i, program=arr.lane[i], frame=None,
                              t_submit=1.0 + float(arr.t[i])))
    sizes = {}
    served = []
    while True:
        got = q.next_batch(cap)
        if got is None:
            break
        name, reqs = got
        sizes.setdefault(name, []).append(len(reqs))
        served.extend(r.rid for r in reqs)
    assert sorted(served) == list(range(n_reqs))      # nothing stranded
    assert len(q) == 0
    counts = {l: sum(1 for x in arr.lane if x == l) for l in lanes}
    for lane, batch_sizes in sizes.items():
        assert all(s == cap for s in batch_sizes[:-1])
        rem = counts[lane] % cap
        assert batch_sizes[-1] == (rem if rem else cap)   # ragged tail


# ---------------------------------------------------------------------------
# 3. Multi-program batching + billing
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def multi_setup():
    """Two distinct resident programs sharing the mnist5 topology family:
    the 10-class classifier and a 2-class wake-up detector."""
    progs = {"mnist5": networks.mnist5(),
             "wake": networks.mnist5(classes=2)}
    arts = {n: _artifact(p, seed=i) for i, (n, p) in enumerate(progs.items())}
    return progs, arts


def test_multi_program_routing_bit_exact(multi_setup):
    """Frames interleaved across resident programs are each served by
    *their* program's plan, bit-exact vs that program's offline forward,
    and every dispatch is single-program (the array runs one instruction
    stream at a time)."""
    progs, arts = multi_setup
    assert interpreter.compile_plan(progs["mnist5"]) is not \
        interpreter.compile_plan(progs["wake"])   # genuinely two plans
    frames = {n: _frames(p, 5, seed=20 + i)
              for i, (n, p) in enumerate(progs.items())}
    oracle = {n: _offline(progs[n], arts[n], frames[n]) for n in progs}

    server = ChipServer(progs, arts, batch=2, interpret=True)
    for i in range(5):                            # interleave submissions
        for n in progs:
            server.submit(n, frames[n][i])
    results = server.drain()

    assert len(results) == 10
    by_prog = {n: [r for r in results if r.program == n] for n in progs}
    for n in progs:
        got = sorted(by_prog[n], key=lambda r: r.rid)
        np.testing.assert_array_equal(np.array([r.label for r in got]),
                                      oracle[n][1])
        np.testing.assert_array_equal(np.stack([r.logits for r in got]),
                                      oracle[n][0])
    # single-program dispatches
    for d in range(max(r.dispatch for r in results) + 1):
        progs_in_d = {r.program for r in results if r.dispatch == d}
        assert len(progs_in_d) <= 1
    stats = server.stats()
    assert stats.served == {"mnist5": 5, "wake": 5}
    assert stats.dispatches == 6                  # ceil(5/2) per program
    assert stats.padded == {"mnist5": 1, "wake": 1}


def test_padding_billed_not_served(mnist_setup):
    """A 5-frame load on batch=4 burns 3 padding slots: they show up in
    the energy bill (µJ per *served* frame rises) but never in results."""
    program, packed, frames, _, labels_ref = mnist_setup
    server = ChipServer({"m": program}, {"m": packed}, batch=4,
                        interpret=True)
    server.submit_many("m", frames[:5])
    results = server.drain()
    assert len(results) == 5
    stats = server.stats()
    assert stats.served == {"m": 5} and stats.padded == {"m": 3}
    per_inf = stats.chip.reports["m"].i2l_energy_per_inference * 1e6
    assert stats.chip.uj_per_frame == pytest.approx(per_inf * 8 / 5)
    np.testing.assert_array_equal(np.array([r.label for r in results]),
                                  labels_ref[:5])


def test_serve_report_mix_composition():
    """Mixed-program bill: µJ/frame is the frame-weighted mean of the
    constituents and frames/s is their harmonic composition — so the mix
    always lands between the per-program figures."""
    progs = {"mnist5": networks.mnist5(), "face": networks.face_detector()}
    reps = {n: energy.analyze_net(p) for n, p in progs.items()}
    rep = energy.serve_report(progs, {"mnist5": 30, "face": 10})
    uj = {n: r.i2l_energy_per_inference * 1e6 for n, r in reps.items()}
    fps = {n: r.inferences_per_s for n, r in reps.items()}
    want_uj = (30 * uj["mnist5"] + 10 * uj["face"]) / 40
    want_fps = 40 / (30 / fps["mnist5"] + 10 / fps["face"])
    assert rep.uj_per_frame == pytest.approx(want_uj)
    assert rep.frames_per_s == pytest.approx(want_fps)
    assert min(uj.values()) <= rep.uj_per_frame <= max(uj.values())
    assert min(fps.values()) <= rep.frames_per_s <= max(fps.values())
    assert rep.total_frames == 40

    empty = energy.serve_report(progs, {})
    assert empty.uj_per_frame == 0.0 and empty.frames_per_s == 0.0


# ---------------------------------------------------------------------------
# 4. Continuous batching: ragged dispatch sizes stay bit-exact
# ---------------------------------------------------------------------------

_RAGGED_CACHE = {}


def _ragged_setup(name):
    """Per-program artifact/oracle cache so hypothesis examples reuse the
    compiled plan instead of rebuilding it per draw."""
    if name not in _RAGGED_CACHE:
        program = networks.REGISTRY[name]()
        packed = _artifact(program)
        frames = _frames(program, 8, seed=13)
        _RAGGED_CACHE[name] = (program, packed, frames,
                               _offline(program, packed, frames))
    return _RAGGED_CACHE[name]


@settings(max_examples=10, deadline=None)
@given(name=st.sampled_from(sorted(networks.REGISTRY)),
       chunks=st.lists(st.integers(1, 4), min_size=1, max_size=4))
def test_continuous_ragged_sizes_bit_exact_vs_offline(name, chunks):
    """The acceptance contract for variable-size dispatch: whatever
    ragged batch sizes the continuous window launches (1, 2, 3-padded-
    to-4, 4), every served label/logit row is bit-exact vs the offline
    forward, for every REGISTRY program.  Unstamped submissions carry no
    deadline, so each step() dispatches exactly the chunk submitted
    before it — the chunk sizes ARE the dispatch sizes (bucketed)."""
    program, packed, frames, (logits_ref, labels_ref) = _ragged_setup(name)
    server = ChipServer({name: program}, {name: packed}, batch=4,
                        interpret=True, policy="continuous")
    sent = 0
    results = []
    for c in chunks:
        take = min(c, len(frames) - sent)
        for _ in range(take):
            server.submit(name, frames[sent], t_submit=0.0)   # unstamped
            sent += 1
        if take:
            got = server.step()
            assert got, "unstamped frames must dispatch immediately"
            results.extend(got)
    results.extend(server.drain())

    assert [r.rid for r in results] == list(range(sent))  # FIFO survived
    np.testing.assert_array_equal(
        np.array([r.label for r in results]), labels_ref[:sent])
    np.testing.assert_array_equal(
        np.stack([r.logits for r in results]), logits_ref[:sent])
    # billing closes: served + padded == billed slots (stats() asserts
    # through energy.serve_report), and only bucket slack was padded
    stats = server.stats()
    assert stats.served == {name: sent}
    assert stats.padding_ratio < 1.0
