"""Serving telemetry (serving/telemetry.py): spans, per-step rows,
counters, and what the servers record into them.

* nesting: a span's self time is its duration less its children's;
* the per-step ring: one row per ``serve.step`` with each stage's self
  seconds, percentiles as numpy computes them, wrap-around and reset;
* the servers: a frozen ``VirtualClock`` records zeros, ``serve.compile``
  counts each new (variant, batch size) once, ``serve.ahead`` each
  launch made with a dispatch in flight, ``serve.ready`` at most once
  a dispatch, with its index, a fleet's kill records
  ``fleet.fail``/``fleet.replace`` under replica names, and a profiler
  trace holds the spans with their dispatch ids.
"""

import glob
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.chip import interpreter, networks
from repro.serving import ChipServer, VirtualClock, telemetry
from repro.serving.queue import FrameResult


class ManualClock:
    """A clock the test moves by hand."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def mnist():
    program = networks.mnist5()
    params = interpreter.init_params(jax.random.PRNGKey(3), program)
    packed = interpreter.fold_params(params, program, packed=True)
    io = program.instrs[0]
    frames = np.asarray(jax.random.randint(
        jax.random.PRNGKey(11), (16, io.height, io.width, io.in_channels),
        0, 2 ** io.bits))
    return program, packed, frames


def _step(p, clk, dispatch, stages):
    """One ``serve.step`` whose stages take ``stages[name]`` seconds."""
    with p.span("serve.step", dispatch=dispatch):
        for name, dt in stages.items():
            with p.span("serve." + name, dispatch=dispatch):
                clk.t += dt


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_nested_spans_give_self_time():
    rec = telemetry.Recorder()
    clk = ManualClock()
    p = rec.probe("r0", clk)
    with p.span("serve.step", dispatch=0):
        clk.t += 1.0
        with p.span("serve.select", dispatch=0):
            clk.t += 2.0
        with p.span("serve.wait", dispatch=0):
            clk.t += 5.0
        clk.t += 1.0
    spans = rec.snapshot()["spans"]
    assert spans["serve.step"]["seconds"] == pytest.approx(9.0)
    assert spans["serve.step"]["self_seconds"] == pytest.approx(2.0)
    assert spans["serve.wait"]["self_seconds"] == pytest.approx(5.0)
    assert spans["serve.select"]["replicas"] == {
        "r0": {"count": 1, "seconds": pytest.approx(2.0)}}
    rows = rec.rows()
    assert rows["step"].tolist() == [9.0] and rows["wait"].tolist() == [5.0]
    assert rows["select"].tolist() == [2.0]
    assert p.seconds["serve.step"] == pytest.approx(9.0)


def test_step_rows_and_percentiles_match_numpy():
    rec = telemetry.Recorder()
    clk = ManualClock()
    p = rec.probe("host3", clk)
    rng = np.random.default_rng(5)
    want = {s: rng.uniform(1e-5, 1e-2, 200) for s in
            ("select", "stack", "put", "launch", "wait", "fetch", "finish")}
    for i in range(200):
        _step(p, clk, i, {s: v[i] for s, v in want.items()})
    snap = rec.snapshot()["steps"]
    assert snap["rows"] == snap["written"] == 200
    step = sum(want.values())
    for col, v in list(want.items()) + [("step", step),
                                        ("host", step - want["wait"])]:
        p50, p95 = np.percentile(v, [50, 95])
        assert snap["p50"][col] == pytest.approx(p50, rel=1e-9), col
        assert snap["p95"][col] == pytest.approx(p95, rel=1e-9), col
    rows = rec.rows()
    assert rows["dispatch"].tolist() == list(range(200))
    assert set(rows["replica"]) == {"host3"}
    np.testing.assert_allclose(rows["put"], want["put"])


def test_ring_wraps_and_reset_zeroes():
    rec = telemetry.Recorder(rows=8)
    clk = ManualClock()
    p = rec.probe("r0", clk)
    for i in range(20):
        _step(p, clk, i, {"wait": float(i)})
    rows = rec.rows()
    assert rows["dispatch"].tolist() == list(range(12, 20))
    assert rows["wait"].tolist() == [float(i) for i in range(12, 20)]
    snap = rec.snapshot()
    assert snap["steps"]["rows"] == 8 and snap["steps"]["written"] == 20
    assert snap["steps"]["p50"]["wait"] == pytest.approx(15.5)
    rec.count("serve.compile", dispatch=3)
    rec.reset()
    snap = rec.snapshot()
    assert snap["spans"] == {} and snap["counters"] == {}
    assert snap["events"] == [] and snap["steps"]["rows"] == 0
    assert len(rec.rows()["dispatch"]) == 0
    _step(p, clk, 0, {"wait": 2.0})         # the probe records on
    snap = rec.snapshot()
    assert snap["spans"]["serve.wait"]["count"] == 1
    assert snap["steps"]["p50"]["wait"] == 2.0


def test_step_without_dispatch_writes_no_row():
    """A step that launched and finished nothing (an empty queue) adds
    to the span totals but not to the per-step rows."""
    rec = telemetry.Recorder()
    clk = ManualClock()
    p = rec.probe("r0", clk)
    _step(p, clk, 0, {"select": 1.0})
    snap = rec.snapshot()
    assert snap["spans"]["serve.step"]["count"] == 1
    assert snap["steps"]["rows"] == 0


def test_counter_events_keep_their_ids():
    rec = telemetry.Recorder()
    p = rec.probe("host1")
    p.count("serve.compile", dispatch=7, batch=64)
    p.count("serve.compile", dispatch=9, batch=32)
    rec.count("other", 5)
    snap = rec.snapshot()
    assert snap["counters"] == {"serve.compile": 2, "other": 5}
    assert snap["events"] == [
        {"dispatch": 7, "batch": 64, "replica": "host1",
         "name": "serve.compile"},
        {"dispatch": 9, "batch": 32, "replica": "host1",
         "name": "serve.compile"}]


# ---------------------------------------------------------------------------
# what the servers record
# ---------------------------------------------------------------------------

def test_frozen_virtual_clock_records_zero_durations(mnist):
    program, packed, frames = mnist
    rec = telemetry.Recorder()
    server = ChipServer({"m": program}, {"m": packed}, batch=4,
                        interpret=True, clock=VirtualClock(start=5.0),
                        telemetry=rec)
    for f in frames[:10]:
        server.submit("m", f)
    assert len(server.drain()) == 10
    snap = rec.snapshot()
    assert snap["spans"]["serve.step"]["count"] >= 3
    assert snap["spans"]["serve.submit"]["count"] == 10
    for name, tot in snap["spans"].items():
        assert tot["seconds"] == 0.0 and tot["self_seconds"] == 0.0, name
    assert snap["steps"]["rows"] == 3
    assert all(v == 0.0 for v in snap["steps"]["p95"].values())
    assert server.stats().host_wall_s == 0.0


@pytest.mark.parametrize("prefetch", [0, 1])
def test_server_books_come_from_its_spans(mnist, prefetch):
    """``host_wall_s`` is the sum of the server's ``serve.step`` spans
    since its last reset; the recorder keeps its totals across it.  A
    row's ``dispatch`` is the step's entry index: synchronous steps
    launch 0 and 1; at depth 1 the first step launches both and the
    second, entered at index 2, only waits on 1."""
    program, packed, frames = mnist
    rec = telemetry.Recorder()
    server = ChipServer({"m": program}, {"m": packed}, batch=4,
                        interpret=True, telemetry=rec, replica="cam7",
                        prefetch=prefetch)
    for f in frames[:8]:
        server.submit("m", f)
    server.drain()
    tot = rec.snapshot()["spans"]["serve.step"]["replicas"]["cam7"]
    assert server.stats().host_wall_s == pytest.approx(tot["seconds"])
    assert server.stats().host_wall_s > 0.0
    server.reset_stats()
    assert server.stats().host_wall_s == 0.0
    assert rec.snapshot()["spans"]["serve.step"]["count"] == tot["count"]
    rows = rec.rows()
    assert set(rows["replica"]) == {"cam7"}
    assert rows["dispatch"].tolist() == ([0, 1] if prefetch == 0
                                         else [0, 2])
    assert (rows["wait"] > 0).all()
    launched = rows["launch"] > 0            # depth 1: the second step
    assert launched.tolist() == [True, prefetch == 0]   # launches none
    assert (rows["queue_wait"][launched] > 0).all()
    assert (rows["step"] >= rows["wait"] + rows["launch"]).all()


def test_compile_counts_once_per_new_shape(mnist):
    """Each (variant, batch size) counts on its first launch only, with
    the dispatch that launched it."""
    program, packed, frames = mnist
    rec = telemetry.Recorder()
    server = ChipServer({"a": program, "b": program},
                        {"a": packed, "b": packed}, batch=4,
                        interpret=True, telemetry=rec)
    for _ in range(3):
        for f in frames[:8]:
            server.submit("a", f)
            server.submit("b", f)
        server.drain()
    snap = rec.snapshot()
    assert snap["counters"]["serve.compile"] == 2
    compiles = [e for e in snap["events"] if e["name"] == "serve.compile"]
    assert sorted(e["variants"] for e in compiles) == ["a", "b"]
    assert sorted(e["dispatch"] for e in compiles) == [0, 1]
    assert all(e["batch"] == 4 for e in compiles)


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_ahead_counts_overlapped_launches(mnist, prefetch):
    """``serve.ahead`` counts launches made while an earlier dispatch was
    in flight: on a full backlog every dispatch but the first at any
    depth >= 1, none when synchronous.  It keeps no events."""
    program, packed, frames = mnist
    rec = telemetry.Recorder()
    server = ChipServer({"m": program}, {"m": packed}, batch=4,
                        interpret=True, telemetry=rec, prefetch=prefetch)
    for f in frames:
        server.submit("m", f)
    assert len(server.drain()) == len(frames)
    snap = rec.snapshot()
    dispatches = snap["spans"]["serve.launch"]["count"]
    assert dispatches == server.stats().dispatches == 4
    assert snap["counters"].get("serve.ahead", 0) == (
        dispatches - 1 if prefetch else 0)
    assert all(e["name"] != "serve.ahead" for e in snap["events"])


@pytest.mark.parametrize("prefetch", [0, 1])
def test_ready_counts_dispatches_computed_before_finish(mnist, prefetch):
    """``serve.ready`` counts a dispatch whose outputs were all computed
    when the host came to finish it, with its dispatch index: at most
    once a dispatch, so never more often than ``serve.launch``."""
    program, packed, frames = mnist
    rec = telemetry.Recorder()
    server = ChipServer({"m": program}, {"m": packed}, batch=4,
                        interpret=True, telemetry=rec, prefetch=prefetch)
    for f in frames:
        server.submit("m", f)
    assert len(server.drain()) == len(frames)
    snap = rec.snapshot()
    launches = snap["spans"]["serve.launch"]["count"]
    ready = [e for e in snap["events"] if e["name"] == "serve.ready"]
    assert snap["counters"].get("serve.ready", 0) == len(ready) <= launches
    ids = [e["dispatch"] for e in ready]
    assert len(set(ids)) == len(ids)
    assert set(ids) <= set(range(launches))
    assert all(e["replica"] == "server" for e in ready)


def test_latency_trace_expands_compact_rows(mnist):
    """The latency books hold one row per lane of a dispatch and expand
    to the per-frame records when read: a two-lane (composite) result
    list, an unstamped frame skipped, stats percentiles over the same
    latencies."""
    program, packed, _ = mnist
    server = ChipServer({"a": program, "b": program},
                        {"a": packed, "b": packed}, batch=4,
                        interpret=True, telemetry=telemetry.Recorder())
    lg = np.zeros(10, np.float32)
    results = [FrameResult(rid=r, program=lane, label=0, logits=lg,
                           dispatch=3, variant=lane, t_submit=ts,
                           t_done=2.0)
               for r, lane, ts in [(0, "a", 1.0), (1, "a", 0.0),
                                   (2, "b", 1.5), (3, "b", 1.25)]]
    server._book(results)
    trace = server.latency_trace()
    assert [t["rid"] for t in trace] == [0, 2, 3]
    assert [t["lane"] for t in trace] == ["a", "b", "b"]
    assert trace[0] == dict(rid=0, lane="a", variant="a", dispatch=3,
                            t_submit=1.0, t_done=2.0, latency_ms=1000.0)
    st = server.stats()
    assert st.p50_ms == pytest.approx(750.0)
    assert st.p99_ms == pytest.approx(np.percentile([1.0, 0.5, 0.75], 99)
                                      * 1e3)


@pytest.mark.parametrize("prefetch", [0, 1])
def test_profiler_trace_holds_serve_spans_with_dispatch_ids(mnist, tmp_path,
                                                            prefetch):
    """Each dispatch's spans carry its index.  At depth 1 a step holds
    the launch of N+1 and the wait of N, so step ids follow the entry
    index while each dispatch still has one launch and one wait."""
    from jax.profiler import ProfileData
    program, packed, frames = mnist
    rec = telemetry.Recorder()
    server = ChipServer({"m": program}, {"m": packed}, batch=4,
                        interpret=True, replica="traced0", telemetry=rec,
                        prefetch=prefetch)
    for f in frames[:4]:
        server.submit("m", f)
    server.drain()                       # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for f in frames[:12]:
            server.submit("m", f)
        server.drain()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    st = {k: v for k, v in ev.stats}
                    if st.get("replica") == "traced0":
                        seen.setdefault(ev.name, []).append(
                            int(st["dispatch"]))
    assert sorted(seen["serve.wait"]) == [1, 2, 3]   # one per dispatch
    assert sorted(seen["serve.launch"]) == [1, 2, 3]
    if prefetch == 0:
        assert set(seen["serve.step"]) >= {1, 2, 3}
    else:                 # launch 1 and 2, wait 1; launch 3, wait 2; ...
        assert set(seen["serve.step"]) >= {1, 3, 4}
    rows = rec.rows()
    assert (rows["step"] >= rows["wait"]).all()


_FLEET = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.core.chip import interpreter, networks
    from repro.serving import FaultInjector, ServeFleet, telemetry

    program = networks.mnist5()
    params = interpreter.init_params(jax.random.PRNGKey(3), program)
    packed = interpreter.fold_params(params, program, packed=True)
    io = program.instrs[0]
    frames = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (40, io.height, io.width, io.in_channels),
        0, 2 ** io.bits))
    rec = telemetry.Recorder()
    fleet = ServeFleet({"m": program}, {"m": packed}, replicas=4, batch=4,
                       devices=jax.devices()[:4], interpret=True,
                       injector=FaultInjector("host0", after_served=8),
                       replace=True, telemetry=rec)
    assert len(jax.devices()) == 4
    served = []
    for half in (frames[:20], frames[20:]):   # the second half reaches
        for f in half:                         # the replacement
            fleet.submit("m", f)
        served += fleet.drain()
    snap = rec.snapshot()
    print(json.dumps({
        "served": len(served),
        "recovery_ms": fleet.stats().recovery_ms,
        "spans": {k: sorted(v["replicas"]) for k, v in snap["spans"].items()},
        "replace_s": snap["spans"]["fleet.replace"]["seconds"],
        "rows": sorted(set(rec.rows()["replica"].tolist())),
    }))
""")


def test_fleet_kill_records_fail_and_replace_under_replica_names():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    p = subprocess.run([sys.executable, "-c", _FLEET], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["served"] >= 40 and got["recovery_ms"] is not None
    assert got["spans"]["fleet.fail"] == ["host0"]
    assert got["spans"]["fleet.replace"] == ["host0r1"]
    assert got["spans"]["fleet.step"] == ["fleet"]
    assert got["replace_s"] > 0.0
    assert set(got["spans"]["serve.step"]) >= {"host1", "host2", "host3",
                                                "host0r1"}
    assert set(got["rows"]) >= {"host1", "host2", "host3", "host0r1"}
