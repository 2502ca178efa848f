"""The chip benchmark's own tests, on the CPU (Pallas in interpret mode).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip

They rehearse every driver on a tiny window, check the work arithmetic
and the trace reduction, show that the check fails on a broken system
and on the lower-precision control, and that ``run.py`` refuses to run
without a TPU.  Nothing here measures a speed.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
TESTDATA = os.path.join(BENCH, "testdata")
PEAKS = work.peaks_for("TPU v5 lite")


def _testdata(name):
    return harness.load_json(os.path.join(TESTDATA, name + ".json"))


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_and_config_load_by_name(cell):
    from repro.core.chip import isa, networks
    c = harness.cell_file(cell)
    assert c["name"] == cell
    entry = {w["name"]: w for w in BENCHMARK["workloads"]}[cell]
    assert c["config"] == entry["config"] and c["chips"] == entry["chips"]
    assert c["why"] == entry["why"]
    for key in ("megakernel", "prefetch", "donate_frames", "bb", "bf", "ft"):
        assert key not in json.dumps(c), key      # no implementation choice
    cfg = harness.config_file(c["config"])
    for name, spec in cfg["programs"].items():
        harness.Harness._check_layers(
            name, networks.REGISTRY[spec["registry"]](), spec, isa)
    assert hasattr(harness.driver_module(c["driver"]), "Target")
    assert c["traffic"]["loop"] in harness.LOOPS


def test_metric_readers_load_by_name():
    for group in ("end_to_end", "per_layer"):
        for m in BENCHMARK[group]:
            assert callable(harness.metric_reader(m["name"]).read)


# ---------------------------------------------------------------------------
# work and peaks
# ---------------------------------------------------------------------------

def test_work_anchors():
    from repro.core.chip import isa, networks
    c1 = harness.config_file("cifar9_s1")["programs"]["cifar9_s1"]["layers"]
    det = harness.layers_of(networks.REGISTRY["face_detector"](), isa)
    assert abs(work.frame_ops(c1) / 2.013e9 - 1) < 0.01
    assert work.layer_ops(c1)[0] == ("conv", 256 * 256 * 4 * 2 * 31 * 31)
    assert work.conv_ops(det) * 16 == work.conv_ops(c1)
    assert work.frame_bytes(c1) == 32 * 32 * 3 * 7 / 8 + 4 * 10
    assert work.weight_bytes(c1) > 8 * 256 * 256 * 4 / 8


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v9 imaginary")


def test_least_time_picks_the_binding_bound():
    t, bound = work.least_time_s(393e12, 1.0, PEAKS)
    assert bound == "compute" and abs(t - 1.0) < 1e-12
    t, bound = work.least_time_s(1.0, 819e9, PEAKS)
    assert bound == "memory" and abs(t - 1.0) < 1e-12


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def _ev(name, start, dur, stats=()):
    return SimpleNamespace(name=name, start_ns=float(start),
                           duration_ns=float(dur), stats=list(stats))


def _profile():
    dev = SimpleNamespace(name="/device:TPU:0", lines=[SimpleNamespace(
        name="XLA Ops", events=[
            _ev("fusion.1", 0, 100),                       # before the window
            _ev("conv", 1000, 300, [("hlo_op", "custom-call.3"),
                                    ("long_name", "tpu_custom_call")]),
            _ev("conv", 1200, 300, [("long_name", "tpu_custom_call")]),
            _ev("copy.2", 2000, 500),
            _ev("conv", 3800, 400, [("long_name", "tpu_custom_call")]),
        ])])
    host = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(
        name="python", events=[
            _ev("bench.window", 900, 3200),
            _ev("bench.submit", 1500, 400),
            _ev("bench.step", 2500, 1400),
            _ev("other", 100, 5000),
        ])])
    return SimpleNamespace(planes=[host, dev])


def test_trace_reduce_on_built_events():
    r = trace_reduce.reduce_profile(_profile(), devices=1)
    assert r["window_s"] == pytest.approx(3200e-9)
    # busy: [1000, 1500] + [2000, 2500] + [3800, 4100] clipped at 4100
    assert r["busy_s"] == pytest.approx(1300e-9)
    assert r["kernel_s"] == pytest.approx(900e-9)
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops["conv"] == pytest.approx(900e-9)
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # [900,1000]: none; [1500,2000]: submit; [2500,3800]: step
    assert gaps == pytest.approx({"none": 100e-9, "submit": 500e-9,
                                  "step": 1300e-9})


def test_trace_reduce_without_device_plane_raises():
    prof = _profile()
    prof.planes = prof.planes[:1]
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(prof)


# 60 ms of a traced cifar9_s1.backlog window on one TPU v5e, cut down to
# the TPU's "XLA Ops" line and the harness's host spans
RECORDED = os.path.join(TESTDATA, "backlog_trace.xplane.pb")


def test_trace_reduce_on_recorded_chip_trace():
    r = trace_reduce.reduce(RECORDED, devices=1)
    assert r["window_s"] == pytest.approx(0.06)
    assert r["busy_s"] == pytest.approx(0.013322747)
    assert r["kernel_s"] == pytest.approx(0.009706514)
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops["binary_conv2x2_block"] > ops["xnor_matmul"] > 0
    assert {k for k, _v in r["idle_gaps"]} <= {"submit", "step", "none"}
    assert sum(v for _k, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


# ---------------------------------------------------------------------------
# every driver on a tiny window, and the check failing on a broken system
# ---------------------------------------------------------------------------

MNIST = "mnist5_test"
TINY = {
    "server": {"name": "cifar9_s1.backlog", "config": MNIST, "chips": 1,
               "driver": "server", "server": {"batch": 4, "policy": "static"},
               "traffic": {"loop": "closed", "queued_batches": 2,
                           "bank_frames": 32}},
    "fleet": {"name": "cifar9_s1.fleet4_failover", "config": MNIST,
              "chips": 1, "driver": "fleet", "server": {"batch": 4},
              "fleet": {"replicas": 2, "kill": "host0", "kill_at": 0.5,
                        "replace": True},
              "traffic": {"loop": "closed", "queued_batches": 2,
                          "bank_frames": 32}},
}


def _measure(kind, seconds=1.0, hook=None, seed=2 ** 33 + 7, trace=False):
    import jax
    cell = TINY[kind]
    return run.measure(cell, _testdata(cell["config"]), BENCHMARK,
                       seed=seed, seconds=seconds, trace=trace,
                       devices=jax.devices()[:1], peaks=PEAKS,
                       t_start=time.perf_counter(), target_hook=hook)


@pytest.mark.parametrize("kind", ["server", "fleet"])
def test_driver_runs_a_tiny_window(kind):
    out = _measure(kind)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    want = {m["name"] for m in BENCHMARK["end_to_end"]
            if harness.applies(m, TINY[kind]["name"])}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())


@pytest.mark.parametrize("kind", ["server", "fleet"])
def test_traced_run_traces_the_last_part_of_the_window(kind, monkeypatch):
    """A traced run starts the profiler at ``TRACE_FROM`` of the window:
    ``mfu`` comes from the untraced part before it, the device numbers
    from the slice, and ``traced_rate_share`` compares the two rates.
    The CPU trace has no TPU plane, so its reduction is stood in for."""
    seen = []

    def reduce(path, devices=1):
        seen.append(path)
        return {"window_s": 0.25, "busy_s": 0.05, "busy_s_per_device": [0.05],
                "kernel_s": 0.04, "device_ops": [["conv", 0.04]],
                "idle_gaps": [["step", 0.2]]}
    monkeypatch.setattr(trace_reduce, "reduce", reduce)
    calls = []
    start = harness.Harness.poll_trace

    def poll(h, now):
        started = start(h, now)
        if started:
            calls.append((now - h.window[0]) / h.seconds)
        return started
    monkeypatch.setattr(harness.Harness, "poll_trace", poll)
    out = _measure(kind, seconds=2.0, trace=True)
    assert out["correct"], out["check"]
    assert len(seen) == 1 and seen[0].endswith(".xplane.pb")
    assert len(calls) == 1 and harness.TRACE_FROM <= calls[0] < 1.0
    want = {m["name"] for m in BENCHMARK["per_layer"]
            if harness.applies(m, TINY[kind]["name"])}
    want.discard("fleet_recovery_ms")          # the fleet's own counter
    assert want <= set(out["metrics"])
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["busy_s"] == 0.05 and out["breakdown"]["device_ops"]


def _alter_answer(target):
    """An answer altered where it is produced: one logit of the first
    frame of every dispatch."""
    ex = target.server.executor
    finish = ex.finish

    def broken(handle):
        out = finish(handle)
        if out:
            r = out[0]
            lg = np.array(r.logits, copy=True)
            lg[0] += 2
            out[0] = dataclasses.replace(r, logits=lg)
        return out
    ex.finish = broken


def _drop_half(target):
    """Half of every batch left out of the answers."""
    ex = target.server.executor
    finish = ex.finish
    ex.finish = lambda handle: (lambda out: out[:len(out) // 2])(
        finish(handle))


def _drop_migration(target):
    """The exchange between replicas left out: the killed replica's
    frames are never handed to the survivors."""
    fleet = target.fleet
    fail = fleet.fail

    def broken(name):
        victim = fleet.replicas[name]
        victim.queue = type(victim.queue)(victim.queue.lanes)
        return fail(name)
    fleet.fail = broken


@pytest.mark.parametrize("kind,hook,number", [
    ("server", _alter_answer, "logit_gap"),
    ("server", _drop_half, "missing"),
    ("fleet", _drop_migration, "missing"),
])
def test_check_fails_on_a_broken_system(kind, hook, number):
    out = _measure(kind, hook=hook)
    assert not out["correct"]
    c = out["check"][number]
    assert c["value"] > c["limit"]


def test_control_fails_at_the_cells_width():
    """The control, sums wrapped to int8, fails cifar9_s1's check at its
    published width (64 frames: a size a CPU test can hold); the float32
    reference in the same place passes."""
    import control
    import jax
    cell = {"name": "cifar9_s1.backlog", "driver": "server",
            "traffic": {"bank_frames": 64}}
    res = control.control(cell, harness.config_file("cifar9_s1"), seed=3,
                          accs=["float32", "int8"], devices=jax.devices()[:1])
    assert res["float32"] == {"label_mismatch": 0, "logit_gap": 0.0}
    assert res["int8"]["label_mismatch"] > 0 and res["int8"]["logit_gap"] > 0


def test_weights_depend_on_the_seed_and_only_on_it():
    import jax
    cell = TINY["server"]
    cfg = _testdata(MNIST)
    hs = [harness.Harness(cell, cfg, seed=s, seconds=0.0, trace=False,
                          devices=jax.devices()[:1]) for s in (5, 5, 6)]
    for h in hs:
        h.build()
    w = [np.asarray(h.weights["mnist5"]["conv"][0]["w"]) for h in hs]
    assert np.array_equal(w[0], w[1]) and not np.array_equal(w[0], w[2])
    assert np.array_equal(hs[0].bank, hs[1].bank)
    assert not np.array_equal(hs[0].bank, hs[2].bank)


# ---------------------------------------------------------------------------
# run.py refuses
# ---------------------------------------------------------------------------

def _run_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "cifar9_s1.backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run_cmd(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_system(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cmd(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
