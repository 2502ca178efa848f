"""The cascade cell's driver, reference and metric, on the CPU (Pallas in
interpret mode): a tiny detector -> recognizer pair through
``run.measure``, the check failing on a broken cascade and on the
lower-precision control, the threshold rule and ``rec_slot_fill``.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip

Nothing here measures a speed.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import program_telemetry  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAKS = work.peaks_for("TPU v5 lite")
CELL = "face_cascade.owner_rare"
TINY = {"name": CELL, "config": "cascade_test", "chips": 1,
        "driver": "cascade", "server": {"batch": 4, "policy": "static"},
        "cascade": {"detector": "detector", "recognizer": "recognizer",
                    "positive_class": 1, "escalation_share": 0.25},
        "traffic": {"loop": "closed", "queued_batches": 2,
                    "bank_frames": 32}}
NUMBERS = {"missing", "duplicate", "unknown", "escalation_mismatch",
           "detector_label_mismatch", "label_mismatch", "logit_gap"}


def _config():
    return harness.load_json(os.path.join(BENCH, "testdata",
                                          "cascade_test.json"))


def _measure(seconds=1.0, hook=None, trace=False, seed=2 ** 33 + 11):
    import jax
    return run.measure(TINY, _config(), BENCHMARK, seed=seed,
                       seconds=seconds, trace=trace,
                       devices=jax.devices()[:1], peaks=PEAKS,
                       t_start=time.perf_counter(), target_hook=hook)


def test_cascade_driver_runs_a_tiny_window():
    out = _measure()
    assert out["correct"], out["check"]
    assert set(out["check"]) == NUMBERS
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in BENCHMARK["end_to_end"]
            if harness.applies(m, CELL)}
    assert want == {"frames_per_s", "setup_s"}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_cascade_traced_run_reports_its_per_layer_metrics(monkeypatch):
    """Every per-layer metric listed for the cell reads in a traced run
    (the CPU trace has no TPU plane, so its reduction is stood in for)."""
    def reduce(path, devices=1):
        return {"window_s": 0.25, "busy_s": 0.05, "busy_s_per_device": [0.05],
                "kernel_s": 0.04, "device_ops": [["cascade", 0.04]],
                "idle_gaps": [["step", 0.2]]}
    monkeypatch.setattr(trace_reduce, "reduce", reduce)
    out = _measure(seconds=2.0, trace=True)
    assert out["correct"], out["check"]
    want = {m["name"] for m in BENCHMARK["per_layer"]
            if harness.applies(m, CELL)}
    assert "rec_slot_fill" in want
    assert want <= set(out["metrics"])
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["metrics"]["rec_slot_fill"]["value"] <= 100.0


def _on_answers(target, alter):
    """``alter(results) -> results`` applied to every finished dispatch's
    answers, where the executor produces them."""
    ex = target.server.executor
    finish = ex.finish
    ex.finish = lambda handle: alter(finish(handle))


def _first_escalated(alter_one):
    """A hook altering the first escalated answer of every dispatch."""
    def alter(out):
        i = next((i for i, r in enumerate(out) if r.detector.escalated),
                 None)
        if i is not None:
            out[i] = alter_one(out[i])
        return out
    return lambda target: _on_answers(target, alter)


def _not_escalated(r):
    """Reported as not escalated: the escalation flag alone flipped."""
    det = dataclasses.replace(r.detector, escalated=False)
    return dataclasses.replace(r, detector=det)


def _recognizer_logit_altered(r):
    lg = np.array(r.logits, copy=True)
    lg[0] += 2
    return dataclasses.replace(r, logits=lg)


def _drop_half(target):
    _on_answers(target, lambda out: out[:len(out) // 2])


@pytest.mark.parametrize("hook,number", [
    (_first_escalated(_not_escalated), "escalation_mismatch"),
    (_first_escalated(_recognizer_logit_altered), "logit_gap"),
    (_drop_half, "missing"),
], ids=["escalation_flipped", "recognizer_logit_altered", "half_dropped"])
def test_check_fails_on_a_broken_cascade(hook, number):
    out = _measure(hook=hook)
    assert not out["correct"]
    c = out["check"][number]
    assert c["value"] > c["limit"]


def test_threshold_rule_picks_the_share_nearest_the_target():
    drv = harness.driver_module("cascade")
    m = [-3, -1, -1, 0, 2, 2, 5, 7]
    # shares at or above each threshold: -3 1, -1 7/8, 0 5/8, 2 4/8,
    # 5 2/8, 7 1/8
    assert drv.threshold_for_share(m, 0.25) == (5, 0.25)
    assert drv.threshold_for_share(m, 0.6) == (0, 0.625)
    # 3/16 lies as near 2/8 as 1/8: the higher threshold wins the tie
    assert drv.threshold_for_share(m, 3 / 16) == (7, 0.125)
    assert drv.threshold_for_share(np.array(m[::-1], float), 2.0) == (-3, 1.0)
    assert drv.threshold_for_share(m, 0.0) == (7, 0.125)


@pytest.mark.parametrize("counters,value", [
    ({"cascade.escalated": 26, "cascade.rec_slots": 32}, 81.25),
    ({"serve.ahead": 3}, None),
])
def test_rec_slot_fill_reads_a_snapshot(monkeypatch, counters, value):
    snap = {"spans": {}, "counters": counters, "events": [],
            "steps": {"rows": 0, "written": 0, "p50": {}, "p95": {}}}
    monkeypatch.setattr(program_telemetry, "snapshot", lambda: snap)
    assert harness.metric_reader("rec_slot_fill").read({}) == value


def test_rec_slot_fill_is_silent_without_telemetry(monkeypatch):
    monkeypatch.setattr(program_telemetry, "snapshot", lambda: None)
    assert harness.metric_reader("rec_slot_fill").read({}) is None


def test_cascade_control_fails_at_the_cells_width():
    """The control, sums wrapped to int8, fails face_cascade's check at
    its published widths (64 frames: a size a CPU test can hold); the
    float32 reference in the same place passes."""
    import control
    import jax
    cell = dict(harness.cell_file(CELL), traffic={"bank_frames": 64})
    res = control.control(cell, harness.config_file("face_cascade"), seed=3,
                          accs=["float32", "int8"], devices=jax.devices()[:1])
    assert res["float32"] == {"escalation_mismatch": 0,
                              "detector_label_mismatch": 0,
                              "label_mismatch": 0, "logit_gap": 0.0}
    assert res["int8"]["logit_gap"] > 0
    assert res["int8"]["escalation_mismatch"] + res["int8"][
        "label_mismatch"] > 0
