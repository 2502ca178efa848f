"""The metrics read from the program's own telemetry
(``benchmarks/chip/program_telemetry.py``), on made-up snapshots, and
their silence where the program has no telemetry to read.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import program_telemetry  # noqa: E402

BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW = ("step_host_ms", "device_wait_ms", "submit_us", "replica_rebuild_ms")


def _snapshot(rows=40):
    p50 = {"queue_wait": 0.009, "step": 0.0069, "select": 2e-5,
           "stack": 4e-4, "put": 6e-4, "launch": 3e-4, "wait": 0.0035,
           "fetch": 5e-5, "finish": 2e-4, "host": 0.0034}
    return {
        "spans": {
            "serve.submit": {"count": 6400, "seconds": 0.064,
                             "self_seconds": 0.064, "replicas": {}},
            "fleet.replace": {"count": 2, "seconds": 0.09,
                              "self_seconds": 0.09, "replicas": {}},
        },
        "counters": {"serve.compile": 1}, "events": [],
        "steps": {"rows": rows, "written": rows,
                  "p50": p50 if rows else {}, "p95": p50 if rows else {}},
    }


@pytest.mark.parametrize("name,value", [
    ("step_host_ms", 3.4), ("device_wait_ms", 3.5), ("submit_us", 10.0),
    ("replica_rebuild_ms", 45.0)])
def test_metric_reads_a_snapshot(name, value, monkeypatch):
    monkeypatch.setattr(program_telemetry, "snapshot", _snapshot)
    assert harness.metric_reader(name).read({}) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW)
def test_metric_is_silent_without_telemetry(name, monkeypatch):
    monkeypatch.setattr(program_telemetry, "snapshot", lambda: None)
    assert harness.metric_reader(name).read({}) is None


@pytest.mark.parametrize("name", NEW)
def test_metric_is_silent_on_an_empty_snapshot(name, monkeypatch):
    empty = _snapshot(rows=0)
    empty["spans"] = {}
    monkeypatch.setattr(program_telemetry, "snapshot", lambda: empty)
    assert harness.metric_reader(name).read({}) is None


def test_snapshot_is_none_where_the_program_has_no_telemetry(monkeypatch):
    """A checkout from before the telemetry module: the import fails and
    the metrics are left out of the line, without an error."""
    import repro.serving
    monkeypatch.delattr(repro.serving, "telemetry", raising=False)
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    assert program_telemetry.snapshot() is None
    for name in NEW:
        assert harness.metric_reader(name).read({}) is None


def test_new_metrics_are_listed_with_their_cells():
    per = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW:
        m = per[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "frames_per_s"
    assert per["replica_rebuild_ms"]["workloads"] == [
        "cifar9_s1.fleet4_failover"]
