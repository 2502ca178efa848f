"""The executor asks for a dispatch's device-to-host copies at launch.

``Executor.launch`` calls ``copy_to_host_async`` once on every output
array of the dispatch it runs, before anyone materializes it: the two
outputs of a single dispatch, each member's two of a composite, and the
six of a fused cascade.  The serve functions here are stubs whose
outputs count those calls, so the test sees the executor's requests
and nothing of JAX's own.
"""

import jax
import numpy as np
import pytest

from repro.core.chip import interpreter, networks
from repro.serving import telemetry
from repro.serving.executor import Executor
from repro.serving.policy import CascadeRoute, Dispatch, LaneDispatch
from repro.serving.queue import FrameRequest

BATCH = 4
CLASSES = 10


class Output:
    """A host array standing in for a device output: it counts the host
    copies asked of it and is always computed."""

    def __init__(self, value):
        self.value = np.asarray(value)
        self.copies = 0

    def copy_to_host_async(self):
        self.copies += 1

    def is_ready(self):
        return True

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        return self.value if dtype is None else self.value.astype(dtype)


@pytest.fixture(scope="module")
def mnist():
    program = networks.mnist5()
    params = interpreter.init_params(jax.random.PRNGKey(3), program)
    packed = interpreter.fold_params(params, program, packed=True)
    return program, packed


def _lane(lane, program, n, rid0=0):
    io = program.instrs[0]
    frame = np.zeros((io.height, io.width, io.in_channels), np.int32)
    return LaneDispatch(lane, lane, tuple(
        FrameRequest(rid0 + i, lane, frame) for i in range(n)))


def _single_out(outs, k):
    logits = Output(np.arange(BATCH * CLASSES).reshape(BATCH, CLASSES) + k)
    labels = Output(np.arange(BATCH) + k)
    outs += [logits, labels]
    return logits, labels


@pytest.mark.parametrize("kind", ["single", "composite", "cascade"])
def test_launch_asks_once_for_every_output_copy(mnist, kind):
    """Each output leaf gets exactly one ``copy_to_host_async`` inside
    ``launch``; ``finish`` then reads the stub's values and, every output
    being computed, counts ``serve.ready`` with the dispatch index."""
    program, packed = mnist
    rec = telemetry.Recorder()
    ex = Executor({"a": program, "b": program}, {"a": packed, "b": packed},
                  batch=BATCH, interpret=True, prefetch=0,
                  probe=rec.probe("stub"))
    outs = []
    if kind == "single":
        dispatch = Dispatch((_lane("a", program, 3),))
        ex._fns["a"] = lambda art, frames: _single_out(outs, 0)
        want = 2
    elif kind == "composite":
        dispatch = Dispatch((_lane("a", program, 3),
                             _lane("b", program, 2, rid0=3)))

        def fn(art, frames):
            assert len(frames) == 2
            pairs = [_single_out(outs, k) for k in range(2)]
            return tuple(p[0] for p in pairs), tuple(p[1] for p in pairs)
        ex._composites[("a", "b")] = dict(plan=None, image=None, fn=fn)
        want = 4
    else:
        dispatch = Dispatch((_lane("a", program, 3),),
                            cascade=CascadeRoute("b", "b", margin=0.0))

        def fn(art, frames, ctrl):
            dl, dlab = _single_out(outs, 0)
            rl, rlab = _single_out(outs, 5)
            queue = Output(np.zeros(BATCH, np.int32))
            counts = Output(np.zeros(2, np.int32))
            outs.extend([queue, counts])
            return dl, dlab, rl, rlab, queue, counts
        ex._cascades[("a", "b", 1)] = dict(plan=None, image=None, fn=fn)
        want = 6
    handle = ex.launch(dispatch, 7)
    assert len(outs) == want
    assert [o.copies for o in outs] == [1] * want
    results = ex.finish(handle)
    labels = [r.label for r in results]
    assert labels == {"single": [0, 1, 2], "composite": [0, 1, 2, 1, 2],
                      "cascade": [0, 1, 2]}[kind]
    snap = rec.snapshot()
    assert snap["counters"]["serve.ready"] == 1
    assert [e for e in snap["events"] if e["name"] == "serve.ready"] == [
        {"name": "serve.ready", "dispatch": 7, "replica": "stub"}]
