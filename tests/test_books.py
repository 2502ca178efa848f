"""The server's books: every dispatch kind bills through ``ChipServer.bill``.

Three paths write the books — a solo dispatch at launch, a fused cascade
when it finishes (from the kernel's counts), a delta-gated dispatch at
its step — and after serving each leaves ``billed == served + padded``,
counts every dispatch exactly once, and, on a program family under the
operating-point policy, has committed to the budget exactly the billed
slots at each variant's per-frame energy.
"""

import pytest

from repro.core.chip import networks
from repro.serving import CascadePipeline, ChipServer
from repro.serving import temporal as tmp
from repro.serving.policy import OperatingPointPolicy
from repro.serving.traffic import video_trace
from test_cascade_fused import _artifact, _frames

BATCH = 2


def _family_server(prefetch):
    """A two-variant mnist5 family (one program, two weight sets) behind
    lane ``m``, under the operating-point policy."""
    prog = networks.mnist5()
    return ChipServer({"a": prog, "b": prog},
                      {"a": _artifact(prog, seed=1),
                       "b": _artifact(prog, seed=2)},
                      batch=BATCH, interpret=True, prefetch=prefetch,
                      families={"m": ("a", "b")})


def _solo(prefetch):
    srv = _family_server(prefetch)
    frames = _frames(networks.mnist5(), 7, seed=3)
    srv.submit_many("m", frames)
    assert len(srv.drain()) == len(frames)
    return srv, -(-len(frames) // BATCH)


def _cascade(prefetch):
    det, rec = networks.mnist5(classes=2), networks.mnist5(classes=5)
    srv = ChipServer({"det": det, "rec": rec},
                     {"det": _artifact(det, seed=1),
                      "rec": _artifact(rec, seed=2)},
                     batch=BATCH, interpret=True, prefetch=prefetch)
    casc = CascadePipeline(srv, "det", "rec", margin=0.0, fused=True)
    frames = _frames(det, 7, seed=3)
    casc.submit_many(frames)
    assert len(casc.drain()) == len(frames)
    return srv, casc.fused_dispatches


def _gate(prefetch):
    srv = _family_server(prefetch)
    io = networks.mnist5().instrs[0]
    trace = video_trace((io.height, io.width, io.in_channels), 3,
                        streams=BATCH, seed=3, change_rate=0.3,
                        levels=2 ** io.bits)
    pipe = tmp.TemporalPipeline(srv, "m", threshold=1.0, rb=1)
    for t in range(len(trace)):
        for s in range(trace.streams):
            pipe.submit(trace.frames[t, s])
    assert len(pipe.drain()) == len(trace) * trace.streams
    return srv, pipe.gated_dispatches


@pytest.mark.parametrize("prefetch", [0, 1])
@pytest.mark.parametrize("path", [_solo, _cascade, _gate],
                         ids=["solo", "cascade", "gate"])
def test_every_dispatch_kind_bills_through_one_method(path, prefetch):
    srv, dispatches = path(prefetch)
    st = srv.stats()
    assert st.billed == sum(st.served.values()) + sum(st.padded.values())
    assert st.billed > 0
    assert st.dispatches == dispatches
    assert srv.total_served == st.total_served
    pol = srv.policy
    if isinstance(pol, OperatingPointPolicy):
        assert sum(pol.variant_dispatches.values()) == dispatches
        # stats().energy_uj is the billed slots of each variant times its
        # per-frame energy
        assert st.energy_uj > 0
        assert pol.spent_uj == pytest.approx(st.energy_uj)
    srv.close()
