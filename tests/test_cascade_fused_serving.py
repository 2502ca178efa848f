"""Fused in-kernel cascade on the served path: ``CascadePipeline(fused=
True)`` over a ``ChipServer`` finalizes the same answers as the host
cascade and the stage oracles at every pipeline depth, runs each fused
dispatch as an ordinary executor step, and bills it from the kernel's
counts.  The kernel and plan are tested in ``test_cascade_fused.py``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.chip import networks
from repro.kernels import cache as warmcache
from repro.serving import (CascadePipeline, ChipServer, margins_of,
                           telemetry)
from test_cascade_fused import (MARGINS, _artifact, _frames, _offline,
                                fused_setup)


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
    assert stats.billed == (sum(stats.served.values())
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
    ``serve.launch``, ``serve.wait`` and ``serve.fetch`` and one per-step
    row each, at every depth; on a backlog every
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
    for name in ("serve.put", "serve.launch", "serve.wait", "serve.fetch"):
        assert spans[name]["count"] == n, name
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
    assert st.billed == n * batch + slots
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
