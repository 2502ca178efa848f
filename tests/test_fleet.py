"""Serve fleet: failover, migration, warm start + clock-domain pins.

Four property groups:

1. **Clock domain** — regression pins for the clock-injection contract:
   every server-side timestamp (``_host_wall_s``, trace ``t_submit`` /
   ``t_done``) comes from the *injected* clock.  Under a
   :class:`VirtualClock` (which only advances when explicitly slept) any
   leak of ``time.perf_counter()`` shows up as a wall-clock-magnitude
   timestamp; these tests pin all of them to the virtual domain.
2. **Failover** — killing one of >= 2 replicas mid-replay loses zero
   frames, served labels stay bit-exact vs the offline oracle, migrated
   frames keep their per-lane order and serve ahead of anything routed
   to the survivor after the failure.
3. **Billing** — fleet-wide ``billed == served + padded`` (including a
   kill with in-flight dispatches: those frames are honestly re-billed
   by whoever serves them, surfaced as ``refired_frames``).
4. **Warm start** — identical serve configurations share one compiled
   serve fn through :mod:`repro.kernels.cache`; a replacement replica's
   bring-up is a cache hit.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.chip import interpreter, networks
from repro.kernels import cache as warmcache
from repro.serving import (ChipServer, FaultInjector, ServeFleet,
                           VirtualClock, poisson_trace, replay)
from repro.serving.queue import FrameQueue, FrameRequest


def _frames(program, n, seed=0):
    io = program.instrs[0]
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n, io.height, io.width, io.in_channels),
        0, 2 ** io.bits))


@pytest.fixture(scope="module")
def mnist_setup():
    program = networks.mnist5()
    params = interpreter.init_params(jax.random.PRNGKey(3), program)
    packed = interpreter.fold_params(params, program, packed=True)
    frames = _frames(program, 24, seed=11)
    plan = interpreter.compile_plan(program)
    logits, labels = plan.forward(packed, jnp.asarray(frames),
                                  interpret=True)
    return program, packed, frames, np.asarray(labels)


def _fleet(program, packed, clock, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("batch", 4)
    return ServeFleet({"mnist5": program}, {"mnist5": packed},
                      interpret=True, clock=clock, sleep=clock.sleep, **kw)


# ---------------------------------------------------------------------------
# 1. Clock-domain pins
# ---------------------------------------------------------------------------

def test_step_wall_time_comes_from_injected_clock(mnist_setup):
    """Regression pin for the server.step() clock fix: with a virtual
    clock that never advances, _host_wall_s must stay exactly 0.0 — any
    direct time.perf_counter() read inside step() would leak a positive
    wall-time delta."""
    program, packed, frames, _ = mnist_setup
    vc = VirtualClock(start=5.0)
    server = ChipServer({"mnist5": program}, {"mnist5": packed},
                        batch=4, interpret=True, clock=vc)
    for f in frames[:6]:
        server.submit("mnist5", f)
    results = server.drain()
    assert len(results) == 6
    assert server._host_wall_s == 0.0
    assert server.stats().host_frames_per_s == 0.0


def test_trace_timestamps_come_from_injected_clock(mnist_setup):
    """Every t_submit / t_done in the latency trace lives in the virtual
    clock's domain (a perf_counter leak would be orders of magnitude
    off the virtual epoch)."""
    program, packed, frames, _ = mnist_setup
    vc = VirtualClock(start=1.0)
    server = ChipServer({"mnist5": program}, {"mnist5": packed},
                        batch=4, interpret=True, clock=vc)
    trace = poisson_trace(("mnist5",), rate=50.0, n=10, seed=7)
    results = replay(server, trace, {"mnist5": frames},
                     clock=vc, sleep=vc.sleep)
    assert results
    recs = server.latency_trace()
    assert recs
    for rec in recs:
        assert 1.0 <= rec["t_submit"] <= vc.now
        assert 1.0 <= rec["t_done"] <= vc.now
        assert rec["latency_ms"] >= 0.0
    assert server._host_wall_s == 0.0


def test_serve_driver_uses_single_injected_clock(capsys):
    """Regression pin for the launch/serve.py clock fix: the LM serving
    driver runs entirely on an injected clock + sleep (previously it
    mixed time.time() with time.perf_counter() across admission pacing
    and the final throughput figure)."""
    from repro.launch import serve as serve_driver
    vc = VirtualClock(start=0.0)
    sleeps = []

    def vsleep(dt):
        sleeps.append(dt)
        vc.sleep(dt)

    serve_driver.main(["--arch", "smollm-360m", "--scaled",
                       "--requests", "3", "--batch", "2",
                       "--prompt-len", "8", "--gen-len", "2",
                       "--rate", "100"],
                      clock=vc, sleep=vsleep)
    out = capsys.readouterr().out
    assert "3 requests" in out
    # paced admission slept on the virtual clock (and never negative)
    assert sleeps and all(dt >= 0 for dt in sleeps)
    assert vc.now == pytest.approx(sum(sleeps))


# ---------------------------------------------------------------------------
# 2. Failover: zero loss, bit-exact, per-lane order
# ---------------------------------------------------------------------------

def test_failover_zero_loss_bit_exact_mid_replay(mnist_setup):
    """Kill one of two replicas mid-replay: every submitted frame is
    served exactly once and every label matches the offline oracle."""
    program, packed, frames, labels = mnist_setup
    vc = VirtualClock()
    inj = FaultInjector("host0", after_served=4)
    fleet = _fleet(program, packed, vc, injector=inj, replace=True)
    trace = poisson_trace(("mnist5",), rate=100.0, n=20, seed=3)
    results = replay(fleet, trace, {"mnist5": frames},
                     clock=vc, sleep=vc.sleep)
    n = len(trace)
    assert sorted(r.rid for r in results) == list(range(n))
    for r in results:
        assert r.label == labels[r.rid % len(frames)]
    st = fleet.stats()
    assert inj.fired
    assert st.failed_replicas == ("host0",)
    assert st.migrated_frames >= 0
    assert st.total_served == n + st.refired_frames


@pytest.fixture
def host_copies(monkeypatch):
    """Every device array asked for a host copy, once per request (held,
    so identity stays unique)."""
    impl = type(jnp.zeros(()))
    asked = []
    copy = impl.copy_to_host_async

    def record(self):
        asked.append(self)
        return copy(self)
    monkeypatch.setattr(impl, "copy_to_host_async", record)
    return asked


@pytest.mark.parametrize("prefetch", [0, 1])
def test_migration_preserves_per_lane_order(mnist_setup, prefetch,
                                            host_copies):
    """Migrated frames enter the survivor's lane front: they keep their
    own relative order and serve before anything routed to the survivor
    after the failure; the survivor's own frames also stay in order.
    At depth 1 each replica already has its next dispatch in flight,
    its host copies already asked for: the victim's is migrated, the
    survivor's is answered first.  Every label matches the oracle."""
    program, packed, frames, labels = mnist_setup
    vc = VirtualClock()
    fleet = _fleet(program, packed, vc, batch=2, replace=False,
                   prefetch=prefetch)
    # blocks of 2: rids 0,1 -> host0; 2,3 -> host1; 4,5 -> host0; 6,7 -> host1
    for f in frames[:8]:
        fleet.submit("mnist5", f)
    first = fleet.step()             # one dispatch on each replica
    assert len(first) == 4
    inflight = list(fleet.replicas["host0"].executor._inflight)
    assert len(inflight) == prefetch
    for handle in inflight:          # launched, its copies under way
        outs = jax.tree_util.tree_leaves((handle["logits"],
                                          handle["labels"]))
        assert [sum(a is o for a in host_copies) for o in outs] == \
            [1] * len(outs)
    orphans = fleet.fail("host0")
    migrated = [r.rid for r in orphans["mnist5"]]
    assert migrated == [4, 5]        # host0's unserved frames, in order
    ahead = fleet.replicas["host1"].executor.inflight_frames()
    assert ahead == 2 * prefetch     # the survivor's launched dispatch
    post = [fleet.submit("mnist5", f) for f in frames[8:12]]
    results = fleet.drain()
    served_after = [r.rid for r in results]
    # zero loss: everything not already served comes out of the drain
    assert sorted(served_after) == [4, 5, 6, 7] + post
    # migrated frames first (in order) after what the survivor had in
    # flight, then the survivor's own queue, then the post-failure
    # admissions
    assert served_after[ahead:ahead + 2] == [4, 5]
    assert served_after.index(6) < served_after.index(7)
    assert max(served_after.index(r) for r in [4, 5, 6, 7]) < \
        min(served_after.index(r) for r in post)
    for r in first + results:
        assert r.label == labels[r.rid]


def test_fail_last_replica_raises(mnist_setup):
    program, packed, frames, _ = mnist_setup
    vc = VirtualClock()
    fleet = _fleet(program, packed, vc, replicas=1, replace=False)
    fleet.submit("mnist5", frames[0])
    with pytest.raises(RuntimeError, match="no survivors"):
        fleet.fail("host0")


def test_requeue_front_order_and_lane_guard():
    q = FrameQueue(["a", "b"])
    q.submit(FrameRequest(rid=10, program="a", frame=None))
    old = [FrameRequest(rid=1, program="a", frame=None),
           FrameRequest(rid=2, program="a", frame=None)]
    q.requeue_front("a", old)
    assert [r.rid for r in q.take("a", 10)] == [1, 2, 10]
    with pytest.raises(ValueError, match="belongs to lane"):
        q.requeue_front("b", old)


# ---------------------------------------------------------------------------
# 3. Billing: billed == served + padded fleet-wide
# ---------------------------------------------------------------------------

def test_fleet_billing_with_padding_and_failure(mnist_setup):
    program, packed, frames, _ = mnist_setup
    vc = VirtualClock()
    inj = FaultInjector("host0", after_served=2)
    # prefetch=1 keeps a dispatch in flight, so the kill aborts real
    # in-flight work and the refired re-bill path is exercised
    fleet = _fleet(program, packed, vc, batch=2, prefetch=1,
                   injector=inj, replace=False)
    for f in frames[:10]:
        fleet.submit("mnist5", f)
    results = fleet.drain()
    assert sorted(r.rid for r in results) == list(range(10))
    st = fleet.stats()
    assert st.billed == st.total_served + sum(st.padded.values())
    assert st.total_served == 10 + st.refired_frames
    assert st.chip.total_frames == st.total_served
    assert st.energy_uj > 0.0
    # the victim's books stay in the fleet bill
    assert "host0" in st.replicas
    dead = st.replicas["host0"]
    assert sum(dead.served.values()) + sum(dead.padded.values()) > 0


def test_default_depth_kill_loses_and_duplicates_nothing(mnist_setup):
    """At the default depth (1) every replica keeps its next dispatch in
    flight, so a kill aborts real in-flight work: every frame is still
    answered exactly once, bit-exact, and the aborted frames are billed
    again by whoever serves them."""
    program, packed, frames, labels = mnist_setup
    vc = VirtualClock()
    fleet = _fleet(program, packed, vc, batch=2,
                   injector=FaultInjector("host0", after_served=4),
                   replace=True)
    assert all(s.prefetch == 1 for s in fleet.replicas.values())
    n = len(frames)
    for f in frames:
        fleet.submit("mnist5", f)
    results = fleet.drain()
    rids = [r.rid for r in results]
    assert sorted(rids) == list(range(n))          # none lost, none twice
    got = {r.rid: r.label for r in results}
    np.testing.assert_array_equal([got[i] for i in range(n)], labels)
    st = fleet.stats()
    assert st.failed_replicas == ("host0",)
    assert st.refired_frames > 0
    assert st.total_served == n + st.refired_frames
    assert st.billed == st.total_served + sum(st.padded.values())


# ---------------------------------------------------------------------------
# 4. Warm start
# ---------------------------------------------------------------------------

def test_warm_start_shares_serve_fn(mnist_setup):
    program, packed, _, _ = mnist_setup
    warmcache.invalidate()
    s1 = ChipServer({"mnist5": program}, {"mnist5": packed},
                    batch=4, interpret=True)
    after_one = warmcache.stats()
    assert after_one["misses"] == 1 and after_one["hits"] == 0
    s2 = ChipServer({"mnist5": program}, {"mnist5": packed},
                    batch=4, interpret=True)
    after_two = warmcache.stats()
    assert after_two["hits"] == 1
    assert s2.executor._fns["mnist5"] is s1.executor._fns["mnist5"]
    # opting out bypasses the cache entirely
    s3 = ChipServer({"mnist5": program}, {"mnist5": packed},
                    batch=4, interpret=True, warm_start=False)
    assert warmcache.stats() == after_two
    assert s3.executor._fns["mnist5"] is not s1.executor._fns["mnist5"]


def test_serve_fn_key_schema(mnist_setup):
    program, _, _, _ = mnist_setup
    k1 = warmcache.serve_fn_key((program,), interpret=True)
    assert k1.startswith(f"v{warmcache.SCHEMA}/serve/")
    assert k1 == warmcache.serve_fn_key((program,), interpret=True)
    k2 = warmcache.serve_fn_key((program,), interpret=True, megakernel=True)
    assert k2 != k1
    k3 = warmcache.serve_fn_key((program,), interpret=True, kind="composite")
    assert k3 != k1


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_persistent_cache_placement(tmp_path, monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and no directory is set in code;
    without it the cache goes to the fixed in-checkout directory."""
    knobs = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in knobs}
    set_dirs = []
    real_update = jax.config.update

    def spy(name, val):
        if name == "jax_compilation_cache_dir":
            set_dirs.append(val)
        real_update(name, val)

    monkeypatch.setattr(jax.config, "update", spy)
    monkeypatch.setattr(warmcache, "_persistent_dir", None)
    if env_dir is None:
        monkeypatch.delenv(warmcache.CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(warmcache.CACHE_ENV, str(tmp_path / env_dir))
    try:
        got = warmcache.enable_persistent()
    finally:
        for k, v in saved.items():
            real_update(k, v)
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if env_dir is None:
        assert got == warmcache.DEFAULT_DIR == os.path.join(
            root, ".jax_compilation_cache")
        assert set_dirs == [got]
    else:
        assert got == str(tmp_path / env_dir)
        assert set_dirs == []
    assert warmcache.stats()["persistent_dir"] == got


def test_replacement_replica_warm_starts(mnist_setup):
    """A replacement spawned after a kill hits the warm-start cache (its
    serve-fn key matches the dead host's) and goes on to serve frames —
    recovery is measurable on the fleet clock."""
    program, packed, frames, labels = mnist_setup
    vc = VirtualClock()
    inj = FaultInjector("host0", after_served=2)
    fleet = _fleet(program, packed, vc, batch=2, injector=inj,
                   replace=True)
    for f in frames[:4]:
        fleet.submit("mnist5", f)
    fleet.drain()
    assert fleet.failed_replicas == ("host0",)
    hits_after_fail = warmcache.stats()["hits"]
    assert hits_after_fail >= 1    # replacement build was (at least) a hit
    # route fresh traffic; the replacement is in rotation and serves
    post = [fleet.submit("mnist5", f) for f in frames[4:12]]
    results = fleet.drain()
    assert sorted(r.rid for r in results) == post
    replacement = [n for n in fleet.live_replicas if n.startswith("host0")]
    assert replacement
    served_by = {n: sum(fleet.replicas[n].stats().served.values())
                 for n in fleet.live_replicas}
    assert served_by[replacement[0]] > 0
    assert fleet.recovery_ms is not None and fleet.recovery_ms >= 0.0
    for r in results:
        assert r.label == labels[r.rid % len(frames)]
