#!/usr/bin/env python3
"""Chip smoke test: serve BinarEye programs on a TPU, bit-exact vs the reference.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips: the multi-chip paths only

One process drives the chip (no children).  Every phase goes through the
serving entry points a user calls (``ChipServer``, ``CascadePipeline``,
``TemporalPipeline``, ``ServeFleet``) on programs of ``networks.REGISTRY``
at their published widths with seeded random weights, and compares each
served label and logit bit for bit with ``interpreter.forward_infer`` (the
float +/-1 reference, run by XLA).  Each phase also checks that its
compiled serve program contains a Mosaic kernel (``tpu_custom_call``).

One chip:
  (a) ChipServer, cifar9_s1 (the full 256-channel array), staged path
  (b) the same through the whole-network megakernel
  (c) shared=True: four S=4 programs in one composite dispatch
  (d) CascadePipeline(fused=True): face_detector -> owner_detector
  (e) TemporalPipeline (delta gate) on cifar9_s4
Four chips (``--four-chips``):
  (s) ChipServer on cifar9_s1 with frames scattered over a 4-device mesh
  (f) a 4-replica ServeFleet, host0 killed mid-stream and replaced

Exits non-zero, printing no result, unless JAX's first device is a TPU,
and on any mismatch or missing kernel.  The last line of stdout is the
JSON result ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX's first device is "
                 f"{devs[0].platform!r}); refusing to fall back")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU devices, found {len(devs)}")
    return devs[:count]


# ---------------------------------------------------------------------------
# Weights, frames and the reference
# ---------------------------------------------------------------------------

def artifacts_for(program, seed: int):
    """(float-folded reference artifact, packed deployment artifact) from
    seeded random weights with one batch of BatchNorm statistics.  One
    jitted program: run eagerly, every op would compile on its own."""
    from repro.core.chip import interpreter

    @jax.jit
    def build(key):
        params = interpreter.init_params(key, program)
        io = program.instrs[0]
        warm = jax.random.randint(jax.random.fold_in(key, 1),
                                  (4, io.height, io.width, io.in_channels),
                                  0, 2 ** io.bits)
        _, params = interpreter.forward_train(params, program, warm)
        return (interpreter.fold_params(params, program),
                interpreter.fold_params(params, program, packed=True))

    return build(jax.random.PRNGKey(seed))


def frames_for(program, n: int, seed: int) -> np.ndarray:
    io = program.instrs[0]
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2 ** io.bits,
                        (n, io.height, io.width, io.in_channels),
                        dtype=np.int32)


def reference(folded, program, frames):
    """forward_infer on the float +/-1 path: (logits, labels) as numpy."""
    from repro.core.chip import interpreter
    with jax.default_matmul_precision("highest"):
        logits, labels = jax.jit(
            lambda f, x: interpreter.forward_infer(f, program, x))(
                folded, jax.numpy.asarray(frames))
    return np.asarray(logits), np.asarray(labels)


def check_equal(what: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = (int(np.sum(got != want)) if got.shape == want.shape
               else "shape")
        raise AssertionError(f"{what}: {bad} mismatches "
                             f"(got {got.shape}, want {want.shape})")


def mosaic_compile(fn, *args) -> float:
    """AOT-compile ``fn`` for ``args``; returns the compile seconds and
    fails unless the program holds a Mosaic kernel."""
    t0 = time.perf_counter()
    text = fn.lower(*args).compile().as_text()
    dt = time.perf_counter() - t0
    if "tpu_custom_call" not in text:
        raise AssertionError("compiled serve program holds no Mosaic kernel "
                             "(tpu_custom_call)")
    return dt


def check_results(results, frames, folded, program, what: str) -> None:
    results = sorted(results, key=lambda r: r.rid)
    logits, labels = reference(folded, program, frames)
    if len(results) != len(frames):
        raise AssertionError(f"{what}: {len(results)} results for "
                             f"{len(frames)} frames")
    check_equal(f"{what} labels", [r.label for r in results], labels)
    check_equal(f"{what} logits", np.stack([r.logits for r in results]),
                logits)


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def serve_phase(name: str, *, megakernel: bool, batch: int = 16,
                n: int = 48) -> dict:
    from repro.core.chip import networks
    from repro.serving import ChipServer
    program = networks.REGISTRY[name]()
    folded, packed = artifacts_for(program, SEED)
    server = ChipServer({name: program}, {name: packed}, batch=batch,
                        megakernel=megakernel)
    ex = server.executor
    frames = frames_for(program, n, SEED + 1)
    compile_s = mosaic_compile(ex._fns[name], ex.artifacts[name],
                               jax.numpy.asarray(frames[:batch]))
    t0 = time.perf_counter()
    server.submit_many(name, frames)
    results = server.drain()
    serve_s = time.perf_counter() - t0
    check_results(results, frames, folded, program, name)
    st = server.stats()
    return dict(frames=len(results), dispatches=st.dispatches,
                compile_s=round(compile_s, 2), serve_s=round(serve_s, 3))


def phase_a() -> dict:
    return serve_phase("cifar9_s1", megakernel=False)


def phase_b() -> dict:
    return serve_phase("cifar9_s1", megakernel=True)


def phase_c() -> dict:
    """Four S=4 programs tile the array: one composite dispatch serves
    all of them."""
    from repro.core.chip import networks
    from repro.serving import ChipServer
    names = ("cifar9_s4", "face_detector", "cifar9_s4t", "mnist5")
    progs = {n: networks.REGISTRY[n]() for n in names}
    arts = {n: artifacts_for(p, SEED + i) for i, (n, p) in
            enumerate(progs.items())}
    batch = 16
    server = ChipServer(progs, {n: a[1] for n, a in arts.items()},
                        batch=batch, shared=True)
    if server.shared_groups != (names,):
        raise AssertionError(f"expected one 4-member shared group, got "
                             f"{server.shared_groups}")
    comp = server.executor.composite_for(names)
    frames = {n: frames_for(p, batch, SEED + 10 + i)
              for i, (n, p) in enumerate(progs.items())}
    compile_s = mosaic_compile(comp["fn"], comp["image"],
                               tuple(jax.numpy.asarray(frames[n])
                                     for n in names))
    for n in names:
        server.submit_many(n, frames[n])
    results = server.drain()
    st = server.stats()
    if st.dispatches != 1 or st.shared_dispatches != 1:
        raise AssertionError(f"expected one shared dispatch, got "
                             f"{st.dispatches} ({st.shared_dispatches} shared)")
    for n in names:
        check_results([r for r in results if r.program == n], frames[n],
                      arts[n][0], progs[n], n)
    return dict(frames=len(results), dispatches=st.dispatches,
                utilization=st.array_utilization,
                compile_s=round(compile_s, 2))


def phase_d() -> dict:
    """Fused cascade: the detector screens every frame in-kernel and the
    margin positives escalate to the recognizer in the same dispatch."""
    from repro.core.chip import interpreter, networks
    from repro.serving import CascadePipeline, ChipServer
    det, rec = "face_detector", "owner_detector"
    progs = {det: networks.face_detector(), rec: networks.owner_detector()}
    arts = {n: artifacts_for(p, SEED + 20 + i) for i, (n, p) in
            enumerate(progs.items())}
    batch = 16
    server = ChipServer(progs, {n: a[1] for n, a in arts.items()},
                        batch=batch)
    casc = CascadePipeline(server, det, rec, margin=0.0, fused=True)
    frames = frames_for(progs[det], 2 * batch, SEED + 21)
    unit = server.executor.cascade_for(det, rec)    # the served unit
    ctrl = interpreter.CascadePlan.margin_ctrl(0.0, batch)
    compile_s = mosaic_compile(unit["fn"], unit["image"],
                               jax.numpy.asarray(frames[:batch]), ctrl)
    casc.submit_many(frames)
    results = sorted(casc.drain(), key=lambda r: r.rid)
    dl, dlab = reference(arts[det][0], progs[det], frames)
    rl, rlab = reference(arts[rec][0], progs[rec], frames)
    margin = dl[:, 1] - dl[:, 0]
    esc = margin >= 0.0
    check_equal("escalations", [r.escalated for r in results], esc)
    check_equal("detector labels", [r.detector_label for r in results], dlab)
    check_equal("cascade labels", [r.label for r in results],
                np.where(esc, rlab, dlab))
    check_equal("cascade logits", np.stack([r.logits for r in results]),
                np.where(esc[:, None], rl, dl))
    return dict(frames=len(results), escalated=int(esc.sum()),
                dispatches=casc.fused_dispatches,
                compile_s=round(compile_s, 2))


def phase_e() -> dict:
    """Delta-gated video: skipped frames answer from the resident cache,
    so every answer must still equal the reference on its own frame."""
    from repro.core.chip import interpreter, networks
    from repro.serving import ChipServer
    from repro.serving.temporal import TemporalPipeline
    from repro.serving.traffic import video_trace
    name = "cifar9_s4"
    program = networks.REGISTRY[name]()
    folded, packed = artifacts_for(program, SEED + 30)
    batch = 16
    server = ChipServer({name: program}, {name: packed}, batch=batch)
    pipe = TemporalPipeline(server, name, threshold=1.0)
    io = program.instrs[0]
    trace = video_trace((io.height, io.width, io.in_channels), 4,
                        streams=batch, seed=SEED + 31, change_rate=0.5,
                        levels=2 ** io.bits)
    unit = server.executor.delta_for(name)
    last, llog = unit["plan"].init_state(batch)
    ctrl = interpreter.DeltaPlan.delta_ctrl(1.0, batch)
    compile_s = mosaic_compile(unit["fn"], unit["image"],
                               jax.numpy.asarray(trace.frames[0]), last,
                               llog, ctrl)
    frames = []
    for t in range(len(trace)):
        for s in range(trace.streams):
            pipe.submit(trace.frames[t, s])
            frames.append(trace.frames[t, s])
    results = pipe.drain()
    check_results(results, np.stack(frames), folded, program, name)
    return dict(frames=len(results), computed=pipe.computed,
                skipped=pipe.skipped, compile_s=round(compile_s, 2))


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------

def phase_sharded(devs) -> dict:
    """cifar9_s1 with frames scattered over a 4-device serving mesh."""
    from repro.core.chip import networks
    from repro.distributed import sharding
    from repro.serving import ChipServer
    name = "cifar9_s1"
    program = networks.REGISTRY[name]()
    folded, packed = artifacts_for(program, SEED)
    mesh = sharding.serve_mesh(devs)
    batch = 32
    server = ChipServer({name: program}, {name: packed}, batch=batch,
                        mesh=mesh)
    ex = server.executor
    frames = frames_for(program, 2 * batch, SEED + 1)
    compile_s = mosaic_compile(
        ex._fns[name], ex.artifacts[name],
        sharding.scatter_frames(mesh, jax.numpy.asarray(frames[:batch])))
    server.submit_many(name, frames)
    check_results(server.drain(), frames, folded, program, name)
    return dict(frames=len(frames), devices=len(devs),
                compile_s=round(compile_s, 2))


def phase_fleet(devs) -> dict:
    """Four one-chip replicas; host0 dies mid-stream, its frames migrate
    and a replacement comes up on its chip.  Every replica's outputs must
    land on its own chip."""
    from repro.core.chip import networks
    from repro.distributed import sharding
    from repro.serving import FaultInjector, ServeFleet
    name = "cifar9_s1"
    program = networks.REGISTRY[name]()
    folded, packed = artifacts_for(program, SEED)
    batch = 16
    wave = 4 * batch               # one block of frames per replica
    # host0 dies at the start of the second wave's dispatch, holding that
    # wave's block: its frames migrate, and the third wave reaches the
    # replacement
    fleet = ServeFleet({name: program}, {name: packed}, replicas=4,
                       batch=batch, devices=devs, replace=True,
                       injector=FaultInjector("host0", after_served=wave))
    frames = frames_for(program, 3 * wave, SEED + 2)
    results = []
    for w in range(3):
        fleet.submit_many(name, frames[w * wave:(w + 1) * wave])
        results.extend(fleet.step())
    results.extend(fleet.drain())
    check_results(results, frames, folded, program, "fleet")
    st = fleet.stats()
    if st.failed_replicas != ("host0",) or "host0r1" not in fleet.replicas:
        raise AssertionError(f"failover did not happen: failed "
                             f"{st.failed_replicas}, live {fleet.live_replicas}")
    if st.migrated_frames == 0 or \
            sum(st.replicas["host0r1"].served.values()) == 0:
        raise AssertionError(f"no frames migrated ({st.migrated_frames}) "
                             f"or the replacement served none")
    placed = {}
    probe = jax.numpy.asarray(frames[:batch])
    for rname in fleet.live_replicas:
        server = fleet.replicas[rname]
        ex = server.executor
        logits, _ = ex._fns[name](ex.artifacts[name],
                                  sharding.scatter_frames(server.mesh, probe))
        placed[rname] = {d.id for d in logits.devices()}
    owners = [next(iter(d)) for d in placed.values() if len(d) == 1]
    if len(owners) != 4 or len(set(owners)) != 4:
        raise AssertionError(f"replica outputs are not on four distinct "
                             f"chips: {placed}")
    return dict(frames=len(results), migrated=st.migrated_frames,
                recovery_ms=st.recovery_ms,
                served={n: sum(r.served.values())
                        for n, r in st.replicas.items()},
                devices={n: sorted(d) for n, d in placed.items()})


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (sharded server and "
                         "fleet failover) on four chips")
    args = ap.parse_args(argv)
    count = 4 if args.four_chips else 1
    devs = require_tpu(count)
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} (of {len(jax.devices())} visible)")

    from repro.kernels import cache as warmcache
    log(f"compile cache: {warmcache.enable_persistent()} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")

    if args.four_chips:
        phases = [("s: sharded ChipServer cifar9_s1, batch 32",
                   lambda: phase_sharded(devs)),
                  ("f: 4-replica fleet, host0 killed and replaced",
                   lambda: phase_fleet(devs))]
    else:
        phases = [("a: ChipServer cifar9_s1 staged", phase_a),
                  ("b: ChipServer cifar9_s1 megakernel", phase_b),
                  ("c: shared 4xS4 composite", phase_c),
                  ("d: fused cascade face->owner", phase_d),
                  ("e: temporal delta gate cifar9_s4", phase_e)]
    failed = []
    t_all = time.perf_counter()
    for label, fn in phases:
        t0 = time.perf_counter()
        try:
            info = fn()
        except Exception as e:                      # report, then fail
            failed.append(label)
            log(f"phase {label}: FAIL after {time.perf_counter() - t0:.1f} s: "
                f"{type(e).__name__}: {str(e)[:2000]}")
            continue
        log(f"phase {label}: ok in {time.perf_counter() - t0:.1f} s {info}")
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"device {d.id} memory: peak {stats.get('peak_bytes_in_use')} "
            f"of {stats.get('bytes_limit')} bytes")
    log(f"total {time.perf_counter() - t_all:.1f} s")
    if failed:
        log(f"FAILED phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
