"""Kernel-level microbenchmark: the packed-domain inference pipeline.

Three measurements, all on this host (CPU XLA; on TPU the same code
lowers through Mosaic):

1. packed XNOR-popcount matmul vs float matmul (the seed's original
   data-movement demonstration, kept as a trend anchor);
2. the fused batched pipeline (``InferencePlan``: single IO pack, fused
   conv->threshold->pool->repack stages, packed hidden FC) vs the seed
   path (per-image ``jax.vmap`` conv kernel + float comparator + repack
   at every layer boundary) on a full benchmark program over a streaming
   batch — this is the end-to-end win of keeping feature maps bit-packed
   — plus a per-layer timing breakdown of the staged path;
3. the whole-network **megakernel** (weight image VMEM-resident, feature
   maps in VMEM scratch, frame tiles double-buffered through one
   ``pallas_call``) vs the staged plan, with the HBM bytes each mode
   moves (``energy.hbm_traffic``) — the all-memory-on-chip headline.
   Tile sizes come from the **persistent autotuner**
   (``kernels.autotune``): the bench tunes (bb, ft) / (bf, bb) for its
   programs on this backend, records the winners in the JSON cache
   (``BENCH_autotune.json``, shipped next to the bench baseline and
   uploaded as a CI artifact) and then benches through the cache-resolved
   tiles — exactly the warm path a deployment hits;
4. frames/sec of the deployed plan, the serving-throughput headline;
5. frames/sec through the chip-tier serving subsystem (``ChipServer``):
   the same packed plan behind the request queue / static-batch
   scheduler, single-program, with two programs resident (S-mode
   multi-program batching), with double-buffered submission
   (``prefetch=True``) — and, when more than one device is visible
   (e.g. ``XLA_FLAGS=--xla_force_host_platform_device_count=4``), over
   the sharded serving mesh;
6. **shared-array dispatch**: four S=4 programs resident at once, served
   time-interleaved (solo dispatches at 25% array occupancy) vs through
   ``ChipServer(shared=True)`` composite dispatches (one ``pallas_call``
   per batch runs all four sub-arrays concurrently) — the paired
   speedup, both frames/s figures and the measured ``array_utilization``
   go into the baseline, and the regression guard holds the speedup
   floor at 1.0;
7. the **always-on cascade** (face detector -> owner recognizer): the
   measured chip-model uJ/frame of screening every frame with the 0.92
   uJ S=4 detector and escalating only logit-margin positives to the
   14.4 uJ S=1 recognizer, vs running the recognizer on every frame —
   ``cascade_savings_vs_recognizer`` is floored at 1.0 by the
   regression guard (the cascade must never cost more than the big net
   alone), plus the cascade's host-side frames/s;
8. the **operating-point controller**: a cifar9 family served under a
   tightened energy budget — ``controller_downshift_ratio`` records the
   fraction of dispatches the controller moved below the top operating
   point (0 would mean the budget knob does nothing);
9. **continuous batching**: a seeded Poisson arrival trace replayed in
   real time against the static policy and the SLO-aware continuous
   policy — p50/p95/p99 input-to-label latency, padding ratio, and
   uJ/frame for both, with ``serve_p99_speedup_vs_static`` and
   ``serve_energy_ratio_vs_static`` floored at 1.0 (continuous must win
   both on the streaming workload) and the per-frame latency traces
   written to ``benchmarks/out/BENCH_latency_trace.json``;
10. **temporal delta gating**: the same seeded video trace (static
    backgrounds + moving patches, committed seed) replayed through the
    delta-gated pipeline at threshold 1 (skip bit-identical frames)
    and at ``-inf`` (gate off = recompute everything) — paired rounds
    give ``temporal_speedup_vs_full`` (floored at 1.0) and the
    chip-model ``temporal_uj_per_frame`` must undercut the ungated
    bill at perfect label agreement.

Results go to ``benchmarks/out/BENCH_fresh.json`` (override with
``BENCH_KERNELS_JSON``; the committed baseline refresh below writes to
the repo root, everything else stays out of the tree);
``benchmarks/check_regression.py`` compares a fresh run against the
*committed* baseline ``BENCH_kernels.json`` and fails CI when the
frames/s keys regress more than 10% (ratio floors on any host; absolute
frames/s when the host class matches).  To refresh the baseline after an
intentional perf change::

    BENCH_KERNELS_JSON=BENCH_kernels.json \
        PYTHONPATH=src python benchmarks/kernel_microbench.py

Results are written to ``BENCH_kernels.json`` so CI keeps a perf
trajectory across PRs.  Exit 0 iff all paths are bit-exact vs their
oracles.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import binarize
from repro.core.chip import energy, interpreter, networks, neuron_array as na
from repro.kernels import autotune, ops, ref
from repro.kernels import binary_conv2x2 as _bc

# default to a fresh-run file under the (gitignored) scratch directory:
# the committed BENCH_kernels.json baseline is only overwritten on an
# explicit BENCH_KERNELS_JSON=BENCH_kernels.json
BENCH_JSON = os.environ.get("BENCH_KERNELS_JSON",
                            os.path.join("benchmarks", "out",
                                         "BENCH_fresh.json"))


def _bench(fn, *args, iters=5):
    """Best-of-iters wall time (us): the min is the least noisy estimator
    on a shared host — contention only ever adds time."""
    jax.block_until_ready(fn(*args))              # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6  # us


def _seed_vmap_forward(program, folded, images):
    """The seed inference path, reproduced verbatim as the baseline: a
    per-image vmap of the 3D conv kernel, float comparator, float pool,
    and a pack/unpack round-trip at *every* layer boundary."""
    ci = fi = 0
    x = None
    from repro.core.chip import isa
    for ins in program.instrs:
        if isinstance(ins, isa.IOInstr):
            x = na.thermometer_encode(images, ins.bits, ins.channels)
        elif isinstance(ins, isa.ConvInstr):
            p = folded["conv"][ci]
            c = x.shape[-1]
            f = p["w"].shape[0]
            x_words = binarize.pack_signs(x, axis=-1)
            w_words = binarize.pack_signs(p["w"].reshape(f, 4, c), axis=-1)
            conv = lambda img: _bc.binary_conv2x2(
                img, w_words, c=c, interpret=ops.default_interpret())
            s = jax.vmap(conv)(x_words).astype(jnp.float32)
            x = na.comparator(s, p["tau"], p["flip"])
            if ins.maxpool:
                x = na.maxpool2x2(x)
            ci += 1
        else:
            if x.ndim == 4:
                x = x.reshape(x.shape[0], -1)
            p = folded["fc"][fi]
            s = na.fc_packed(x, p["w"])
            x = s if ins.final else binarize.hard_sign(s)
            fi += 1
    return x, jnp.argmax(x, axis=-1)


def _bench_matmul(results):
    key = jax.random.PRNGKey(0)
    M, K, N = 512, 1024, 512
    a = jnp.where(jax.random.bernoulli(key, shape=(M, K)), 1, -1).astype(jnp.int8)
    w = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(1), shape=(K, N)),
                  1, -1).astype(jnp.int8)

    a_f = a.astype(jnp.float32)
    w_f = w.astype(jnp.float32)
    a_words = ops.pack(a)
    w_words = ops.pack(w.T)

    float_mm = jax.jit(lambda x, y: x @ y)
    packed_mm = jax.jit(lambda x, y: ref.xnor_matmul_packed_ref(x, y, K))

    t_float = _bench(float_mm, a_f, w_f)
    t_packed = _bench(packed_mm, a_words, w_words)
    ok = bool(jnp.all(packed_mm(a_words, w_words).astype(jnp.float32)
                      == a_f @ w_f))

    print(f"\n== XNOR-popcount vs float matmul ({M}x{K}x{N}) ==")
    print(f"float f32 matmul : {t_float:9.0f} us")
    print(f"packed xnor path : {t_packed:9.0f} us   "
          f"({t_float/t_packed:.1f}x vs float on CPU XLA)")
    print(f"bitpacked operand bytes: {a_words.nbytes + w_words.nbytes} "
          f"vs float {a_f.nbytes + w_f.nbytes} "
          f"({(a_f.nbytes + w_f.nbytes)/(a_words.nbytes + w_words.nbytes):.0f}x "
          "bandwidth density)")
    print(f"exact match vs float oracle: {ok}")
    results["xnor_matmul_us"] = round(t_packed, 1)
    results["float_matmul_us"] = round(t_float, 1)
    results["matmul_speedup_vs_float"] = round(t_float / t_packed, 2)
    return ok


def _bench_staged_layers(plan, packed, imgs, results):
    """Per-layer timing breakdown of the staged path: where do the µs go
    (and which layer boundaries the megakernel fuses away)."""
    x = imgs
    ci = fi = 0
    rows = []
    for st in plan.stages:
        if isinstance(st, interpreter._IOStage):
            fn = jax.jit(lambda a, st=st: na.thermometer_encode_packed(
                a, st.bits, st.channels))
            name = "IO encode"
        elif isinstance(st, interpreter._ConvStage):
            p = packed["conv"][ci]
            fn = jax.jit(lambda a, p=p, st=st: ops.binary_conv2x2_block(
                a, p["w_words"], p["tau"], p["flip"], st.c, pool=st.pool))
            name = f"conv{ci}" + ("+pool" if st.pool else "")
            ci += 1
        else:
            if x.ndim == 4:
                x = x.reshape(x.shape[0], -1)
            p = packed["fc"][fi]
            fn = jax.jit(lambda a, p=p, st=st: ops.xnor_matmul(
                a, p["w_words"], st.in_features, pack_out=st.pack_out))
            name = f"fc{fi}" + (" (final)" if st.final else "")
            fi += 1
        rows.append((name, _bench(fn, x, iters=3)))
        out = fn(x)
        if rows[-1][0].startswith("fc") and not st.final and not st.pack_out:
            out = binarize.pack_signs(
                binarize.hard_sign(out.astype(jnp.float32)), axis=-1)
        x = out
    print("staged per-layer breakdown:")
    for name, t in rows:
        print(f"  {name:12s}: {t:8.0f} us")
    results["staged_layer_us"] = {name: round(t, 1) for name, t in rows}


def _bench_pipeline(results):
    """Fused staged plan vs the seed per-image-vmap path, full program
    over a streaming batch, with the staged per-layer breakdown."""
    program = networks.mnist5()
    batch = 64
    key = jax.random.PRNGKey(2)
    params = interpreter.init_params(key, program)
    io = program.instrs[0]
    imgs = jax.random.randint(
        jax.random.PRNGKey(3), (batch, io.height, io.width, io.in_channels),
        0, 2 ** io.bits)
    _, params = interpreter.forward_train(params, program, imgs[:8])
    folded = interpreter.fold_params(params, program)
    packed = interpreter.pack_folded(folded)

    plan = interpreter.compile_plan(program)
    # tune the staged conv tiles for this (program, backend, batch) and
    # bench through the cache so the trajectory tracks the warm path
    tuned = autotune.tune_staged_conv(plan, packed, imgs,
                                      bf_candidates=(32, 64),
                                      bb_candidates=(8, 16), iters=2)
    print(f"autotuned staged conv tiles: bf={tuned['bf']} bb={tuned['bb']}")
    results["staged_conv_tuned_bf"] = tuned["bf"]
    results["staged_conv_tuned_bb"] = tuned["bb"]
    # interpret=None -> per-backend choice: Python interpret on CPU,
    # Mosaic lowering on a real TPU (keeps the perf trajectory honest)
    fused = jax.jit(lambda pk, im: plan.forward(pk, im))
    seed = jax.jit(lambda fl, im: _seed_vmap_forward(program, fl, im))

    # paired alternation (see _bench_megakernel): each back-to-back pair
    # sees the same host load, so the median of per-pair ratios is a
    # load-robust speedup; the us fields report best-of-reps.
    jax.block_until_ready(fused(packed, imgs))
    jax.block_until_ready(seed(folded, imgs))
    t_fused = t_seed = float("inf")
    ratios = []
    for _ in range(7):
        t0 = time.perf_counter()
        jax.block_until_ready(seed(folded, imgs))
        ts = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        jax.block_until_ready(fused(packed, imgs))
        tf = (time.perf_counter() - t0) * 1e6
        t_seed, t_fused = min(t_seed, ts), min(t_fused, tf)
        ratios.append(ts / tf)

    logits_f, labels_f = fused(packed, imgs)
    logits_s, labels_s = seed(folded, imgs)
    ok = bool(jnp.all(logits_f == logits_s) and jnp.all(labels_f == labels_s))
    fps = batch / (t_fused * 1e-6)
    speedup = sorted(ratios)[len(ratios) // 2]

    print(f"\n== Packed pipeline ({program.instrs[1].features}-wide mnist5, "
          f"batch={batch}) ==")
    print(f"seed per-image vmap path : {t_seed:9.0f} us/batch "
          "(int32->float->repack at every layer)")
    print(f"fused batched plan       : {t_fused:9.0f} us/batch "
          "(bit-packed end to end)")
    print(f"  -> {speedup:.2f}x, {fps:,.0f} frames/s host-sim throughput")
    print(f"fused plan bit-exact vs seed path: {ok}")
    _bench_staged_layers(plan, packed, imgs, results)
    results["pipeline_seed_vmap_us"] = round(t_seed, 1)
    results["pipeline_fused_us"] = round(t_fused, 1)
    results["pipeline_fused_speedup"] = round(speedup, 2)
    results["pipeline_frames_per_s"] = round(fps, 1)
    results["pipeline_batch"] = batch
    return ok, speedup


def _bench_megakernel(results):
    """Whole-network megakernel vs the staged plan on the paper's always-on
    benchmark net (cifar9 at the S=4 minimum-energy point): 8 conv layers
    whose inter-layer feature maps the staged path round-trips through HBM
    and the megakernel keeps in VMEM scratch."""
    program = networks.cifar9(4)
    batch, bb = 32, 16
    key = jax.random.PRNGKey(4)
    params = interpreter.init_params(key, program)
    io = program.instrs[0]
    imgs = jax.random.randint(
        jax.random.PRNGKey(5), (batch, io.height, io.width, io.in_channels),
        0, 2 ** io.bits)
    _, params = interpreter.forward_train(params, program, imgs[:4])
    packed = interpreter.fold_params(params, program, packed=True)
    image = interpreter.build_weight_image(packed, program)
    plan = interpreter.compile_plan(program)
    # tune (bb, ft) for this (program, backend, batch); the mega fn below
    # resolves its tiles from the cache (bb=None/ft=None), i.e. the bench
    # measures the autotuned f-tiled kernel a warm deployment runs
    tuned = autotune.tune_mega(plan, image, imgs,
                               bb_candidates=(4, 8, bb),
                               ft_candidates=(0, 32), iters=2)
    print(f"autotuned megakernel tiles: bb={tuned['bb']} ft={tuned['ft']}")
    bb = tuned["bb"]
    staged = jax.jit(lambda pk, im: plan.forward(pk, im))
    mega = jax.jit(lambda ig, im: plan.forward_mega(ig, im))

    # alternate the contenders rep by rep: each back-to-back pair sees the
    # same host load, so the *median of per-pair ratios* is a far less
    # noisy speedup estimator on a shared CPU than comparing two
    # independent minima (per-pair ratios scatter with load spikes, the
    # median cancels them); the us fields still report best-of-reps.
    jax.block_until_ready(staged(packed, imgs))
    jax.block_until_ready(mega(image, imgs))
    t_staged = t_mega = float("inf")
    ratios = []
    for _ in range(15):
        t0 = time.perf_counter()
        jax.block_until_ready(staged(packed, imgs))
        ts = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        jax.block_until_ready(mega(image, imgs))
        tm = (time.perf_counter() - t0) * 1e6
        t_staged, t_mega = min(t_staged, ts), min(t_mega, tm)
        ratios.append(ts / tm)

    logits_st, labels_st = staged(packed, imgs)
    logits_mg, labels_mg = mega(image, imgs)
    ok = bool(jnp.all(logits_mg == logits_st)
              and jnp.all(labels_mg == labels_st))
    speedup = sorted(ratios)[len(ratios) // 2]
    fps = batch / (t_mega * 1e-6)
    traffic = energy.hbm_traffic(program, batch=batch)

    print(f"\n== Megakernel (cifar9 S=4, 9 layers, batch={batch}, "
          f"bb={bb}) ==")
    print(f"staged plan (per-layer calls): {t_staged:9.0f} us/batch")
    print(f"resident megakernel          : {t_mega:9.0f} us/batch "
          f"({speedup:.2f}x, {fps:,.0f} frames/s)")
    print(f"HBM bytes/batch: staged {traffic.staged_bytes/1e6:.2f} MB -> "
          f"megakernel {traffic.mega_bytes/1e6:.2f} MB "
          f"({traffic.reduction:.1f}x less off-chip traffic; "
          f"{traffic.weight_image_bytes/1024:.0f} kB weight image resident)")
    print(f"megakernel bit-exact vs staged plan: {ok}")
    results["megakernel_us"] = round(t_mega, 1)
    results["megakernel_staged_us"] = round(t_staged, 1)
    results["megakernel_bb"] = bb
    results["megakernel_ft"] = tuned["ft"]
    results["megakernel_batch"] = batch
    results["megakernel_program"] = "cifar9_s4"
    results["megakernel_speedup_vs_staged"] = round(speedup, 2)
    results["megakernel_frames_per_s"] = round(fps, 1)
    results["hbm_staged_bytes_per_batch"] = traffic.staged_bytes
    results["hbm_megakernel_bytes_per_batch"] = traffic.mega_bytes
    results["hbm_traffic_reduction"] = round(traffic.reduction, 2)
    return ok


def _bench_serve(results):
    """Serving-layer throughput: the packed plan behind the scheduler.

    Artifacts and synthetic frame streams come from the serving driver's
    own helpers (``launch.chip_serve``) so the bench measures exactly the
    admission path the driver serves.
    """
    from repro.distributed import sharding
    from repro.launch import chip_serve
    from repro.serving import ChipServer

    batch, n_frames = 8, 32
    progs = {"mnist5": networks.mnist5(),
             "wake": networks.mnist5(classes=2)}
    arts, frames, oracle = {}, {}, {}
    for i, (name, prog) in enumerate(progs.items()):
        arts[name] = chip_serve.build_artifact(prog, seed=10 + i,
                                               warm_bn=True)
        frames[name] = chip_serve.frame_stream(prog, n_frames, seed=20 + i)
        plan = interpreter.compile_plan(prog)
        oracle[name] = np.asarray(
            jax.jit(lambda pk, im, plan=plan: plan.forward(pk, im)[1])(
                arts[name], jnp.asarray(frames[name])))

    def serve(names, label, mesh=None, prefetch=False):
        server = ChipServer({n: progs[n] for n in names},
                            {n: arts[n] for n in names},
                            batch=batch, mesh=mesh, prefetch=prefetch)
        for n in names:                        # warm the compile caches
            server.submit_many(n, frames[n][:batch])
        server.drain()
        dt = float("inf")
        for _round in range(3):                # best-of-3 timed drains
            t0 = time.perf_counter()
            for i in range(n_frames):          # interleaved arrival
                for n in names:
                    server.submit(n, frames[n][i])
            res = server.drain()
            dt = min(dt, time.perf_counter() - t0)
        per = {n: [] for n in names}
        for r in sorted(res, key=lambda r: r.rid):   # per-program FIFO
            per[r.program].append(r.label)
        ok = all(np.array_equal(np.array(per[n]), oracle[n][:len(per[n])])
                 for n in names)
        fps = len(res) / dt
        print(f"{label:24s}: {fps:10,.0f} frames/s "
              f"({len(res)} frames, {dt*1e3:.0f} ms, bit-exact={ok})")
        return fps, ok

    print(f"\n== Chip-tier serving (batch={batch}, {jax.device_count()} "
          "device(s)) ==")
    fps_1, ok_1 = serve(["mnist5"], "single program")
    fps_m, ok_m = serve(list(progs), "two programs resident")
    fps_p, ok_p = serve(["mnist5"], "single program, prefetch",
                        prefetch=True)
    results["serve_frames_per_s"] = round(fps_1, 1)
    results["serve_frames_per_s_multi"] = round(fps_m, 1)
    results["serve_frames_per_s_prefetch"] = round(fps_p, 1)
    results["serve_batch"] = batch
    ok = ok_1 and ok_m and ok_p
    if jax.device_count() > 1:
        mesh = sharding.serve_mesh()
        fps_s, ok_s = serve(["mnist5"],
                            f"sharded x{mesh.devices.size}", mesh=mesh)
        results["serve_frames_per_s_sharded"] = round(fps_s, 1)
        results["serve_devices"] = int(mesh.devices.size)
        ok = ok and ok_s
    return ok


def _bench_continuous_serve(results):
    """Continuous batching vs static dispatch on one committed Poisson
    trace: frames replayed at their seeded arrival offsets against both
    policies, same seed, same host.  The arrival rate is calibrated from
    the measured full-batch dispatch time (rate = 0.4 / T_batch, so
    arrivals are much slower than a full-batch service and the static
    policy's pad is pure waste), and the SLO from the same measurement,
    making the bench regime host-independent.  The continuous server
    runs with a small headroom so its window target stays pinned at 1 in
    this regime (nominal target 0.2 frames — the EWMA estimate would
    have to read 5x the true rate before the window ever holds a frame):
    the comparison is then *structural* — per-frame service time T1 vs
    the static policy's always-T_batch — rather than riding the replay
    loop's millisecond scheduling jitter.  Continuous batching must
    deliver a lower p99 input-to-label latency at equal-or-better uJ/f
    and a strictly lower padding ratio —
    ``serve_p99_speedup_vs_static`` and ``serve_energy_ratio_vs_static``
    are >= 1.0 floors in ``check_regression.py``.  The per-frame latency
    traces go to ``BENCH_latency_trace.json`` (CI uploads them next to
    the bench JSON)."""
    from repro.launch import chip_serve
    from repro.serving import (ChipServer, ContinuousPolicy, poisson_trace,
                               replay)

    batch, n_frames, seed = 16, 64, 123
    prog = networks.mnist5()
    art = chip_serve.build_artifact(prog, seed=30, warm_bn=True)
    bank = chip_serve.frame_stream(prog, batch, seed=31)
    plan = interpreter.compile_plan(prog)
    seq = np.stack([bank[i % batch] for i in range(n_frames)])
    oracle = np.asarray(jax.jit(
        lambda pk, im: plan.forward(pk, im)[1])(art, jnp.asarray(seq)))

    def make_server(policy, slo_ms=50.0):
        if policy == "continuous":
            policy = ContinuousPolicy(slo_ms=slo_ms, headroom=0.25,
                                      deadline_frac=0.25)
        server = ChipServer({"m": prog}, {"m": art}, batch=batch,
                            policy=policy, slo_ms=slo_ms)
        # warm every bucket size the continuous ladder can dispatch
        # (1, 2, 4, 8, 16) so no timed frame pays a jit compile; warm
        # frames go in unstamped (t_submit=0) and the ledger is wiped
        # after, so compile stalls never reach the latency percentiles
        sz = 1
        while sz <= batch:
            for f in bank[:sz]:
                server.submit("m", f, t_submit=0.0)
            server.drain()
            sz *= 2
        server.reset_stats()
        return server

    # calibrate: T_batch = one warm full-batch dispatch on this host
    server = make_server("static")
    t_full = float("inf")
    for _ in range(5):
        server.submit_many("m", bank)
        t0 = time.perf_counter()
        server.drain()
        t_full = min(t_full, time.perf_counter() - t0)
    rate = 0.4 / t_full                  # arrivals far slower than service
    slo_ms = max(2.0, 2 * t_full * 1e3)
    trace = poisson_trace(["m"], rate=rate, n=n_frames, seed=seed)

    runs = {p: dict(server=make_server(p, slo_ms=slo_ms), ok=True)
            for p in ("static", "continuous")}
    # paired best-of-5: each round replays the SAME trace through both
    # policies back to back, and each policy keeps its lowest-p99 round
    # — host contention only ever adds latency, so the min is the
    # least-noisy tail estimator (same idiom as the paired us benches)
    for _round in range(5):
        for policy, r in runs.items():
            server = r["server"]
            server.reset_stats()
            res = replay(server, trace, {"m": bank})
            stats = server.stats()
            labels = [x.label for x in sorted(res, key=lambda x: x.rid)]
            r["ok"] = r["ok"] and np.array_equal(np.array(labels), oracle)
            if "stats" not in r or stats.p99_ms < r["stats"].p99_ms:
                r["stats"], r["trace"] = stats, server.latency_trace()
    for policy, r in runs.items():
        stats = r["stats"]
        print(f"{policy:12s}: p50 {stats.p50_ms:7.2f} / p99 "
              f"{stats.p99_ms:7.2f} ms, padding {stats.padding_ratio:.3f}, "
              f"{stats.chip.uj_per_frame:.2f} uJ/f, "
              f"{stats.dispatches} dispatches, bit-exact={r['ok']}")

    st, ct = runs["static"]["stats"], runs["continuous"]["stats"]
    ok = runs["static"]["ok"] and runs["continuous"]["ok"]
    p99_speedup = st.p99_ms / ct.p99_ms if ct.p99_ms else 0.0
    uj_ratio = (st.chip.uj_per_frame / ct.chip.uj_per_frame
                if ct.chip.uj_per_frame else 0.0)

    print(f"\n== Continuous batching (poisson trace, {n_frames} frames at "
          f"{rate:,.0f} f/s, SLO {slo_ms:.1f} ms, seed {seed}) ==")
    print(f"p99 input-to-label : {st.p99_ms:.2f} -> {ct.p99_ms:.2f} ms "
          f"({p99_speedup:.2f}x)")
    print(f"padding ratio      : {st.padding_ratio:.3f} -> "
          f"{ct.padding_ratio:.3f}")
    print(f"uJ/frame           : {st.chip.uj_per_frame:.2f} -> "
          f"{ct.chip.uj_per_frame:.2f} ({uj_ratio:.2f}x)")
    results["serve_p50_ms"] = round(ct.p50_ms, 3)
    results["serve_p95_ms"] = round(ct.p95_ms, 3)
    results["serve_p99_ms"] = round(ct.p99_ms, 3)
    results["serve_p50_ms_static"] = round(st.p50_ms, 3)
    results["serve_p99_ms_static"] = round(st.p99_ms, 3)
    results["serve_padding_ratio_continuous"] = round(ct.padding_ratio, 4)
    results["serve_padding_ratio_static"] = round(st.padding_ratio, 4)
    results["serve_uj_per_frame_continuous"] = round(ct.chip.uj_per_frame, 3)
    results["serve_uj_per_frame_static"] = round(st.chip.uj_per_frame, 3)
    results["serve_frames_per_s_continuous"] = round(ct.host_frames_per_s, 1)
    results["serve_p99_speedup_vs_static"] = round(p99_speedup, 2)
    results["serve_energy_ratio_vs_static"] = round(uj_ratio, 2)
    results["serve_traffic_kind"] = trace.kind
    results["serve_traffic_seed"] = seed
    results["serve_traffic_rate"] = round(rate, 1)
    results["serve_slo_ms"] = round(slo_ms, 2)

    trace_json = os.environ.get("BENCH_LATENCY_JSON",
                                os.path.join("benchmarks", "out",
                                             "BENCH_latency_trace.json"))
    os.makedirs(os.path.dirname(trace_json) or ".", exist_ok=True)
    with open(trace_json, "w") as f:
        json.dump({"meta": dict(kind=trace.kind, seed=seed,
                                rate=round(rate, 1), n=n_frames,
                                slo_ms=round(slo_ms, 2)),
                   "static": runs["static"]["trace"],
                   "continuous": runs["continuous"]["trace"]}, f, indent=2)
    print(f"wrote per-frame latency traces to {trace_json}")
    return ok


def _bench_shared_serve(results):
    """Shared-array dispatch: four S=4 programs resident at once, served
    time-interleaved (each solo dispatch occupies one 64-channel
    sub-array, 25% of the array) vs through ``ChipServer(shared=True)``
    (one composite ``pallas_call`` per batch runs all four sub-arrays
    concurrently).  Paired alternation gives a load-robust speedup; the
    regression guard floors it at 1.0."""
    from repro.launch import chip_serve
    from repro.serving import ChipServer

    batch, n_frames = 8, 16
    progs = {"mnist5": networks.mnist5(),
             "wake": networks.mnist5(classes=2),
             "tri": networks.mnist5(classes=3),
             "five": networks.mnist5(classes=5)}
    arts, frames, oracle = {}, {}, {}
    for i, (name, prog) in enumerate(progs.items()):
        arts[name] = chip_serve.build_artifact(prog, seed=40 + i,
                                               warm_bn=True)
        frames[name] = chip_serve.frame_stream(prog, n_frames, seed=60 + i)
        plan = interpreter.compile_plan(prog)
        oracle[name] = np.asarray(
            jax.jit(lambda pk, im, plan=plan: plan.forward(pk, im)[1])(
                arts[name], jnp.asarray(frames[name])))

    # tune the quad composite's (bb, ft) under its own fingerprint — the
    # shared server resolves them from the cache at dispatch time
    cplan, cimage = interpreter.pack_programs(progs, arts)
    tuned = autotune.tune_composite(
        cplan, cimage, tuple(jnp.asarray(frames[n][:batch]) for n in progs),
        bb_candidates=(4, 8), ft_candidates=(0, 32), iters=2)
    print(f"autotuned composite tiles: bb={tuned['bb']} ft={tuned['ft']}")
    results["shared_composite_tuned_bb"] = tuned["bb"]
    results["shared_composite_tuned_ft"] = tuned["ft"]

    def make_server(shared):
        server = ChipServer(progs, arts, batch=batch, shared=shared)
        for n in progs:                        # warm the compile caches
            server.submit_many(n, frames[n][:batch])
        server.drain()
        return server

    def timed_drain(server):
        t0 = time.perf_counter()
        for i in range(n_frames):              # interleaved arrival
            for n in progs:
                server.submit(n, frames[n][i])
        res = server.drain()
        dt = time.perf_counter() - t0
        per = {n: [] for n in progs}
        for r in sorted(res, key=lambda r: r.rid):
            per[r.program].append(r.label)
        ok = all(np.array_equal(np.array(per[n]), oracle[n][:n_frames])
                 for n in progs)
        return len(res) / dt, dt, ok

    solo, shared = make_server(False), make_server(True)
    fps_solo = fps_shared = 0.0
    ok = True
    ratios = []
    for _round in range(3):                    # paired rounds, same load
        f_a, dt_a, ok_a = timed_drain(solo)
        f_b, dt_b, ok_b = timed_drain(shared)
        fps_solo, fps_shared = max(fps_solo, f_a), max(fps_shared, f_b)
        ratios.append(dt_a / dt_b)
        ok = ok and ok_a and ok_b
    speedup = sorted(ratios)[len(ratios) // 2]
    util_solo = solo.stats().array_utilization
    util_shared = shared.stats().array_utilization

    print(f"\n== Shared-array dispatch (4 x S=4 resident, batch={batch}) ==")
    print(f"solo interleaved dispatch : {fps_solo:10,.0f} frames/s "
          f"(array utilization {util_solo:.2f})")
    print(f"shared composite dispatch : {fps_shared:10,.0f} frames/s "
          f"(array utilization {util_shared:.2f}, {speedup:.2f}x)")
    print(f"shared dispatch bit-exact vs solo oracle: {ok}")
    results["serve_frames_per_s_solo4"] = round(fps_solo, 1)
    results["serve_frames_per_s_shared"] = round(fps_shared, 1)
    results["serve_shared_speedup_vs_solo"] = round(speedup, 2)
    # array_utilization is the shared-dispatch path's occupancy (the CI
    # headline); _solo4 is the time-interleaved control at 1/S
    results["array_utilization"] = round(util_shared, 3)
    results["array_utilization_solo4"] = round(util_solo, 3)
    results["serve_shared_programs"] = len(progs)
    return ok


def _bench_cascade(results):
    """The paper's always-on hierarchy as a measured serving path: the
    S=4 face detector (0.92 uJ/f analogue) screens every frame, only
    logit-margin positives escalate to the S=1 owner recognizer (14.4
    uJ/f analogue).  The measured uJ/frame must stay strictly below
    running the recognizer on every frame at identical escalated labels
    — ``cascade_savings_vs_recognizer`` is a >= 1.0 floor in
    ``check_regression.py``."""
    from repro.launch import chip_serve
    from repro.serving import CascadePipeline, ChipServer

    batch, n_frames = 4, 12
    det, rec = networks.face_detector(), networks.owner_detector()
    progs = {"det": det, "rec": rec}
    arts = {n: chip_serve.build_artifact(p, seed=70 + i, warm_bn=True)
            for i, (n, p) in enumerate(progs.items())}
    frames = chip_serve.frame_stream(det, n_frames, seed=90)
    rec_plan = interpreter.compile_plan(rec)
    rec_oracle = np.asarray(jax.jit(
        lambda pk, im: rec_plan.forward(pk, im)[1])(
            arts["rec"], jnp.asarray(frames)))
    # calibrate the escalation threshold instead of eyeballing it: the
    # detector's own offline positive calls stand in for a labelled
    # held-out split (an untrained detector has no ground truth), and
    # calibrate_margin picks the *cheapest* margin whose escalations
    # still capture 95% of those positives — the margin becomes a
    # recall contract rather than the old median-margin heuristic
    from repro.serving import calibrate_margin
    det_plan = interpreter.compile_plan(det)
    det_logits = np.asarray(jax.jit(
        lambda pk, im: det_plan.forward(pk, im)[0])(
            arts["det"], jnp.asarray(frames)))
    margin = calibrate_margin(frames, det_logits.argmax(axis=1) == 1,
                              0.95, detector=det, artifact=arts["det"])

    def run_once():
        server = ChipServer(progs, arts, batch=batch)
        casc = CascadePipeline(server, "det", "rec", positive_class=1,
                               margin=margin)
        t0 = time.perf_counter()
        casc.submit_many(frames)
        out = casc.drain()
        dt = time.perf_counter() - t0
        return casc, out, dt

    run_once()                                 # warm the compile caches
    casc, out, dt = run_once()
    rep = casc.report()
    # escalated labels must be bit-exact vs the recognizer run offline
    # on those same frames
    ok = all(int(rec_oracle[c.rid]) == c.label
             for c in out if c.escalated)
    fps = len(out) / dt

    print(f"\n== Always-on cascade (face_detector -> owner_detector, "
          f"batch={batch}) ==")
    print(f"escalation rate    : {rep.escalation_rate:.2f} "
          f"({rep.escalated}/{rep.frames} frames)")
    print(f"cascade bill       : {rep.uj_per_frame:.2f} uJ/frame "
          f"(det {rep.detector_uj:.2f} + rate x rec {rep.recognizer_uj:.2f})")
    print(f"recognizer-on-all  : {rep.uj_per_frame_recognizer_only:.2f} "
          f"uJ/frame -> {rep.savings:.2f}x saved")
    print(f"host throughput    : {fps:,.0f} frames/s; escalated labels "
          f"bit-exact vs offline recognizer: {ok}")
    results["cascade_uj_per_frame"] = round(rep.uj_per_frame, 3)
    results["cascade_recognizer_only_uj_per_frame"] = round(
        rep.uj_per_frame_recognizer_only, 3)
    results["cascade_savings_vs_recognizer"] = round(rep.savings, 3)
    results["cascade_escalation_rate"] = round(rep.escalation_rate, 3)
    results["serve_frames_per_s_cascade"] = round(fps, 1)
    return ok


def _bench_cascade_fused(results):
    """In-kernel fused cascade vs the host-side cascade on the SAME
    replayed stream: one composite dispatch per detector batch (the
    escalation mask and the recognizer drain both live inside the
    kernel) against the host path's separate detector dispatches,
    result routing and deferred recognizer batches.  Paired alternation
    (see _bench_megakernel): each back-to-back pair sees the same host
    load, so the median of per-pair ratios is the speedup estimator —
    ``cascade_fused_speedup_vs_host`` is a >= 1.0 floor in
    ``check_regression.py``.  Labels must be bit-exact between the two
    paths (and vs the offline recognizer) on every run."""
    from repro.launch import chip_serve
    from repro.serving import CascadePipeline, ChipServer, calibrate_margin

    batch, n_frames = 4, 12
    det, rec = networks.face_detector(), networks.owner_detector()
    progs = {"det": det, "rec": rec}
    arts = {n: chip_serve.build_artifact(p, seed=70 + i, warm_bn=True)
            for i, (n, p) in enumerate(progs.items())}
    frames = chip_serve.frame_stream(det, n_frames, seed=123)
    rec_plan = interpreter.compile_plan(rec)
    rec_oracle = np.asarray(jax.jit(
        lambda pk, im: rec_plan.forward(pk, im)[1])(
            arts["rec"], jnp.asarray(frames)))
    det_plan = interpreter.compile_plan(det)
    det_logits = np.asarray(jax.jit(
        lambda pk, im: det_plan.forward(pk, im)[0])(
            arts["det"], jnp.asarray(frames)))
    margin = calibrate_margin(frames, det_logits.argmax(axis=1) == 1,
                              0.95, detector=det, artifact=arts["det"])

    def run(fused):
        server = ChipServer(progs, arts, batch=batch)
        casc = CascadePipeline(server, "det", "rec", margin=margin,
                               fused=fused)
        t0 = time.perf_counter()
        casc.submit_many(frames)
        out = sorted(casc.drain(), key=lambda c: c.rid)
        dt = time.perf_counter() - t0
        rep = casc.report()
        server.close()
        return out, dt, rep

    run(False)                                 # warm both compile caches
    run(True)
    t_host = t_fused = float("inf")
    ratios = []
    ok = True
    for _ in range(5):
        out_h, th, rep_h = run(False)
        out_f, tf, rep_f = run(True)
        t_host, t_fused = min(t_host, th), min(t_fused, tf)
        ratios.append(th / tf)
        ok = ok and all(
            (h.rid, h.label, h.escalated) == (f.rid, f.label, f.escalated)
            for h, f in zip(out_h, out_f))
        ok = ok and all(int(rec_oracle[c.rid]) == c.label
                        for c in out_f if c.escalated)
    speedup = sorted(ratios)[len(ratios) // 2]
    fps = n_frames / t_fused

    print(f"\n== Fused in-kernel cascade (same pair, one dispatch per "
          f"detector batch, batch={batch}) ==")
    print(f"host cascade       : {t_host * 1e3:8.1f} ms/stream")
    print(f"fused cascade      : {t_fused * 1e3:8.1f} ms/stream "
          f"({speedup:.2f}x, {fps:,.0f} frames/s)")
    print(f"fused bill         : {rep_f.uj_per_frame:.2f} uJ/frame "
          f"(host {rep_h.uj_per_frame:.2f}; escalation rate "
          f"{rep_f.escalation_rate:.2f})")
    print(f"fused labels bit-exact vs host + offline recognizer: {ok}")
    results["cascade_fused_speedup_vs_host"] = round(speedup, 2)
    results["cascade_fused_uj_per_frame"] = round(rep_f.uj_per_frame, 3)
    results["cascade_fused_ms_per_stream"] = round(t_fused * 1e3, 2)
    results["serve_frames_per_s_cascade_fused"] = round(fps, 1)
    return ok


def _bench_controller(results):
    """The operating-point controller under a tightened energy budget:
    a cifar9 family (full-depth S=4 + depth-truncated S=4) served with
    the budget pinned halfway between the two variants' steady-state
    powers, so the controller must visibly downshift —
    ``controller_downshift_ratio`` lands strictly between 0 and 1."""
    from repro.launch import chip_serve
    from repro.serving import ChipServer

    batch, n_frames = 4, 24
    fam = {"cifar9_s4": networks.cifar9(4),
           "cifar9_s4t": networks.cifar9_truncated()}
    arts = {n: chip_serve.build_artifact(p, seed=80 + i, warm_bn=True)
            for i, (n, p) in enumerate(fam.items())}
    pts = energy.operating_points(fam, networks.ACCURACY)
    powers = {p.name: p.power_uj_s for p in pts}
    budget = (max(powers.values()) + min(powers.values())) / 2

    def serve(budget_uj_s):
        server = ChipServer(fam, arts, batch=batch,
                            families={"cifar10": tuple(fam)},
                            budget_uj_s=budget_uj_s)
        server.submit_many("cifar10",
                           chip_serve.frame_stream(fam["cifar9_s4"],
                                                   n_frames, seed=95))
        server.drain()
        return server.stats()

    serve(None)                                # warm the compile caches
    stats = serve(budget)
    ok = 0.0 < stats.downshift_ratio < 1.0
    print(f"\n== Operating-point controller (cifar9_s4 <-> cifar9_s4t, "
          f"budget {budget:,.0f} uJ/s) ==")
    print(f"operating points   : " + " > ".join(
        f"{p.name}[{p.uj_per_frame:.2f}uJ/f, {powers[p.name]:,.0f}uJ/s]"
        for p in pts))
    print(f"variant dispatches : {stats.variant_dispatches} "
          f"(downshift ratio {stats.downshift_ratio:.2f}, "
          f"array utilization {stats.array_utilization:.2f})")
    print(f"energy billed      : {stats.energy_uj:,.0f} uJ under the "
          f"budget; mixes both points: {ok}")
    results["controller_downshift_ratio"] = round(stats.downshift_ratio, 3)
    results["controller_array_utilization"] = round(
        stats.array_utilization, 3)
    results["controller_budget_uj_s"] = round(budget, 1)
    return ok


def _bench_fleet(results):
    """Fleet failover + warm start on the wall clock.

    Two tracked numbers: ``replica_warm_start_speedup`` — bring-up time
    (construct a server AND serve its first batch, i.e. cold-start-to-
    first-served-frame) of a cold warm-start cache vs a hot one (floor
    >= 1.0 in ``check_regression.py``); and ``fleet_failover_recovery_ms``
    — kill-to-first-served-frame of the replacement replica a 2-host
    fleet spawns after a mid-stream host loss (lower-is-better latency
    key).  Zero frame loss and bit-exact labels vs the offline oracle
    are the pass condition."""
    from repro.kernels import cache as warmcache
    from repro.launch import chip_serve
    from repro.serving import ChipServer, FaultInjector, ServeFleet

    batch, n_frames = 4, 32
    prog = networks.mnist5()
    art = chip_serve.build_artifact(prog, seed=30, warm_bn=True)
    frames = chip_serve.frame_stream(prog, n_frames, seed=40)
    plan = interpreter.compile_plan(prog)
    oracle = np.asarray(jax.jit(
        lambda pk, im: plan.forward(pk, im)[1])(art, jnp.asarray(frames)))

    def bring_up():
        t0 = time.perf_counter()
        server = ChipServer({"mnist5": prog}, {"mnist5": art}, batch=batch)
        server.submit_many("mnist5", frames[:batch])
        server.drain()
        return time.perf_counter() - t0

    warmcache.invalidate()                     # measure a true cold start
    t_cold = bring_up()
    t_warm = min(bring_up() for _ in range(3))
    speedup = t_cold / t_warm

    # -- failover: kill host0 mid-stream, replacement must serve -----------
    inj = FaultInjector("host0", after_served=batch)
    fleet = ServeFleet({"mnist5": prog}, {"mnist5": art},
                       replicas=2, batch=batch, injector=inj, replace=True)
    res = []
    for i in range(0, n_frames, batch):        # interleave admit/serve so
        for f in frames[i:i + batch]:          # the kill lands mid-stream
            fleet.submit("mnist5", f)          # and the replacement gets
        res.extend(fleet.step())               # fresh traffic
    res.extend(fleet.drain())
    st = fleet.stats()
    got = {r.rid: r.label for r in res}
    ok = (len(got) == n_frames
          and all(got[i] == int(oracle[i]) for i in range(n_frames))
          and st.billed == st.total_served + sum(st.padded.values())
          and st.failed_replicas == ("host0",)
          and fleet.recovery_ms is not None)
    recovery_ms = fleet.recovery_ms if fleet.recovery_ms is not None else -1.0

    print(f"\n== Serve fleet (2 hosts, batch={batch}, kill host0 "
          f"after {batch} frames) ==")
    print(f"bring-up           : cold {t_cold*1e3:.0f} ms, warm "
          f"{t_warm*1e3:.0f} ms -> {speedup:.2f}x warm-start speedup")
    print(f"failover           : recovery {recovery_ms:.1f} ms, "
          f"{st.migrated_frames} migrated (+{st.refired_frames} refired), "
          f"{len(got)}/{n_frames} served, bit-exact={ok}")
    print(f"fleet bill         : {st.chip.uj_per_frame:.3f} uJ/frame, "
          f"billed {st.billed} == served {st.total_served} + padded "
          f"{sum(st.padded.values())}")
    results["fleet_failover_recovery_ms"] = round(recovery_ms, 2)
    results["replica_warm_start_speedup"] = round(speedup, 2)
    results["fleet_replicas"] = 2
    results["fleet_migrated_frames"] = st.migrated_frames
    results["fleet_refired_frames"] = st.refired_frames
    results["fleet_uj_per_frame"] = round(st.chip.uj_per_frame, 3)
    return ok


def _bench_temporal(results):
    """Delta-gated always-on video vs full recompute on the SAME
    committed seeded trace: a static-background + moving-patch scene
    (``video_trace``, seed pinned below) replayed twice through the
    identical delta kernel — once at threshold 1 (skip bit-identical
    packed frames) and once at ``-inf`` (gate off, every lane
    recomputes).  Paired alternation (see _bench_megakernel) makes the
    median per-pair ratio the speedup estimator —
    ``temporal_speedup_vs_full`` is a >= 1.0 floor in
    ``check_regression.py``, and the chip-model ``temporal_uj_per_frame``
    must undercut the ungated bill.  Both paths run the same kernel, so
    labels must be bit-exact vs each other AND the offline oracle."""
    from repro.launch import chip_serve
    from repro.serving import ChipServer, TemporalPipeline, video_trace

    batch, n_steps = 8, 16
    prog = networks.mnist5()
    art = chip_serve.build_artifact(prog, seed=77, warm_bn=True)
    io = prog.instrs[0]
    trace = video_trace((io.height, io.width, io.in_channels), n_steps,
                        streams=batch, seed=77, change_rate=0.25,
                        levels=2 ** io.bits)
    n_frames = len(trace) * trace.streams
    plan = interpreter.compile_plan(prog)
    flat = trace.frames.reshape((-1,) + trace.frames.shape[2:])
    oracle = np.asarray(jax.jit(
        lambda pk, im: plan.forward(pk, im)[1])(
            interpreter.ensure_packed(art), jnp.asarray(flat)))

    def run(threshold):
        server = ChipServer({"mnist5": prog}, {"mnist5": art}, batch=batch)
        pipe = TemporalPipeline(server, "mnist5", threshold=threshold,
                                rb=2)
        t0 = time.perf_counter()
        for t in range(len(trace)):            # time-major: one dispatch
            for s in range(trace.streams):     # per camera tick
                pipe.submit(trace.frames[t, s])
        out = sorted(pipe.drain(), key=lambda r: r.rid)
        dt = time.perf_counter() - t0
        rep = pipe.report()
        skip = pipe.skip_ratio
        server.close()
        return out, dt, rep, skip

    run(float("-inf"))                         # warm the compile caches
    run(1.0)                                   # (same kernel either way)
    t_full = t_gated = float("inf")
    ratios = []
    ok = True
    out_g = []
    rep_g = rep_f = None
    skip = 0.0
    for _ in range(5):
        out_f, tf, rep_f, _ = run(float("-inf"))
        out_g, tg, rep_g, skip = run(1.0)
        t_full, t_gated = min(t_full, tf), min(t_gated, tg)
        ratios.append(tf / tg)
        ok = ok and [r.label for r in out_g] == [r.label for r in out_f]
    speedup = sorted(ratios)[len(ratios) // 2]
    fps = n_frames / t_gated
    agree = float(np.mean([r.label == int(oracle[r.rid])
                           for r in out_g]))
    ok = (ok and agree == 1.0 and skip > 0.0
          and rep_g.uj_per_frame < rep_g.uj_per_frame_ungated)

    print(f"\n== Temporal delta gating (mnist5 always-on video, "
          f"{trace.streams} streams x {n_steps} steps, threshold 1) ==")
    print(f"full recompute     : {t_full * 1e3:8.1f} ms/stream "
          f"({rep_f.uj_per_frame:.2f} uJ/frame)")
    print(f"delta gated        : {t_gated * 1e3:8.1f} ms/stream "
          f"({speedup:.2f}x, {fps:,.0f} frames/s)")
    print(f"gated bill         : {rep_g.uj_per_frame:.2f} uJ/frame vs "
          f"{rep_g.uj_per_frame_ungated:.2f} ungated "
          f"(skip ratio {skip:.2f}, {rep_g.savings:.2f}x saved)")
    print(f"labels bit-exact vs full path + offline oracle: {ok}")
    results["temporal_skip_ratio"] = round(skip, 3)
    results["temporal_speedup_vs_full"] = round(speedup, 2)
    results["temporal_uj_per_frame"] = round(rep_g.uj_per_frame, 3)
    results["temporal_uj_per_frame_ungated"] = round(
        rep_g.uj_per_frame_ungated, 3)
    results["temporal_label_agreement"] = round(agree, 3)
    results["temporal_ms_per_stream"] = round(t_gated * 1e3, 2)
    results["serve_frames_per_s_temporal"] = round(fps, 1)
    return ok


def run(csv: bool = True):
    import platform
    results = {"backend": jax.default_backend(),
               # absolute frames/s are only comparable on the same machine
               # class; the regression guard checks this fingerprint and
               # downgrades absolute-key mismatches to warnings when the
               # host changed (ratio floors always apply).
               "host": f"{platform.machine()}-{os.cpu_count()}cpu"}
    ok_mm = _bench_matmul(results)
    ok_pipe, speedup = _bench_pipeline(results)
    ok_mega = _bench_megakernel(results)
    ok_serve = _bench_serve(results)
    ok_cont = _bench_continuous_serve(results)
    ok_shared = _bench_shared_serve(results)
    ok_cascade = _bench_cascade(results)
    ok_fused_casc = _bench_cascade_fused(results)
    ok_ctrl = _bench_controller(results)
    ok_fleet = _bench_fleet(results)
    ok_temporal = _bench_temporal(results)
    ok = (ok_mm and ok_pipe and ok_mega and ok_serve and ok_cont
          and ok_shared and ok_cascade and ok_fused_casc and ok_ctrl
          and ok_fleet and ok_temporal)
    results["autotune_cache"] = autotune.cache_path()

    os.makedirs(os.path.dirname(BENCH_JSON) or ".", exist_ok=True)
    with open(BENCH_JSON, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(f"\nwrote {BENCH_JSON}")
    if csv:
        print(f"CSV,kernel_microbench,{results['pipeline_fused_us']:.0f},"
              f"fused_speedup={speedup:.2f};"
              f"fps={results['pipeline_frames_per_s']:.0f};exact={int(ok)}")
    return ok


if __name__ == "__main__":
    from repro.kernels import cache as warmcache
    warmcache.enable_persistent()
    raise SystemExit(0 if run() else 1)
