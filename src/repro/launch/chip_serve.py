"""Chip-tier serving driver: ``python -m repro.launch.chip_serve [...]``.

Continuous static-batch frame service over one or more resident BinarEye
programs: synthetic frame streams are enqueued per program, the
:class:`~repro.serving.ChipServer` dispatches fixed-size batches through
each program's compiled packed :class:`InferencePlan` (round-robin across
programs — the chip's S-mode recombination across concurrent tasks), and
the run closes with the host throughput plus the chip-model bill
(µJ/frame, frames/s, average power analogue) from ``chip/energy.py``.

It prints the device it runs on first (platform, kind, count) and keeps
compiled programs in JAX's persistent cache (``kernels/cache.py``).

Examples::

    PYTHONPATH=src python -m repro.launch.chip_serve --programs mnist5
    PYTHONPATH=src python -m repro.launch.chip_serve --programs cifar9_s1 \
        --requests 64 --batch 16 --megakernel
    PYTHONPATH=src python -m repro.launch.chip_serve \
        --programs mnist5,face_detector --requests 48 --batch 8 --shard

``--shard`` serves over all local devices (one packed-weight replica per
device, frames scattered on the batch axis); on a 1-device host it
degrades to the plain jit path, and under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` it exercises the
real N-way scatter on CPU.

Two runtime modes on top of plain static serving:

* ``--policy operating-point`` serves program *families* — names in
  ``--programs`` may be family names from ``networks.FAMILIES`` (e.g.
  ``cifar10``), whose member variants are all compiled and served behind
  one lane by the energy-accuracy controller; ``--budget-uj-s`` caps the
  chip-model average power (uJ of I2L energy per second of chip time)
  and a tight budget forces visible downshifts::

      PYTHONPATH=src python -m repro.launch.chip_serve \
          --policy operating-point --programs cifar10 --budget-uj-s 400

* ``--cascade`` runs the paper's always-on hierarchy: the 0.92 uJ/f S=4
  face detector screens every frame and only logit-margin positives
  (``--margin``) escalate to the 14.4 uJ/f S=1 owner recognizer::

      PYTHONPATH=src python -m repro.launch.chip_serve --cascade

* ``--video`` serves a seeded always-on *video* stream through the
  delta-gated temporal pipeline: each batch slot carries one camera
  stream, the in-kernel popcount gate recomputes only the streams whose
  packed frame actually changed (``--delta-threshold``), and skipped
  frames answer from the resident last-logits cache at delta-compute-
  only cost.  ``--target-agreement A`` calibrates the cheapest
  threshold still agreeing with ungated labels at rate A on a held-out
  trace; ``--target-skip S`` instead picks the smallest threshold
  reaching skip ratio S::

      PYTHONPATH=src python -m repro.launch.chip_serve \
          --video --change-rate 0.2 --target-agreement 0.95

* ``--traffic {poisson,bursty,diurnal}`` replays a seeded arrival trace
  in real time instead of enqueueing everything up front — the streaming
  workload the paper's always-on figures assume.  ``--rate`` sets the
  arrival rate (frames/s), ``--slo-ms`` the per-lane latency SLO, and
  ``--policy continuous`` turns on the rolling admission window that
  autoscales the batch against the measured rate::

      PYTHONPATH=src python -m repro.launch.chip_serve \
          --traffic poisson --rate 200 --policy continuous --slo-ms 20
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.core.chip import energy, interpreter, networks
from repro.distributed import sharding
from repro.serving import CascadePipeline, ChipServer, make_trace, replay


def build_artifact(program, seed: int, warm_bn: bool):
    """Packed deployment artifact for a program: init (+ optional one-batch
    BN warm so thresholds are realistic), fold, bit-pack — one jitted
    program, so an accelerator compiles it once instead of op by op."""
    @jax.jit
    def build(key):
        params = interpreter.init_params(key, program)
        if warm_bn:
            io = program.instrs[0]
            imgs = jax.random.randint(
                jax.random.fold_in(key, 1),
                (4, io.height, io.width, io.in_channels), 0, 2 ** io.bits)
            _, params = interpreter.forward_train(params, program, imgs)
        return interpreter.fold_params(params, program, packed=True)

    return build(jax.random.PRNGKey(seed))


def _prefetch_kw(args) -> dict:
    """The pipeline depth the flags ask for, or nothing when neither is
    given, so the server's default depth applies."""
    if args.prefetch_depth is not None:
        return {"prefetch": args.prefetch_depth}
    return {"prefetch": 1} if args.prefetch else {}


def frame_stream(program, n: int, seed: int):
    """Deterministic synthetic frames shaped for the program's IO layer."""
    io = program.instrs[0]
    key = jax.random.PRNGKey(seed)
    return np.asarray(jax.random.randint(
        key, (n, io.height, io.width, io.in_channels), 0, 2 ** io.bits))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--programs", default="mnist5",
                    help="comma-separated names from networks.REGISTRY")
    ap.add_argument("--requests", type=int, default=24,
                    help="total frames across all programs")
    ap.add_argument("--batch", type=int, default=8, help="static batch size")
    ap.add_argument("--shard", action="store_true",
                    help="serve over all local devices (frames scattered)")
    ap.add_argument("--donate", action="store_true",
                    help="donate streamed frame buffers to the computation")
    ap.add_argument("--megakernel", action="store_true",
                    help="serve through the whole-network VMEM-resident "
                         "megakernel (weight image resident, zero HBM "
                         "traffic between layers)")
    ap.add_argument("--prefetch", action="store_true",
                    help="double-buffer submission: launch batch N+1 "
                         "before blocking on batch N (depth 1, the "
                         "server's default)")
    ap.add_argument("--prefetch-depth", type=int, default=None,
                    help="pipeline submission to depth k (0: synchronous; "
                         "k >= 2 adds async host result fetch); without "
                         "it or --prefetch the server's default applies")
    ap.add_argument("--shared", action="store_true",
                    help="shared-array dispatch: programs whose S-modes "
                         "tile the 256-channel array exactly run as ONE "
                         "composite pallas_call per batch (true sub-array "
                         "sharing instead of interleaved dispatches)")
    ap.add_argument("--autotune", action="store_true",
                    help="measure-and-cache the best kernel tile sizes "
                         "for each resident program on this backend "
                         "before serving (persisted in the autotune "
                         "cache, see kernels/autotune.py)")
    ap.add_argument("--policy",
                    choices=("static", "operating-point", "continuous"),
                    default="static",
                    help="dispatch policy: 'static' serves each lane "
                         "with its own program; 'operating-point' serves "
                         "program families (names in --programs may be "
                         "networks.FAMILIES entries) at the energy-"
                         "accuracy point the budget and backlog call for; "
                         "'continuous' adds the rolling admission window "
                         "that autoscales the batch against measured "
                         "arrival rate and --slo-ms (composes with the "
                         "operating-point controller when families are "
                         "served)")
    ap.add_argument("--traffic", choices=("poisson", "bursty", "diurnal"),
                    default=None,
                    help="replay a seeded arrival trace in real time "
                         "instead of enqueueing all frames up front")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="traffic arrival rate in frames/s (all lanes)")
    ap.add_argument("--slo-ms", type=float, default=50.0,
                    help="per-lane input-to-label latency SLO for the "
                         "continuous policy's admission window")
    ap.add_argument("--budget-uj-s", type=float, default=None,
                    help="operating-point controller energy budget: max "
                         "chip-model average power in uJ/s (uW); tight "
                         "budgets force downshifts to cheaper variants")
    ap.add_argument("--cascade", action="store_true",
                    help="run the always-on cascade demo: the S=4 face "
                         "detector screens every frame, logit-margin "
                         "positives escalate to the S=1 owner recognizer")
    ap.add_argument("--margin", type=float, default=0.0,
                    help="cascade escalation threshold on the detector's "
                         "logit margin")
    ap.add_argument("--fused", action="store_true",
                    help="serve the cascade as ONE fused kernel dispatch "
                         "per batch: escalation mask + recognizer drain "
                         "in-kernel (bit-exact vs the host cascade)")
    ap.add_argument("--target-recall", type=float, default=None,
                    metavar="R",
                    help="calibrate the escalation margin on a held-out "
                         "split instead of using --margin: the cheapest "
                         "margin whose escalations capture R of the "
                         "positive frames (detector-labelled)")
    ap.add_argument("--video", action="store_true",
                    help="serve a seeded video stream through the delta-"
                         "gated temporal pipeline: skip unchanged frames "
                         "in-kernel, answer them from the last-logits "
                         "cache (first --programs entry; batch = streams)")
    ap.add_argument("--delta-threshold", type=float, default=1.0,
                    help="packed-Hamming gate: a stream recomputes when "
                         "its frame delta vs the resident last frame "
                         "reaches this many bits (1 = skip only bit-"
                         "identical frames; -inf = gate off)")
    ap.add_argument("--target-agreement", type=float, default=None,
                    metavar="A",
                    help="calibrate the gate threshold on a held-out "
                         "video trace: the cheapest threshold whose "
                         "gated labels agree with ungated inference on "
                         "at least A of the frames")
    ap.add_argument("--target-skip", type=float, default=None, metavar="S",
                    help="calibrate the gate threshold for energy: the "
                         "smallest threshold reaching skip ratio S on a "
                         "held-out video trace")
    ap.add_argument("--change-rate", type=float, default=0.25,
                    help="video trace: per-stream probability a frame "
                         "differs from the previous one")
    ap.add_argument("--scene-every", type=int, default=0,
                    help="video trace: full scene change every N frames "
                         "(0 = never)")
    ap.add_argument("--no-warm-bn", action="store_true",
                    help="skip the one-batch BN warm (faster, cruder "
                         "thresholds)")
    ap.add_argument("--fleet", type=int, default=1,
                    help="serve through N replica hosts (disjoint "
                         "host-major sub-meshes of the local devices; "
                         "frames scatter in blocks of --batch)")
    ap.add_argument("--kill", default=None, metavar="REPLICA",
                    help="fault-inject: kill this replica (e.g. host0) "
                         "mid-stream; its frames migrate to survivors "
                         "(requires --fleet >= 2)")
    ap.add_argument("--kill-after", type=int, default=8,
                    help="fire the --kill injection once this many "
                         "frames have been served fleet-wide")
    ap.add_argument("--no-replace", action="store_true",
                    help="do not spawn a warm-started replacement for "
                         "the killed replica")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    print(f"device: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}")
    from repro.kernels import cache as warmcache
    print(f"compile cache: {warmcache.enable_persistent()}")

    if args.cascade:
        return run_cascade(args)
    if args.video:
        return run_video(args)

    names = [n.strip() for n in args.programs.split(",") if n.strip()]
    families = {}
    if args.policy in ("operating-point", "continuous"):
        # family names expand to their member variants behind one lane
        expanded = []
        for n in names:
            if n in networks.FAMILIES:
                families[n] = networks.FAMILIES[n]
                expanded.extend(networks.FAMILIES[n])
            else:
                expanded.append(n)
        names = expanded
    unknown = [n for n in names if n not in networks.REGISTRY]
    if unknown:
        ap.error(f"unknown programs {unknown}; have "
                 f"{sorted(networks.REGISTRY)} and families "
                 f"{sorted(networks.FAMILIES)}")

    programs = {n: networks.REGISTRY[n]() for n in names}
    print(f"folding deployment artifacts for {names} ...")
    artifacts = {n: build_artifact(p, args.seed + i, not args.no_warm_bn)
                 for i, (n, p) in enumerate(programs.items())}

    if args.fleet > 1:
        return run_fleet(args, names, programs, artifacts, families)
    if args.kill:
        ap.error("--kill needs --fleet >= 2 (nowhere to migrate frames)")

    if args.autotune:
        from repro.kernels import autotune
        for n, p in programs.items():
            plan = interpreter.compile_plan(p)
            frames = jax.numpy.asarray(frame_stream(p, args.batch, args.seed))
            if args.megakernel:
                image = interpreter.ensure_image(artifacts[n], p)
                entry = autotune.tune_mega(plan, image, frames)
                print(f"autotuned {n}: megakernel bb={entry['bb']} "
                      f"ft={entry['ft']} ({entry['us']:.0f} us)")
            else:
                packed = interpreter.ensure_packed(artifacts[n])
                entry = autotune.tune_staged_conv(plan, packed, frames)
                print(f"autotuned {n}: staged conv bf={entry['bf']} "
                      f"bb={entry['bb']} ({entry['us']:.0f} us)")
        if args.shared:
            # the shared path's hot kernel is the composite, keyed under
            # its own fingerprint — tune each group it will form
            from repro.serving.scheduler import plan_shared_groups
            for members in plan_shared_groups(programs):
                cplan, cimage = interpreter.pack_programs(
                    {m: programs[m] for m in members},
                    {m: artifacts[m] for m in members})
                frames = tuple(jax.numpy.asarray(
                    frame_stream(programs[m], args.batch, args.seed))
                    for m in members)
                entry = autotune.tune_composite(cplan, cimage, frames)
                print(f"autotuned {'+'.join(members)}: composite "
                      f"bb={entry['bb']} ft={entry['ft']} "
                      f"({entry['us']:.0f} us)")

    mesh = sharding.serve_mesh() if args.shard else None
    ndev = mesh.devices.size if mesh is not None else 1
    server = ChipServer(programs, artifacts, batch=args.batch, mesh=mesh,
                        donate_frames=args.donate,
                        megakernel=args.megakernel, **_prefetch_kw(args),
                        shared=args.shared, policy=args.policy,
                        families=families or None,
                        budget_uj_s=args.budget_uj_s,
                        slo_ms=args.slo_ms)
    print(f"resident programs: {names}  (batch={args.batch}, "
          f"devices={ndev}, S-modes={[programs[n].s for n in names]}, "
          f"megakernel={args.megakernel}, prefetch={server.prefetch}, "
          f"shared={args.shared}, policy={args.policy})")
    if families:
        for fam, members in families.items():
            pts = energy.operating_points(
                {m: programs[m] for m in members}, networks.ACCURACY)
            print(f"family {fam}: " + " > ".join(
                f"{p.name}[{p.uj_per_frame:.2f}uJ/f @{p.accuracy:.1%}]"
                for p in pts)
                + (f"  (budget {args.budget_uj_s:,.0f} uJ/s)"
                   if args.budget_uj_s else "  (no budget)"))
    if args.shared:
        groups = server.shared_groups
        print("shared-array groups: "
              + (", ".join("+".join(g) for g in groups)
                 if groups else "none (S-modes do not tile the array)"))

    lanes = list(server.queue.lanes)
    geom_prog = {lane: programs[server.families.get(lane, (lane,))[0]]
                 for lane in lanes}
    per = {lane: frame_stream(geom_prog[lane],
                              -(-args.requests // len(lanes)),
                              args.seed + 100 + i)
           for i, lane in enumerate(lanes)}
    if args.traffic:
        # seeded arrival trace, replayed with real-time pacing: frames
        # hit the queue at their trace offsets and latency is measured
        # against the arrival process
        trace = make_trace(args.traffic, lanes, args.rate, args.requests,
                           seed=args.seed)
        print(f"replaying {args.traffic} trace: {len(trace)} frames at "
              f"{args.rate:,.0f} f/s mean over {len(lanes)} lane(s), "
              f"seed {args.seed}, SLO {args.slo_ms:.0f} ms "
              f"({trace.duration_s:.2f} s span)")
        results = replay(server, trace, per)
    else:
        # interleaved synthetic streams: round-robin submission up front
        idx = {lane: 0 for lane in lanes}
        submitted = 0
        while submitted < args.requests:
            lane = lanes[submitted % len(lanes)]
            server.submit(lane, per[lane][idx[lane]])
            idx[lane] += 1
            submitted += 1
        results = server.drain()
    stats = server.stats()

    counts = {lane: 0 for lane in lanes}
    for r in results:
        counts[r.program] += 1
    print(f"\nserved {len(results)} frames in {stats.dispatches} dispatches "
          f"({stats.host_wall_s*1e3:.0f} ms host)")
    for lane in lanes:
        members = server.families.get(lane, (lane,))
        uj = [stats.chip.reports[m].i2l_energy_per_inference * 1e6
              for m in members]
        print(f"  {lane:>14}: {counts[lane]:3d} served, "
              f"{stats.padded[lane]} padded slots, "
              + (f"{uj[0]:.2f} uJ/frame, S={programs[lane].s}"
                 if len(members) == 1 else
                 f"{min(uj):.2f}-{max(uj):.2f} uJ/frame across "
                 f"{len(members)} operating points"))
    if stats.policy == "operating-point":
        vd = {v: n for v, n in stats.variant_dispatches.items() if n}
        print(f"operating points    : {vd} "
              f"(downshift ratio {stats.downshift_ratio:.2f}, "
              f"energy {stats.energy_uj:,.0f} uJ"
              + (f" under budget {stats.budget_uj_s:,.0f} uJ/s)"
                 if stats.budget_uj_s else ", no budget)"))
    print(f"host-sim throughput : {stats.host_frames_per_s:,.0f} frames/s")
    if stats.p99_ms > 0.0:
        slo = args.slo_ms
        met = sum(1 for e in server.latency_trace()
                  if e["latency_ms"] <= slo) / max(1, len(server.latency_trace()))
        print(f"input-to-label      : p50 {stats.p50_ms:.2f} / "
              f"p95 {stats.p95_ms:.2f} / p99 {stats.p99_ms:.2f} ms "
              f"({met:.1%} within the {slo:.0f} ms SLO)")
        print(f"padding ratio       : {stats.padding_ratio:.3f} burned "
              f"slots per billed slot")
    print(f"array utilization   : {stats.array_utilization:.2f} mean "
          f"occupied fraction over {stats.dispatches} dispatches "
          f"({stats.shared_dispatches} shared)")
    print(f"chip-model bill     : {stats.chip.uj_per_frame:.2f} uJ/frame, "
          f"{stats.chip.frames_per_s:,.0f} frames/s at Emin, "
          f"{stats.chip.power_w*1e3:.2f} mW avg "
          f"(paper: up to 1700 f/s, 0.9 mW I2L at S=4)")
    return results, stats


def run_fleet(args, names, programs, artifacts, families):
    """Serve through a :class:`~repro.serving.ServeFleet`: N replica
    hosts over disjoint sub-meshes, optional mid-stream fault injection
    (``--kill host0``) with survivor migration and a warm-started
    replacement host."""
    from repro.serving import FaultInjector, ServeFleet

    injector = (FaultInjector(args.kill, after_served=args.kill_after)
                if args.kill else None)
    fleet = ServeFleet(programs, artifacts, replicas=args.fleet,
                       batch=args.batch, injector=injector,
                       replace=not args.no_replace,
                       donate_frames=args.donate,
                       megakernel=args.megakernel, **_prefetch_kw(args),
                       shared=args.shared, policy=args.policy,
                       families=families or None,
                       budget_uj_s=args.budget_uj_s, slo_ms=args.slo_ms)
    ndev = sum(len(d) for d in fleet._devices.values())
    print(f"serve fleet: {args.fleet} replicas over {ndev} device(s), "
          f"batch={args.batch}, policy={args.policy}"
          + (f", kill {args.kill} after {args.kill_after} frames "
             f"(replace={not args.no_replace})" if args.kill else ""))

    lanes = list(fleet.lanes)
    fam_map = dict(families or {})
    geom_prog = {lane: programs[fam_map.get(lane, (lane,))[0]]
                 for lane in lanes}
    per = {lane: frame_stream(geom_prog[lane],
                              -(-args.requests // len(lanes)),
                              args.seed + 100 + i)
           for i, lane in enumerate(lanes)}
    if args.traffic:
        trace = make_trace(args.traffic, lanes, args.rate, args.requests,
                           seed=args.seed)
        print(f"replaying {args.traffic} trace: {len(trace)} frames at "
              f"{args.rate:,.0f} f/s over {len(lanes)} lane(s)")
        results = replay(fleet, trace, per)
    else:
        idx = {lane: 0 for lane in lanes}
        results = []
        for submitted in range(args.requests):
            lane = lanes[submitted % len(lanes)]
            fleet.submit(lane, per[lane][idx[lane]])
            idx[lane] += 1
            if submitted % args.batch == args.batch - 1:
                # interleave serving so a --kill lands mid-stream, not
                # after admission
                results.extend(fleet.step())
        results.extend(fleet.drain())
        results = sorted(results, key=lambda r: r.rid)

    st = fleet.stats()
    print(f"\nfleet served {st.total_served} frames in {st.dispatches} "
          f"dispatches across {len(st.replicas)} replica(s)")
    for name, rs in sorted(st.replicas.items()):
        mark = " (FAILED)" if name in st.failed_replicas else ""
        print(f"  {name:>10}{mark}: {sum(rs.served.values()):3d} served, "
              f"{sum(rs.padded.values())} padded, "
              f"{rs.dispatches} dispatches")
    if st.failed_replicas:
        print(f"failover            : {st.migrated_frames} frames migrated "
              f"(+{st.refired_frames} refired), recovery "
              + (f"{st.recovery_ms:.1f} ms" if st.recovery_ms is not None
                 else "n/a (replacement served no frames)"))
    print(f"billing             : {st.billed} billed == "
          f"{st.total_served} served + {sum(st.padded.values())} padded "
          f"(padding ratio {st.padding_ratio:.3f})")
    if st.p99_ms > 0.0:
        print(f"input-to-label      : p50 {st.p50_ms:.2f} / "
              f"p95 {st.p95_ms:.2f} / p99 {st.p99_ms:.2f} ms (merged)")
    print(f"host-sim throughput : {st.host_frames_per_s:,.0f} frames/s")
    print(f"chip-model bill     : {st.chip.uj_per_frame:.2f} uJ/frame, "
          f"{st.chip.frames_per_s:,.0f} frames/s ({len(st.replicas)} "
          f"chips in parallel), {st.chip.power_w*1e3:.2f} mW total")
    ws = st.warm_start
    print(f"warm-start cache    : {ws['hits']} hits / {ws['misses']} "
          f"misses, {ws['build_s']*1e3:.0f} ms building")
    return results, st


def run_cascade(args):
    """The paper's always-on hierarchy: S=4 face detector on every frame,
    logit-margin positives escalate to the S=1 owner recognizer.

    ``--fused`` serves it as one in-kernel cascade dispatch per batch;
    ``--target-recall R`` calibrates the margin on a held-out split
    (detector-labelled positives as the recall ground truth) instead of
    taking ``--margin`` verbatim.
    """
    det_name, rec_name = "face_detector", "owner_detector"
    programs = {det_name: networks.face_detector(),
                rec_name: networks.owner_detector()}
    print(f"folding deployment artifacts for cascade "
          f"{det_name} -> {rec_name} ...")
    artifacts = {n: build_artifact(p, args.seed + i, not args.no_warm_bn)
                 for i, (n, p) in enumerate(programs.items())}
    server = ChipServer(programs, artifacts, batch=args.batch,
                        megakernel=args.megakernel, **_prefetch_kw(args))
    casc = CascadePipeline(server, det_name, rec_name,
                           positive_class=1, margin=args.margin,
                           fused=args.fused)
    if args.target_recall is not None:
        # held-out calibration split (disjoint seed from the served
        # stream); with no labelled data in the demo, the detector's own
        # positives are the recall ground truth
        cal = frame_stream(programs[det_name], max(args.requests, 32),
                           args.seed + 200)
        plan = interpreter.compile_plan(programs[det_name])
        _, cal_labels = plan.forward(
            interpreter.ensure_packed(artifacts[det_name]), cal)
        margin = casc.calibrate(cal, np.asarray(cal_labels) == 1,
                                args.target_recall)
        print(f"calibrated margin   : {margin:+.1f} (target recall "
              f"{args.target_recall:.2f} on {len(cal)} held-out frames)")
    frames = frame_stream(programs[det_name], args.requests, args.seed + 100)
    casc.submit_many(frames)
    results = casc.drain()
    rep = casc.report()
    stats = server.stats()
    mode = ("fused in-kernel escalation, "
            f"{casc.fused_dispatches} dispatches" if args.fused
            else "host-side escalation")
    print(f"\ncascade served {len(results)} frames "
          f"({rep.escalated} escalated, rate {rep.escalation_rate:.2f}, "
          f"margin >= {casc.margin:+.1f}, {mode})")
    print(f"detector stage      : {rep.detector_uj:.2f} uJ/frame x "
          f"{rep.frames} frames (+{stats.padded[det_name]} padded)")
    print(f"recognizer stage    : {rep.recognizer_uj:.2f} uJ/frame x "
          f"{rep.escalated} frames (+{stats.padded[rec_name]} padded)")
    print(f"cascade bill        : {rep.uj_per_frame:.2f} uJ/frame vs "
          f"{rep.uj_per_frame_recognizer_only:.2f} recognizer-on-every-"
          f"frame ({rep.savings:.2f}x saved; paper: 0.92 -> 14.4 uJ/f)")
    return results, rep


def run_video(args):
    """Always-on video through the delta-gated temporal pipeline: one
    camera stream per batch slot over a seeded content trace
    (``traffic.video_trace``), in-kernel popcount gating against the
    resident last frame, skipped frames answered from the last-logits
    cache and billed at delta-compute-only cost.

    ``--target-agreement`` / ``--target-skip`` calibrate the threshold
    on a disjoint-seed held-out trace (agreement vs ungated labels, or a
    skip-ratio energy contract) instead of taking ``--delta-threshold``
    verbatim.
    """
    from repro.serving import temporal
    from repro.serving.traffic import video_trace

    if args.target_agreement is not None and args.target_skip is not None:
        raise SystemExit("--target-agreement and --target-skip are "
                         "mutually exclusive")
    name = args.programs.split(",")[0].strip()
    if name not in networks.REGISTRY:
        raise SystemExit(f"unknown program {name!r}; have "
                         f"{sorted(networks.REGISTRY)}")
    program = networks.REGISTRY[name]()
    io = program.instrs[0]
    print(f"folding deployment artifact for {name} ...")
    artifact = build_artifact(program, args.seed, not args.no_warm_bn)
    server = ChipServer({name: program}, {name: artifact}, batch=args.batch,
                        megakernel=args.megakernel, **_prefetch_kw(args))
    # fine-grained drain chunks: recompute work scales with the changed
    # count instead of rounding every dispatch up to a full batch
    pipe = temporal.TemporalPipeline(server, name,
                                     threshold=args.delta_threshold,
                                     rb=max(1, args.batch // 4))
    steps = -(-args.requests // args.batch)
    shape = (io.height, io.width, io.in_channels)
    if args.target_agreement is not None or args.target_skip is not None:
        cal = video_trace(shape, max(steps, 8), streams=args.batch,
                          seed=args.seed + 200,
                          change_rate=args.change_rate,
                          scene_change_every=args.scene_every,
                          levels=2 ** io.bits)
        if args.target_agreement is not None:
            thr = pipe.calibrate(cal.frames, args.target_agreement)
            print(f"calibrated threshold: {thr:.0f} bits (target "
                  f"agreement {args.target_agreement:.2f} on "
                  f"{len(cal) * cal.streams} held-out frames)")
        else:
            thr = temporal.threshold_for_skip(cal.frames, args.target_skip,
                                              program=program)
            pipe.threshold = thr
            print(f"calibrated threshold: {thr:.0f} bits (target skip "
                  f"{args.target_skip:.2f} on {len(cal) * cal.streams} "
                  f"held-out frames)")
    trace = video_trace(shape, steps, streams=args.batch,
                        seed=args.seed + 100, change_rate=args.change_rate,
                        scene_change_every=args.scene_every,
                        levels=2 ** io.bits)
    print(f"video stream        : {args.batch} streams x {steps} frames "
          f"(change rate {args.change_rate:.2f}, "
          f"{trace.change_ratio:.2f} actually changed, seed "
          f"{args.seed + 100}), gate >= {pipe.threshold:.0f} bits")
    for t in range(len(trace)):
        for s in range(trace.streams):
            pipe.submit(trace.frames[t, s])
    results = pipe.drain()
    rep = pipe.report()
    stats = server.stats()
    print(f"\ntemporal served {len(results)} frames in "
          f"{pipe.gated_dispatches} gated dispatches: {rep.computed} "
          f"computed (+{rep.computed_padded} drain padding), "
          f"{rep.skipped} skipped (skip ratio {rep.skip_ratio:.2f})")
    print(f"host-sim throughput : {stats.host_frames_per_s:,.0f} frames/s")
    print(f"temporal bill       : {rep.uj_per_frame:.3f} uJ/frame "
          f"({rep.delta_uj:.3f} delta toll on every frame) vs "
          f"{rep.uj_per_frame_ungated:.3f} ungated "
          f"({rep.savings:.2f}x saved)")
    return results, rep


if __name__ == "__main__":
    main()
