"""Topology-optimization serving demo (the paper's digital-twin workload
as a service): train a multi-load-case CRONet into the model registry,
then serve heterogeneous load cases with per-request latency, deadline,
and CRONet hit-rate reporting.

The model comes from the versioned registry (--registry):

  * ``--train`` trains a NEW multi-load-case surrogate (fea/dataset.py
    sampler: random load position/angle/magnitude plus the canonical
    MBB case) and registers it — checkpoint + cfg + u_scale + training
    load distribution + held-out eval metrics.
  * without ``--train`` the demo serves the latest registered
    checkpoint (or ``--model TAG``) and errors clearly when the
    registry is empty — there is no untrained fallback: an untrained
    net's hit rate is 0%, which is precisely what the registry exists
    to fix.

Serving modes (same as before):
  * drain (default): enqueue everything up front, run to completion.
  * streaming (--arrival-rate > 0): Poisson arrivals with freshness
    deadlines against the running engine.
  * mixed-mesh (--meshes AxB,CxD,...): one ``repro.serve.TopoGateway``
    buckets every discretization behind one bounded admission queue.
    ``--swap`` additionally hot-swaps the gateway to another registry
    version MID-STREAM (default: re-loads the serving tag) and reports
    that zero in-flight requests were dropped. ``--canary TAG``
    canaries a registry version on every bucket
    (``--canary-fraction`` of admissions routed to a canary engine),
    reports the per-tag acceptance/deadline stats, and PROMOTEs the
    survivor — or surfaces the auto-rollback, if the canary regressed
    against the concurrent primary traffic.

Flywheel mode (--flywheel, mixed-mesh only) arms the serving-data
flywheel on the gateway: rejected traffic (requests the residual gate
bounced back to FEA) is harvested into per-bucket LoadCases, and after
the main wave a driven ``FlywheelController`` loop keeps serving the
same schedule while ticking the controller — a bucket whose windowed
acceptance sits under ``--flywheel-trigger`` harvests its failures,
fine-tunes a mesh-specialized child from its serving checkpoint
(``finetune_from_tag``: warm start + replayed synthetic mix, REAL
training — expect minutes, tune ``--flywheel-steps``), canaries it on
its own bucket, and promotes on a sustained windowed win. The demo
then prints the typed event trail and the child's registry lineage.
``--flywheel-retain K`` additionally sweeps the registry down to the
last K versions per lineage between ticks (0 = never sweep; sweeps
DELETE old unpinned versions, so it defaults off for a persistent
registry).

    PYTHONPATH=src python examples/serve_topo.py --train \
        [--registry experiments/registry] [--train-steps 600] \
        [--train-cases 6] [--size small] [--requests 12] [--slots 4] \
        [--arrival-rate 2.0] [--deadline 6.0] \
        [--meshes 30x10,48x16] [--max-pending 64] [--overload block] \
        [--swap [TAG]] [--canary TAG [--canary-fraction 0.25]] \
        [--flywheel [--flywheel-steps 300] [--flywheel-waves 4] \
         [--flywheel-trigger 0.5] [--flywheel-retain 0]]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, "src")

import numpy as np


def parse_meshes(spec):
    meshes = []
    for tok in spec.split(","):
        nelx, nely = tok.lower().split("x")
        meshes.append((int(nelx), int(nely)))
    return meshes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="small",
                    choices=["small", "medium", "large"])
    ap.add_argument("--registry", default="experiments/registry",
                    help="model registry root (versioned checkpoints)")
    ap.add_argument("--train", action="store_true",
                    help="train a multi-load-case surrogate and register "
                         "it before serving (otherwise: serve the latest "
                         "registered checkpoint)")
    ap.add_argument("--model", default=None,
                    help="serve this registry tag instead of the latest")
    ap.add_argument("--tag", default=None,
                    help="tag for the newly trained model (--train)")
    ap.add_argument("--train-steps", type=int, default=600)
    ap.add_argument("--train-cases", type=int, default=16,
                    help="sampled load cases in the training distribution "
                         "(coverage density is the generalization lever)")
    ap.add_argument("--train-iters", type=int, default=40,
                    help="SIMP iterations per training trajectory")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--backend", default="oracle",
                    choices=["oracle", "megakernel"])
    ap.add_argument("--threshold", type=float, default=0.1,
                    help="residual gate: accept CRONet while its relative "
                         "error vs FEA stays under this (0.1 is the "
                         "measured operating point where off-distribution "
                         "loads accept; 0.05 is the paper's on-"
                         "distribution setting)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrival rate in requests/s; 0 = drain "
                         "mode (submit everything up front)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request freshness deadline in seconds "
                         "(streaming mode; 0 = no deadlines)")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable slack-safe slot preemption")
    ap.add_argument("--meshes", default="",
                    help="comma-separated mesh list, e.g. 30x10,48x16: "
                         "serve ALL of them through one TopoGateway "
                         "(round-robin request assignment)")
    ap.add_argument("--max-pending", type=int, default=64,
                    help="gateway admission queue capacity (mixed-mesh "
                         "mode); 0 = unbounded")
    ap.add_argument("--overload", default="block",
                    choices=["block", "reject", "shed-latest-deadline"],
                    help="gateway policy when the admission queue is full")
    ap.add_argument("--swap", nargs="?", const="__same__", default=None,
                    metavar="TAG",
                    help="mixed-mesh mode: hot-swap the gateway to this "
                         "registry tag mid-stream (no TAG = re-load the "
                         "serving version) and report zero dropped "
                         "in-flight requests")
    ap.add_argument("--canary", default=None, metavar="TAG",
                    help="mixed-mesh mode: canary this registry tag on "
                         "every bucket mid-stream (--canary-fraction of "
                         "admissions), then report the per-tag stats and "
                         "promote — or the auto-rollback, if the canary "
                         "regressed")
    ap.add_argument("--canary-fraction", type=float, default=0.25)
    ap.add_argument("--flywheel", action="store_true",
                    help="mixed-mesh mode: arm the serving-data flywheel "
                         "(harvest rejected traffic, fine-tune a "
                         "per-bucket specialist, canary, promote) and "
                         "drive it after the main wave")
    ap.add_argument("--flywheel-waves", type=int, default=4,
                    help="extra serving waves driven through the "
                         "flywheel loop (each wave re-serves the "
                         "schedule, then ticks the controller)")
    ap.add_argument("--flywheel-steps", type=int, default=300,
                    help="fine-tune steps for the harvested specialist")
    ap.add_argument("--flywheel-trigger", type=float, default=0.5,
                    help="bucket CRONet acceptance below which a "
                         "flywheel cycle starts")
    ap.add_argument("--flywheel-retain", type=int, default=0,
                    help="registry retention: keep this many versions "
                         "per lineage, sweeping between ticks (0 = "
                         "never sweep — sweeps DELETE old unpinned "
                         "versions)")
    ap.add_argument("--observe", action="store_true",
                    help="trace every request (spans + per-tick "
                         "records), spool telemetry snapshots next to "
                         "the registry, show the live metrics dashboard "
                         "during streaming runs, and print one sampled "
                         "request timeline at the end")
    args = ap.parse_args()

    from repro.common import use_compile_cache
    use_compile_cache()
    from repro.configs.cronet import get_cronet_config
    from repro.fea import dataset as dsm
    from repro.fea import fea2d, train_cronet
    from repro.serve import FlywheelController, HarvestLog, \
        ModelRegistry, NoModelError, QueueFull, RegistryRetention, \
        RequestShed, TopoGateway, TopoRequest, TopoServingEngine

    cfg = get_cronet_config(args.size)
    registry = ModelRegistry(args.registry)

    if args.train:
        print(f"== 1. train multi-load-case CRONet "
              f"({args.train_cases} cases x {args.train_iters} SIMP "
              f"iters, {args.train_steps} steps) ==")
        data = dsm.build_dataset(
            cfg, cases=dsm.sample_load_cases(args.train_cases, seed=0),
            n_iter=args.train_iters)
        record, result = train_cronet.train_and_register(
            cfg, registry, tag=args.tag, data=data,
            steps=args.train_steps, verbose=False,
            error_threshold=args.threshold)
        print(f"   mse {result.losses[0]:.4f} -> {result.losses[-1]:.6f}; "
              f"held-out acceptance "
              f"{result.eval_metrics['acceptance']:.0%} "
              f"@ threshold {args.threshold}")
        print(f"   registered {record.tag!r} (v{record.version}) in "
              f"{args.registry}")
        serve_tag = record.tag
    else:
        serve_tag = args.model
        try:
            record = (registry.get(serve_tag) if serve_tag
                      else registry.latest())
            if record is None:
                raise NoModelError("empty registry")
        except NoModelError:
            sys.exit(
                f"error: no trained model "
                f"{serve_tag + ' ' if serve_tag else ''}in registry "
                f"'{args.registry}'.\nTrain and register one first:\n"
                f"  PYTHONPATH=src python examples/serve_topo.py --train "
                f"--registry {args.registry}")
        serve_tag = record.tag
        acc = record.metrics.get("acceptance")
        print(f"== 1. serving registry checkpoint {record.tag!r} "
              f"(v{record.version}, u_scale={record.u_scale:.1f}, "
              f"{len(record.load_cases)} training load cases"
              + (f", held-out acceptance {acc:.0%}" if acc is not None
                 else "") + ") ==")

    meshes = (parse_meshes(args.meshes) if args.meshes
              else [(cfg.nelx, cfg.nely)])
    print(f"== 2. {args.requests} load cases over "
          f"{len(meshes)} mesh(es) "
          f"({','.join(f'{a}x{b}' for a, b in meshes)}) ==")
    rng = np.random.default_rng(0)
    probs = []
    for i in range(args.requests):
        nelx, nely = meshes[i % len(meshes)]   # round-robin over the fleet
        if i == 0:
            # the canonical MBB load case (the training anchor)
            probs.append(fea2d.point_load_problem(nelx, nely))
        else:
            # OFF-distribution point loads — the requests the
            # multi-load-case surrogate exists to accelerate
            probs.append(fea2d.point_load_problem(
                nelx, nely,
                load_node=(int(rng.integers(0, nelx - 1)), 0),
                load=(0.0, float(-0.5 - rng.random()))))

    harvest_log = None
    if args.flywheel:
        if not args.meshes:
            sys.exit("error: --flywheel needs the gateway "
                     "(--meshes AxB,...)")
        if args.canary:
            sys.exit("error: --flywheel drives its own canaries; "
                     "drop --canary")
        harvest_log = HarvestLog(capacity=64, accept_below=0.8)
    trace_every = 1 if args.observe else 0
    if args.meshes:
        service = TopoGateway.from_registry(
            registry, tag=serve_tag, slots=args.slots, precision="fp32",
            max_pending=args.max_pending or None, overload=args.overload,
            error_threshold=args.threshold, backend=args.backend,
            preempt=not args.no_preempt, harvest=harvest_log,
            canary_window=32, bucket_window=64, trace_every=trace_every)
        label = f"gateway[{args.overload}]"
    else:
        params, record = registry.load(serve_tag)
        service = TopoServingEngine(
            cfg, params, record.u_scale, slots=args.slots,
            precision="fp32", error_threshold=args.threshold,
            backend=args.backend, preempt=not args.no_preempt,
            model_tag=record.tag, trace_every=trace_every)
        label = "engine"

    snapshotter = None
    dash_stop = dash_thread = None
    if args.observe:
        import threading

        from repro.obs import TelemetrySnapshotter, dashboard

        telemetry_path = os.path.join(args.registry, "telemetry.jsonl")
        snapshotter = TelemetrySnapshotter(
            telemetry_path, interval_s=2.0,
            extra=lambda: service.throughput_stats()).start()
        print(f"== observe: tracing every request; telemetry -> "
              f"{telemetry_path} (+ .prom) ==")
        if args.arrival_rate > 0:
            # live dashboard only for streaming runs — drain mode's
            # interleaved per-request prints would fight the ANSI
            # clear/redraw loop for the terminal
            dash_stop = threading.Event()
            dash_thread = threading.Thread(
                target=dashboard.watch,
                kwargs=dict(stats_fn=service.throughput_stats,
                            interval_s=1.0, stop=dash_stop),
                daemon=True)
    if args.swap and not args.meshes:
        sys.exit("error: --swap needs the gateway (--meshes AxB,...)")
    if args.canary and not args.meshes:
        sys.exit("error: --canary needs the gateway (--meshes AxB,...)")
    deadline = args.deadline if args.deadline > 0 else None

    rejected = []

    def try_submit(futs, req, deadline_s=None):
        """submit() that survives a full queue under --overload reject
        (QueueFull is the policy working, not a demo failure)."""
        try:
            futs.append(service.submit(req, deadline_s=deadline_s))
        except QueueFull:
            rejected.append(req)

    def harvest(futs):
        done, shed = [], []
        for f in futs:
            try:
                done.append(f.result(timeout=3600))
            except RequestShed:
                shed.append(f.request)
        return done, shed

    def maybe_swap(futs):
        """--swap: hot-swap the gateway mid-stream, after the backlog is
        submitted but before it finishes — queued requests must survive."""
        if not args.swap:
            return
        target = serve_tag if args.swap == "__same__" else args.swap
        pending_before = sum(1 for f in futs if not f.done())
        t0 = time.time()
        new_tag = service.swap_model(target)
        print(f"== hot-swapped to {new_tag!r} in {time.time() - t0:.2f}s "
              f"with {pending_before} request(s) in flight ==")

    def maybe_canary(futs):
        """--canary: start a canary experiment mid-stream, on every
        bucket, against the live backlog."""
        if not args.canary:
            return
        for m in meshes:   # explicit targets: buckets may be unbuilt
            service.canary(args.canary, fraction=args.canary_fraction,
                           mesh=m)
        print(f"== canary {args.canary!r} at "
              f"{args.canary_fraction:.0%} of admissions on "
              f"{len(meshes)} bucket(s) ==")

    def finish_canary():
        """Report the experiment outcome: promote a surviving canary,
        or surface the auto-rollback that already fired."""
        if not args.canary:
            return
        for ev in service.events:
            if ev.kind == "rollback":
                print(f"== canary {ev.tag!r} AUTO-ROLLED-BACK on "
                      f"{ev.mesh[0]}x{ev.mesh[1]}: {ev.reason} ==")
        live = service.canary_stats()
        for key, info in live.items():
            c, p = info["canary"], info["primary"]
            print(f"== canary[{key}]: {info['routed_canary']} served "
                  f"(acceptance {c['cronet_hit_rate']:.0%} vs primary "
                  f"{p['cronet_hit_rate']:.0%}) ==")
        if live:
            tags = service.promote()
            print(f"== promoted {tags} to serving; registry stamped "
                  f"promoted_at ==")

    if args.arrival_rate > 0:
        print(f"== 3. stream at {args.arrival_rate:.2f} req/s onto the "
              f"{label} ({args.slots} slots/mesh, {args.backend} backend, "
              f"deadline {args.deadline or 'none'}s) ==")
        # warm-up: compile each mesh's batched step outside the timed
        # region so the first arrival is not charged for XLA compilation
        warm = [service.submit(TopoRequest(
            uid=-1 - k, problem=probs[k % len(probs)], n_iter=2))
            for k in range(max(args.slots, len(meshes)))]
        harvest(warm)
        if dash_thread is not None:
            dash_thread.start()
        arrivals = np.cumsum(
            rng.exponential(1.0 / args.arrival_rate, args.requests))
        t0 = time.time()
        futs = []
        for i, prob in enumerate(probs):
            # absolute schedule: time spent inside submit() (it can block
            # briefly behind an admission) must not drift the arrival rate
            lag = t0 + arrivals[i] - time.time()
            if lag > 0:
                time.sleep(lag)
            try_submit(futs, TopoRequest(uid=i, problem=prob,
                                         n_iter=args.iters),
                       deadline_s=deadline)
            if args.canary and i == args.requests // 3:
                maybe_canary(futs)
        maybe_swap(futs)
        done, shed = harvest(futs)
        if dash_stop is not None:
            dash_stop.set()
            dash_thread.join(timeout=5.0)
        finish_canary()
        wall = time.time() - t0
    else:
        print(f"== 3. drain {args.requests} requests through the {label} "
              f"({args.slots} slots/mesh, {args.backend} backend) ==")
        t0 = time.time()
        futs = []
        maybe_canary(futs)   # before the backlog: the split applies to it
        for i, p in enumerate(probs):
            try_submit(futs, TopoRequest(uid=i, problem=p,
                                         n_iter=args.iters))
        maybe_swap(futs)
        done, shed = harvest(futs)
        finish_canary()
        wall = time.time() - t0

    for r in done:
        total = r.cronet_iters + r.fea_iters
        dl = ("  hit" if r.deadline_met
              else " MISS" if r.deadline_met is not None else "     ")
        pre = f"  parked x{r.preemptions}" if r.preemptions else ""
        mesh = (f"  {r.problem.nelx}x{r.problem.nely}"
                if len(meshes) > 1 else "")
        tag = f"  [{r.model_tag}]" if args.swap else ""
        print(f"  req {r.uid:2d}:{mesh} compliance={r.compliance:9.2f}  "
              f"cronet {r.cronet_iters}/{total}  "
              f"latency {r.latency_s:.2f}s  queued {r.queue_wait_s:.2f}s"
              f"{dl}{pre}{tag}")
    for r in shed:
        print(f"  req {r.uid:2d}: SHED by the overload policy")
    for r in rejected:
        print(f"  req {r.uid:2d}: REJECTED at submit (queue full)")
    if args.swap:
        failed = sum(1 for f in futs
                     if f.exception() is not None
                     and not isinstance(f.exception(), RequestShed))
        print(f"== swap integrity: {len(done)} completed, {failed} "
              f"dropped/failed in flight ==")
    stats = service.throughput_stats(done, wall_s=wall)
    line = (f"== {stats['problems_per_s']:.2f} problems/s, "
            f"CRONet hit rate {100 * stats['cronet_hit_rate']:.1f}%, "
            f"p50/p99 latency {stats['p50_latency_s']:.2f}/"
            f"{stats['p99_latency_s']:.2f}s")
    # drain mode never attaches deadlines, so a hit rate there would be
    # the vacuous 1.0 default — only report it for streaming runs
    if args.arrival_rate > 0 and deadline is not None:
        line += (f", deadline hit rate "
                 f"{100 * stats['deadline_hit_rate']:.1f}%, "
                 f"{stats['preemptions']:.0f} preemptions")
    if shed:
        line += f", {len(shed)} shed"
    if rejected:
        line += f", {len(rejected)} rejected"
    print(line + f", wall {wall:.2f}s ==")
    if args.meshes:
        # per-mesh breakdown over the measured pool only (the engines'
        # own completion rings would also count the warm-up requests)
        for m in meshes:
            pool = [r for r in done
                    if (r.problem.nelx, r.problem.nely) == m]
            s = service.throughput_stats(pool)
            print(f"   {m[0]}x{m[1]}: {len(pool)} served, "
                  f"p50 {s['p50_latency_s']:.2f}s, "
                  f"CRONet {100 * s['cronet_hit_rate']:.1f}%")

    if args.observe:
        from repro.obs import dashboard
        final_stats = (service.throughput_stats(per_mesh=True)
                       if args.meshes else service.throughput_stats())
        print(dashboard.render(stats=final_stats))
        # drill-down: the full timeline of one served request — phase
        # spans tile submit -> done, so the durations sum to its e2e
        sample = next((service.trace(r.uid) for r in done
                       if service.trace(r.uid) is not None), None)
        if sample is not None:
            print(sample.render())
        snapshotter.stop()
        print(f"== observe: {snapshotter.snapshots_written} telemetry "
              f"snapshot(s) written ==")

    if args.flywheel:
        retention = (RegistryRetention(registry,
                                       keep_per_lineage=args.flywheel_retain,
                                       interval_s=0.0)
                     if args.flywheel_retain > 0 else None)
        fly = FlywheelController(
            service, harvest_log, trigger_below=args.flywheel_trigger,
            min_completed=6, min_harvest=2, cooldown_s=3600.0,
            canary_fraction=0.5, canary_min_requests=3,
            canary_margin=0.05, promote_after=4, promote_timeout=120.0,
            finetune_steps=args.flywheel_steps, replay_cases=2,
            harvest_n_iter=16, harvest_max_cases=8, retention=retention)
        hs = harvest_log.snapshot()
        print(f"== 4. flywheel: {hs['harvested']} rejected load case(s) "
              f"harvested from {hs['recorded']} completion(s); driving "
              f"up to {args.flywheel_waves} wave(s) ==")
        uid0 = 10_000
        for w in range(args.flywheel_waves):
            fly.tick()   # trigger -> harvest -> fine-tune -> canary
            if fly.history:
                break
            futs = [service.submit(TopoRequest(uid=uid0 + i, problem=p,
                                               n_iter=args.iters))
                    for i, p in enumerate(probs)]
            uid0 += len(futs)
            harvest(futs)
        fly.stop()
        for ev in service.events:
            if ev.kind.startswith("flywheel") or ev.kind in (
                    "canary-start", "promote", "rollback"):
                mesh_s = (f"{ev.mesh[0]}x{ev.mesh[1]}" if ev.mesh
                          else "-")
                print(f"   {ev.kind:18s} {mesh_s:7s} "
                      f"{ev.tag or '-':24s} {ev.reason}")
        for cyc in fly.history:
            d = cyc.describe()
            print(f"== flywheel[{d['mesh']}] {d['state'].upper()}: "
                  f"{d['base_tag']!r} -> {d['child_tag']!r} "
                  f"({d['n_cases']} harvested case(s))"
                  + (f"; {d['error']}" if d["error"] else "") + " ==")
            if cyc.child_tag and cyc.child_tag in registry.tags():
                rec = registry.get(cyc.child_tag)
                print(f"   lineage: v{rec.version} {rec.tag!r} "
                      f"parent={rec.parent!r} mesh={rec.mesh} "
                      f"held-out acceptance "
                      f"{rec.metrics.get('acceptance', float('nan')):.0%}")
        if not fly.history:
            live = fly.cycles()
            print("== flywheel: no cycle reached a terminal state ("
                  + (f"live: {live}" if live else
                     "buckets healthy or not enough traffic") + ") ==")
        if retention is not None and retention.dropped:
            print(f"== retention: swept {retention.dropped} ==")
    service.shutdown()


if __name__ == "__main__":
    main()
