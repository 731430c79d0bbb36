"""`repro.serve` — the topology-optimization serving surface.

Public API (everything else in this package is implementation detail):

  * ``TopoGateway`` — the mesh-agnostic front door: submit requests for
    ANY ``(nelx, nely)`` mesh; they are bucketed into lazily-built
    per-mesh engines behind one bounded (priority, EDF) admission queue
    with pluggable overload policies (gateway.py).
  * ``TopoServingEngine`` — a single-mesh slot-batched streaming engine
    (topo_service.py); use it directly when the workload is one mesh.
  * ``TopoRequest`` / ``TopoFuture`` — the unit of work and its
    completion handle (types.py), shared end to end.
  * ``OverloadPolicy`` + the typed failures ``QueueFull`` /
    ``RequestShed`` — backpressure behaviour of a full admission queue.
  * ``EngineState`` / ``EngineClosed`` — the explicit lifecycle state
    machine; submitting to a shut-down engine/gateway raises.
  * ``ModelRegistry`` / ``ModelRecord`` + ``NoModelError`` — the
    versioned checkpoint registry (registry.py): training runs register
    immutable versions (params + cfg + u_scale + load distribution +
    eval metrics); the gateway resolves its served model from here
    (``TopoGateway.from_registry``) and hot-swaps versions with
    ``gateway.swap_model(tag)`` without dropping queued requests.
  * ``pool_stats`` / ``throughput_view`` — the ONE shared metric core
    behind every ``throughput_stats()`` (engine-level, per-mesh,
    aggregate, and the LM-decode server's) — rate + latency
    percentiles computed the same way everywhere.
  * Fleet operations — ``ModelResolver`` (per-bucket checkpoint
    resolution: mesh-specialized version if registered, else fleet
    default), ``gateway.canary(tag, fraction, mesh=...)`` +
    ``promote()``/``rollback()`` with auto-rollback on
    acceptance/deadline regression (``TagStats`` per tag, typed
    ``FleetEvent`` log), ``swap_model(tag, mesh=...)`` per-bucket
    swaps, and pool elasticity (``idle_evict_s`` cold-bucket eviction
    with lazy bitwise-equal rebuild, ``autoscale`` slot widths from
    observed arrival rates, and elastic width ladders — see below).
  * Serving-data flywheel (flywheel.py) — ``HarvestLog`` (the
    gateway's completion-path sink for fell-back-to-FEA traffic),
    ``FlywheelController`` + ``FlywheelState`` (the unattended
    harvest -> fine-tune -> canary -> promote state machine), and
    ``RegistryRetention`` (scheduled ``registry.sweep()`` keep-policy)
    — see the flywheel quickstart below.

Quickstart (mixed-mesh serving)::

    from repro.serve import TopoGateway, TopoRequest

    gw = TopoGateway(cfg, params, u_scale, slots=4,
                     max_pending=64, overload="shed-latest-deadline")
    fut = gw.submit(TopoRequest(uid=0, problem=prob_30x10, n_iter=60),
                    deadline_s=6.0)
    fut2 = gw.submit(TopoRequest(uid=1, problem=prob_48x16, n_iter=60),
                     deadline_s=6.0, priority=1)   # jumps every deadline
    req = fut.result()            # req.density, req.deadline_met, ...
    stats = gw.throughput_stats(per_mesh=True)
    gw.shutdown()

Elastic width ladders + shape classes (pool elasticity without
rebuilds)::

    gw = TopoGateway(cfg, params, u_scale,
                     ladder=(2, 4, 8, 16),      # per-tick rung choice
                     shape_classes=[(16, 8), (32, 8)],
                     autoscale=True, max_slots=16)

``ladder=`` makes slot width a PER-TICK choice instead of a rebuild
event: every bucket engine is built at ``max_slots`` wide, precompiles
the ladder of batch widths at activation, and each tick dispatches at
the smallest rung covering live occupancy — a trickle-phase request
stops paying full-width tick latency just because the engine was
provisioned for bursts. A request served at rung W is bitwise-equal to
the same request on a dedicated fixed-width-W engine; mid-stream rung
changes drop nothing (live lanes compact via exact lane moves).

``shape_classes=`` pads nearby meshes onto canonical shape classes
with a passive border (zero stiffness, fixed dofs, masked filter/OC),
so the compile cache grows with ``len(ladder) x len(shape_classes)``
instead of the fleet's mesh count; densities are cropped back to the
original mesh on completion. Padded serving is bitwise-reproducible
against any engine of the same shape class (it is a different
discretization than the exact mesh, so not bitwise vs an unpadded
engine).

With ``autoscale=True`` the maintenance pass additionally converts the
observed per-bucket arrival rate into a live admission cap
(``engine.set_target_slots``, snapped up to a rung, ``resize`` fleet
events) instead of picking a build-time width — nothing is ever
dropped or rebuilt when the target moves.

Device-resident serving (``fea_backend=`` and backend auto-detection)::

    gw = TopoGateway(cfg, params, u_scale,
                     backend="megakernel",   # CRONet forward as one kernel
                     fea_backend="fused")    # CG iteration as one kernel

``fea_backend="fused"`` moves the batched-CG FEA fallback onto the
fused-solve Pallas kernel (kernels/cg_fused.py): ONE kernel launch runs
the entire Jacobi-PCG convergence loop with the krylov state
VMEM-resident throughout, so a tick exchanges only admissions,
park/restore, and completions with the host. Inside the compiled tick
(the only place the engine ever runs it) densities are
BITWISE-identical to ``fea_backend="reference"`` — the knob is pure
deployment policy, switchable per engine or fleet-wide through the
gateway, and never invalidates a bitwise serving contract.

Every Pallas entry point (the megakernel forward, the fused CG, and the
primitive kernels underneath) resolves ``interpret=None`` by platform
auto-detection: real Mosaic lowering on TPU/GPU, the Pallas interpreter
ONLY as the CPU fallback (``repro.kernels.resolve_interpret``). Tests
and benchmarks can still force a mode with an explicit ``True``/``False``.
On a CPU host the fused backend is the same XLA code path as the
reference plus fewer per-iteration reductions — modestly faster, and
bitwise-equal by construction (``benchmarks/topo_serving.py --device``
measures both).

Serving-data flywheel (train -> serve -> harvest -> fine-tune ->
promote, unattended)::

    from repro.fea import train_cronet
    from repro.serve import (FlywheelController, HarvestLog,
                             ModelRegistry, RegistryRetention,
                             TopoGateway)

    reg = ModelRegistry("runs/registry")
    train_cronet.train_and_register(cfg, reg, tag="prod", steps=2000)

    log = HarvestLog(capacity=64, accept_below=0.8,
                     spool_dir="runs/harvest")       # bounded spooling
    gw = TopoGateway.from_registry(reg, "prod", harvest=log,
                                   canary_window=64, bucket_window=256)
    fly = FlywheelController(
        gw, log,
        trigger_below=0.5,        # bucket acceptance that starts a cycle
        retention=RegistryRetention(reg, keep_per_lineage=2))
    fly.start()                   # daemon; or drive fly.tick() yourself

    # ... serve traffic; a bucket losing to the residual gate now
    # harvests its failures, fine-tunes a mesh-specialized child from
    # its serving checkpoint (finetune_from_tag: warm start + replayed
    # synthetic mix), canaries it on its own bucket, and promotes on a
    # sustained windowed win — auto-rollback guards the downside.
    for ev in gw.events:          # the whole story, typed
        print(ev.kind, ev.mesh, ev.tag, ev.reason)
    fly.stop(); gw.shutdown()

``examples/serve_topo.py --flywheel`` runs this loop end to end;
``benchmarks/topo_serving.py --flywheel --smoke`` is the CI gate.

Observability (``repro.obs`` — zero-dependency, bitwise-invisible)::

    from repro.obs import TelemetrySnapshotter, default_registry

    gw = TopoGateway(cfg, params, u_scale, trace_every=1)
    snap = TelemetrySnapshotter("runs/telemetry.jsonl",
                                extra=gw.throughput_stats).start()
    fut = gw.submit(TopoRequest(uid=0, problem=prob, n_iter=60))
    req = fut.result()
    tr = gw.trace(req.uid)        # or req.trace
    print(tr.render())            # queued -> compute [-> parked] spans,
                                  # per-tick records, CRONet-vs-CG split;
                                  # phase durations tile req's e2e exactly
    for ev in gw.fleet_events():  # typed event log, sorted on t_mono
        print(ev.kind, ev.tag)
    snap.stop(); gw.shutdown()

``trace_every=N`` samples every Nth submission with a ``Trace``: phase
spans (queued / compute / parked) whose boundaries reuse the engine's
own bookkeeping stamps — so they tile submit -> completion exactly —
plus a bounded per-tick ring and the accepted-vs-fallback iteration
split read only at sync boundaries the tick loop already pays for.
Every layer also records into one process-wide ``MetricsRegistry``
(``default_registry()``): queue depth, admission wait, per-(mesh, rung,
backend) tick latency, CG iterations, hit/fallback counters,
preemptions, sheds, canary/flywheel transitions, compile events.
``TelemetrySnapshotter`` spools atomic-replace JSONL (+ a Prometheus
text file) on a daemon cadence; ``repro.obs.dashboard.watch`` renders a
live terminal view (``examples/serve_topo.py --observe``). Tracing
never touches device math: densities are bitwise-identical with it on
or off (``benchmarks/topo_serving.py --observe`` gates this, plus a
<5% tick-latency overhead budget nightly).

The LM-decode serving half (``server``, ``decode``) is deliberately NOT
re-exported here: import those modules directly.
"""
from repro.serve.flywheel import (FlywheelController, FlywheelCycle,
                                  FlywheelState, HarvestLog,
                                  RegistryRetention)
from repro.serve.gateway import TopoGateway
from repro.serve.registry import (ModelRecord, ModelRegistry,
                                  ModelResolver, NoModelError)
from repro.serve.topo_service import TopoServingEngine
from repro.serve.types import (EngineClosed, EngineState, FleetEvent,
                               GatewayOverloaded, OverloadPolicy,
                               QueueFull, RequestShed, TagStats,
                               TopoFuture, TopoRequest,
                               pool_stats, throughput_view)

__all__ = [
    "TopoGateway",
    "TopoServingEngine",
    "ModelRegistry",
    "ModelRecord",
    "ModelResolver",
    "NoModelError",
    "TopoRequest",
    "TopoFuture",
    "OverloadPolicy",
    "GatewayOverloaded",
    "QueueFull",
    "RequestShed",
    "EngineState",
    "EngineClosed",
    "FleetEvent",
    "TagStats",
    "HarvestLog",
    "FlywheelController",
    "FlywheelCycle",
    "FlywheelState",
    "RegistryRetention",
    "pool_stats",
    "throughput_view",
]
