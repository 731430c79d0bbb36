"""Streaming slot-batched topology-optimization serving engine.

The digital-twin workload the paper targets is a continuous ARRIVAL
PROCESS: monitoring events ship load cases one at a time, each with a
freshness deadline, and the updated design must come back before the
deadline passes. This engine serves that workload the way
serve/server.py serves LM decode — requests occupy fixed batch slots and
every tick advances a slot group one hybrid NN-FEA iteration with a
single compiled step — but admission is live:

  * ``submit(req) -> TopoFuture`` is thread-safe and can be called while
    the tick loops are running; the new request is admitted at the next
    tick boundary with NO recompilation (the compiled step is shaped by
    (batch width, mesh), neither of which admission changes).
  * Admission order is (priority, earliest-deadline-first) with
    deterministic tie-breaking and a starvation horizon for
    deadline-less requests (serve/scheduler.py).
  * A slot whose occupant has slack can be preempted for a request about
    to miss its deadline: the occupant's per-lane optimization state is
    parked (lane gather to host, fea/hybrid.park_slot), the lane is
    re-seeded, and the parked request re-enters the queue with its
    original rank, resuming bitwise-exactly on re-admission
    (fea/hybrid.restore_slot).
  * With ``ladder=``, slot width becomes a PER-TICK rung choice instead
    of a rebuild event: the engine precompiles a small sorted ladder of
    batch widths at start (bounding its compile-cache cardinality at
    ``len(ladder)``) and every tick dispatches at the smallest compiled
    rung >= live occupancy — padding lanes are idle problems the masked
    CG ``need`` mask skips. Rung changes migrate live lanes with the
    same exact gather/scatter park/restore uses, so a mid-stream rung
    change drops nothing and perturbs no trajectory.
  * ``shape_padded=True`` marks an engine serving a canonical SHAPE
    CLASS: requests arrive padded onto the class mesh
    (fea2d.pad_problem) carrying a passive-border element mask, and
    harvested densities are cropped back to ``req.orig_mesh``. Compile
    cache across a fleet then grows with len(ladder) x len(shape
    classes), not with the number of distinct request meshes.
  * Lifecycle is an explicit state machine (serve/types.EngineState):
    ``stop()`` is the restartable pause the ``run()`` drain shim cycles
    through; ``shutdown()`` is terminal — ``submit()`` afterwards raises
    ``EngineClosed`` instead of hanging or racing the tick loops.
  * ``run(requests)`` remains as a thin submit+drain compatibility shim
    over the streaming core.

One engine serves ONE mesh: requests whose ``(nelx, nely)`` differs from
the engine's are rejected at submit time. serve/gateway.py is the
mesh-agnostic front door — it buckets mixed-mesh traffic into a pool of
these engines behind one bounded admission queue.

Scaling axes are unchanged from the drain-mode engine: slots per shard
(one compiled step serves the group) and shards (slot groups pinned to
distinct XLA devices — ``shard_devices`` is the single source of truth
for that pinning — each driven by its own tick-loop thread pulling from
the shared EDF queue; on CPU, force host devices with
--xla_force_host_platform_device_count=N to put shards on cores).

Because every op in the batched step is bitwise batch-invariant (see
fea/hybrid.py) and park/restore is an exact lane gather/scatter, the
density an occupied slot produces is exactly the density a standalone
``run_hybrid`` call produces for that request — across admission orders,
slot counts, and preemption cycles. Scheduling buys deadlines, not
approximation.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.cronet import CRONetConfig
from repro.fea import fea2d, hybrid
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.scheduler import (INF, EDFScheduler, SlotView, ladder_rungs,
                                   preempt_victim, rung_for)
from repro.serve.types import (EngineClosed, EngineState, TopoFuture,
                               TopoRequest, pool_stats)

__all__ = ["TopoRequest", "TopoFuture", "TopoServingEngine", "auto_shards",
           "shard_devices", "engine_from_spec", "PHASES"]

# The phases of a shard's tick, in loop order. Each is a profiler span
# ``topo.<phase>`` and a label of ``topo_host_seconds_total`` (wall) and
# ``topo_host_cpu_seconds_total`` (the thread's CPU time); together they
# tile the loop. sync: waits on the device; harvest: one host copy of
# the finished lanes' results, resolve and metrics; admit: scheduler
# lock, EDF pops, preemption decision; park: a preemption's park and
# re-queue; rung: ladder width changes with their lane moves; seed:
# parked-lane restores and the reset mask; upload: the lane-write
# program (slot constants to the device, TrunkNet inputs, lane resets);
# dispatch: the compiled step and the per-lane bookkeeping after it;
# wait: idle, no lane occupied.
PHASES = ("sync", "harvest", "admit", "park", "rung", "seed", "upload",
          "dispatch", "wait")


@dataclasses.dataclass
class _Admission:
    """A queued unit of work: fresh submission or parked preemptee."""
    req: TopoRequest
    future: TopoFuture
    parked: Optional[hybrid.HybridState] = None  # host lane snapshot
    iters_done: int = 0
    first_admit_t: Optional[float] = None
    seq: int = -1                # original EDF rank, preserved across parks
    eff_deadline: float = INF
    # trace bookkeeping (traced requests only): (it, n_cronet, n_fea,
    # cg_iters) device-counter values already attributed to trace
    # windows, so each flush records only the delta since the last one.
    # Survives park/restore because the counters themselves do.
    tr_base: tuple = (0, 0, 0, 0)

    @property
    def iters_left(self) -> int:
        return self.req.n_iter - self.iters_done


@functools.lru_cache(maxsize=64)
def _mesh_template(nelx: int, nely: int):
    """Per-mesh slot constants (element DOF map, element stiffness,
    penalization) — pure functions of the mesh, shared READ-ONLY by
    every engine built for it. Cached at module level so a gateway
    lazily REBUILDING a bucket after a cold eviction (pool elasticity)
    pays neither the stencil assembly nor fresh device uploads: together
    with the ``make_hybrid_step`` cache (same mesh + u_scale = the
    already-compiled step), an engine rebuild is thread spawn + state
    init, not a cold start."""
    template = fea2d.mbb_problem(nelx, nely)
    return template.edof, template.KE, template.penal, template.e_min


def _pack_lanes(f, free, fixed_x, volfrac, elem) -> np.ndarray:
    """One host row per lane, ``[f | free | fixed_x | volfrac | elem]``
    (``elem`` only on shape-padded engines): the lane write's single
    transfer. A fresh buffer, so later host writes cannot reach it."""
    cols = [f, free, fixed_x, volfrac[:, None]]
    if elem is not None:
        cols.append(elem.reshape(len(elem), -1))
    return np.concatenate(cols, axis=1)


@functools.lru_cache(maxsize=64)
def _lane_write_program(nelx: int, nely: int, masked: bool):
    """The compiled lane write of a dirty tick, shared by every engine of
    one mesh and mask variant (jit traces once per rung width):

        write(state, lanes, reset) -> (f, free_mask, fixed_x_mask,
                                       volfrac, elem_mask), load_vol, state

    ``lanes`` is ``_pack_lanes`` output for lanes ``[:width]``; ``reset``
    ((width,) bool) flags the lanes to re-seed. It unpacks the slot
    constants, computes the TrunkNet inputs as ``fea2d.load_volume_b``
    does, and resets the flagged lanes of the donated state
    (``hybrid.reset_lanes``): one dispatch and one host buffer in place
    of an eager op per leaf and lane."""
    ndof = 2 * (nelx + 1) * (nely + 1)
    trace_count = [0]  # bumped per retrace, like make_hybrid_step's

    @functools.partial(jax.jit, donate_argnums=(0,))
    def write(state: hybrid.HybridState, lanes, reset):
        trace_count[0] += 1
        f, free, fixed_x = (lanes[:, k * ndof:(k + 1) * ndof]
                            for k in range(3))
        volfrac = lanes[:, 3 * ndof]
        elem = (lanes[:, 3 * ndof + 1:].reshape(-1, nely, nelx)
                if masked else None)
        load_vol = jax.vmap(
            lambda f, m: fea2d._load_volume(f, m, nelx, nely))(f, fixed_x)
        state = hybrid.reset_lanes(state, reset, volfrac, elem)
        return (f, free, fixed_x, volfrac, elem), load_vol, state

    write.trace_count = trace_count
    return write


def auto_shards(slots: int, device_count: Optional[int] = None) -> int:
    """Largest shard count <= device_count that divides `slots` while
    keeping shard width >= 2 (the minimum bitwise-invariant batch)."""
    if device_count is None:
        device_count = jax.local_device_count()
    for s in range(min(device_count, slots // 2), 1, -1):
        if slots % s == 0:
            return s
    return 1


def shard_devices(slots: int, shards: Optional[int] = None,
                  devices: Optional[list] = None) -> list:
    """Resolve the shard count and pin each shard to a device — the ONE
    place that logic lives (the engine ctor, restarts, and anything that
    wants to predict placement all call this). Round-robin over the local
    device list, so the assignment is a pure function of (slots, shards,
    device list): rebuilding or restarting an engine with the same
    arguments yields the same pinning."""
    if devices is None:
        devices = jax.local_devices()
    if shards is None:
        shards = auto_shards(slots, len(devices))
    if slots < 2:
        # XLA lowers a unit batch dim differently (breaks the bitwise
        # slot-invariance contract); 2 is the minimum invariant width
        raise ValueError("TopoServingEngine needs slots >= 2")
    if slots % shards != 0 or slots // shards < 2:
        raise ValueError(f"slots={slots} not divisible into "
                         f"{shards} shards of width >= 2")
    if shards > len(devices):
        raise ValueError(f"{shards} shards > {len(devices)} devices")
    return [devices[i % len(devices)] for i in range(shards)]


class _Shard:
    """One slot group: host-side slot constants + device-resident state,
    driven by exactly one tick-loop thread (lane bookkeeping is therefore
    single-writer; only the EDF queue is shared). ``index`` is its place
    in the engine, the ``shard`` label of its counters."""

    def __init__(self, engine: "TopoServingEngine", index: int, device):
        self.engine = engine
        self.index = index
        self.device = device
        cfg = engine.cfg
        L = engine.shard_width
        ndof = 2 * (cfg.nelx + 1) * (cfg.nely + 1)
        # empty slots carry f == 0 so the masked CG treats them as
        # converged in zero iterations. Host arrays stay FULL width L;
        # _upload() packs [:width] for the current ladder rung.
        self.f = np.zeros((L, ndof), np.float32)
        self.free = np.zeros((L, ndof), np.float32)
        self.fixed_x = np.zeros((L, ndof), np.float32)
        self.volfrac = np.full((L,), 0.5, np.float32)
        # per-slot passive-border masks (shape-class engines only)
        self.elem = (np.ones((L, cfg.nely, cfg.nelx), np.float32)
                     if engine.shape_padded else None)
        self.slot_adm: List[Optional[_Admission]] = [None] * L
        self.slot_iters = [0] * L
        self.rungs = engine._rungs   # sorted widths, rungs[-1] == L
        self.width = self.rungs[-1]  # currently-dispatched batch width
        self.cap = L                 # live admission cap (set_target_slots)
        self.rung_steps = {r: 0 for r in self.rungs}
        self.rung_changes = 0
        self.migrations = 0          # device lane moves from rung shrinks
        self.params = None          # device copy, refreshed by activate()
        self.mesh = None            # the BatchProblem's mesh leaves, on device
        self.bp = None
        self.load_vol = None
        self.state = None
        # (state, its results on the host): the harvest's copy, made by
        # the first lane_result call for a state and reused by the rest
        self.harvest_copy = None
        self.steps = 0              # dispatched this activation
        self.steps_flushed = 0      # of which in topo_steps_total
        self.busy_t0: Optional[float] = None   # sync-point timing window
        self.steps_in_window = 0
        # host wall and CPU seconds of the tick loop by phase since the
        # last flush into topo_host_seconds_total and
        # topo_host_cpu_seconds_total; one reusable span per phase
        self.host_s = [0.0] * len(PHASES)
        self.host_cpu_s = [0.0] * len(PHASES)
        self.phases = tuple(
            obs_trace.Phase("topo." + name, self.host_s, self.host_cpu_s, i)
            for i, name in enumerate(PHASES))

    def activate(self):
        """Fresh idle state for a (re)started tick loop."""
        e = self.engine
        L = e.shard_width
        self.f[:] = 0.0
        self.free[:] = 0.0
        self.fixed_x[:] = 0.0
        self.volfrac[:] = 0.5
        if self.elem is not None:
            self.elem[:] = 1.0
        self.slot_adm = [None] * L
        self.slot_iters = [0] * L
        self.steps = 0
        self.steps_flushed = 0
        self.busy_t0 = None
        self.steps_in_window = 0
        # params are re-put per activation: a swap_params() between
        # activations (hot model swap) takes effect on the next start
        self.params = jax.device_put(e.params, self.device)
        # the mesh leaves of every BatchProblem this activation uploads:
        # put once, reused by each lane write
        self.mesh = jax.device_put(
            (e.cfg.nelx, e.cfg.nely, e._edof, e._KE, e._penal, e._e_min),
            self.device)
        # precompile every ladder rung before serving traffic (no-op for
        # ladder=None engines and on restarts)
        e._warm_ladder(self.device, self.params)
        # an idle shard starts on the smallest rung; occupancy pulls the
        # width up through _set_width as admissions land
        self.width = self.rungs[0]
        self.state = jax.device_put(
            hybrid.init_state(e.cfg, self._idle_bp(self.width)), self.device)
        self._upload()

    def _idle_bp(self, width: int) -> fea2d.BatchProblem:
        e = self.engine
        idle = fea2d.idle_problem(e.cfg.nelx, e.cfg.nely)
        if e.shape_padded:
            # all-ones mask keeps the treedef identical to live traffic,
            # so the warmed compile is the one real requests hit (the
            # masked step is its own compiled family — bitwise contracts
            # hold within it, not vs the unmasked step)
            idle = idle._replace(elem_mask=jnp.ones(
                (e.cfg.nely, e.cfg.nelx), jnp.float32))
        return fea2d.stack_problems([idle] * width)

    def _upload(self, reset: Optional[np.ndarray] = None):
        """Write lanes ``[:width]`` to the device in one compiled program
        (``_lane_write_program``): the slot constants as one packed
        buffer, ``load_vol`` computed on the device, and the lanes
        flagged in ``reset`` (``seed``'s mask; None flags none)
        re-seeded in the donated state."""
        e = self.engine
        w = self.width
        if reset is None:
            reset = np.zeros(w, bool)
        lanes = _pack_lanes(self.f[:w], self.free[:w], self.fixed_x[:w],
                            self.volfrac[:w],
                            self.elem[:w] if self.elem is not None else None)
        (f, free, fixed_x, volfrac, elem), self.load_vol, self.state = \
            e._lane_write(self.state, lanes, reset)
        nelx, nely, edof, KE, penal, e_min = self.mesh
        self.bp = fea2d.BatchProblem(
            nelx=nelx, nely=nely, edof=edof, KE=KE, f=f, free_mask=free,
            fixed_x_mask=fixed_x, volfrac=volfrac, penal=penal, e_min=e_min,
            elem_mask=elem)
        e._m_writes.inc(mesh=e._mesh_label)
        e._m_resets.inc(int(reset.sum()), mesh=e._mesh_label)

    def fill(self, lane: int, adm: Optional[_Admission]):
        """Write lane HOST constants + bookkeeping for an admission (or
        clear them). Device-state seeding is a separate step (``seed``)
        because under ladder dispatch the lane's device state may not
        exist yet — the tick picks its rung (and resizes the state)
        after admissions land. Caller must _upload() afterwards."""
        if adm is None:
            self.f[lane] = 0.0
            self.free[lane] = 0.0
            self.fixed_x[lane] = 0.0
            self.volfrac[lane] = 0.5
            if self.elem is not None:
                self.elem[lane] = 1.0
        else:
            p = adm.req.problem
            self.f[lane] = np.asarray(p.f)
            self.free[lane] = np.asarray(p.free_mask)
            self.fixed_x[lane] = np.asarray(p.fixed_x_mask)
            self.volfrac[lane] = p.volfrac
            if self.elem is not None:
                self.elem[lane] = (np.asarray(p.elem_mask)
                                   if p.elem_mask is not None else 1.0)
        self.slot_adm[lane] = adm

    def seed(self, lanes: List[int]) -> np.ndarray:
        """Seed the device state of ``lanes``: an exact restore for a
        parked admission, here and now; a fresh reset otherwise (also for
        harvested lanes left empty), flagged in the returned (width,)
        mask for the tick's ``_upload``, which resets them all in its one
        program."""
        reset = np.zeros(self.width, bool)
        for lane in lanes:
            adm = self.slot_adm[lane]
            if adm is not None and adm.parked is not None:
                self.state = hybrid.restore_slot(self.state, lane,
                                                 adm.parked)
                self.slot_iters[lane] = adm.iters_done
                adm.parked = None
            else:
                reset[lane] = True
                self.slot_iters[lane] = 0
        return reset

    def move_lane(self, src: int, dst: int, live: bool):
        """Relocate a lane's occupant to a lower index (rung-shrink
        compaction). ``live=True`` also moves the device state (exact
        lane copy); pending admissions have no device state yet and only
        need their host constants + bookkeeping relabeled."""
        self.f[dst] = self.f[src]
        self.free[dst] = self.free[src]
        self.fixed_x[dst] = self.fixed_x[src]
        self.volfrac[dst] = self.volfrac[src]
        if self.elem is not None:
            self.elem[dst] = self.elem[src]
        self.slot_adm[dst] = self.slot_adm[src]
        self.slot_iters[dst] = self.slot_iters[src]
        self.slot_adm[src] = None
        self.slot_iters[src] = 0
        self.f[src] = 0.0
        self.free[src] = 0.0
        self.fixed_x[src] = 0.0
        self.volfrac[src] = 0.5
        if self.elem is not None:
            self.elem[src] = 1.0
        if live:
            self.state = hybrid.move_slot(self.state, src, dst)
            self.migrations += 1

    def _set_width(self, new_width: int, pending: List[int]) -> bool:
        """Re-rung the shard to ``new_width``: compact occupied lanes
        below the new width (device moves for live lanes, relabels for
        ``pending`` not-yet-seeded ones — ``pending`` is updated in
        place), then resize the device state. Returns True if the width
        changed (caller must _upload)."""
        if new_width == self.width:
            return False
        for src in range(len(self.slot_adm) - 1, new_width - 1, -1):
            if self.slot_adm[src] is None:
                continue
            dst = next(i for i in range(new_width)
                       if self.slot_adm[i] is None and i not in pending)
            self.move_lane(src, dst, live=src not in pending)
            if src in pending:
                pending[pending.index(src)] = dst
        self.state = hybrid.resize_state(self.state, new_width)
        self.width = new_width
        self.rung_changes += 1
        return True

    def lane_result(self, lane: int) -> tuple:
        """A finished lane's result on the host: (density, compliance,
        cronet_iters, fea_iters, cg_iters, cg_breakdowns), sliced from a
        host copy of every lane's. The first call for the current
        ``state`` makes the copy (counted in
        ``topo_harvest_copies_total``): ``jax.device_get`` issues the six
        whole arrays' transfers together and waits on them once, and runs
        no program. Later calls for the same state reuse it, so a tick
        that harvests several lanes copies once."""
        st = self.state
        if self.harvest_copy is None or self.harvest_copy[0] is not st:
            self.harvest_copy = (st, jax.device_get(
                (st.x, st.compliance, st.n_cronet, st.n_fea, st.cg_iters,
                 st.cg_breakdowns)))
            e = self.engine
            e._m_harvest_copies.inc(mesh=e._mesh_label, shard=self.index)
        x, compliance, n_cronet, n_fea, cg_iters, cg_breakdowns = \
            self.harvest_copy[1]
        return (x[lane].copy(), float(compliance[lane]),
                int(n_cronet[lane]), int(n_fea[lane]), int(cg_iters[lane]),
                int(cg_breakdowns[lane]))

    def park(self, lane: int) -> _Admission:
        """Evict the lane's occupant: lane-gather its state to host and
        return the admission carrying the snapshot (syncs the device)."""
        adm = self.slot_adm[lane]
        adm.parked = hybrid.park_slot(self.state, lane)
        adm.iters_done = self.slot_iters[lane]
        adm.req.preemptions += 1
        self.slot_adm[lane] = None
        return adm


class TopoServingEngine:
    """Serve TopoRequests sharing the engine's (nelx, nely) mesh over
    `slots` batch slots in `shards` device-pinned slot groups, with live
    streaming admission.

    Streaming API: ``submit(req) -> TopoFuture`` (starts the tick loops
    on first use), ``drain()`` to wait for quiescence, ``stop()`` to
    pause the worker threads (the engine restarts cleanly on the next
    submit), ``shutdown()`` to close the engine for good (``submit``
    afterwards raises ``EngineClosed``). ``run(requests)`` is a
    compatibility shim: submit all, wait for all, stop the loops if this
    call started them.

    Scheduling: (priority, EDF) admission with a `starvation_horizon`
    bound for deadline-less requests; `preempt=True` enables slack-safe
    slot preemption (see serve/scheduler.py). `tick_time_s` overrides the
    measured per-step time estimate the preemption test uses
    (deterministic tests set it; production leaves the EMA).

    completed_limit bounds the completed-request history ring
    (`throughput_stats` reports over it): a long-lived engine keeps the
    most recent `completed_limit` results instead of growing without
    bound.

    backend: "oracle" (core/cronet.py forward) or "megakernel"
    (kernels/cronet_pipeline.py, batched over the Pallas grid; interpret
    mode is auto-detected per platform — the interpreter only as CPU
    fallback).
    fea_backend: "reference" (pure-XLA batched CG) or "fused"
    (kernels/cg_fused.py single-pallas_call iteration). Bitwise-identical
    densities either way (fea2d.solve_b docstring), so the knob is pure
    deployment policy; it threads through TopoGateway(**engine_kwargs).
    shards: None = auto (one shard per available device while shard width
    stays >= 2); 1 = single compiled group (single-device behaviour).

    ladder: optional sorted width ladder (e.g. (2, 4, 8, 16), clamped to
    [2, shard_width]; shard_width is always a rung). When set, every
    tick dispatches at the smallest rung >= live occupancy and the whole
    ladder is precompiled at start, so the engine's compile count is
    bounded by len(ladder) no matter how occupancy varies.
    ``set_target_slots`` then caps live admissions per shard at a rung —
    the gateway's autoscale lever, applied per tick instead of per
    rebuild. ladder=None is the pre-ladder engine: one fixed width.

    shape_padded: the engine serves a canonical shape CLASS — requests
    arrive padded to (cfg.nelx, cfg.nely) by fea2d.pad_problem with a
    passive-border ``elem_mask``, and harvested densities are cropped
    back to ``req.orig_mesh``. The flag is explicit (not inferred from
    traffic) so the ladder warmup compiles the masked step variant the
    live requests will hit.
    """

    def __init__(self, cfg: CRONetConfig, params, u_scale: float,
                 slots: int = 8, precision: str = "fp32",
                 error_threshold: float = 0.05, verify_every: int = 3,
                 rmin: float = 1.5, backend: str = "oracle",
                 shards: Optional[int] = None, preempt: bool = True,
                 starvation_horizon: float = 60.0,
                 tick_time_s: Optional[float] = None,
                 completed_limit: int = 1024,
                 model_tag: Optional[str] = None,
                 ladder: Optional[Sequence[int]] = None,
                 shape_padded: bool = False,
                 fea_backend: str = "reference",
                 trace_every: int = 0,
                 metrics: Optional[obs_metrics.MetricsRegistry] = None):
        self._devices = shard_devices(slots, shards)
        self.cfg = cfg
        self.slots = slots
        self.shards = len(self._devices)
        self.shard_width = slots // self.shards
        self.ladder = tuple(int(r) for r in ladder) if ladder else None
        self._rungs = (ladder_rungs(self.shard_width, self.ladder)
                       if self.ladder is not None else (self.shard_width,))
        self.shape_padded = shape_padded
        # one warm-up lock per device: shards on distinct devices warm
        # their ladders concurrently (compiles release the GIL)
        self._warm_locks = {dev: threading.Lock() for dev in self._devices}
        self._warmed_devices: set = set()
        self.u_scale = u_scale
        self.precision = precision
        self.backend = backend
        self.fea_backend = fea_backend
        self.model_tag = model_tag
        self._error_threshold = error_threshold
        self._verify_every = verify_every
        self._rmin = rmin
        self.params = hybrid.cast_params(params, precision)
        self.step = hybrid.make_hybrid_step(
            cfg, u_scale, error_threshold, verify_every, rmin, precision,
            backend, fea_backend)
        self._lane_write = _lane_write_program(cfg.nelx, cfg.nely,
                                               shape_padded)
        self.preempt = preempt
        self.tick_time_s = tick_time_s
        (self._edof, self._KE,
         self._penal, self._e_min) = _mesh_template(cfg.nelx, cfg.nely)
        self._shards = [_Shard(self, i, dev)
                        for i, dev in enumerate(self._devices)]
        self._sched = EDFScheduler(starvation_horizon)
        self._threads: List[threading.Thread] = []
        self._running = False
        self._stopping = False
        self._closed = False
        self._ever_started = False
        self._inflight = 0
        self._failure: Optional[BaseException] = None
        self._completed: collections.deque = collections.deque(
            maxlen=completed_limit)
        self._lifecycle = threading.Lock()
        self._sec_per_step: Optional[float] = None
        # ---- observability (repro.obs): all recording is host-side
        # stamps/increments, so densities are bitwise-identical with
        # tracing on or off. trace_every=N samples every Nth submission
        # (0 = off); metrics default to the process-wide registry.
        self.trace_every = int(trace_every)
        self._trace_n = 0
        self.metrics = (metrics if metrics is not None
                        else obs_metrics.default_registry())
        self._mesh_label = f"{cfg.nelx}x{cfg.nely}"
        m = self.metrics
        self._m_wait = m.histogram(
            "topo_admission_wait_s",
            "submit -> first slot admission (queue age)")
        self._m_tick = m.histogram(
            "topo_tick_latency_s",
            "per-compiled-step latency by (mesh, rung, backend)")
        self._m_cg = m.histogram(
            "topo_cg_iters",
            "CG iterations burned by a completed request's FEA fallbacks",
            buckets=obs_metrics.DEFAULT_COUNT_BUCKETS)
        self._m_cg_broke = m.counter(
            "topo_cg_breakdowns_total",
            "FEA fallbacks whose CG stopped at a breakdown, unconverged")
        self._m_done = m.counter(
            "topo_completions_total",
            "completed requests by (mesh, deadline outcome)")
        self._m_preempt = m.counter(
            "topo_preemptions_total",
            "slot evictions (park) in favour of more urgent work")
        self._m_iters = m.counter(
            "topo_iters_total",
            "hybrid iterations by path: CRONet-accepted vs FEA fallback")
        self._m_inflight = m.gauge(
            "topo_inflight",
            "accepted-but-unresolved requests per engine mesh")
        self._m_host = m.counter(
            "topo_host_seconds_total",
            "host wall seconds of the shard tick loops by (mesh, shard, "
            "phase)")
        self._m_host_cpu = m.counter(
            "topo_host_cpu_seconds_total",
            "CPU seconds of the shard tick-loop threads by (mesh, shard, "
            "phase)")
        self._m_steps = m.counter(
            "topo_steps_total",
            "compiled steps dispatched by the shard tick loops, by (mesh, "
            "shard)")
        self._m_writes = m.counter(
            "topo_lane_writes_total",
            "compiled lane-write programs dispatched (dirty ticks and "
            "activations), by mesh")
        self._m_resets = m.counter(
            "topo_lanes_reset_total",
            "lanes re-seeded by the lane-write programs, by mesh")
        self._m_harvest_copies = m.counter(
            "topo_harvest_copies_total",
            "device-to-host copies of the finished lanes' results the "
            "harvest made, by (mesh, shard)")
        self.preemptions = 0        # engine lifetime eviction count
        self._steps_base = 0        # steps from finished activations
        self.last_run_steps = 0     # most recent run() only
        self._steps_lock = threading.Lock()

    @property
    def total_steps(self) -> int:
        """Engine-lifetime compiled-step count (live, includes the
        current activation's in-flight shard counters)."""
        with self._steps_lock:
            return self._steps_base + sum(sh.steps for sh in self._shards)

    # --------------------------------------------------------- lifecycle

    @property
    def running(self) -> bool:
        return self._running

    @property
    def inflight(self) -> int:
        """Requests accepted but not yet resolved (queued + in slots) —
        the gateway's per-engine depth signal."""
        return self._inflight

    @property
    def state(self) -> EngineState:
        if self._failure is not None:
            return EngineState.FAILED
        if self._closed:
            return EngineState.CLOSED
        with self._lifecycle:
            if self._running and any(t.is_alive() for t in self._threads):
                return EngineState.RUNNING
        return EngineState.STOPPED if self._ever_started else EngineState.NEW

    def start(self):
        """Spawn one tick-loop thread per shard (idempotent)."""
        with self._lifecycle:
            if self._closed:
                raise EngineClosed(
                    f"engine ({self.cfg.nelx}x{self.cfg.nely}) is shut "
                    f"down; build a new one")
            if self._running:
                if any(t.is_alive() for t in self._threads):
                    return
                # a stop(wait=False) left _running set after the workers
                # drained and exited: recover and restart
                self._threads = []
            if self._failure is not None:
                raise RuntimeError("engine failed; build a new one") \
                    from self._failure
            self._stopping = False
            self._running = True
            self._ever_started = True
            self._threads = [
                threading.Thread(target=self._shard_loop, args=(sh,),
                                 name=f"topo-shard-{i}", daemon=True)
                for i, sh in enumerate(self._shards)]
            for t in self._threads:
                t.start()

    def stop(self, wait: bool = True):
        """Pause serving: workers finish the queue and all occupied
        slots, then exit. With wait=True, joins the threads. The engine
        RESTARTS on the next submit()/start() — use ``shutdown()`` to
        close it for good."""
        with self._lifecycle:
            if not self._running and not self._threads:
                return
            with self._sched.cond:
                self._stopping = True
                self._sched.cond.notify_all()
            threads = list(self._threads)
        if wait:
            for t in threads:
                t.join()
            with self._lifecycle:
                self._running = False
                self._threads = []

    def shutdown(self, wait: bool = True):
        """Terminal stop: drain like ``stop()`` and transition to
        CLOSED — every later submit()/start() raises ``EngineClosed``
        (in-flight work still completes)."""
        self._closed = True
        self.stop(wait)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted request has resolved."""
        with self._sched.cond:
            return self._sched.cond.wait_for(
                lambda: self._inflight == 0 or self._failure is not None,
                timeout)

    def swap_params(self, params, u_scale: Optional[float] = None, *,
                    model_tag: Optional[str] = None):
        """Replace the engine's model between activations (the hot-swap
        mechanism behind ``TopoGateway.swap_model``): new fp32 params,
        optionally a new deployed ``u_scale`` (the compiled step is
        rebuilt through the ``make_hybrid_step`` cache — same batch
        shapes, so a swap never recompiles unless u_scale changed), and
        the ``model_tag`` stamped on every subsequent completion.

        The engine must be quiescent: call ``drain()`` + ``stop()``
        first (the gateway's ``swap_model`` does exactly that). The next
        ``submit()``/``start()`` restarts the tick loops, and each
        shard's ``activate()`` re-uploads the new params to its device.
        """
        with self._lifecycle:
            if self._running and any(t.is_alive() for t in self._threads):
                raise RuntimeError(
                    "swap_params on a running engine: drain() and stop() "
                    "it first (TopoGateway.swap_model does this)")
            self.params = hybrid.cast_params(params, self.precision)
            if u_scale is not None and u_scale != self.u_scale:
                self.u_scale = u_scale
                self.step = hybrid.make_hybrid_step(
                    self.cfg, u_scale, self._error_threshold,
                    self._verify_every, self._rmin, self.precision,
                    self.backend, self.fea_backend)
            self.model_tag = model_tag

    # ------------------------------------------------------------ ladder

    @property
    def rungs(self) -> tuple:
        """Compiled per-shard width ladder (single entry for ladder=None)."""
        return self._rungs

    def _warm_ladder(self, device, params):
        """Compile, on ``device``, every program a tick can dispatch there,
        before traffic lands — 'compile-at-start of the whole ladder'. Per
        rung: one idle step, a park and a restore, the resizes to every
        other rung, a lane move and the lane write (a harvest's host copy
        runs no program). Eager lane ops compile once per shape and
        device, not per lane index, so lane 0 stands for every lane; the
        jit cache then serves every later tick. Idempotent per device
        (restarts skip it), under that device's own lock, so shards on
        distinct devices warm concurrently; no-op for ladder=None
        engines."""
        if self.ladder is None:
            return
        with self._warm_locks[device]:
            if device in self._warmed_devices:
                return
            states = {}
            for r in self._rungs:
                bp = jax.device_put(self._shards[0]._idle_bp(r), device)
                st = jax.device_put(hybrid.init_state(self.cfg, bp), device)
                st = self.step(params, bp, fea2d.load_volume_b(bp), st)
                jax.block_until_ready(st.it)
                # a preemption's park and restore
                st = hybrid.restore_slot(st, 0, hybrid.park_slot(st, 0))
                jax.block_until_ready(st.it)
                states[r] = st
            # rung transitions dispatch un-jitted resize/compaction ops
            # whose first use would otherwise compile INSIDE a serving
            # tick (a multi-hundred-ms latency spike on the first burst);
            # touch every rung pair and a lane move here instead
            ndof = 2 * (self.cfg.nelx + 1) * (self.cfg.nely + 1)
            for a in self._rungs:
                for b in self._rungs:
                    if a != b:
                        jax.block_until_ready(
                            hybrid.resize_state(states[a], b).it)
                # first compaction at a fresh width compiles the eager
                # lane ops; per-lane residuals after this are dispatch-only
                jax.block_until_ready(hybrid.move_slot(states[a], 1, 0).it)
                # the tick's lane write at this rung, in the engine's mask
                # variant (it donates states[a], used no more)
                elem = (np.ones((a, self.cfg.nely, self.cfg.nelx),
                                np.float32) if self.shape_padded else None)
                zeros = np.zeros((a, ndof), np.float32)
                lanes = _pack_lanes(zeros, zeros, zeros,
                                    np.full(a, 0.5, np.float32), elem)
                jax.block_until_ready(self._lane_write(
                    states[a], lanes, np.ones(a, bool))[2].x)
            self._warmed_devices.add(device)

    def set_target_slots(self, n: int) -> int:
        """Live autoscale lever (ladder engines only): cap concurrent
        occupancy at ``n`` total slots, snapped UP to a per-shard rung.
        Takes effect at the next tick boundary — queued requests above
        the cap simply wait; nothing is dropped or rebuilt. Returns the
        applied total (== ``slots`` for ladder=None engines, which only
        resize via rebuild)."""
        if self.ladder is None:
            return self.slots
        per = max(2, -(-int(n) // self.shards))   # ceil-divide across shards
        rung = rung_for(per, self._rungs)
        for sh in self._shards:
            sh.cap = rung
        return rung * self.shards

    # --------------------------------------------------------- streaming

    def submit(self, req: TopoRequest,
               deadline_s: Optional[float] = None, priority: int = 0,
               _future: Optional[TopoFuture] = None) -> TopoFuture:
        """Thread-safe live admission: enqueue `req` ((priority, EDF)
        rank) and return a completion future. Starts the tick loops if
        needed; the request is admitted at a tick boundary without
        recompiling the batched step.

        ``_future`` is the gateway hook: a pre-stamped request arriving
        with its front-door future keeps that future (and its original
        submit_t/deadline), so callers see one handle end to end.
        """
        p = req.problem
        if (p.nelx, p.nely) != (self.cfg.nelx, self.cfg.nely):
            raise ValueError(
                f"request {req.uid} mesh {p.nelx}x{p.nely} does not "
                f"match engine mesh {self.cfg.nelx}x{self.cfg.nely}")
        if deadline_s is not None:
            req.deadline_s = deadline_s
        if priority:
            req.priority = priority
        self.start()   # no-op while workers are alive; EngineClosed if shut
        # deadline/latency bookkeeping runs on the monotonic clock: an
        # NTP step must not fabricate deadline misses (wall-clock is used
        # only for the user-facing completed_t stamp at harvest)
        now = time.monotonic()
        if _future is None:
            fut = TopoFuture(req)
            req.submit_t = now
            req.deadline = (now + req.deadline_s
                            if req.deadline_s is not None else None)
        else:
            fut = _future   # gateway already stamped submit_t/deadline
        # trace sampling: every Nth submission rides with a Trace. The
        # queued span opens at the request's OWN submit stamp (gateway
        # front-door stamp when routed), so span sums tile the full
        # end-to-end latency, not just the engine-local part.
        if self.trace_every > 0 and req.trace is None:
            self._trace_n += 1
            if self._trace_n % self.trace_every == 0:
                req.trace = obs_trace.Trace(req.uid)
        if req.trace is not None and req.trace.submit_t is None:
            req.trace.begin(obs_trace.QUEUED, t=req.submit_t)
        adm = _Admission(req, fut)
        with self._sched.cond:
            if self._closed:
                raise EngineClosed("engine is shut down")
            if self._stopping:
                # a restartable stop() is still draining: this is a
                # transient pause, NOT the terminal CLOSED state — the
                # engine accepts again once the drain finishes
                raise RuntimeError(
                    "engine is stopping; retry once stop() completes")
            if self._failure is not None:
                raise RuntimeError("engine failed") from self._failure
            self._inflight += 1
            self._m_inflight.set(self._inflight, mesh=self._mesh_label)
            entry = self._sched.push(adm, req.deadline, now,
                                     priority=req.priority)
            adm.seq, adm.eff_deadline = entry.seq, entry.eff_deadline
        return fut

    def trace(self, uid: int) -> Optional[obs_trace.Trace]:
        """Look up a completed request's trace by uid (None when the
        request wasn't sampled or has scrolled out of the completed
        ring)."""
        with self._sched.cond:
            for r in self._completed:
                if r.uid == uid:
                    return r.trace
        return None

    # --------------------------------------------------------- tick loop

    def _estimate(self) -> float:
        if self.tick_time_s is not None:
            return self.tick_time_s
        est = self._sec_per_step
        return est if est is not None else 0.0

    def _trace_flush(self, adm: _Admission, t: float, it: int, cro: int,
                     fea: int, cg: int):
        """Append the accepted-vs-fallback delta since the last flush to
        the admission's trace window ring (traced requests only)."""
        b = adm.tr_base
        d_it, d_cro, d_fea, d_cg = it - b[0], cro - b[1], fea - b[2], cg - b[3]
        if d_it or d_cro or d_fea or d_cg:
            adm.req.trace.window(t, d_it, d_cro, d_fea, d_cg)
        adm.tr_base = (it, cro, fea, cg)

    def _flush_host(self, shard: _Shard):
        """Move the shard's per-phase host wall and CPU seconds into
        ``topo_host_seconds_total`` and ``topo_host_cpu_seconds_total``,
        and its steps since the last flush into ``topo_steps_total``, each
        under the shard's ``shard`` label (tick-loop thread only)."""
        wall, cpu = shard.host_s, shard.host_cpu_s
        for i, name in enumerate(PHASES):
            if wall[i] or cpu[i]:
                self._m_host.inc(wall[i], mesh=self._mesh_label,
                                 shard=shard.index, phase=name)
                self._m_host_cpu.inc(cpu[i], mesh=self._mesh_label,
                                     shard=shard.index, phase=name)
                wall[i] = cpu[i] = 0.0
        if shard.steps > shard.steps_flushed:
            self._m_steps.inc(shard.steps - shard.steps_flushed,
                              mesh=self._mesh_label, shard=shard.index)
            shard.steps_flushed = shard.steps

    def _harvest_lane(self, shard: _Shard, lane: int, now: float):
        """Pull a finished lane's result from the tick's host copy
        (``_Shard.lane_result``) + resolve."""
        adm = shard.slot_adm[lane]
        req = adm.req
        (req.density, req.compliance, req.cronet_iters, req.fea_iters,
         req.cg_iters, req.cg_breakdowns) = shard.lane_result(lane)
        if req.orig_mesh is not None:
            # shape-class serving: crop the passive border back off so
            # the caller sees the mesh they submitted
            req.density = fea2d.crop_density(req.density, *req.orig_mesh)
        req.model_tag = self.model_tag
        t_done = time.monotonic()    # deadline math: monotonic, like submit
        req.completed_t = time.time()  # user-facing wall-clock stamp
        req.latency_s = t_done - adm.first_admit_t
        req.deadline_met = (None if req.deadline is None
                            else t_done <= req.deadline)
        req.done = True
        if req.trace is not None:
            # final window + completion BEFORE resolving, so done
            # callbacks (the gateway's trace registry) see it complete
            self._trace_flush(adm, t_done,
                              req.cronet_iters + req.fea_iters,
                              req.cronet_iters, req.fea_iters,
                              req.cg_iters)
            req.trace.finish(t=t_done, iters=req.cronet_iters
                             + req.fea_iters)
        shard.slot_adm[lane] = None
        with self._sched.cond:
            self._completed.append(req)
            self._inflight -= 1
            self._m_inflight.set(self._inflight, mesh=self._mesh_label)
            self._sched.cond.notify_all()
        adm.future._resolve()
        outcome = ("none" if req.deadline_met is None
                   else "met" if req.deadline_met else "missed")
        self._m_done.inc(mesh=self._mesh_label, outcome=outcome)
        if req.cronet_iters:
            self._m_iters.inc(req.cronet_iters, mesh=self._mesh_label,
                              path="cronet")
        if req.fea_iters:
            self._m_iters.inc(req.fea_iters, mesh=self._mesh_label,
                              path="fea")
        self._m_cg.observe(req.cg_iters, mesh=self._mesh_label)
        if req.cg_breakdowns:
            self._m_cg_broke.inc(req.cg_breakdowns, mesh=self._mesh_label)
        # the harvest's copy waited on every dispatched step: close the
        # timing window and update the per-step estimate
        if shard.steps_in_window > 0 and shard.busy_t0 is not None:
            per = (t_done - shard.busy_t0) / shard.steps_in_window
            self._sec_per_step = (per if self._sec_per_step is None
                                  else 0.5 * self._sec_per_step + 0.5 * per)
            self._m_tick.observe(per, n=shard.steps_in_window,
                                 mesh=self._mesh_label, rung=shard.width,
                                 backend=self.fea_backend)
        shard.busy_t0 = t_done
        shard.steps_in_window = 0

    def _admit_lane(self, shard: _Shard, lane: int, adm: _Admission,
                    now: float):
        if adm.first_admit_t is None:
            adm.first_admit_t = now
            adm.req.admitted_t = now
            adm.req.queue_wait_s = now - adm.req.submit_t
            self._m_wait.observe(adm.req.queue_wait_s,
                                 mesh=self._mesh_label)
        if adm.req.trace is not None:
            # closes the open queued/parked span at the same stamp, so
            # the phase timeline stays contiguous across preemptions
            adm.req.trace.begin(obs_trace.COMPUTE, t=now, lane=lane)
        shard.fill(lane, adm)

    def _shard_loop(self, shard: _Shard):
        """One shard's tick loop. Each tick is a ``topo.tick`` step span in
        the profiler's trace, tiled by its ``PHASES`` spans; their host
        wall and CPU seconds go into ``topo_host_seconds_total`` and
        ``topo_host_cpu_seconds_total``, and the steps into
        ``topo_steps_total``, every second dispatch and when the loop
        exits."""
        sched = self._sched
        try:
            shard.activate()
            while True:
                with jax.profiler.StepTraceAnnotation("topo.tick",
                                                      step_num=shard.steps):
                    if not self._tick(shard):
                        break
        except BaseException as exc:  # fail every waiter, don't hang
            with sched.cond:
                self._failure = exc
                self._stopping = True
                while True:
                    entry = sched.pop()
                    if entry is None:
                        break
                    self._inflight -= 1
                    entry.payload.future._resolve(exc)
                for i, adm in enumerate(shard.slot_adm):
                    if adm is not None:
                        shard.slot_adm[i] = None
                        self._inflight -= 1
                        adm.future._resolve(exc)
                self._sched.cond.notify_all()
            raise
        finally:
            self._flush_host(shard)
            with self._steps_lock:
                self._steps_base += shard.steps
                shard.steps = 0

    def _tick(self, shard: _Shard) -> bool:
        """One tick: harvest finished lanes, drain admissions (EDF pops +
        at most one slack-safe preemption), pick the ladder rung for the
        live occupancy (compact + resize when it changed), seed the lanes
        touched this tick, dispatch the next compiled step; or, with no
        lane occupied, wait for work. No device sync except before a
        harvest, at park and every second dispatch. Returns False when
        the loop is to exit."""
        sched = self._sched
        L = self.shard_width
        (sync, harvest, admit, park, rung, seed, upload, dispatch,
         wait) = shard.phases
        now = time.monotonic()
        done = [i for i, adm in enumerate(shard.slot_adm)
                if adm is not None and shard.slot_iters[i] >= adm.req.n_iter]
        if done:
            # the lane reads would wait for the device anyway: waiting
            # here first leaves the harvest phase host work and its one
            # copy of the finished lanes' results
            with sync:
                jax.block_until_ready(shard.state)
            with harvest:
                for i in done:
                    self._harvest_lane(shard, i, now)
        harvested = bool(done)
        # -- admissions: atomic vs concurrent submit(). fill() writes host
        # constants only; device seeding waits until the tick's rung is
        # settled (seeds list below)
        dirty = harvested
        seeds: List[int] = []     # admitted lanes awaiting device seed
        cleared: List[int] = []   # harvested lanes left empty
        cap = shard.cap
        with admit, sched.cond:
            occupied_n = sum(a is not None for a in shard.slot_adm)
            for i in range(L):
                if shard.slot_adm[i] is not None:
                    continue
                entry = sched.pop() if occupied_n < cap else None
                if entry is None:
                    if harvested:
                        shard.fill(i, None)  # clear stale load
                        cleared.append(i)
                    continue
                self._admit_lane(shard, i, entry.payload, now)
                seeds.append(i)
                occupied_n += 1
                dirty = True
            # preemption: queue head about to miss, no free lane. Decide
            # and pop the head under the lock; the actual park (a device
            # sync) happens after release so other shards and submit()
            # are not stalled behind it. Popping the head BEFORE
            # re-queueing the victim also matters: a long-waiting
            # deadline-less victim can outrank the head (starvation
            # horizon), and popping after the push would hand the lane
            # straight back to the evictee. Preemption stays keyed to a
            # TRULY full shard: a rung cap below full width pauses
            # admission but never evicts (the cap is elasticity, not
            # urgency).
            victim = preempt_entry = None
            head = sched.peek() if self.preempt else None
            if head is not None and all(a is not None
                                        for a in shard.slot_adm):
                views = [
                    None if a is None else SlotView(
                        deadline=(a.req.deadline if a.req.deadline
                                  is not None else INF),
                        iters_left=a.req.n_iter - shard.slot_iters[i],
                        preemptible=i not in seeds)
                    for i, a in enumerate(shard.slot_adm)]
                victim = preempt_victim(
                    head.deadline, head.payload.iters_left,
                    views, now, self._estimate())
                if victim is not None:
                    preempt_entry = sched.pop()
            idle = (preempt_entry is None
                    and all(a is None for a in shard.slot_adm))
            if idle:
                if self._stopping and len(sched._heap) == 0:
                    return False
                shard.busy_t0 = None
                shard.steps_in_window = 0
        if idle:
            with wait, sched.cond:
                # checked again under the lock: a submit() since the
                # admit phase has already notified
                if not sched._heap and not self._stopping:
                    sched.cond.wait(timeout=0.1)
            return True
        if preempt_entry is not None:
            with park:
                parked = shard.park(victim)   # device sync, lock-free
                self.preemptions += 1
                self._m_preempt.inc(mesh=self._mesh_label)
                if parked.req.trace is not None:
                    # the parked snapshot is already on host: flush the
                    # window up to the park and open the parked span
                    # (closed again at re-admission)
                    t_park = time.monotonic()
                    self._trace_flush(
                        parked, t_park, int(parked.parked.it),
                        int(parked.parked.n_cronet),
                        int(parked.parked.n_fea),
                        int(parked.parked.cg_iters))
                    parked.req.trace.begin(obs_trace.PARKED, t=t_park,
                                           iters_done=parked.iters_done)
                sched.push(parked, parked.req.deadline, now,
                           seq=parked.seq, eff_deadline=parked.eff_deadline,
                           priority=parked.req.priority)
                self._admit_lane(shard, victim, preempt_entry.payload, now)
                seeds.append(victim)
                dirty = True
        # -- ladder rung: smallest compiled width >= occupancy. Live lanes
        # above the new width migrate down via exact lane copies BEFORE
        # the state is sliced, so a rung shrink never touches a
        # trajectory; seeds (admitted this tick, no device state yet) are
        # relabeled in place.
        width = rung_for(sum(a is not None for a in shard.slot_adm),
                         shard.rungs)
        if width != shard.width:
            with rung:
                shard._set_width(width, seeds)
                dirty = True
        # reset harvested-but-idle lane state, unless a rung shrink sliced
        # it off or compacted a live lane into it
        cleared = [i for i in cleared
                   if i < shard.width and shard.slot_adm[i] is None]
        reset = None
        if seeds or cleared:
            with seed:
                reset = shard.seed(seeds + cleared)
        if dirty:
            with upload:
                shard._upload(reset)
        # -- tick: one compiled step, admissions drain before the next
        # one; dispatch is async
        with dispatch:
            if shard.busy_t0 is None:
                shard.busy_t0 = time.monotonic()
            shard.state = self.step(shard.params, shard.bp,
                                    shard.load_vol, shard.state)
            shard.steps += 1
            shard.rung_steps[shard.width] += 1
            shard.steps_in_window += 1
            t_tick = None    # stamped lazily, only if a lane is traced
            for i in range(L):
                adm_i = shard.slot_adm[i]
                if adm_i is not None:
                    shard.slot_iters[i] += 1
                    if adm_i.req.trace is not None:
                        if t_tick is None:
                            t_tick = time.monotonic()
                        adm_i.req.trace.tick(t_tick, shard.width,
                                             shard.slot_iters[i])
        # bound the dispatch-ahead depth: unchecked, the host can queue the
        # whole burst to the next completion (~shard width x n_iter steps)
        # before the device catches up, and a request admitted
        # "immediately" would start computing behind that backlog —
        # blowing exactly the tight deadlines the scheduler exists to
        # protect. Waiting on the current frontier every 2 dispatches
        # keeps admission-to-silicon latency <= 2 ticks at negligible
        # pipeline cost (host-side bookkeeping is microseconds per tick).
        if shard.steps_in_window % 2 == 0:
            with sync:
                jax.block_until_ready(shard.state.it)
            self._flush_host(shard)
        return True

    # -------------------------------------------------------------- shim

    def run(self, requests: List[TopoRequest]) -> List[TopoRequest]:
        """Drain-mode compatibility shim over the streaming core: submit
        everything, wait for completion, and stop the tick loops if this
        call started them. Returns the requests with densities filled."""
        steps_before = self.total_steps
        was_running = self._running
        futs = [self.submit(r) for r in requests]
        for f in futs:
            f.result()
        if not was_running:
            self.stop()
        self.last_run_steps = self.total_steps - steps_before
        return requests

    # ------------------------------------------------------------- stats

    def throughput_stats(self, requests: Optional[List[TopoRequest]] = None,
                         wall_s: Optional[float] = None) -> Dict[str, float]:
        """Serving stats over `requests` (default: the completed-request
        ring, i.e. the most recent `completed_limit` completions). See
        types.pool_stats for the shared metric definitions."""
        if requests is None:
            with self._sched.cond:
                pool = list(self._completed)
        else:
            pool = requests
        stats = pool_stats(pool, wall_s)
        stats.update({
            "preemptions": float(self.preemptions),
            "batched_steps": float(self.last_run_steps),
            "total_steps": float(self.total_steps),
            "model_tag": self.model_tag,
            "fea_backend": self.fea_backend,
        })
        if self.ladder is not None:
            rung_steps: Dict[int, int] = {r: 0 for r in self._rungs}
            for sh in self._shards:
                for r, c in sh.rung_steps.items():
                    rung_steps[r] += c
            stats["ladder"] = {
                "rungs": list(self._rungs),
                "widths": [sh.width for sh in self._shards],
                "caps": [sh.cap for sh in self._shards],
                "rung_steps": {str(r): float(c)
                               for r, c in sorted(rung_steps.items())},
                "rung_changes": float(sum(sh.rung_changes
                                          for sh in self._shards)),
                "migrations": float(sum(sh.migrations
                                        for sh in self._shards)),
            }
        return stats


# ------------------------------------------------------------- worker build


def engine_from_spec(spec: Dict) -> "TopoServingEngine":
    """Build a ``TopoServingEngine`` from a picklable description — the
    ONE engine factory the multi-process serving path reuses in-worker
    (serve/workers.py ships a spec over the RPC pipe instead of a live
    engine, which could never pickle its threads/locks/device buffers).

    ``spec`` keys:

      * ``cfg`` — the bucket's ``CRONetConfig`` (already mesh-replaced).
      * ``params`` / ``u_scale`` — explicit model arrays; OR
      * ``registry_root`` + ``model_tag`` — load the params from the
        shared on-disk ``ModelRegistry`` instead of pickling the full
        tree through the pipe (the cross-process deployment shape: one
        registry, many workers, params read once per worker).
      * ``slots`` / ``model_tag`` / ``ladder`` / ``shape_padded`` —
        engine geometry, verbatim ctor kwargs.
      * ``engine_kwargs`` — remaining ``TopoServingEngine`` kwargs
        (``fea_backend``, ``precision``, ``preempt``, ...).

    Because construction runs through the same ctor with the same
    params, a worker-built engine's densities are bitwise-equal to an
    in-process engine's for the same requests — the multi-process path
    moves WHERE the engine runs, never what it computes.
    """
    cfg = spec["cfg"]
    params = spec.get("params")
    u_scale = spec.get("u_scale")
    tag = spec.get("model_tag")
    if params is None:
        root = spec.get("registry_root")
        if root is None:
            raise ValueError("engine spec needs params or registry_root")
        from repro.serve.registry import ModelRegistry
        params, rec = ModelRegistry(root).load(tag)
        tag = rec.tag
        u_scale = u_scale if u_scale is not None else rec.u_scale
    return TopoServingEngine(
        cfg, params, u_scale,
        slots=int(spec.get("slots", 8)),
        model_tag=tag,
        ladder=spec.get("ladder"),
        shape_padded=bool(spec.get("shape_padded", False)),
        **dict(spec.get("engine_kwargs") or {}))
