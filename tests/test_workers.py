"""Multi-process engine-worker tests (serve/workers.py).

Three layers:
  * device-free units: the length-prefixed pickle framing (round-trip,
    torn-frame detection) and the ``RemoteEngine`` crash-split logic
    against a stub pool/handle — admitted in-flight work fails typed
    ``WorkerLost``, never-admitted work requeues in ORIGINAL submission
    order (priority + absolute deadline ride along, so EDF rank is
    preserved);
  * real processes, deterministic crash: a gateway serving through one
    worker, ``kill -9`` mid-tick — the admitted request's future fails
    with ``WorkerLost`` (carrying the dead worker's id), the queued one
    transparently completes on the respawned worker, the ``worker-*``
    FleetEvents narrate the loss/respawn/reassign/requeue, and the
    registry lease survives because the bucket proxy never left the
    gateway;
  * property-style interleaving sweep (slow tier, mirroring
    tests/test_flywheel.py's): random rounds of traffic + worker kills
    through a registry-backed two-worker gateway — every future
    resolves (density or typed ``WorkerLost``), zero drops, zero
    mis-tags, leases balance after shutdown.

Worker processes are spawned (never forked — the child must not inherit
the parent's XLA state), so each spawn re-imports jax: tests here keep
worker counts and respawn rounds small on purpose.
"""
import collections
import dataclasses
import multiprocessing
import os
import random
import signal
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest
from test_gateway import wait_until

from repro.serve import (TopoGateway, TopoRequest, WorkerLost)
from repro.serve.types import TopoFuture
from repro.serve.workers import (RemoteEngine, _recv_msg, _send_msg)

U_SCALE = 50.0


# ------------------------------------------------------------- framing


def test_framing_roundtrip_and_torn_frame_detection():
    a, b = multiprocessing.get_context("spawn").Pipe(duplex=True)
    lock = threading.Lock()
    msg = {"op": "submit", "payload": np.arange(6).reshape(2, 3),
           "nested": {"deadline": 12.5}}
    _send_msg(a, lock, msg)
    got = _recv_msg(b)
    assert got["op"] == "submit"
    np.testing.assert_array_equal(got["payload"], msg["payload"])

    # a frame whose prefix disagrees with its body is a torn write
    # (worker killed mid-send): typed error, not a pickle explosion
    import struct
    a.send_bytes(struct.pack("!I", 999) + b"\x80\x04short")
    with pytest.raises(ValueError, match="torn frame"):
        _recv_msg(b)
    a.close()
    with pytest.raises((EOFError, OSError)):
        _recv_msg(b)
    b.close()


# ----------------------------------- crash-split units (stub pool/handle)


class _StubHandle:
    """Records submit RPCs instead of crossing a pipe."""

    def __init__(self, worker_id=7, fail=False):
        self.worker_id = worker_id
        self.fail = fail
        self.submitted = []          # uids, arrival order

    def call(self, op, timeout=None, **fields):
        if self.fail:
            raise WorkerLost("stub worker down", worker_id=self.worker_id)
        if op == "submit":
            self.submitted.append(fields["req"].uid)
        return True


def _stub_proxy(handle):
    pool = SimpleNamespace(rpc_timeout_s=5.0, registry_root=None,
                           _note_completion=lambda *a, **k: None,
                           _forget_engine=lambda p: None)
    cfg = SimpleNamespace(nelx=12, nely=4)
    return RemoteEngine(pool, handle, engine_id=0, mesh=(12, 4), cfg=cfg,
                        spec={"cfg": cfg}, model_tag="m", slots=2)


def _preq(uid, priority=0, deadline_s=None):
    req = TopoRequest(uid=uid, problem=SimpleNamespace(nelx=12, nely=4),
                      n_iter=4, deadline_s=deadline_s, priority=priority)
    return req


def test_pool_refuses_non_cpu_backend(monkeypatch):
    """Workers are a CPU-only mechanism: a parent whose JAX backend is an
    accelerator already owns the device, so the pool must refuse before
    spawning a single worker."""
    import jax

    from repro.serve.workers import WorkerPool

    spawned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(WorkerPool, "_spawn",
                        lambda self: spawned.append(self))
    with pytest.raises(RuntimeError, match="CPU-only"):
        WorkerPool(1, heartbeat_s=0)
    assert not spawned


def test_crash_split_fails_admitted_typed_and_requeues_in_edf_order():
    h0 = _StubHandle(worker_id=0)
    eng = _stub_proxy(h0)
    futs = [eng.submit(_preq(i, priority=i % 2, deadline_s=30.0 + i))
            for i in range(5)]
    assert h0.submitted == [0, 1, 2, 3, 4]
    # uids 0 and 2 reached a tick on the (about to die) worker
    eng._on_admitted(0, time.monotonic())
    eng._on_admitted(2, time.monotonic())

    admitted, queued = eng._split_pending()
    assert [r.uid for r, _ in admitted] == [0, 2]
    assert [r.uid for r, _ in queued] == [1, 3, 4]   # original order
    eng._fail_admitted(admitted, worker_id=0, reason="kill -9")
    for f in (futs[0], futs[2]):
        exc = f.exception()
        assert isinstance(exc, WorkerLost) and exc.worker_id == 0

    h1 = _StubHandle(worker_id=1)
    assert eng._rebind(h1, queued) == 3
    # resubmitted on the replacement in ORIGINAL submission order, on
    # the ORIGINAL request objects — priority and the absolute
    # monotonic deadline ride along, so the engine-side EDF scheduler
    # reconstructs the exact rank the dead worker saw
    assert h1.submitted == [1, 3, 4]
    assert eng.inflight == 3
    with eng._sched.cond:
        pend = [ent[0] for ent in eng._pending.values()]
    assert [r.priority for r in pend] == [1, 1, 0]
    assert all(r.deadline is not None for r in pend)
    for uid in (1, 3, 4):
        assert not futs[uid].done()


def test_rebind_onto_dead_replacement_fails_every_future_typed():
    eng = _stub_proxy(_StubHandle(worker_id=0))
    futs = [eng.submit(_preq(i)) for i in range(3)]
    _, queued = eng._split_pending()
    eng._rebind(_StubHandle(worker_id=1, fail=True), queued)
    for f in futs:
        assert isinstance(f.exception(), WorkerLost)
    assert eng.inflight == 0


# --------------------------------------------- real processes: kill -9


@pytest.fixture(scope="module")
def trained():
    import jax

    from repro.common import materialize
    from repro.configs.cronet import get_cronet_config
    from repro.core import cronet

    cfg = dataclasses.replace(get_cronet_config("small"),
                              nelx=12, nely=4, hist_len=3)
    params = materialize(cronet.param_specs(
        dataclasses.replace(cfg, dtype="float32")), jax.random.key(0))
    return cfg, params


def _problems(n, nelx=12, nely=4):
    from repro.fea import fea2d
    return [fea2d.point_load_problem(nelx, nely,
                                     load_node=(i % (nelx - 1), 0),
                                     load=(0.0, -1.0 - 0.1 * i))
            for i in range(n)]


def test_kill9_mid_tick_fails_admitted_typed_and_requeues_rest(trained):
    """THE crash contract: kill -9 a worker while one request is in a
    tick and another is queued behind it. The admitted one fails with
    a typed ``WorkerLost`` naming the dead worker; the queued one is
    requeued onto the respawned worker and completes; the fleet-event
    log narrates every transition; zero requests are dropped."""
    cfg, params = trained
    probs = _problems(4)
    gw = TopoGateway(cfg, params, U_SCALE, slots=2, max_pending=16,
                     workers=1,
                     worker_pool_kwargs={"heartbeat_s": 0.5})
    try:
        # uids 0-1 run long (they will be mid-tick at the kill); uids
        # 2-3 queue behind the two slots and never reach a tick
        futs = [gw.submit(TopoRequest(uid=i, problem=p,
                                      n_iter=200 if i < 2 else 4))
                for i, p in enumerate(probs)]
        assert wait_until(
            lambda: gw.engines.get((12, 4)) is not None, timeout=120)
        proxy = gw.engines[(12, 4)]
        assert isinstance(proxy, RemoteEngine)
        # wait until uids 0-1 are ADMITTED to ticks (the worker-side
        # monitor reported them) while 2-3 sit queued behind the slots
        def _admitted(uid):
            with proxy._sched.cond:
                ent = proxy._pending.get(uid)
                return ent is not None and ent[2]
        assert wait_until(lambda: _admitted(0) and _admitted(1),
                          timeout=120)
        victim_pid = gw._pool._workers[0].proc.pid
        victim_id = gw._pool._workers[0].worker_id
        os.kill(victim_pid, signal.SIGKILL)

        results = {}
        for i, f in enumerate(futs):
            try:
                results[i] = f.result(timeout=300)
            except WorkerLost as exc:
                results[i] = exc
        # uids 0-1 were mid-tick: typed loss carrying the dead
        # worker's id
        for i in (0, 1):
            assert isinstance(results[i], WorkerLost)
            assert results[i].worker_id == victim_id
        # uids 2-3 never reached a tick on the dead worker: they
        # completed on the respawn, densities intact, relabelled
        for i in (2, 3):
            assert not isinstance(results[i], BaseException)
            assert results[i].done and results[i].density is not None
            assert results[i].worker_id is not None
            assert results[i].worker_id != victim_id
        kinds = [e.kind for e in gw.fleet_events()]
        for k in ("worker-spawn", "worker-lost", "worker-reassign",
                  "worker-requeue"):
            assert k in kinds, f"missing {k} in {kinds}"
        assert gw._pool.stats()["restarts"] >= 1
    finally:
        gw.shutdown()


@pytest.mark.slow
def test_worker_interleaving_sweep_no_drops_no_mistags(trained, tmp_path):
    """Property-style sweep (the flywheel suite's idiom): random rounds
    of traffic and worker kills through a registry-backed two-worker
    gateway. Invariants after every round: every future resolves with
    a density or a typed ``WorkerLost``; completions carry the tag they
    were routed under and a worker id; nothing is dropped. After
    shutdown: leases balance to zero."""
    from repro.serve import ModelRegistry

    cfg, params = trained
    reg = ModelRegistry(str(tmp_path))
    reg.register(params, cfg, U_SCALE, tag="prod")
    gw = TopoGateway.from_registry(
        reg, tag="prod", slots=2, max_pending=64, workers=2,
        worker_pool_kwargs={"heartbeat_s": 0.5})
    rng = random.Random(20260808)
    probs = _problems(6)
    uid = 0
    try:
        for rnd in range(4):
            futs = []
            for _ in range(rng.randint(3, 6)):
                futs.append(gw.submit(TopoRequest(
                    uid=uid, problem=probs[uid % len(probs)],
                    n_iter=rng.randint(3, 8),
                    deadline_s=600.0 if rng.random() < 0.5 else None,
                    priority=rng.randint(0, 2))))
                uid += 1
            if rnd in (1, 2):       # two kill rounds out of four
                live = gw._pool.live_workers()
                victim = rng.choice(live)
                os.kill(victim.proc.pid, signal.SIGKILL)
            completed = lost = 0
            for f in futs:
                try:
                    r = f.result(timeout=300)
                    assert r.density is not None
                    assert r.model_tag == "prod"
                    assert r.routed_tag == "prod"
                    assert r.worker_id is not None
                    completed += 1
                except WorkerLost:
                    lost += 1
            assert completed + lost == len(futs)
        assert gw._pool.stats()["restarts"] >= 1
    finally:
        gw.shutdown()
    assert reg.leased() == {}
