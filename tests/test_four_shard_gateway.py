"""One ``TopoGateway`` in front of a four-shard engine (four slot groups
pinned one per device, one tick-loop thread each, one EDF queue), in a
SUBPROCESS with four forced host devices, as in ``test_four_shard.py``:
default jnp backends, 12x4 mesh. It serves three registry-backed
gateways and reports what each case checks:

1. A wave of mixed priorities and deadlines through
   ``TopoGateway.from_registry(..., slots=8, shards=4)``: every future
   resolves with a density, every shard steps part of it, every
   completion carries the registered tag as ``model_tag`` and
   ``routed_tag``, and each density and compliance is bitwise equal to
   a ``shards=1`` gateway's on the same requests.
2. With every lane of every shard held by a long request, queued
   requests leave the engine's one EDF queue in (priority, deadline)
   order, whichever shard frees a lane.
3. ``shutdown()`` with work in flight resolves every future (completed,
   or ``EngineClosed`` for a submit after it) and leaves no tick-loop or
   dispatcher thread alive; the registry holds no lease afterwards.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import dataclasses, json, sys, threading, time
import jax, numpy as np
from repro.common import materialize
from repro.configs.cronet import get_cronet_config
from repro.core import cronet
from repro.fea import fea2d
from repro.serve import (EngineClosed, ModelRegistry, TopoGateway,
                         TopoRequest)

cfg = dataclasses.replace(get_cronet_config("small"), nelx=12, nely=4,
                          hist_len=3)
specs = cronet.param_specs(dataclasses.replace(cfg, dtype="float32"))
params = jax.jit(lambda key: materialize(specs, key))(jax.random.key(7))
rng = np.random.default_rng(13)
probs = [fea2d.point_load_problem(
             12, 4, load_node=(int(x), 0), load=(0.0, float(-0.5 - rng.random())))
         for x in rng.permutation(11)[:10]]
reg = ModelRegistry(sys.argv[1])
reg.register(params, cfg, 50.0, tag="prod")
out = {}

def gateway(**kw):
    return TopoGateway.from_registry(reg, "prod", max_pending=None,
                                     precision="fp32", error_threshold=0.05,
                                     **kw)

def wait(cond, what):
    t0 = time.time()
    while not cond():
        assert time.time() - t0 < 300, what
        time.sleep(0.002)

# 1. a wave of mixed priorities and deadlines, four shards against one
WAVE = [dict(uid=i, p=i % 10, n_iter=5 + i % 4, priority=i % 3,
             deadline_s=None if i % 2 else 600.0 - i)
        for i in range(14)]

def serve_wave(gw):
    futs = [gw.submit(TopoRequest(uid=w["uid"], problem=probs[w["p"]],
                                  n_iter=w["n_iter"]),
                      deadline_s=w["deadline_s"], priority=w["priority"])
            for w in WAVE]
    return [f.result(timeout=300) for f in futs]

gw4 = gateway(slots=8, shards=4)
four = serve_wave(gw4)
eng = gw4.engines[(12, 4)]
out["placed"] = sorted({str(sh.device) for sh in eng._shards})
out["steps_per_shard"] = [sum(sh.rung_steps.values()) for sh in eng._shards]
out["leased_while_serving"] = reg.leased()
gw1 = gateway(slots=4, shards=1)
one = serve_wave(gw1)
gw1.shutdown()
out["wave"] = [dict(uid=r.uid, done=bool(r.done),
                    shape=None if r.density is None else list(r.density.shape),
                    model_tag=r.model_tag, routed_tag=r.routed_tag)
               for r in four]
out["bitwise"] = [bool(np.array_equal(a.density, b.density)
                       and a.compliance == b.compliance)
                  for a, b in zip(four, one)]

# 2. every lane of every shard full: queued requests leave the one EDF
# queue in (priority, deadline) order
pops = []
pop = eng._sched.pop
def recording_pop():
    entry = pop()
    if entry is not None:
        pops.append(entry.payload.req.uid)
    return entry
eng._sched.pop = recording_pop
fillers = [gw4.submit(TopoRequest(uid=100 + i, problem=probs[i],
                                  n_iter=150 + 10 * i))
           for i in range(8)]
wait(lambda: all(all(a is not None for a in sh.slot_adm)
                 for sh in eng._shards), "lanes never filled")
QUEUED = [dict(uid=200 + k, priority=k % 2, deadline_s=300.0 + 37.0 * ((5 * k) % 8))
          for k in range(8)]
queued = [gw4.submit(TopoRequest(uid=q["uid"], problem=probs[q["uid"] % 10],
                                 n_iter=3),
                     deadline_s=q["deadline_s"], priority=q["priority"])
          for q in QUEUED]
wait(lambda: len(eng._sched) == len(QUEUED), "queued never reached the engine")
out["lanes_full_when_queued"] = (
    all(all(a is not None for a in sh.slot_adm) for sh in eng._shards)
    and not any(f.done() for f in fillers))
for f in fillers + queued:
    f.result(timeout=300)
eng._sched.pop = pop
out["queued"] = QUEUED
out["pops"] = [u for u in pops if u >= 200]

# 3. shutdown with work in flight
gw = gateway(slots=8, shards=4)
futs = [gw.submit(TopoRequest(uid=300 + i, problem=probs[i % 10],
                              n_iter=20 + i), priority=i % 2)
        for i in range(20)]
wait(lambda: (gw.engines.get((12, 4)) is not None
              and any(a is not None for sh in gw.engines[(12, 4)]._shards
                      for a in sh.slot_adm)), "nothing admitted")
gw.shutdown()
try:
    gw.submit(TopoRequest(uid=399, problem=probs[0], n_iter=3))
    late_outcome = "accepted"
except EngineClosed:
    late_outcome = "EngineClosed"
outcomes = []
for f in futs:
    try:
        outcomes.append("completed" if f.result(timeout=300).density is not None
                        else "no density")
    except EngineClosed:
        outcomes.append("EngineClosed")
out["shutdown_outcomes"] = outcomes
out["late_submit"] = late_outcome
gw4.shutdown()
out["threads_after"] = sorted(t.name for t in threading.enumerate()
                              if t.name.startswith(("topo-shard-",
                                                    "topo-gateway-")))
out["leased_after"] = reg.leased()
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    root = str(tmp_path_factory.mktemp("registry"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, root], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2500:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_mixed_wave_resolves_every_future_with_a_density(served):
    wave = served["wave"]
    assert len(wave) == 14
    for r in wave:
        assert r["done"] and r["shape"] == [4, 12], r


def test_one_gateway_queue_feeds_all_four_shards(served):
    assert len(served["placed"]) == 4, served["placed"]
    per_shard = served["steps_per_shard"]
    assert all(n > 0 for n in per_shard), per_shard


def test_completions_carry_the_registered_tag(served):
    for r in served["wave"]:
        assert r["model_tag"] == r["routed_tag"] == "prod", r


def test_four_shard_gateway_bitwise_equals_one_shard_gateway(served):
    assert served["bitwise"] and all(served["bitwise"]), served["bitwise"]


def test_full_shards_admit_queued_requests_in_edf_order(served):
    assert served["lanes_full_when_queued"]
    ranked = sorted(served["queued"],
                    key=lambda q: (-q["priority"], q["deadline_s"]))
    assert served["pops"] == [q["uid"] for q in ranked]


def test_shutdown_in_flight_resolves_every_future_and_stops_threads(served):
    assert served["shutdown_outcomes"], served
    assert set(served["shutdown_outcomes"]) <= {"completed", "EngineClosed"}
    assert served["late_submit"] == "EngineClosed"
    assert served["threads_after"] == [], served["threads_after"]


def test_leases_balance_after_shutdown(served):
    assert served["leased_while_serving"].get("prod", 0) >= 1
    assert served["leased_after"] == {}
