"""The four-shard engine: four slot groups pinned one per device, one
tick-loop thread each, one EDF queue. Runs in a SUBPROCESS with four
forced host devices (--xla_force_host_platform_device_count=4), so the
main pytest process keeps its single device; the default jnp backends
on a 12x4 mesh keep it to seconds.

One subprocess serves three engines and reports what each case checks:

1. A four-shard engine (slots 8, width 2) against a one-shard engine on
   the same seeded requests and seeded random weights: densities and
   compliances bitwise equal; a pure-FEA request against
   ``simp.run_simp`` within ``chip_smoke.py --chips 4``'s tolerances.
2. A four-shard ladder engine (slots 16, width 4, ladder (2, 4)): once
   ``start()`` has activated every shard, a wave that takes every shard
   through both rungs, harvests at both and parks and restores a
   request preempted for one with a deadline compiles nothing (the
   ``backend_compile_duration`` listener that counts compiles and cache
   loads).
3. Its counters: the ``shard`` labels of ``topo_host_seconds_total`` and
   ``topo_steps_total`` sum to their totals, and
   ``topo_host_cpu_seconds_total`` is at most the wall seconds for every
   (shard, phase).
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import dataclasses, json, time
import jax, numpy as np
from chip_smoke import PHASE_C_TOL, PHASE_X_TOL, _fea_solver, fea_compliance
from repro.common import materialize
from repro.configs.cronet import get_cronet_config
from repro.core import cronet
from repro.fea import fea2d, simp
from repro.obs.metrics import MetricsRegistry
from repro.serve.topo_service import PHASES, TopoRequest, TopoServingEngine

compiles = [0]
def on_duration(name, _secs, **_kw):
    if name.endswith("backend_compile_duration"):
        compiles[0] += 1
jax.monitoring.register_event_duration_secs_listener(on_duration)

cfg = dataclasses.replace(get_cronet_config("small"), nelx=12, nely=4,
                          hist_len=3)
specs = cronet.param_specs(dataclasses.replace(cfg, dtype="float32"))
params = jax.jit(lambda key: materialize(specs, key))(jax.random.key(7))
rng = np.random.default_rng(11)
probs = [fea2d.point_load_problem(
             12, 4, load_node=(int(x), 0), load=(0.0, float(-0.5 - rng.random())))
         for x in rng.permutation(12)[:10]]
kw = dict(precision="fp32", error_threshold=0.05)
out = {}

# 1. four shards of width 2 against one shard of width 4 (whose step the
# ladder engine of case 2 reuses on the first device)
def serve(**engine_kw):
    eng = TopoServingEngine(cfg, params, 50.0, **kw, **engine_kw)
    reqs = [TopoRequest(uid=i, problem=p, n_iter=6 + i % 3)
            for i, p in enumerate(probs)]
    eng.run(reqs)
    placed = sorted({str(sh.device) for sh in eng._shards})
    eng.shutdown()
    return reqs, placed
four, placed = serve(slots=8, shards=4)
one, _ = serve(slots=4, shards=1)
out["placed"] = placed
out["bitwise"] = [bool(np.array_equal(a.density, b.density)
                       and a.compliance == b.compliance)
                  for a, b in zip(four, one)]
solver = _fea_solver()
vs_simp = []
for r, p in [(r, p) for r, p in zip(four, probs) if not r.cronet_iters][:1]:
    x_ref = simp.run_simp(p, n_iter=r.n_iter,
                          solver=lambda x, p=p: solver(p, x))[0].x
    c, c_ref = fea_compliance(solver, p, r.density), fea_compliance(solver, p, x_ref)
    vs_simp.append((abs(c - c_ref) / c_ref,
                    float(np.mean(np.abs(r.density - np.asarray(x_ref))))))
out["vs_simp"] = vs_simp
out["tols"] = [PHASE_C_TOL, PHASE_X_TOL]

# 2. a wave through every rung of every shard compiles nothing
reg = MetricsRegistry()
eng = TopoServingEngine(cfg, params, 50.0, slots=16, shards=4, ladder=(2, 4),
                        tick_time_s=10.0, metrics=reg, **kw)
# distinct budgets: each shard's four lanes finish one by one, so every
# shard runs at width 4, then shrinks to 2
wave = [TopoRequest(uid=100 + i, problem=probs[i % 10],
                    n_iter=8 + 2 * i if i < 16 else 2)
        for i in range(18)]
eng.start()
t0 = time.time()
while not all(sh.bp is not None for sh in eng._shards):
    assert time.time() - t0 < 300, "shards never activated"
    time.sleep(0.01)
before = compiles[0]
futs = [eng.submit(r) for r in wave[:16]]
while not all(all(a is not None for a in sh.slot_adm) for sh in eng._shards):
    assert time.time() - t0 < 300, "lanes never filled"
    time.sleep(0.002)
futs += [eng.submit(r, deadline_s=35.0) for r in wave[16:]]
for f in futs:
    f.result(timeout=300)
eng.shutdown()
out["compiled_in_wave"] = compiles[0] - before
out["preemptions"] = eng.preemptions
out["rung_steps"] = eng.throughput_stats()["ladder"]["rung_steps"]
out["rung_widths_per_shard"] = [sorted(r for r, n in sh.rung_steps.items() if n)
                                for sh in eng._shards]

# 3. per-shard counters
def series(name):
    c = reg.counter(name)
    return [(dict(k), c.value(**dict(k))) for k in c.labelsets()], c.total()
out["host"] = series("topo_host_seconds_total")
out["cpu"] = series("topo_host_cpu_seconds_total")
out["steps"] = series("topo_steps_total")
out["total_steps"] = eng.total_steps
out["phases"] = list(PHASES)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def served():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2500:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_four_shards_serve_what_one_shard_serves(served):
    assert len(served["placed"]) == 4, served["placed"]
    assert served["bitwise"] and all(served["bitwise"]), served["bitwise"]
    c_tol, x_tol = served["tols"]
    assert served["vs_simp"], "no pure-FEA request to compare with SIMP"
    for dc, dx in served["vs_simp"]:
        assert dc <= c_tol and dx <= x_tol, (dc, dx)


def test_activation_compiles_every_program_a_tick_dispatches(served):
    # the wave reached both rungs on every shard, and a park and restore
    assert served["rung_widths_per_shard"] == [[2, 4]] * 4, served
    assert served["preemptions"] >= 1, served
    assert served["compiled_in_wave"] == 0, served


def test_shard_labels_sum_to_the_totals_and_cpu_within_wall(served):
    phases = set(served["phases"])
    for name in ("host", "cpu"):
        rows, total = served[name]
        assert {r["shard"] for r, _ in rows} == {"0", "1", "2", "3"}
        assert {r["phase"] for r, _ in rows} <= phases
        assert sum(v for _, v in rows) == pytest.approx(total)
    rows, total = served["steps"]
    by_shard = {r["shard"]: v for r, v in rows}
    assert set(by_shard) == {"0", "1", "2", "3"}
    assert all(v > 0 for v in by_shard.values())
    assert sum(by_shard.values()) == total == served["total_steps"]
    wall = {(r["shard"], r["phase"]): v for r, v in served["host"][0]}
    # a span reads the thread's CPU clock inside its wall interval; the
    # two clocks may run apart by NTP's slew (at most 500 ppm)
    for (r, cpu) in served["cpu"][0]:
        assert 0.0 <= cpu <= wall[(r["shard"], r["phase"])] * (1 + 5e-4) \
            + 1e-9, (r, cpu)
