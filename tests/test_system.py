"""End-to-end behaviour tests: trainer with checkpoint/resume, serving
engine, hybrid NN-FEA loop, HLO analyzer."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import materialize
from repro.configs.base import get_config
from repro.models import model as M
from repro.optim import adamw
from repro.train.steps import TrainConfig
from repro.train.trainer import RunConfig, Trainer


def _tc(steps=6):
    return TrainConfig(optimizer=adamw.AdamWConfig(
        lr=1e-3, warmup_steps=1, total_steps=steps))


def test_trainer_end_to_end(tmp_path):
    cfg = get_config("granite-8b").reduce()
    rc = RunConfig(steps=6, batch=2, seq=16, ckpt_dir=str(tmp_path),
                   ckpt_every=3, log_every=2)
    t = Trainer(cfg, _tc(), rc)
    _, _, hist = t.run()
    assert hist[-1]["step"] == 6
    assert all(np.isfinite(h["loss"]) for h in hist)
    # checkpoint landed
    from repro.checkpoint import manager as ckpt
    assert ckpt.latest_step(str(tmp_path)) == 6


def test_trainer_resumes(tmp_path):
    cfg = get_config("granite-8b").reduce()
    rc = RunConfig(steps=4, batch=2, seq=16, ckpt_dir=str(tmp_path),
                   ckpt_every=2, log_every=1)
    t = Trainer(cfg, _tc(4), rc)
    t.run()
    # extend run: trainer must resume from step 4, not restart
    rc2 = RunConfig(steps=6, batch=2, seq=16, ckpt_dir=str(tmp_path),
                    ckpt_every=2, log_every=1)
    t2 = Trainer(cfg, _tc(6), rc2)
    _, _, hist2 = t2.run()
    assert hist2[0]["step"] >= 5   # started past the checkpoint


def test_trainer_with_compression(tmp_path):
    cfg = get_config("granite-8b").reduce()
    tc = TrainConfig(compress_pod_grads=True,
                     optimizer=adamw.AdamWConfig(lr=1e-3, warmup_steps=1,
                                                 total_steps=5))
    rc = RunConfig(steps=5, batch=2, seq=16, log_every=1)
    _, _, hist = Trainer(cfg, tc, rc).run()
    assert np.isfinite(hist[-1]["loss"])
    assert hist[-1]["loss"] < hist[0]["loss"] * 1.5


def test_serving_engine():
    from repro.serve.server import Request, ServingEngine
    cfg = get_config("qwen2.5-32b").reduce()
    params = materialize(M.param_specs(cfg), jax.random.key(0))
    engine = ServingEngine(cfg, params, slots=2, max_len=48)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, 200, size=5 + i).astype(np.int32),
                    max_new=4) for i in range(3)]
    done = engine.run(reqs)
    assert all(r.done and r.output is not None and len(r.output) == 4
               for r in done)
    stats = engine.throughput_stats(done)
    assert stats["total_new_tokens"] == 12


def test_serving_engine_partial_group_wall_clock_accounting():
    """Regression: the throughput wall clock used to divide every
    request's group latency by the full slot width, so a PARTIAL final
    group (3 requests on a 2-slot engine leaves a group of 1) credited
    its padded slots with work they never did and overstated
    tokens/s. Each group must contribute its dt to the wall exactly
    once — members divide by actual group occupancy."""
    from types import SimpleNamespace

    from repro.serve.server import Request, ServingEngine

    def _reqs(spec):
        out = []
        for group_size, dt in spec:
            for _ in range(group_size):
                r = Request(uid=len(out), prompt=np.zeros(4, np.int32),
                            max_new=4)
                r.done, r.output = True, np.zeros(4, np.int32)
                r.latency_s, r.group_size = dt, group_size
                out.append(r)
        return out

    eng = SimpleNamespace(slots=4)   # throughput_stats only reads slots
    # two full groups + one half-full final group, 1 s each
    reqs = _reqs([(4, 1.0), (4, 1.0), (2, 1.0)])
    stats = ServingEngine.throughput_stats(eng, reqs)
    assert stats["total_new_tokens"] == 40
    # wall = 3 group-seconds exactly; the pre-fix accounting read 2.5 s
    # (the final group contributed 2/4 instead of 2/2) and inflated
    # tokens/s by 20%
    assert stats["tokens_per_s"] == pytest.approx(40 / 3.0)
    # legacy completions without a group stamp fall back to slot width
    legacy = _reqs([(4, 1.0)])
    for r in legacy:
        r.group_size = 0
    assert ServingEngine.throughput_stats(eng, legacy)["tokens_per_s"] \
        == pytest.approx(4 * 4 / 1.0)


def test_hybrid_loop_smoke():
    """12-iteration hybrid NN-FEA loop with an untrained net: must fall
    back to FEA every time and still match the pure-FEA trajectory."""
    import dataclasses

    from repro.configs.cronet import get_cronet_config
    from repro.core import cronet
    from repro.fea import hybrid
    cfg = dataclasses.replace(get_cronet_config("small"), nelx=12, nely=4)
    params = materialize(cronet.param_specs(
        dataclasses.replace(cfg, dtype="float32")), jax.random.key(0))
    res = hybrid.run_hybrid(cfg, params, u_scale=100.0, n_iter=12,
                            precision="fp32")
    assert res.fea_invocations >= 10      # untrained net is rejected
    assert res.solution_accuracy > 95.0   # therefore tracks pure FEA


def test_hlo_analyzer_scan_exact():
    from repro.launch.hlo_analysis import analyze
    L = 5

    def f(ws, x):
        def body(x, w):
            return jnp.dot(x, w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    ws = jax.ShapeDtypeStruct((L, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    compiled = jax.jit(f).lower(ws, x).compile()
    costs = analyze(compiled.as_text())
    assert costs.flops == 2 * L * 8 * 64 * 64


def test_input_specs_cover_all_cells():
    """Every applicable (arch x shape) produces abstract inputs with no
    allocation (the dry-run's contract)."""
    from repro.configs.all import ASSIGNED
    from repro.configs.base import applicable_shapes
    from repro.launch.specs import input_specs
    n = 0
    for arch in ASSIGNED:
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            specs = input_specs(cfg, shape)
            assert all(isinstance(l, jax.ShapeDtypeStruct)
                       for l in jax.tree.leaves(specs))
            n += 1
    assert n == 31   # 40 assigned cells minus 9 documented skips


@pytest.mark.parametrize("env_dir", [None, "placed"])
def test_use_compile_cache_places_the_cache(monkeypatch, tmp_path, env_dir):
    """Entry points keep JAX's persistent compile cache where
    JAX_COMPILATION_CACHE_DIR says and then set no directory themselves;
    without it the cache goes to the fixed ``.jax_cache`` at the root of
    the checkout. Either way every compile is kept, however short."""
    from repro import common

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    updates = []
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert common.use_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want),
                           ("jax_persistent_cache_min_compile_time_secs", 0)]
    else:
        placed = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        assert common.use_compile_cache() == placed
        assert updates == [("jax_persistent_cache_min_compile_time_secs", 0)]
    assert jax.config.jax_compilation_cache_dir == before
