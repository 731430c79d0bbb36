"""The correctness check, driven through a whole run on the CPU at a size a
test can hold: the cell large.batch on a 30x10 mesh, with the pure-XLA
backends (the Pallas kernels would run in their interpreter here), a
3-second window. The harness's look for a chip is skipped.

Requests run 14 iterations, so the surrogate scores the FEA from the
eleventh on.

- a sound run comes out correct;
- the controls come out not correct: the program's bf16 path (bf16
  weights and surrogate inputs), and the reference put in the program's
  place in bf16, which fails every compared number, and filter + OC
  alone in bf16;
- so does each fault a serving cell can have, planted under the timed
  path: a step that returns its state unchanged, and an answer altered
  where it is produced (the harvest).
"""
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import run  # noqa: E402

SMALL = {"nelx": 30, "nely": 10, "backend": "oracle",
         "fea_backend": "reference"}
SEED = 2**32 + 77


def cell(overrides=None, faults=None, variants=False):
    return run.run_cell("large.batch", SEED, 3.0, False,
                        overrides={**SMALL, **(overrides or {})},
                        mix_overrides={"n_iter": 14}, allow_cpu=True,
                        faults=faults, variants=variants, log=lambda *a: None)


def each_engine(gw, fn):
    for eng in gw.engines.values():
        fn(eng)


def unchanged_step(gw):
    def plant(eng):
        eng.step = lambda params, bp, load_vol, state: state
    each_engine(gw, plant)


def altered_answer(gw):
    def plant(eng):
        harvest = eng._harvest_lane

        def bad(shard, lane, now):
            req = shard.slot_adm[lane].req
            harvest(shard, lane, now)
            req.density = np.clip(np.asarray(req.density) + 0.05, 0.0, 1.0)
        eng._harvest_lane = bad
    each_engine(gw, plant)


@pytest.fixture(autouse=True)
def _cpu_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def test_sound_run_is_correct():
    res = cell()
    assert res["correct"], res["checks"]
    assert res["metrics"]["designs_per_s"]["value"] > 0
    assert res["failed"] == 0


def test_control_is_not_correct():
    res = cell({"precision": "bf16"})
    assert not res["correct"]
    assert res["checks"]["err_gap"]["value"] > res["checks"]["err_gap"]["limit"]


def test_stand_in_control_fails_every_number():
    res = cell(variants=True)
    assert res["correct"], res["checks"]
    ctl = res["variants"]["control_bf16"]
    assert not ctl["correct"]
    for name, c in ctl["checks"].items():
        assert c["value"] > c["limit"], (name, c)
    for fault in ("filter_oc_bf16", "altered_answer", "unchanged_step"):
        assert not res["variants"][fault]["correct"], res["variants"][fault]


@pytest.mark.parametrize("fault", [unchanged_step, altered_answer],
                         ids=["unchanged_step", "altered_answer"])
def test_fault_is_not_correct(fault):
    res = cell(faults=fault)
    assert not res["correct"], res["checks"]
