"""Compile the serving path for a TPU v5e that is described, not attached.

The chip's compiler is installed with JAX, so these ahead-of-time
compiles catch what only Mosaic/XLA:TPU refuse (tile-misaligned
slices, illegal block shapes, scoped-VMEM overflow, unsupported
reshapes) without a chip. Nothing runs: they say nothing about results
or times.

The topology is described inside a module fixture, never while a module
is imported, and everything built from it is built in fixtures or
tests. Only one process at a time may load the TPU library, so these
tests stay in this one file and compile in the test's own process.

``resolve_interpret`` sees the CPU backend here, so the kernels get an
explicit ``interpret=False``.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.cronet import get_cronet_config
from repro.core import cronet
from repro.fea import fea2d, hybrid
from repro.kernels import cg_fused, cronet_pipeline

SIZES = ("small", "medium", "large")
WIDTHS = (2, 8)
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache, topologies

    with pytest.MonkeyPatch.context() as mp:
        # keep libtpu's logs out of /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler, or its library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                       sharding=sharding), tree)


def _params(cfg, sharding):
    specs = cronet.param_specs(cfg)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                       sharding=sharding),
        specs, is_leaf=lambda x: hasattr(x, "logical_axes"))


def _batch(cfg, width):
    return fea2d.stack_problems(
        [fea2d.point_load_problem(cfg.nelx, cfg.nely)] * width)


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("size", SIZES)
def test_cronet_fused_compiles_for_v5e(one_chip, size, width):
    """The megakernel at the published widths and hist_len=10."""
    cfg = get_cronet_config(size)
    assert cfg.hist_len == 10
    lv = jax.ShapeDtypeStruct((width, 4, cfg.nely + 1, cfg.nelx + 1, 1),
                              jnp.float32, sharding=one_chip)
    hist = jax.ShapeDtypeStruct(
        (width, cfg.hist_len, cfg.nely, cfg.nelx, 1), jnp.float32,
        sharding=one_chip)
    compiled = jax.jit(
        lambda p, a, b: cronet_pipeline.cronet_fused(
            cfg, p, a, b, interpret=False)).lower(
        _params(cfg, one_chip), lv, hist).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("size", SIZES)
def test_solve_b_fused_compiles_for_v5e(one_chip, size, width):
    """The fused Jacobi-PCG solve on the three published meshes."""
    cfg = get_cronet_config(size)
    X = jax.ShapeDtypeStruct((width, cfg.nely, cfg.nelx), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(
        lambda bp, x: cg_fused.solve_b_fused(bp, x, interpret=False)).lower(
        _abstract(_batch(cfg, width), one_chip), X).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_default_hybrid_step_compiles_for_v5e(one_chip):
    """One whole serving tick on the default backends (oracle forward,
    reference CG) at CRONet-large, batch 8, fits one chip."""
    cfg = get_cronet_config("large")
    bp = _batch(cfg, 8)
    state = hybrid.init_state(cfg, bp)
    step = hybrid.make_hybrid_step(cfg, 50.0, precision="fp32")
    compiled = step.lower(
        _params(dataclasses.replace(cfg, dtype="float32"), one_chip),
        _abstract(bp, one_chip),
        _abstract(fea2d.load_volume_b(bp), one_chip),
        _abstract(state, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_fused_hybrid_step_names_its_scopes_and_kernels(one_chip,
                                                        monkeypatch):
    """The serving tick on the deployed backends (megakernel forward, fused
    CG) carries the four named scopes in its op metadata and both kernel
    names on their custom calls, so that a profiler trace can attribute
    device time to them."""
    from repro.kernels import cg_fused as cg_mod

    monkeypatch.setattr(cronet_pipeline, "resolve_interpret", lambda _: False)
    monkeypatch.setattr(cg_mod, "resolve_interpret", lambda _: False)
    cfg = get_cronet_config("small")
    bp = _batch(cfg, 2)
    step = hybrid.make_hybrid_step(cfg, 377.622, precision="fp32",
                                   backend="megakernel", fea_backend="fused")
    text = step.lower(
        _params(dataclasses.replace(cfg, dtype="float32"), one_chip),
        _abstract(bp, one_chip),
        _abstract(fea2d.load_volume_b(bp), one_chip),
        _abstract(hybrid.init_state(cfg, bp), one_chip)).compile().as_text()
    for scope in ("cronet_forward", "gate", "cg_solve", "sens_filter_oc"):
        assert f'op_name="jit(step)/{scope}/' in text, scope
    for kernel in ("cronet_fused", "cg_fused"):
        assert re.search(rf"%{kernel}(\.\d+)? = .*custom_call_target="
                         r'"tpu_custom_call"', text), kernel
