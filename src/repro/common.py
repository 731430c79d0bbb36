"""Shared utilities: parameter declaration, pytree helpers, dtype policy.

The framework is functional: a model is (param_specs, apply). ``ParamSpec``
is the single source of truth for a weight's shape, logical sharding axes,
and initializer, so the dry-run can build abstract trees (no allocation)
and the trainer can materialize real ones from the same declaration.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# ---------------------------------------------------------------------------
# Parameter declarations
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declaration of one parameter tensor.

    shape        : tensor shape
    logical_axes : one logical axis name per dim (see parallel/sharding.py
                   for the logical->mesh rules); None = replicated dim
    init         : 'normal' | 'zeros' | 'ones' | ('scaled', fan_in) |
                   ('uniform', scale) — resolved in materialize()
    dtype        : parameter dtype
    """

    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    init: Any = "normal"
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (
            f"shape {self.shape} vs axes {self.logical_axes}"
        )

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def _resolve_init(spec: ParamSpec, key: jax.Array) -> jax.Array:
    init = spec.init
    if init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if init == "normal":
        fan_in = spec.shape[0] if len(spec.shape) >= 1 else 1
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)
    if isinstance(init, tuple) and init[0] == "scaled":
        std = 1.0 / math.sqrt(max(init[1], 1))
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)
    if isinstance(init, tuple) and init[0] == "uniform":
        return (
            jax.random.uniform(key, spec.shape, jnp.float32, -init[1], init[1])
        ).astype(spec.dtype)
    if isinstance(init, tuple) and init[0] == "constant":
        return jnp.full(spec.shape, init[1], spec.dtype)
    raise ValueError(f"unknown init {init!r}")


def abstract_tree(specs: PyTree) -> PyTree:
    """ShapeDtypeStruct tree from a ParamSpec tree (no allocation)."""
    return jax.tree.map(
        lambda s: s.abstract(), specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def logical_axes_tree(specs: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: s.logical_axes, specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def materialize(specs: PyTree, key: jax.Array) -> PyTree:
    """Initialize real parameters from a ParamSpec tree."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(key, len(leaves))
    vals = [_resolve_init(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def param_count(specs: PyTree) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(int(np.prod(s.shape)) for s in leaves)


def param_bytes(specs: PyTree) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize for s in leaves)


# ---------------------------------------------------------------------------
# Misc helpers
# ---------------------------------------------------------------------------


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing is set here. Otherwise the cache goes to
    ``.jax_cache`` at the root of the checkout: a fixed path, so the
    next run of any entry point from the same checkout finds it.
    Every compile is kept, however short: JAX's default keeps only those
    over one second, and a serving path compiles many small programs
    (one per ladder rung and kernel) whose sum is its warm-up time.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def pad_to_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def tree_bytes(tree: PyTree) -> int:
    return sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(tree)
    )


def cast_tree(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype) if hasattr(x, "astype") else x, tree)


def check_finite(tree: PyTree) -> jax.Array:
    """True iff every leaf is finite everywhere (for NaN smoke assertions)."""
    leaves = [jnp.all(jnp.isfinite(l.astype(jnp.float32))) for l in jax.tree.leaves(tree)]
    return jnp.all(jnp.stack(leaves))
