"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run forces 512 host devices while tests/benches run on 1.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the model code places arrays
    with sharding constraints and leaves the rest to the partitioner.
    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which those
    constraints become part of the array types and, for example, the KV
    cache update in ``transformer.apply_attn`` (an unsharded cache slice
    and a constrained update) is a sharding type error."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ``data`` is the FSDP/batch axis, ``model`` the tensor-parallel
    axis; ``pod`` (multi-pod only) is an outer data-parallel axis crossing
    the DCN/pod boundary (gradient compression applies there, see
    optim/compress.py).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many devices exist (tests/smoke)."""
    return _auto_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def dp_degree(mesh) -> int:
    import numpy as np

    return int(np.prod([mesh.shape[a] for a in batch_axes(mesh)]))
