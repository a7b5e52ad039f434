"""Production mesh definitions (single-pod 16x16, multi-pod 2x16x16).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
forces 512 host devices via XLA_FLAGS before first jax init, while smoke
tests and benches must see the 1 real CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: tuple, axes: tuple) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: the model code places arrays
    through ``with_sharding_constraint`` (``distributed.shard.constrain``),
    which ``Explicit`` axes — ``make_mesh``'s default since JAX 0.9 —
    refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over however many local devices exist (CPU tests)."""
    return _auto_mesh((data, model), ("data", "model"))


def mesh_data_axes(mesh: Mesh) -> tuple:
    """Physical axes that together form the logical batch/FSDP axis."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
