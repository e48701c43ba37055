"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Device mesh whose axes are all ``Auto``: the compiler propagates
    shardings from the constraints and argument layouts the code gives
    (``jax.make_mesh`` otherwise makes ``Explicit`` axes, under which every
    op with an ambiguous output sharding must be told one)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (v5e pod slice); multi_pod stacks 2 pods =
    512 chips with a leading "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh (CPU tests): every rule degenerates to replicate."""
    return make_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes the batch dimension shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n
