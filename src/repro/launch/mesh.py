"""Production mesh definitions (TPU v5e).

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init — see dryrun.py which must
set XLA_FLAGS before anything else).

Every axis is ``AxisType.Auto``: shardings are propagated by GSPMD and
the model's bare-``PartitionSpec`` sharding constraints name mesh axes,
which ``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one 256-chip v5e pod) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes carrying batch/FSDP sharding ('pod' included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"


def make_host_mesh(n: int = 8):
    """Small mesh over forced host devices (CPU examples / tests)."""
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
