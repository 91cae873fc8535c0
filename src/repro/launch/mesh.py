"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state.  The single-pod production mesh is 16x16
(256 chips, "data" x "model"); the multi-pod mesh is (2,16,16) with the
leading "pod" axis crossing the data-center network.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model_axis: int | None = None) -> Mesh:
    """A small mesh over whatever devices exist (CPU tests, smoke runs)."""
    n = len(jax.devices())
    model = model_axis or (2 if n % 2 == 0 and n > 1 else 1)
    data = n // model
    return _make_mesh((data, model), ("data", "model"))


def mesh_num_devices(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
