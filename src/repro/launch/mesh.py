"""Mesh construction — production pods and host-device test meshes.

FUNCTIONS, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).

``host_mesh_shape`` is the pure shape-selection policy (unit-testable
without devices); ``make_host_mesh`` applies it to whatever devices exist.
The host mesh is what the SPMD engine path (``core/spmd.py``) runs on:
axis ``"model"`` stripes hidden/embedding dims, axis ``"data"`` stripes
super-batch rows.
"""
from __future__ import annotations

import numpy as np
import jax


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axes: ``jax.make_mesh`` defaults to
    Explicit axes, which ``with_sharding_constraint`` refuses."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def host_mesh_shape(n: int, *, model: int | None = None) -> tuple[int, int]:
    """(data, model) shape for ``n`` devices.

    ``model=`` pins the model-axis width (it must divide ``n``).  Otherwise
    the model axis takes the largest of 4/2/1 that divides ``n`` — wide
    hidden dims benefit from model parallelism first — and the data axis
    absorbs the rest.  Deliberate odd-count handling: n=6 -> (3, 2),
    n=7 -> (7, 1), n=1 -> (1, 1); never a dropped device, never a
    non-rectangular mesh.
    """
    if n < 1:
        raise ValueError(f"need at least one device, got n={n}")
    if model is not None:
        if model < 1 or n % model != 0:
            raise ValueError(f"model={model} must divide device count {n}")
        return (n // model, model)
    for m in (4, 2, 1):
        if m <= n and n % m == 0:
            return (n // m, m)
    raise AssertionError("unreachable: 1 divides every n")


def make_host_mesh(n: int | None = None, *, model: int | None = None,
                   shape: tuple[int, int] | None = None):
    """A (data, model) mesh over the host's devices (tests/examples/SPMD).

    ``n`` uses only the first n devices (a submesh of a forced-host pool);
    ``model`` pins the model-axis width; ``shape`` bypasses the selection
    policy entirely.  Defaults to all devices with the
    ``host_mesh_shape`` policy.
    """
    devs = jax.devices()
    if n is None:
        n = len(devs)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, only {len(devs)} exist")
    if shape is None:
        shape = host_mesh_shape(n, model=model)
    elif shape[0] * shape[1] != n:
        raise ValueError(f"shape {shape} does not cover n={n} devices")
    grid = np.asarray(devs[:n], dtype=object).reshape(shape)
    return jax.sharding.Mesh(grid, ("data", "model"))
