"""Production mesh builders.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests and benches see the real single CPU device).
"""

from __future__ import annotations

from typing import Tuple

import jax


class MeshShapeError(ValueError):
    """A mesh shape that cannot be built on this host.

    Carries the offending ``shape`` (what was asked for) and ``n_devices``
    (what the host exposes) so `launch.serve --mesh` failures are actionable
    — e.g. "2x2 needs 4 devices, host has 1; set
    XLA_FLAGS=--xla_force_host_platform_device_count=4".
    """

    def __init__(self, message: str, *, shape=None, n_devices=None):
        super().__init__(message)
        self.shape = tuple(shape) if shape is not None else None
        self.n_devices = n_devices


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def _auto(n: int) -> tuple:
    """Auto axis types: shardings propagate through the partitioner, as the
    code here is written for (``jax.make_mesh`` defaults to Explicit)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_host_mesh(model_axis: int = 1):
    """Whatever this host has (CPU tests): (n_dev/model, model)."""
    n = len(jax.devices())
    if model_axis <= 0 or n % model_axis != 0:
        raise MeshShapeError(
            f"host has {n} device(s), not divisible into a "
            f"({n}/{model_axis}, {model_axis}) (data, model) mesh",
            shape=(n, model_axis),
            n_devices=n,
        )
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=_auto(2))


def parse_mesh(spec: str) -> Tuple[int, int]:
    """Parse a ``DxM`` mesh spec ("2x2" -> (2, 2)); raises MeshShapeError."""
    parts = str(spec).lower().split("x")
    try:
        d, m = (int(p) for p in parts)
    except ValueError:
        d = m = 0
    if len(parts) != 2 or d < 1 or m < 1:
        raise MeshShapeError(
            f"mesh spec {spec!r} is not of the form DxM (e.g. '2x2')",
            shape=None,
        )
    return d, m


def make_serve_mesh(data: int = 1, model: int = 1):
    """A (data, model) mesh over the first data*model host devices.

    Unlike `make_host_mesh` (which consumes every device the host has),
    this builds exactly the shape asked for — the serving golden contract
    runs the same traffic over 1x1 / 2x1 / 1x2 / 2x2 on one forced-device
    host.  Raises MeshShapeError with a remediation hint when the host
    exposes fewer devices than data*model.
    """
    devices = jax.devices()
    need = data * model
    if data < 1 or model < 1:
        raise MeshShapeError(
            f"mesh shape ({data}, {model}) has a non-positive axis",
            shape=(data, model),
            n_devices=len(devices),
        )
    if need > len(devices):
        raise MeshShapeError(
            f"mesh {data}x{model} needs {need} device(s), host has "
            f"{len(devices)}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"(before the process starts) or shrink the mesh",
            shape=(data, model),
            n_devices=len(devices),
        )
    import numpy as np

    grid = np.asarray(devices[:need]).reshape(data, model)
    return jax.sharding.Mesh(grid, ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes that carry the batch (ZeRO/data-parallel) dimension."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
