"""Production meshes. Functions (not module constants) so importing this
module never touches jax device state.

The canonical axis vocabulary is 4D ``(pod, data, seq, model)``; the old
2D/3D shapes are degenerate cases (rank-2 = ``(data, model)``, rank-3 =
``(pod, data, model)``). The rule tables in :mod:`repro.dist.plan` skip
absent axes, so every spec path works unchanged across ranks.
"""
from __future__ import annotations

import math

import jax
import numpy as np

# rank -> axis names (trailing/leading degenerate axes dropped)
MESH_AXIS_NAMES = {
    2: ("data", "model"),
    3: ("pod", "data", "model"),
    4: ("pod", "data", "seq", "model"),
}


def parse_mesh_shape(shape_str: str) -> tuple:
    """``"1x4x2x16"`` -> ``(1, 4, 2, 16)`` (rank 2-4)."""
    dims = tuple(int(x) for x in shape_str.lower().split("x"))
    if len(dims) not in MESH_AXIS_NAMES:
        raise ValueError(
            f"mesh shape must have rank 2-4, got {shape_str!r}"
        )
    return dims


def mesh_label(mesh) -> str:
    """``2x16x16``-style label from a mesh's axis sizes."""
    return "x".join(str(s) for s in mesh.devices.shape)


def _make_mesh(shape, axes):
    # Auto-typed axes: shardings propagate through jit.
    # When the shape uses fewer devices than the backend exposes (e.g. a
    # 128-chip 4D config under the 512-device XLA flag), slice the leading
    # devices in row-major order — the same order jax.make_mesh uses.
    n = math.prod(shape)
    devices = jax.devices()
    if n != len(devices):
        if n > len(devices):
            raise ValueError(
                f"mesh shape {shape} needs {n} devices, have {len(devices)}"
            )
        return jax.sharding.Mesh(
            np.asarray(devices[:n]).reshape(shape), axes
        )
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod.

    ``shape`` (a tuple or a ``"1x4x2x16"`` string) overrides the default:
    rank 2/3/4 maps onto the trailing/leading axes of
    ``(pod, data, seq, model)`` per :data:`MESH_AXIS_NAMES` — rank 4
    enables the ``seq`` axis (sequence parallelism) alongside expert/tensor
    parallelism on ``model``.
    """
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    elif isinstance(shape, str):
        shape = parse_mesh_shape(shape)
    else:
        shape = tuple(shape)
    return _make_mesh(shape, MESH_AXIS_NAMES[len(shape)])


def make_host_mesh():
    """Single-device mesh for CPU smoke tests."""
    return _make_mesh((1, 1), ("data", "model"))
