"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Production topology (TPU v5e): one pod = 16×16 = 256 chips, meshed as
("data", "model"); multi-pod adds a leading "pod" axis (2×16×16 = 512).
Data-parallel gradients ride ("pod", "data"); tensor/expert parallel ride
"model".  The same function builds reduced meshes for CI via `shape`.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False,
                         shape: tuple[int, ...] | None = None,
                         axes: tuple[str, ...] | None = None):
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    if axes is None:
        axes = (("pod", "data", "model") if len(shape) == 3
                else ("data", "model"))
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count for dry-runs")
    return _auto_mesh(shape, axes, devices[:need])


def _auto_mesh(shape, axes, devices):
    """A mesh whose axes are all ``Auto``: shardings are placement hints
    that the compiler propagates (``with_sharding_constraint`` and the
    logical rule table), not part of array types.  ``jax.make_mesh``
    defaults to ``Explicit`` axes, under which a gather from a sharded
    table or a matmul contracting a sharded dimension must name its output
    sharding at every call site."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_serve_mesh(shape: "int | tuple[int, ...] | None" = None, *,
                    axes: tuple[str, ...] | None = None):
    """Serving-shaped mesh: whatever devices exist, no 256-chip floor.

    One fleet replica = one device slice, so serving meshes are small and
    1-D/2-D: ``N`` (or ``(N,)``) is N devices on ``("model",)``;
    ``(D, M)`` is ``("data", "model")``.  ``shape=None`` takes every
    visible device on ``"model"``.  Raises with the exact ``XLA_FLAGS``
    incantation when the host is short — host-platform test meshes are a
    first-class use, unlike :func:`make_production_mesh`.
    """
    devices = jax.devices()
    if shape is None:
        shape = (len(devices),)
    elif isinstance(shape, int):
        shape = (shape,)
    else:
        shape = tuple(shape)
    if not shape or any(s < 1 for s in shape):
        raise ValueError(f"bad serve-mesh shape {shape}")
    if axes is None:
        if len(shape) > 2:
            raise ValueError(
                f"serve meshes are 1-D or 2-D, got shape {shape}; pass "
                "axes= explicitly for exotic topologies")
        axes = ("model",) if len(shape) == 1 else ("data", "model")
    need = math.prod(shape)
    if len(devices) < need:
        raise RuntimeError(
            f"serve mesh {shape} needs {need} devices, have {len(devices)}"
            f" — set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} (before jax initializes) for a host-device mesh")
    return _auto_mesh(shape, axes, devices[:need])
