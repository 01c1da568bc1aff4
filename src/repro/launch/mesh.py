"""Production mesh definitions.

``make_production_mesh()`` is a FUNCTION (importing this module never
touches jax device state).  Single pod: 16×16 = 256 chips (TPU v5e pod);
multi-pod: 2×16×16 = 512 chips with a leading "pod" axis whose collectives
ride the (slower) inter-pod links — gradient compression
(repro.parallel.compression) targets exactly that axis.

``make_serving_mesh()`` is the 1-D data-parallel mesh the sharded DART
serving engine (``repro.engine.sharded``) replicates over: one "data"
axis covering every addressable device.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (fake or real) devices exist — used by
    reduced-config tests."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _make_mesh((data, model), ("data", "model"))


def make_serving_mesh(data: int | None = None):
    """1-D ("data",) mesh for data-parallel serving.  ``data`` defaults to
    every addressable device (fake CPU devices included)."""
    n = len(jax.devices())
    data = n if data is None else data
    assert data <= n, (data, n)
    return _make_mesh((data,), ("data",))


def dp_size(mesh) -> int:
    s = 1
    for a in ("pod", "data"):
        s *= mesh.shape.get(a, 1)
    return s
