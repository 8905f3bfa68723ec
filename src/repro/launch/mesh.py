"""Production mesh construction.

A pod is 256 chips arranged ``(16, 16) ("data", "model")``; the multi-pod
deployment is 2 pods = 512 chips ``(2, 16, 16) ("pod", "data", "model")``.
The ``"model"`` axis is ICI-contiguous — Group-Rescale (DESIGN.md §1) confines
expert all-to-alls to it.

These are FUNCTIONS, not module constants: importing this module never touches
jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Arbitrary mesh for tests / elastic reconfiguration."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int = 1, n_model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests use forced host devices)."""
    n = len(jax.devices())
    assert n_data * n_model <= n, (n_data, n_model, n)
    return make_mesh((n_data, n_model), ("data", "model"))
