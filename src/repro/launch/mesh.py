"""Production mesh construction.

A FUNCTION (not a module constant) so importing never touches jax device
state. Single pod: (16, 16) = 256 chips, axes (data, model). Multi-pod:
(2, 16, 16) = 512 chips, axes (pod, data, model) — the ``pod`` axis composes
with ``data`` for the batch dimension (DP spans pods over DCN; TP stays
intra-pod on ICI).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever this host has (smoke tests / examples): 1 device -> (1, 1)."""
    n = len(jax.devices())
    model = 1
    for m in (8, 4, 2, 1):
        if n % m == 0:
            model = m
            break
    return _make_mesh((n // model, model), ("data", "model"))
