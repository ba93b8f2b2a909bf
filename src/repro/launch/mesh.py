"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  Single-pod: 256 chips as
(data=16, model=16).  Multi-pod: 2 pods x 256 chips as
(pod=2, data=16, model=16) — the pod axis is the DCN-connected dimension.

Meshes are built with Auto axis types (``make_mesh``): sharding is
propagated by GSPMD from the ``dist.api`` constraints, not carried in
the types.  Install one as the ambient mesh with ``jax.set_mesh``.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types on every axis."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False,
                         shape: tuple[int, ...] | None = None,
                         axes: tuple[str, ...] | None = None):
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    if axes is None:
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None,
                   axes: tuple[str, ...] = ("data",)):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    return make_mesh((n,), axes)
