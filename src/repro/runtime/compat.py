"""Every mesh the framework builds, with ``Auto`` axis types, so GSPMD
keeps propagating shardings the way the model code expects."""

from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["make_mesh", "abstract_mesh"]


def _auto(axes: Sequence[str]):
    return (jax.sharding.AxisType.Auto,) * len(tuple(axes))


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=_auto(axes))


def abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Shape-only mesh stand-in (device-free spec sanitization in tests)."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes),
                                     axis_types=_auto(axes))
