"""Production mesh definitions (functions, not module constants: importing
this module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests).

    The requested ``model`` (tensor-parallel) degree takes priority: it is
    clamped only by the total device count, and ``data`` then fits into
    whatever remains.  Clamping ``data`` first would funnel ``model``
    through ``n // data`` and silently drop a tp degree the host (e.g. one
    forced via ``XLA_FLAGS=--xla_force_host_platform_device_count``) can
    actually satisfy.
    """
    n = len(jax.devices())
    model = max(min(model, n), 1)
    data = max(min(data, n // model), 1)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
