"""Production mesh builders (functions, never module-level constants, so
importing this module never touches jax device state). Axes are
``Auto``: the sharding rules place arrays with sharding constraints,
which jax applies only on auto axes."""
from __future__ import annotations

import jax


def _auto(axes) -> tuple:
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by tests."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(("data", "model")))
