"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and only then calls it.

Axes:
* ``data``  — batch / FSDP axis (16-way per pod)
* ``model`` — tensor/expert parallel axis (16-way, intra-pod ICI)
* ``pod``   — the cross-pod (DCN) axis in the multi-pod mesh; specs treat
  ``("pod", "data")`` as one combined FSDP axis.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int):
    """``jax.make_mesh`` defaults to Explicit axes; the sharding rules here
    are written for Auto ones (the compiler propagates shardings)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh():
    """Whatever this host actually has (CPU smoke runs: 1 device)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"), axis_types=_auto(2))
