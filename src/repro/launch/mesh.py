"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. The dry-run launcher sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing
jax; real deployments get the same shapes from actual TPU topology.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# The chip the production mesh is made of, as ``jax.Device.device_kind``
# names it: the dry-run's roofline reckons with this kind's peaks.
TARGET_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever devices exist (tests / smoke runs on 1 CPU device)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
