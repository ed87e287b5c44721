"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state. The dry-run process
forces 512 host devices before any jax import; real deployments get real
TPU device counts.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have "
            f"{len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"for the dry-run")
    return make_mesh(shape, axes, devices)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests / elastic rescale)."""
    n = 1
    for s in shape:
        n *= s
    devices = (devices or jax.devices())[:n]
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axes))
