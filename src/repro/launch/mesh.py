"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The production target is TPU v5e-style pods:
16x16 = 256 chips per pod; the multi-pod mesh prepends a "pod" axis
(2 pods = 512 chips for the dry-run; the axes generalise to N pods).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint`` / ``device_put``, which refer to
    Auto axes only (``jax.make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, n_devices: int | None = None,
                   devices=None):
    """The first ``n_devices`` (default: all) of ``devices`` (default:
    the local ones) as a (data, model) mesh — tests, examples, and
    ``rl_train --devices``."""
    devices = list(jax.devices() if devices is None else devices)
    n = n_devices or len(devices)
    if n > len(devices) or n % model:
        raise ValueError(f"cannot build a ({n // model}, {model}) mesh "
                         f"from {len(devices)} devices")
    return make_mesh((n // model, model), ("data", "model"), devices[:n])
