"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run must set
``XLA_FLAGS`` before the first jax call.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; the multi-pod mesh prepends a pod axis of 2."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(n_devices: int | None = None, model_parallel: int = 2):
    """Small local mesh over the first ``n_devices`` of ``jax.devices()``
    (all of them by default); asking for more than exist raises."""
    devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"asked for a {n}-device mesh, but only "
                         f"{len(devices)} devices are visible")
    model = model_parallel
    while model > 1 and n % model:
        model //= 2
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devices[:n])


def required_devices(multi_pod: bool) -> int:
    return 512 if multi_pod else 256
