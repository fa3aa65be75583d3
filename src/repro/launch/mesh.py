"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, devices=None):
    """(data, model) mesh over ``devices`` (default: every device)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    model = min(model_parallel, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         devices=devices, axis_types=(AxisType.Auto,) * 2)
