"""Mesh construction.

FUNCTIONS, not module-level constants: importing this module never
touches jax device state.  The dry-run forces 512 host devices via
XLA_FLAGS *before* any jax import (see dryrun.py); smoke tests and
benchmarks see the normal single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_data_mesh(devices=None):
    """A 1-D ("data",) mesh over ``devices`` (default: every local
    device) — one CAD rank, and one attention server, per chip."""
    devs = list(devices if devices is not None else jax.devices())
    return _mesh((len(devs),), ("data",), devs)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes ("data", "model").
    Multi-pod: (2, 16, 16) = 512 chips, axes ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return _mesh(shape, axes, devs[:n])


def device_summary() -> dict:
    """The devices JAX runs on, as every run's first line names them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
