"""Production meshes (multi-pod dry-run spec).

Defined as functions so importing this module never touches jax device
state; the dry-run entry point sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax

from repro.models.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh needs {n} devices but only {len(devices)} present — "
            "run via repro.launch.dryrun (sets "
            "--xla_force_host_platform_device_count=512)")
    return make_mesh(shape, axes, devices=devices)


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over whatever host devices exist (sharding unit tests)."""
    n = 1
    for s in shape:
        n *= s
    return make_mesh(shape, axes, devices=jax.devices()[:n])
