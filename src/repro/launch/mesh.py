"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
while smoke tests and benches see the real single CPU device.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} "
            f"present — run through launch/dryrun.py, which forces 512 "
            f"host platform devices")
    return _auto_mesh(shape, axes, devices)


def make_mesh_for(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic re-planning, tests on small device counts)."""
    n = math.prod(shape)
    return _auto_mesh(shape, axes, jax.devices()[:n])


def _auto_mesh(shape, axes, devices):
    # jax.make_mesh defaults to Explicit axes, under which the logical
    # sharding constraints (sharding/rules.py) and gathers raise; every
    # sharding in this repo is a GSPMD hint, so the axes are Auto
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))
