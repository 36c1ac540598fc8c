"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; real deployments get the same shapes from the TPU topology.

  single-pod: (16, 16)    -> ("data", "model")   = 256 chips (v5e pod)
  multi-pod:  (2, 16, 16) -> ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_serving_mesh"]


def make_serving_mesh(shards: int | None = None, axis: str = "data"):
    """1-D data-parallel mesh for sharded serving: the first ``shards``
    devices (default: all) under a single ``axis`` name.

    Real deployments get the devices from the accelerator (a v5e host
    has four chips). CPU dry-runs / CI simulate the fleet with
    ``JAX_PLATFORMS=cpu`` and
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before the
    first jax device query.
    """
    devices = jax.devices()
    if shards is None:
        shards = len(devices)
    if shards < 1:
        raise ValueError("shards must be >= 1")
    if len(devices) < shards:
        platform = devices[0].platform
        hint = (f" — to simulate them, set JAX_PLATFORMS=cpu and "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={shards} "
                "before the first jax device use" if platform == "cpu"
                else "")
        raise RuntimeError(
            f"need {shards} devices for a serving mesh, this host has "
            f"{len(devices)} {platform} device(s){hint}")
    return jax.make_mesh((shards,), (axis,), devices=devices[:shards])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(jax.devices())} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))
