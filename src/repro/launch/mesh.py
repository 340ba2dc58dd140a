"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests and
benches see the real 1-CPU world).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_axes(n: int) -> tuple:
    """axis_types for jax.make_mesh: n Auto axes (the partitioner, not
    the program, decides shardings inside jit)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, auto_axes(len(axes)))


def make_host_mesh(model_parallel: int = 1, devices: int | None = None):
    """(data, model) mesh over the first `devices` devices that exist
    (default: all of them)."""
    devs = jax.devices()[:devices]
    n = len(devs)
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), auto_axes(2), devices=devs)


def dp_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)
