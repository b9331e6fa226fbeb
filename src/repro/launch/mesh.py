"""Production mesh construction (TPU v5e pods; CPU host devices for dry-run).

Importing this module never touches jax device state — meshes are built
inside functions only.
"""
from __future__ import annotations

import os

import jax

__all__ = ["make_production_mesh", "make_mesh", "worker_mesh",
           "force_host_devices", "HW"]

_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count="


class HW:
    """TPU v5e hardware constants used by the roofline analysis."""
    PEAK_FLOPS_BF16 = 197e12        # per chip
    HBM_BW = 819e9                  # bytes/s per chip
    ICI_BW = 50e9                   # bytes/s per link
    HBM_BYTES = 16e9                # per chip
    VMEM_BYTES = 16 * 2 ** 20       # ~16 MiB per core


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def worker_mesh(data_axis: int, model_axis: int):
    """A ("data", "model") mesh over the attached devices: ``data_axis`` ×
    ``model_axis`` when there are enough, else one worker per device."""
    n_dev = len(jax.devices())
    if n_dev >= data_axis * model_axis:
        return make_mesh((data_axis, model_axis), ("data", "model"))
    return make_mesh((n_dev, 1), ("data", "model"))


def force_host_devices(n: int) -> None:
    """Ask XLA's CPU backend for ``n`` host devices (CPU rehearsals of
    multi-device paths).  Keeps any other ``XLA_FLAGS``; takes effect only
    if no JAX backend has started yet, and a TPU ignores it."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(_HOST_DEVICES_FLAG)]
    os.environ["XLA_FLAGS"] = " ".join(flags + [f"{_HOST_DEVICES_FLAG}{n}"])


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 4, n_model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for the multi-device subprocess tests (8 host devices)."""
    if multi_pod:
        return make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))
