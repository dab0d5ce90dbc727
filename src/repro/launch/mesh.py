"""Production mesh construction.

IMPORTANT: importing this module never touches jax device state — the mesh
is built lazily inside the function, so smoke tests see 1 CPU device while
dryrun.py (which sets XLA_FLAGS first) sees its 512 placeholders.
"""
from __future__ import annotations

import jax

from repro.configs.base import MeshConfig


def _auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules here
    are GSPMD constraints, which ``Explicit`` axes (the default) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data×model single pod; (2,16,16) pod×data×model for 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run via "
            "launch/dryrun.py (it sets xla_force_host_platform_device_count)")
    return _auto_mesh(shape, axes, devices[:n])


def make_mesh_from_config(cfg: MeshConfig):
    devices = jax.devices()
    n = cfg.num_devices
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return _auto_mesh(cfg.shape, cfg.axes, devices[:n])


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for subprocess-based distributed tests."""
    n = 1
    for s in shape:
        n *= s
    return _auto_mesh(shape, axes, jax.devices()[:n])
