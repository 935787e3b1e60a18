"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state.  The single-pod mesh is (data=16, model=16) = 256 chips;
the multi-pod mesh adds a leading pod axis: (pod=2, data=16, model=16) = 512.

The ``pod`` axis doubles as the Raptor *flight* axis: a serving invocation
flown at concurrency 2 runs one member per pod (DESIGN.md §2).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis under automatic sharding."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_config_mesh(devices=None):
    """1-D ``("config",)`` mesh — the sweep driver's multi-controller axis.

    ``sim/sweeps.py`` shards config-grid sweeps over this mesh; on CPU-only
    hosts the devices come from ``--xla_force_host_platform_device_count``
    (``sim.sweeps.force_host_devices``), so the same code path runs on a
    multi-chip host and a GitHub runner.  Built from an explicit device
    list, so a sweep can run on a prefix of the devices.
    """
    import numpy as np
    devs = list(devices) if devices is not None else jax.devices()
    return jax.sharding.Mesh(np.asarray(devs), ("config",))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes that shard the batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def tp_size(mesh) -> int:
    return mesh.shape.get("model", 1)


def dp_size(mesh) -> int:
    out = 1
    for a in batch_axes(mesh):
        out *= mesh.shape[a]
    return out
