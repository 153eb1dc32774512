"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256 class).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis crosses
DCN and carries only the once-per-step gradient reduction (optionally int8
compressed, distributed/compression.py); FSDP ('data') and TP ('model') stay
on intra-pod ICI.

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax

#: every mesh axis is Auto: the models annotate shardings, XLA places
AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math
    import numpy as np
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) == need:
        return jax.make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)} - the "
            f"dry-run must set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count=512 before importing jax")
    # more devices than needed (e.g. 512 host devices, single-pod 256 mesh)
    return jax.sharding.Mesh(
        np.asarray(devs[:need]).reshape(shape), axes,
        axis_types=(AUTO,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (subprocess sets device count)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AUTO,) * len(axes))
