"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state.  Single-pod: 16x16 = 256 chips (v5e pod), axes (data, model).
Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model); the pod axis is
pure DP over DCN (hierarchical gradient sync — see train/trainer.py).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devs = jax.devices()[:math.prod(shape)]
    return Mesh(np.asarray(devs).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh over the first ``prod(shape)`` local devices (tests, measured
    tuning, ``launch/train.py --mesh dxt``).  Fails when the process sees
    fewer devices than the mesh needs."""
    n, have = math.prod(shape), len(jax.devices())
    if have < n:
        raise ValueError(f"mesh {shape} needs {n} devices, this process "
                         f"sees {have}")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])
