"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state.  Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model); "pod"
composes with "data" for gradient sync so pod count scales elastically.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under launch/dryrun.py which forces 512 host devices")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_debug_mesh(dp: int = 2, tp: int = 2):
    """Small mesh for multi-device unit tests (subprocess with 4/8 devs)."""
    return jax.make_mesh((dp, tp), ("data", "model"),
                         devices=jax.devices()[:dp * tp],
                         axis_types=(AxisType.Auto,) * 2)
