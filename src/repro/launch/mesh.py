"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module must not
touch jax device state.  The 'pod' axis is the scale-out dimension: a
1000+-node deployment is (pods, data, model) with identical code because
every collective in the framework is expressed over named mesh axes.
"""

from __future__ import annotations

import math

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_debug_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)} — call "
            f"repro.platform.host_devices(512) before jax initializes "
            f"(dryrun.py does this automatically)")
    # Auto axes: the models place activations with
    # with_sharding_constraint, which Explicit axes reject
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devs[:need])


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU multi-device tests (device count forced by the
    calling test via repro.platform in a subprocess)."""
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()[:need]
    return jax.sharding.Mesh(np.asarray(devs).reshape(shape), axes)
