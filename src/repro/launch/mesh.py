"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
forces 512 host devices via XLA_FLAGS while tests/benches must see 1.
"""
from __future__ import annotations

import math

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 ("data", "model") single-pod or 2x16x16 ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_elastic_mesh(devices=None, *, model_parallel: int | None = None):
    """Best-effort (data, model) mesh from whatever devices are alive.

    Used by the elastic-restart path: after a failure the job restarts with
    however many devices remain; the mesh is re-factorized (model axis kept
    as large as divides the device count, capped at the configured TP) and
    the checkpoint is resharded on load.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if model_parallel is None:
        model_parallel = min(16, n)
    while n % model_parallel:
        model_parallel -= 1
    dp = n // model_parallel
    arr = np.array(devices).reshape(dp, model_parallel)
    return jax.sharding.Mesh(arr, ("data", "model"))
