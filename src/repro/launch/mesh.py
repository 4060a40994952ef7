"""Production mesh construction (TPU v5e; 256 chips/pod).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's
``xla_force_host_platform_device_count`` trick to work.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


# jax's device_kind of the production chip the meshes below model
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (data=FL clients, model=TP) or 2x16x16 two-pod
    (pod=edge hierarchy / cross-silo clients). Uses a device subset when the
    dry-run host exposes more placeholder devices than the mesh needs."""
    import numpy as np
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, data: int | None = None, pod: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    if data is None:
        data = n // (model * pod)
    shape = (pod, data, model) if pod > 1 else (data, model)
    axes = ("pod", "data", "model") if pod > 1 else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
