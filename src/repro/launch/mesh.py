"""Production mesh construction (+ FRED-style device placement).

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state — required for the
dry-run, which must set ``xla_force_host_platform_device_count`` before any
jax initialization.

Placement note (paper §V, option 4): FRED maps workers of the same MP group
onto *consecutive* physical NPUs, then PP, then DP.  On a TPU torus the
analogous property is "TP groups on ICI-contiguous chips", which
``jax.make_mesh`` already provides when ``model`` is the innermost axis —
the device order is row-major, so the 16 chips of one model group are
physically adjacent.  ``fred_device_order`` makes the policy explicit (and
testable) for arbitrary logical (mp, dp, pp) shapes, mirroring
``repro.core.placement`` which implements the same algorithm for the
wafer-scale simulator.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def make_production_mesh(*, multi_pod: bool = False, devices=None):
    """(16, 16) ``(data, model)`` single-pod or (2, 16, 16)
    ``(pod, data, model)`` multi-pod mesh."""
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if devices is not None:
        devs = np.asarray(devices).reshape(shape)
        return jax.sharding.Mesh(devs, axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """Arbitrary mesh for tests/examples (e.g. (4,2) on 8 host devices)."""
    import jax
    if devices is not None:
        devs = np.asarray(devices).reshape(tuple(shape))
        return jax.sharding.Mesh(devs, tuple(axes))
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def fred_device_order(n_devices: int, mp: int, dp: int, pp: int) -> np.ndarray:
    """FRED placement: worker (m, d, p) → physical NPU index.

    Workers of the same MP group sit on consecutive devices; MP groups of
    the same PP stage follow; DP replicas iterate outermost (paper Sec. V:
    "map the training workers within the same MP group on consecutive
    physical NPUs followed by iterating over workers within PP and DP").

    Returns an (mp, dp, pp) → device-id array.
    """
    assert mp * dp * pp <= n_devices
    order = np.zeros((mp, dp, pp), dtype=np.int64)
    nid = 0
    for d in range(dp):
        for p in range(pp):
            for m in range(mp):
                order[m, d, p] = nid
                nid += 1
    return order
