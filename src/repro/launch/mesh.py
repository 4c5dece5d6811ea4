"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required for the dry-run's
xla_force_host_platform_device_count trick to work.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    import numpy as np
    n = int(np.prod(shape))
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} "
            "(dry-runs must set xla_force_host_platform_device_count first)")
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, multi_pod: bool = False):
    """Small mesh for CI-scale sharding tests (requires >= n devices)."""
    if multi_pod:
        return _mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))


def make_tp_mesh(n: int, axis: str = "model"):
    """1-D tensor-parallel mesh over ``n`` devices.

    The mesh the sharded GEMMs in ``distributed/shard_gemm.py`` run over:
    one named axis that weight N/K shards (and MoE expert groups) are laid
    out along.  ``n = 1`` is valid and runs the same shard_map code paths
    degenerately — useful for oracle-parity tests.
    """
    return _mesh((n,), (axis,))
