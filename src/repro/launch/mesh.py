"""Production mesh builders (v5e pods).

16x16 = 256 chips/pod; multi-pod adds a leading "pod" axis (2 pods = 512).
Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh

#: the canonical hint for forcing a multi-device host platform in tests/CI
HOST_DEVICES_FLAG = "XLA_FLAGS=--xla_force_host_platform_device_count"


def _check_devices(needed: int, who: str) -> None:
    have = jax.device_count()
    if have < needed:
        raise RuntimeError(
            f"{who} needs {needed} devices but only {have} "
            f"{'is' if have == 1 else 'are'} visible. Set "
            f"{HOST_DEVICES_FLAG}={needed} in the environment BEFORE jax "
            "initializes (a fresh process), or run on real accelerators; "
            "tests should skip via launch.mesh.require_devices instead.")


def require_devices(n: int) -> None:
    """pytest-skip the calling test when fewer than ``n`` devices exist."""
    import pytest

    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices; run under {HOST_DEVICES_FLAG}={n}")


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    _check_devices(int(np.prod(shape)), "make_production_mesh")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0) -> Mesh:
    """Small mesh for CI tests (requires xla_force_host_platform_device_count)."""
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    _check_devices(int(np.prod(shape)), "make_test_mesh")
    return _make_mesh(shape, axes)


def make_tp_mesh(tp: int) -> Mesh:
    """Serving tensor-parallel mesh: ("data", "model") with data=1, over
    the first ``tp`` devices in the order ``jax.make_mesh`` picks for the
    chip topology."""
    _check_devices(tp, f"make_tp_mesh(tp={tp})")
    return _make_mesh((1, tp), ("data", "model"))


# Hardware constants for the roofline report (TPU v5e)
PEAK_FLOPS_BF16 = 197e12        # per chip
PEAK_FLOPS_INT8 = 394e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
HBM_PER_CHIP = 16 * 1024**3    # 16 GiB
