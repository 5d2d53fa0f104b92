"""Production mesh definitions.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls these.
"""

from __future__ import annotations

import os

import jax


def fake_cpu_devices(n: int) -> None:
    """Give XLA:CPU ``n`` devices, under ``JAX_PLATFORMS=cpu`` only.

    Only the CPU backend can fake devices; on an accelerator a mesh takes
    the chips present, and ``jax.make_mesh`` refuses one larger than that.
    Call before JAX first asks for its devices.
    """
    if n > 1 and os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} "
            + os.environ.get("XLA_FLAGS", ""))


def _mesh(shape, axes):
    # raises ValueError when the mesh needs more devices than are present
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """One pod = 16x16 = 256 chips (data, model); two pods add a leading
    pure-DP 'pod' axis across the slow inter-pod links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh(dp: int = 1, tp: int = 1, pods: int = 1):
    """Small mesh for CPU tests (uses however many host devices exist)."""
    if pods > 1:
        return _mesh((pods, dp, tp), ("pod", "data", "model"))
    return _mesh((dp, tp), ("data", "model"))


def mesh_axes(mesh) -> dict:
    names = mesh.axis_names
    return {
        "pod_axis": "pod" if "pod" in names else None,
        "pods": mesh.shape.get("pod", 1) if "pod" in names else 1,
        "data_axis": "data",
        "dp": mesh.shape["data"],
        "model_axis": "model",
        "tp": mesh.shape["model"],
    }
