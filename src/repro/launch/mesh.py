"""Production and test meshes.

Functions, not module constants — importing this module never touches jax
device state (device count is locked at first jax init, and only the dry-run
sets the 512-device host-platform flag).

`fold_copy_axis` is the sharded serving engine's replica-group trick
(DESIGN.md §14): a ("data", "model") mesh whose data axis is divisible by
the TMR copy count reshapes into ("copy", "data", "model") — the three TMR
copies land on three *disjoint replica groups* of existing data-parallel
devices, so parallel/semi TMR reuses replicas that are already there
instead of tripling any one device's work.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_test_mesh", "make_tmr_serving_mesh",
           "fold_copy_axis", "require_devices"]


def _auto_mesh(shape, axes) -> Mesh:
    """A mesh with Auto axis types: the engine places arrays with
    NamedShardings and pins activations with `with_sharding_constraint`
    under an ambient mesh, which Explicit axes (the `jax.make_mesh` default
    since JAX 0.8) reject.  A mesh over part of the host (3 chips of a 2x2
    TPU, say) takes the first devices in order: `jax.make_mesh` orders
    devices by the physical torus and cannot place a partial one."""
    types = (AxisType.Auto,) * len(axes)
    n = math.prod(shape)
    if n < jax.device_count():
        return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes,
                    axis_types=types)
    return jax.make_mesh(shape, axes, axis_types=types)


def require_devices(n: int, what: str) -> None:
    """Fail with an actionable message when the host exposes fewer devices
    than a mesh needs (jax's own error is an opaque device-count mismatch
    that never mentions the forced-host-platform escape hatch)."""
    have = jax.device_count()
    if have < n:
        raise ValueError(
            f"{what} needs {n} devices but this host exposes only {have}. "
            f"On CPU, force virtual host devices BEFORE jax initializes: "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"(or os.environ['XLA_FLAGS'] at the very top of the script).")


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: one pod = 16x16 = 256 chips as
    ("data", "model"); two pods = 512 chips with a leading "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    require_devices(2 * 16 * 16 if multi_pod else 16 * 16,
                    f"production mesh {'x'.join(map(str, shape))}")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU sharding tests (requires forced host devices)."""
    require_devices(data * model, f"test mesh {data}x{model}")
    return _auto_mesh((data, model), ("data", "model"))


def make_tmr_serving_mesh(copies: int = 3, data: int = 5, model: int = 16):
    """Dedicated TMR serving mesh: ("copy", "data", "model") with the copy
    axis sized to the TMR copy count — 3x5x16 = 240 of a 256-chip pod serve
    triple-redundant with 5-way data parallelism inside each replica group.
    Equivalent to `fold_copy_axis(make_test_mesh(copies*data, model))`."""
    require_devices(copies * data * model,
                    f"TMR serving mesh {copies}x{data}x{model}")
    return _auto_mesh((copies, data, model), ("copy", "data", "model"))


def fold_copy_axis(mesh: Mesh, copies: int = 3) -> Optional[Mesh]:
    """Fold a leading TMR copy axis onto a mesh's data-axis replica groups.

    ("data", "model") with data % copies == 0 -> ("copy", "data", "model")
    over the SAME devices, data shrunk by the copy factor: each copy owns a
    disjoint replica group of data//copies devices.  Returns None when the
    data axis cannot host the copies (callers then keep the original mesh
    and replicate the copy axis instead — correct, just not free).
    A mesh that already has a "copy" axis is returned unchanged.
    """
    if "copy" in mesh.axis_names:
        return mesh
    if mesh.axis_names != ("data", "model"):
        return None
    d = mesh.shape["data"]
    if d % copies != 0:
        return None
    devices = mesh.devices.reshape(copies, d // copies,
                                   mesh.shape["model"])
    return Mesh(devices, ("copy", "data", "model"),
                axis_types=(AxisType.Auto,) * 3)
