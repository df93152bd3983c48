"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.  Axes:

  * ``pod``   — the slow-ICI/DCN axis between pods (multi-pod only)
  * ``data``  — fast-ICI axis used for data parallelism + FSDP weight
                sharding (+ sequence/context parallelism for bs=1 decode)
  * ``model`` — tensor/expert parallel axis

Weight FSDP runs over every non-``model`` axis, so parameters and optimizer
state shard ``pod*data*model``-ways — this is what fits 398B-param configs
(4.8 TB of fp32 AdamW state) into 16 GiB/chip.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def _axis_type_kwargs(n: int) -> dict:
    """Every mesh axis is ``Auto`` (GSPMD propagates the shardings)."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests / elastic re-meshing)."""
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def make_host_mesh():
    """All-devices data-parallel mesh for CPU smoke tests/examples
    (shape ``(n, 1)`` — the ``model`` axis is 1, no tensor parallelism)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), **_axis_type_kwargs(2))


def parse_mesh_shape(arg: str) -> Tuple[int, int]:
    """Parse a ``DATAxMODEL`` CLI mesh-shape argument (``"4x2"``, ``"4,2"``
    or ``"8"`` — a bare count means all-data-parallel).  Serving launchers
    route this through :func:`make_mesh` so ``--mesh 1x8`` can actually
    exercise tensor parallelism; the old hardcoded ``make_host_mesh()``
    pinned the ``model`` axis to 1 no matter how many devices
    ``XLA_FLAGS=--xla_force_host_platform_device_count`` exposed."""
    parts = [p for p in arg.replace(",", "x").lower().split("x") if p]
    try:
        dims = tuple(int(p) for p in parts)
    except ValueError:
        raise ValueError(f"--mesh expects DATAxMODEL (e.g. 4x2), got {arg!r}")
    if len(dims) == 1:
        dims = (dims[0], 1)
    if len(dims) != 2 or any(d < 1 for d in dims):
        raise ValueError(f"--mesh expects two positive dims DATAxMODEL "
                         f"(e.g. 4x2), got {arg!r}")
    n = len(jax.devices())
    if dims[0] * dims[1] > n:
        raise ValueError(
            f"--mesh {arg!r} needs {dims[0] * dims[1]} devices but only {n} "
            f"are visible (set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count=N for a forced CPU mesh)")
    return dims


def dp_axes(mesh) -> Tuple[str, ...]:
    """Data-parallel (and FSDP) axes: every axis except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
