"""Production meshes.  Functions, not module constants — importing this file
never touches jax device state (the dry-run sets XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh():
    """Whatever this host has (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=_auto(("data", "model")))


def _auto(axes):
    # sharding is propagated from with_sharding_constraint hints
    # (parallel/ctx.py), which Explicit axes, make_mesh's default, reject
    return (AxisType.Auto,) * len(axes)


def dp_axes(mesh) -> tuple[str, ...]:
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)
