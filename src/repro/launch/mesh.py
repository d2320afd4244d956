"""Mesh factories for the production topologies.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax call, and tests must keep their single CPU device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax import make_mesh
from jax.sharding import AxisType

from repro.configs.base import MULTI_POD, SINGLE_POD, MeshConfig


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) ("data", "model") single pod; (2, 16, 16) ("pod", "data",
    "model") across two pods — 256 chips per pod, 512 total."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_from_config(cfg: MeshConfig):
    return make_mesh(cfg.shape, cfg.axes,
                     axis_types=(AxisType.Auto,) * len(cfg.axes))


def make_host_mesh(shape: Tuple[int, ...] = (1,),
                   axes: Tuple[str, ...] = ("data",)):
    """Tiny mesh over whatever devices exist (tests / CPU examples)."""
    n = 1
    for s in shape:
        n *= s
    assert n <= len(jax.devices()), (shape, len(jax.devices()))
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_env_mesh(num_devices: Optional[int] = None, *,
                  divides: Optional[int] = None, axis: str = "env"):
    """1-D data-parallel mesh for the sharded fused rollout / fleet batch.

    ``num_devices`` defaults to every visible device.  When ``divides`` is
    given (the stacked env count E or the serving batch width), the mesh
    degrades to the largest device count that divides it instead of failing
    — the same degrade-don't-error policy as ``distributed.sharding``.
    ``axis`` names the single mesh axis ("env" for the rollout paths,
    "batch" for the serving batch).
    """
    avail = len(jax.devices())
    n = min(num_devices or avail, avail)
    if divides is not None:
        while n > 1 and divides % n:
            n -= 1
    return make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def mesh_config(mesh) -> MeshConfig:
    return MeshConfig(tuple(mesh.devices.shape), tuple(mesh.axis_names))


def dp_axes(mesh) -> Tuple[str, ...]:
    """Axes that carry data parallelism (pod + data)."""
    names = tuple(mesh.axis_names)
    return tuple(a for a in ("pod", "data") if a in names)
