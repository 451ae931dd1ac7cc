"""Production meshes + the declarative ``MeshSpec`` plans carry.

Functions, not module-level constants, so importing this module never
touches jax device state.  Peak device numbers live in repro.launch.hw.

Every mesh here is built by ``make_mesh`` with ``AxisType.Auto`` axes: the
round engine places data with ``with_sharding_constraint`` (GSPMD) and
splits the cohort with ``jax.shard_map``, both of which expect compiler-
chosen (Auto) propagation.  ``jax.make_mesh`` defaults to ``Explicit``
axes, under which those constraints and the in-scan gathers raise
``ShardingTypeError``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` over the local devices with Auto-typed axes (see
    the module docstring for why not Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


@dataclass(frozen=True)
class MeshSpec:
    """Declarative data-parallel mesh for ``ExecutionPlan(mesh=...)``.

    ``devices=None`` takes every locally visible device; ``devices=n``
    pins the mesh to the first ``n`` (n <= ``jax.device_count()``,
    validated at build time so a plan authored for an 8-device host fails
    loudly on a 1-device one instead of silently training unsharded).
    ``axis`` names the single mesh axis; the default ``"data"`` is what
    ``sharding.rules.FED_MESH_RULES`` maps the 'clients' logical axis onto,
    so the round engine's cohort splits across the mesh while params,
    server state and the aggregated delta stay replicated.

    Frozen + hashable: the spec keys the jit caches (a sharded and an
    unsharded run never alias a compiled executable) and the session's
    mesh/dataset caches.
    """
    devices: Optional[int] = None
    axis: str = "data"

    def __post_init__(self):
        if self.devices is not None and (
                not isinstance(self.devices, int) or self.devices < 1):
            raise ValueError(
                f"MeshSpec.devices must be a positive int or None (= all "
                f"local devices), got {self.devices!r}")
        if not self.axis or not isinstance(self.axis, str):
            raise ValueError(
                f"MeshSpec.axis must be a non-empty mesh-axis name, got "
                f"{self.axis!r}")

    def n_devices(self) -> int:
        """Concrete mesh size (resolves ``devices=None`` against the live
        backend)."""
        return jax.device_count() if self.devices is None else self.devices

    def build(self):
        """The jax ``Mesh`` this spec names (1-D over ``axis``)."""
        n = self.n_devices()
        if n > jax.device_count():
            raise ValueError(
                f"MeshSpec wants {n} devices but only "
                f"{jax.device_count()} are visible (force host devices "
                f"with XLA_FLAGS=--xla_force_host_platform_device_count=N "
                f"before jax initializes)")
        return make_mesh((n,), (self.axis,))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the locally available devices (CPU tests/examples)."""
    n = jax.device_count()
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
