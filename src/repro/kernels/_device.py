"""Interpret-mode resolution shared by the main-path kernels' ops wrappers.

A Pallas TPU kernel is compiled on a TPU and interpreted on the CPU (the
tests).  Nothing else: on a TPU it never interprets, and a kernel whose
target cannot be told raises instead of guessing.  The target comes from:

1. the operands — a concrete array knows the device it is committed to;
2. ``kernel_platform(...)`` — inside jit the operands are tracers, so the
   caller that knows where the program runs names it.  ``FederatedTrainer``
   enters it once per ``run()`` with the platform of the devices it places
   on, which threads the choice into every round it traces; a kernel
   compiled by hand (e.g. for a described chip in a CPU process,
   ``tests/test_tpu_compile.py``) is traced under it too.

Concrete operands that contradict the scope raise, and so does interpret
mode in a process whose default backend is a TPU.  Only the raw kernels
take an explicit ``interpret=``.

``whole_on_each_device`` covers the mesh planes: the TPU compiler cannot
partition a Mosaic kernel, so under an active mesh the fused server update
runs whole on every device, inside a ``shard_map`` with replicated
operands.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.sharding import current_mesh

_scope = threading.local()


@contextlib.contextmanager
def kernel_platform(platform: str):
    """Jitted kernels traced in this thread (and scope) target ``platform``
    ("tpu" or "cpu")."""
    interpret_for(platform)            # validate early
    prev = getattr(_scope, "platform", None)
    _scope.platform = platform
    try:
        yield
    finally:
        _scope.platform = prev


def interpret_for(platform: str) -> bool:
    """Compiled on a TPU, interpreted on the CPU, refused elsewhere."""
    if platform not in ("tpu", "cpu"):
        raise ValueError(
            f"the Pallas kernels here target 'tpu' (compiled) or 'cpu' "
            f"(interpret mode); got platform {platform!r}")
    return platform == "cpu"


def _operand_platforms(operands: Any) -> set:
    out = set()
    for x in jax.tree.leaves(operands):
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            out.update(d.platform for d in x.devices())
    return out


def resolve_interpret(operands: Any) -> bool:
    """True when the kernel must run in interpret mode (CPU target)."""
    platforms = _operand_platforms(operands)
    if len(platforms) > 1:
        raise ValueError(
            f"kernel operands span several platforms {sorted(platforms)}")
    scope = getattr(_scope, "platform", None)
    if platforms:
        (platform,) = platforms
        if platform == "cpu" and jax.default_backend() == "tpu":
            raise ValueError(
                "kernel operands are committed to the CPU in a TPU process; "
                "put them on the TPU (jax.device_put) — the kernel does not "
                "fall back to interpret mode beside a chip")
        if scope is not None and scope != platform:
            raise ValueError(
                f"kernel operands live on {platform!r}, which contradicts "
                f"the kernel_platform({scope!r}) scope")
    elif scope is not None:
        platform = scope
    else:
        raise ValueError(
            "inside jit the kernel cannot see its target device: trace it "
            "under kernels._device.kernel_platform(...) (FederatedTrainer."
            "run does)")
    mode = interpret_for(platform)
    if mode and jax.default_backend() == "tpu":
        raise ValueError("interpret mode is refused in a TPU process")
    return mode


def whole_on_each_device(fn):
    """``fn`` itself outside a mesh; under the active mesh context, ``fn``
    run whole on every device of it (replicated operands and results)."""
    mesh = current_mesh()
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)
