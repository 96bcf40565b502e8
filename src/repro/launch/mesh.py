"""Mesh construction for the production topologies.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else (tests, benches) sees the 1 real CPU device.

Logical axes:
  pod   : inter-pod data parallelism (gradient all-reduce over DCN)
  data  : intra-pod data parallelism + FSDP parameter sharding + sequence
          sharding for batch-1 long-context decode
  model : tensor/expert parallelism (heads, ffn hidden, experts, vocab)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """jax.make_mesh with Auto axes (the sharding rules rely on the
    compiler propagating shardings between annotated points)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests, elastic re-scale)."""
    return _mk(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests)."""
    return _mk((data, model), ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """The axes batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
