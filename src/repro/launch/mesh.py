"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax init,
and smoke tests/benches must keep seeing 1 device.

Mesh axes:
  pod   : cross-pod data parallelism (and optional pipeline stages)
  data  : in-pod data parallelism + FSDP (params/optimizer sharded here)
  model : tensor parallelism + expert parallelism
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests / elastic restarts."""
    return _make_mesh(shape, axes)


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes carrying the batch dimension: ('pod','data') when pod exists."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
