"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests and benches must keep seeing 1 device).

Axis semantics:
  pod   — crosses the DCN boundary between pods. Only gradient/pure-DP/
          spatial-DP traffic is placed on it; ICI-heavy collectives
          (TP, EP, sequence-sharded decode combines) stay inside a pod.
  data  — batch / FSDP / spatial-slab axis (ICI).
  model — TP / EP / sequence-sharding axis (ICI).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.

    The repo places arrays with ``NamedSharding`` / ``device_put`` and
    constrains them with ``with_sharding_constraint``; both assume Auto
    axes, while ``jax.make_mesh`` defaults to Explicit ones.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist (tests / single-host runs)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Axes that shard the batch/FSDP dimension (pod included when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
