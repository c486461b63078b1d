"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls this.

Topology mapping (TPU v5e posture): 'model' on the innermost ICI ring (TP
collectives are latency-critical), 'data' on the remaining ICI dims, 'pod'
over DCN.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic replans, tests on small device counts).

    Every axis is ``Auto``: shardings are constraints the compiler
    propagates (``with_sharding_constraint``, ``shard_map`` specs), which
    is what the sharding policy and the transfer plans are written for.
    ``jax.make_mesh`` defaults to ``Explicit`` axes, which type every array
    by its sharding and refuse both."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def describe(mesh) -> str:
    return " × ".join(f"{k}={v}" for k, v in mesh.shape.items()) + \
        f"  ({len(mesh.devices.ravel())} chips)"
