"""Mesh construction: the one place the repo builds a `jax.sharding.Mesh`.

Functions (not module-level constants) so importing this module never
touches jax device state — required because the dry-run forces 512 host
devices via XLA_FLAGS before any jax import, while tests/benches must see
the single real CPU device.

Every mesh gets `AxisType.Auto` axes. `jax.make_mesh` defaults to
`Explicit` axes, under which `with_sharding_constraint` is an assert and
gathers/einsums need explicit out-shardings; the serve plan's exact-TP
layout and the trainer's activation constraints are written for
compiler-propagated (Auto) sharding.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], names: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """`jax.make_mesh` with Auto axes; `devices` picks a subset (default:
    the first prod(shape) devices)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16,16) = 256 chips, axes (data, model).
    Multi-pod:  (2,16,16) = 512 chips, axes (pod, data, model) — `pod` is
    DP across the inter-pod DCN; gradient all-reduce crosses it once/step."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(model: int = 4, data: int = 2, *, multi_pod: bool = False):
    """Small mesh for CPU-host testing (8 forced host devices)."""
    if multi_pod:
        return make_mesh((2, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
