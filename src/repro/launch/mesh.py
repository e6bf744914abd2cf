"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — dryrun.py must
set XLA_FLAGS before any jax call).

Topology (TPU v5e pods):
  single-pod:  (data=16, model=16)        = 256 chips
  multi-pod:   (pod=2, data=16, model=16) = 512 chips
The `pod` axis composes with `data` into the DP/FSDP dimension (gradient
reduce-scatter intra-pod over ICI, all-reduce across pods over DCI);
`model` carries TP/SP/EP.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist —
    used by the sharded-smoke tests."""
    return _make_mesh((data, model), ("data", "model"))
