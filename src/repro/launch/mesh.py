"""Production meshes. Functions, never module-level constants, so importing
this module never touches jax device state (assignment requirement)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods =
    512 chips with a leading 'pod' axis (cross-pod data parallelism, or
    pod-level prefill/decode disaggregation per DESIGN.md section 5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(model_axis: int = 1):
    """Whatever this host actually has (tests / examples on CPU)."""
    n = len(jax.devices())
    data = n // model_axis
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
