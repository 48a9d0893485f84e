"""Mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state: multi-device tests set
``XLA_FLAGS=--xla_force_host_platform_device_count`` before jax starts, and
compiles for a described topology pass its devices in.

Every mesh here has ``Auto`` axes: the model code places arrays through
``NamedSharding`` constraints and lets the compiler propagate the rest.
(``jax.make_mesh`` defaults to ``Explicit`` axes, under which a gather such
as the embedding lookup must state its output sharding.)
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """A mesh of ``shape`` over the first ``prod(shape)`` devices, with
    ``Auto`` axis types."""
    n = int(np.prod(shape))
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices[:n])

