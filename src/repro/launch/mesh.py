"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; tests and benches see the real devices.

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


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if len(jax.devices()) < int(np.prod(shape)):
        raise RuntimeError(
            f"need {int(np.prod(shape))} devices for the production mesh, "
            f"have {len(jax.devices())} — run under dryrun.py which sets "
            f"--xla_force_host_platform_device_count=512")
    return make_mesh(shape, axes)
