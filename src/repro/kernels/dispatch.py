"""Kernel implementation dispatch.

Implementations:
  ``xla``               pure-jnp reference path (the ref.py oracles) — used by
                        the 512-device dry-runs (that is what cost_analysis
                        inspects) and as the numerical oracle.
  ``pallas_interpret``  Pallas kernel bodies executed in interpret mode on
                        CPU — how this container validates the TPU kernels.
  ``pallas``            compiled Pallas (Mosaic) — the TPU target.

Resolution order: explicit argument > ``repro_kernel_impl`` context >
``REPRO_KERNEL_IMPL`` env var > auto (pallas on TPU, xla elsewhere).
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax
from jax.experimental.pallas import tpu as pltpu

from ..core.resources import VMEM_BYTES

_VALID = ("xla", "pallas_interpret", "pallas", "auto")
_state = threading.local()


def _auto() -> str:
    try:
        platform = jax.devices()[0].platform
    except RuntimeError:
        platform = "cpu"
    return "pallas" if platform == "tpu" else "xla"


def current_impl() -> str:
    impl = getattr(_state, "impl", None) \
        or os.environ.get("REPRO_KERNEL_IMPL", "auto")
    if impl not in _VALID:
        raise ValueError(f"bad kernel impl {impl!r}; want one of {_VALID}")
    return _auto() if impl == "auto" else impl


@contextlib.contextmanager
def kernel_impl(impl: str):
    """Force a kernel implementation within a scope (tests use
    ``pallas_interpret``)."""
    if impl not in _VALID:
        raise ValueError(f"bad kernel impl {impl!r}")
    prev = getattr(_state, "impl", None)
    _state.impl = impl
    try:
        yield
    finally:
        _state.impl = prev


def use_pallas() -> bool:
    return current_impl() in ("pallas", "pallas_interpret")


def interpret_mode() -> bool:
    return current_impl() == "pallas_interpret"


def compiler_params(dimension_semantics=None) -> pltpu.CompilerParams:
    """Mosaic parameters every kernel compiles with.  The scoped-VMEM limit
    is the solver's VMEM budget (``core.resources.VMEM_BYTES``): one number
    bounds both the plans the solver accepts and what a kernel may hold."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_BYTES)
