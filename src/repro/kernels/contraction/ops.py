"""Jit'd public wrapper for the generalized contraction: dispatch + padding.

Applies the plan's computation padding (operands zero-padded to the spec's
padded trip counts — exact for both product-contractions and projected
sums), runs the kernel (or the einsum oracle under the ``xla`` impl), and
slices the output back to the original extents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import dispatch
from . import kernel, ref
from .spec import ContractionSpec


def _pad_operand(a: jax.Array, ori: tuple[int, ...],
                 padded: tuple[int, ...]) -> jax.Array:
    assert a.shape == ori, (a.shape, ori)
    pads = tuple((0, p - o) for o, p in zip(ori, padded))
    if any(p for (_, p) in pads):
        return jnp.pad(a, pads)
    return a


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_kernel(spec: ContractionSpec, interpret: bool,
                *operands: jax.Array) -> jax.Array:
    padded = [
        _pad_operand(a, spec.ori_shape(o), spec.padded_shape(o))
        for a, o in zip(operands, spec.all_reads)
    ]
    out = kernel.contract(spec, *padded, interpret=interpret)
    return out[tuple(slice(0, n) for n in spec.out_ori)]


@functools.partial(jax.jit, static_argnums=(0,))
def _run_ref(spec: ContractionSpec, *operands: jax.Array) -> jax.Array:
    return ref.contract(spec, *operands)


def _tracing(operands) -> bool:
    """True when called under an enclosing trace (e.g. the whole-plan
    program jit) — the outer jit wrapper would only add a nested-jit layer
    with its own trace cache, so inline the raw computation instead."""
    return any(isinstance(a, jax.core.Tracer) for a in operands)


def contract(spec: ContractionSpec, *operands: jax.Array,
             impl: str | None = None) -> jax.Array:
    """Evaluate ``spec`` on unpadded operands (reads then init_reads)."""
    impl = impl or dispatch.current_impl()
    tracing = _tracing(operands)
    if impl == "xla":
        if tracing:
            return ref.contract(spec, *operands)
        return _run_ref(spec, *operands)
    if tracing:
        return _run_kernel.__wrapped__(spec, impl == "pallas_interpret",
                                       *operands)
    return _run_kernel(spec, impl == "pallas_interpret", *operands)
