"""Pure-jnp oracle for :class:`ContractionSpec` — the ``xla`` impl path.

Evaluates the spec's semantics directly with einsum on the *unpadded*
operands; numerically identical (up to f32 association order) to the Pallas
kernel, and to the statement-level reference executor.

``combine_terms`` is the single definition of the op semantics ("mul" =
joint product contraction, "add"/"sub" = signed sum of per-operand
projections, "unary:<name>"/"binary:<name>" = pointwise function families);
the Pallas kernel body reuses it on VMEM blocks so oracle and kernel cannot
drift apart.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .spec import ACC, ContractionSpec, Operand

# ---------------------------------------------------------------------------
# Pointwise op families — "unary:<name>" / "binary:<name>" statement ops.
# One table shared by the statement oracle, the xla impl and the Pallas
# kernel epilogue (all jnp/lax primitives, traceable inside kernels).
# ---------------------------------------------------------------------------
_UNARY: dict[str, Callable] = {
    "logistic": jax.lax.logistic,
    "tanh": jnp.tanh,
    "exp": jnp.exp,
    "log": jnp.log,
    "log1p": jnp.log1p,
    "expm1": jnp.expm1,
    "sqrt": jnp.sqrt,
    "rsqrt": jax.lax.rsqrt,
    "cbrt": jax.lax.cbrt,
    "erf": jax.lax.erf,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "abs": jnp.abs,
    "sign": jnp.sign,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "round": jnp.round,
}

_BINARY: dict[str, Callable] = {
    "max": jnp.maximum,
    "min": jnp.minimum,
    "div": jnp.divide,
}


def unary_fn(name: str) -> Callable:
    """Resolve a ``unary:<name>`` suffix, including the parameterized
    families ``pow_<k>`` (integer_pow) and ``max_const:<c>``/``min_const:<c>``
    (clamps against a folded scalar literal, e.g. relu's ``max(x, 0)``)."""
    if name.startswith("pow_"):
        k = int(name[len("pow_"):])
        return lambda v: v ** k
    if name.startswith("max_const:"):
        c = float(name[len("max_const:"):])
        return lambda v: jnp.maximum(v, c)
    if name.startswith("min_const:"):
        c = float(name[len("min_const:"):])
        return lambda v: jnp.minimum(v, c)
    try:
        return _UNARY[name]
    except KeyError:
        raise KeyError(f"unknown unary op {name!r}") from None


def binary_fn(name: str) -> Callable:
    try:
        return _BINARY[name]
    except KeyError:
        raise KeyError(f"unknown binary op {name!r}") from None


def has_unary(name: str) -> bool:
    return name in _UNARY


def has_binary(name: str) -> bool:
    return name in _BINARY


def scale_offset(val: jax.Array, coeff: float, offset: float) -> jax.Array:
    """``coeff * val + offset`` without emitting no-op arithmetic."""
    if coeff != 1.0:
        val = val * jnp.float32(coeff)
    if offset != 0.0:
        val = val + jnp.float32(offset)
    return val


def project_term(sub: str, out_sub: str, v: jax.Array,
                 out_shape: tuple[int, ...]) -> jax.Array:
    """Project one operand onto the output iterators.

    Operand iterators absent from the output are summed out (einsum
    projection); output iterators absent from the operand are broadcast —
    the frontend's lowering of ``broadcast_in_dim`` and of size-1
    elementwise operands relies on this (einsum alone cannot introduce an
    output label its inputs lack).  The projection runs in f32.
    """
    keep = "".join(c for c in out_sub if c in sub)
    term = jnp.einsum(f"{sub}->{keep}", v.astype(jnp.float32))
    if keep != out_sub:
        missing = tuple(i for i, c in enumerate(out_sub) if c not in keep)
        term = jnp.broadcast_to(jnp.expand_dims(term, missing), out_shape)
    return term


def combine_terms(subs: list[str], out_sub: str, op: str,
                  vals: list[jax.Array],
                  zero_shape: tuple[int, ...]) -> jax.Array:
    """Combine operands per the op semantics (shared by oracle + kernel).

    ``"sub"`` is the sum-of-projections with the first operand positive and
    every later operand negated (``a - b - c``) — the lowering of the
    elementwise ``sub``/``neg`` primitives.

    Operands keep their own dtype and every result is f32.  A contraction
    with an f32 operand runs in f32 at ``HIGHEST`` precision: the TPU's default
    (in XLA and in Mosaic alike) multiplies f32 in one bf16 pass, which
    would not compute the f32 statement.  Half-precision operands need
    no more than the default pass.
    """
    if not vals:
        return jnp.zeros(zero_shape, jnp.float32)
    if op.startswith("unary:"):
        return unary_fn(op[len("unary:"):])(
            project_term(subs[0], out_sub, vals[0], zero_shape))
    if op.startswith("binary:"):
        return binary_fn(op[len("binary:"):])(
            project_term(subs[0], out_sub, vals[0], zero_shape),
            project_term(subs[1], out_sub, vals[1], zero_shape))
    if op == "mul":
        if all(set(sub) <= set(out_sub) for sub in subs):
            # Nothing is contracted: a pure elementwise/broadcast product.
            # Plain multiplies fuse into neighboring XLA ops; the einsum
            # form lowers to a batch dot_general that does not.
            total = None
            for sub, v in zip(subs, vals):
                term = project_term(sub, out_sub, v, zero_shape)
                total = term if total is None else total * term
            return total
        if any(v.dtype == jnp.float32 for v in vals):
            # one dtype for both sides: Mosaic refuses a mixed-dtype dot
            return jnp.einsum(f"{','.join(subs)}->{out_sub}",
                              *(v.astype(jnp.float32) for v in vals),
                              precision=jax.lax.Precision.HIGHEST)
        return jnp.einsum(f"{','.join(subs)}->{out_sub}", *vals,
                          preferred_element_type=jnp.float32)
    total = None
    for i, (sub, v) in enumerate(zip(subs, vals)):
        term = project_term(sub, out_sub, v, zero_shape)
        if op == "sub" and i > 0:
            term = -term
        total = term if total is None else total + term
    return total


def _combine(spec: ContractionSpec, operands: tuple[Operand, ...],
             vals: list[jax.Array], op: str,
             zero_shape: tuple[int, ...]) -> jax.Array:
    return combine_terms(spec.einsum_inputs(operands), spec.out_subscript,
                         op, vals, zero_shape)


def apply_epilogue(spec: ContractionSpec, val: jax.Array,
                   epi_vals: list[jax.Array]) -> jax.Array:
    """Run the spec's elementwise epilogue chain over ``val``.

    ``epi_vals`` supplies the non-ACC operand values in ``spec.epi_reads``
    order — either unpadded full arrays (oracle path) or VMEM blocks
    (kernel path); the einsum subscripts work identically on both.
    """
    if not spec.epilogue:
        return val
    lt = spec.letters()
    out_sub = spec.out_subscript
    shape = tuple(val.shape)
    it = iter(epi_vals)
    for epi in spec.epilogue:
        subs, vals = [], []
        for o in epi.reads:
            subs.append("".join(lt[x] for x in o.iters))
            vals.append(val if o.array == ACC else next(it))
        val = scale_offset(combine_terms(subs, out_sub, epi.op, vals, shape),
                           epi.coeff, epi.offset)
    return val


def contract(spec: ContractionSpec, *operands: jax.Array) -> jax.Array:
    """Reference evaluation.  ``operands`` = spec.reads, then
    spec.init_reads, then spec.epi_reads, each with the spec's *original*
    (unpadded) shape."""
    n, ni = len(spec.reads), len(spec.init_reads)
    reads, init_reads = list(operands[:n]), list(operands[n:n + ni])
    epi_vals = list(operands[n + ni:])
    val = scale_offset(_combine(spec, spec.reads, reads, spec.op,
                                spec.out_ori),
                       spec.coeff, spec.offset)
    if spec.init_reads:
        val = val + scale_offset(
            _combine(spec, spec.init_reads, init_reads, spec.init_op,
                     spec.out_ori),
            spec.init_coeff, spec.init_offset)
    return apply_epilogue(spec, val, epi_vals)
