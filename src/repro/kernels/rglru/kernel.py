"""RG-LRU linear recurrence Pallas kernel (RecurrentGemma / Griffin).

    h_t = a_t * h_{t-1} + u_t

with data-dependent decay a_t in (0,1) and pre-gated input u_t (the wrapper
computes a_t = exp(c * softplus(Lambda) * sigmoid(r_t)) terms; the kernel is
the sequential hot loop).  The sequence dimension is blocked; the TPU grid
executes sequence blocks in order, so the hidden state lives in a VMEM
scratch that persists across grid steps — the paper's "reuse buffer defined
above the inter-tile loop" (d_{a,0}) realised as carried state.

Layouts: a, u (B, S, D) -> h (B, S, D); grid (B, S/bs).  The sequence
block ``bs`` is sized from D and the dtype so the double-buffered a, u and
h blocks fit the scoped VMEM limit (:func:`block_rows`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.resources import VMEM_BYTES
from ..dispatch import compiler_params


def _rglru_kernel(a_ref, u_ref, o_ref, h_ref, *, bs: int):
    @pl.when(pl.program_id(1) == 0)
    def _reset():
        h_ref[...] = jnp.zeros_like(h_ref)

    def body(r, h):
        a = a_ref[0, r, :].astype(jnp.float32)
        u = u_ref[0, r, :].astype(jnp.float32)
        h = a * h + u
        o_ref[0, r, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, body, h_ref[0])
    h_ref[0] = h


def block_rows(d: int, itemsize: int, max_rows: int = 256) -> int:
    """Largest power-of-two sequence block (>= 8, <= ``max_rows``) whose
    three (bs, d) blocks, double-buffered, fit ``VMEM_BYTES`` next to the
    f32 state scratch."""
    bs = max_rows
    while bs > 8 and 6 * bs * d * itemsize + 4 * d > VMEM_BYTES:
        bs //= 2
    return bs


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def rglru(a: jax.Array, u: jax.Array, *, bs: int,
          interpret: bool = False) -> jax.Array:
    b, s, d = a.shape
    assert s % bs == 0, (s, bs)
    return pl.pallas_call(
        functools.partial(_rglru_kernel, bs=bs),
        grid=(b, s // bs),
        in_specs=[
            pl.BlockSpec((1, bs, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bs, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
        compiler_params=compiler_params(),
    )(a, u)
