"""Public wrapper: which caches the kernel serves, its block sizes and the
layout of q.

``serves`` is the one decision, taken from what the cache shows: under a
Pallas implementation the kernel reads bf16 or float32 entries with heads
a whole number of 128-lane tiles wide, in KV blocks that divide the cache
and keep the (8, 128) tiling, where one sequence's K+V block fits a grid
step's budget.  Every other cache (int8 with per-vector scales, 64-wide
heads, a length with no such block, the ``xla`` implementation) keeps the
model's XLA decode attention.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import dispatch
from . import kernel

BLOCK = 128                    # cache positions per KV block, at most
STEP_BYTES = 4 * 2 ** 20       # K+V bytes a grid step moves, at most


def block_size(sc: int) -> int | None:
    """Positions per KV block for a cache of ``sc``: the largest divisor of
    ``sc`` that divides ``BLOCK``, if it keeps the (8, 128) tiling."""
    c = math.gcd(sc, BLOCK)
    return c if c % 8 == 0 else None


def serves(dtype, head_dim: int, kv_heads: int, sc: int) -> int | None:
    """The KV block the kernel reads a cache of ``sc`` positions in, or None
    where the model's XLA decode attention reads it."""
    ok = dispatch.use_pallas() and head_dim % 128 == 0 \
        and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                 jnp.dtype(jnp.float32))
    c = block_size(sc) if ok else None
    row_bytes = kv_heads * head_dim * jnp.dtype(dtype).itemsize
    return c if c and 2 * c * row_bytes <= STEP_BYTES else None


def seq_block(b: int, block: int, row_bytes: int) -> int:
    """Sequences per block: the most that divide ``b`` and keep a grid
    step's K+V (``2 * block * row_bytes`` a sequence) within
    ``STEP_BYTES``; at least one."""
    bs = max(1, min(b, STEP_BYTES // (2 * block * row_bytes)))
    while b % bs:
        bs -= 1
    return bs


def kv_blocks(sc: int, lengths: np.ndarray, block: int | None
              ) -> tuple[int, int]:
    """(read, skipped) KV blocks of one layer over steps at ``lengths``
    (the step's position + 1).  The kernel (``block`` from ``serves``)
    reads the blocks below the length, the whole cache once a ring buffer
    is full; the XLA path (``block`` None) reads the whole cache, counted
    in blocks of ``BLOCK``."""
    if block is None:
        return len(lengths) * -(-sc // BLOCK), 0
    read = int(np.sum(-(-np.minimum(lengths, sc) // block)))
    return read, len(lengths) * (sc // block) - read


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     at, length, slot, k_new: jax.Array, v_new: jax.Array,
                     *, block: int | None = None, scale: float | None = None,
                     impl: str | None = None) -> jax.Array:
    """One new token q (B,1,H,D) against layer ``at`` of the stacked caches
    (L,B,Sc,Hkv*D): the positions below ``length`` (at least 1) except
    ``slot`` (where the caller writes k_new/v_new (B,1,Hkv*D) after this
    read), plus the new token itself.  ``block`` defaults to
    ``block_size(Sc)``.  -> (B,1,H,D)."""
    impl = impl or dispatch.current_impl()
    assert impl in ("pallas", "pallas_interpret"), impl
    b, _, h, d = q.shape
    _, _, sc, f = k_cache.shape
    hkv = f // d
    c = block or block_size(sc)
    scalars = jnp.stack([jnp.asarray(x, jnp.int32)
                         for x in (at, length, slot)])
    out = kernel.decode_attention(
        q.reshape(b, hkv, h // hkv, d), k_cache, v_cache, k_new, v_new,
        scalars, block=c, seqs=seq_block(b, c, f * k_cache.dtype.itemsize),
        scale=d ** -0.5 if scale is None else scale,
        interpret=(impl == "pallas_interpret"))
    return out.reshape(b, 1, h, d)
