"""Decode attention Pallas kernel: one new token per sequence against the
stacked KV cache, reading only the cache blocks below the step's length.

The cache is the layer scan's stacked array ``(L, B, Sc, Hkv*D)``; the
layer index and the length arrive by scalar prefetch, so the kernel reads
layer ``at``'s blocks straight from it (no per-layer slice is copied) and
KV head ``h`` is the lane-aligned column range ``[h*D, (h+1)*D)``.

The grid is one axis over the (sequence block, KV block) pairs below the
length, with the KV block innermost: its extent is ``n_seq * nv`` for a
length covering ``nv`` blocks, set at run time, and step ``t`` is sequence
block ``t // nv``, KV block ``t % nv``.  Blocks past the length are
neither copied nor computed, no grid step is spent on them, and the next
block's copy overlaps the current one's compute across sequence blocks
too.

Softmax is online in float32 per (sequence, head) row; scores and the
value product take the promoted dtype of q and the cache (bf16 for both
in bf16) with float32 accumulation.  The token being decoded joins as
one more key in the last step of its sequence block, and the cache slot
it will be written to is masked out: the kernel reads the cache as it
stood before this step's write.

Layouts: q (B, Hkv, G, D); k/v cache (L, B, Sc, Hkv*D); k_new/v_new
(B, 1, Hkv*D); out (B, Hkv, G, D).  ``scalars`` = int32 (at, length,
slot).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..dispatch import compiler_params

NEG_INF = -1e30


def _n_blocks(length, block: int):
    """KV blocks holding the positions below ``length``."""
    return lax.div(length + block - 1, block)


def _decode_kernel(s_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block: int, hkv: int, d: int,
                   scale: float):
    length, slot = s_ref[1], s_ref[2]
    nv = _n_blocks(length, block)
    j = lax.rem(pl.program_id(0), nv)
    bs, _, g, _ = q_ref.shape

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def keep(shape, dim):
        pos = j * block + lax.broadcasted_iota(jnp.int32, shape, dim)
        return jnp.logical_and(pos < length, pos != slot)

    key_ok = keep((bs, g, block), 2)          # scores (bs, G, C)
    row_ok = keep((bs, block, d), 1)          # values (bs, C, D)
    dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    for h in range(hkv):
        cols = slice(h * d, (h + 1) * d)
        s = jnp.einsum("bgd,bcd->bgc", q_ref[:, h].astype(dt),
                       k_ref[:, :, cols].astype(dt),
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(key_ok, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(key_ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = corr * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
        # rows past the length may hold anything, NaN included: 0 * NaN
        # would reach the sum, so they are zeroed, not just weighted 0
        v = jnp.where(row_ok, v_ref[:, :, cols], 0).astype(dt)
        acc_ref[h] = corr * acc_ref[h] + jnp.einsum(
            "bgc,bcd->bgd", p.astype(dt), v,
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(j == nv - 1)
    def _finish():
        for h in range(hkv):
            cols = slice(h * d, (h + 1) * d)
            q = q_ref[:, h].astype(jnp.float32)
            s = jnp.sum(q * kn_ref[:, :, cols].astype(jnp.float32), axis=-1,
                        keepdims=True) * scale
            m_prev = m_ref[h]
            m = jnp.maximum(m_prev, s)
            corr = jnp.exp(m_prev - m)
            e = jnp.exp(s - m)
            l = corr * l_ref[h] + e
            acc = corr * acc_ref[h] + e * vn_ref[:, :, cols].astype(
                jnp.float32)
            o_ref[:, h] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block", "seqs", "scale", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     k_new: jax.Array, v_new: jax.Array, scalars: jax.Array,
                     *, block: int, seqs: int, scale: float,
                     interpret: bool = False) -> jax.Array:
    b, hkv, g, d = q.shape
    _, _, sc, f = k_cache.shape
    assert f == hkv * d and sc % block == 0 and b % seqs == 0, \
        (q.shape, k_cache.shape, block, seqs)

    def seq(t, s):                  # grid step -> sequence block
        return lax.div(t, _n_blocks(s[1], block))

    q_spec = pl.BlockSpec((seqs, hkv, g, d),
                          lambda t, s: (seq(t, s), 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (pl.squeezed, seqs, block, f),
        lambda t, s: (s[0], seq(t, s), lax.rem(t, _n_blocks(s[1], block)),
                      0))
    new_spec = pl.BlockSpec((seqs, 1, f), lambda t, s: (seq(t, s), 0, 0))
    kernel = functools.partial(_decode_kernel, block=block, hkv=hkv, d=d,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // seqs * _n_blocks(scalars[1], block),),
            in_specs=[q_spec, kv_spec, kv_spec, new_spec, new_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, seqs, g, 1), jnp.float32),
                pltpu.VMEM((hkv, seqs, g, 1), jnp.float32),
                pltpu.VMEM((hkv, seqs, g, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=compiler_params(("arbitrary",)),
        name="decode_attention",
    )(scalars, q, k_cache, v_cache, k_new, v_new)
