"""Blocked (flash) attention Pallas kernel: causal / sliding-window, GQA.

TPU adaptation of attention tiling: the (bq, bk) block pair is the solver's
intra-tile; the kv grid dimension is the pipelined reduction loop (online
softmax replaces the associative sum), and fully-masked blocks are skipped
with ``pl.when`` — the block-level analogue of the paper's triangular-domain
density (only ~half the S x S blocks of a causal map do work).

GQA never materialises repeated KV heads: the kv BlockSpec index_map sends
query head ``h`` to kv head ``h // group`` — a pure index transformation
(zero bytes), where the XLA reference path must broadcast.

Layouts: q (B*H, S, D), k/v (B*Hkv, S, D), out (B*H, S, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..dispatch import compiler_params

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 bq: int, bk: int, n_k: int, causal: bool,
                 window: int | None, scale: float):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level visibility: rows [i*bq, i*bq+bq), cols [j*bk, j*bk+bk).
    row_lo = i * bq
    row_hi = row_lo + bq - 1
    col_lo = j * bk
    col_hi = col_lo + bk - 1
    visible = jnp.bool_(True)
    if causal:
        visible = jnp.logical_and(visible, col_lo <= row_hi)
    if window is not None:
        visible = jnp.logical_and(visible, col_hi >= row_lo - (window - 1))

    @pl.when(visible)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        rows = row_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = col_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.bool_(jnp.ones((bq, bk), jnp.bool_))
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window is not None:
            mask = jnp.logical_and(mask, cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "scale", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jax.Array:
    bh_q, s, d = q.shape
    bh_kv = k.shape[0]
    assert bh_q % bh_kv == 0, (bh_q, bh_kv)
    group = bh_q // bh_kv
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    if scale is None:
        scale = d ** -0.5
    n_q, n_k = s // bq, s // bk
    kernel = functools.partial(
        _attn_kernel, bq=bq, bk=bk, n_k=n_k, causal=causal, window=window,
        scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(bh_q, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh_q, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=compiler_params(),
    )(q, k, v)
