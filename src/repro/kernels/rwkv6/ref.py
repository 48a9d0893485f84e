"""Pure-jnp oracle for the WKV6 recurrence.

Sequence-chunked with a rematerialised (checkpointed) inner scan: the
backward pass stores only chunk-boundary states ((BH, dk, dv) every
``chunk`` steps) instead of every per-step state — without this, training
rwkv6-1.6b at 4k context materialises TBs of per-step (dk, dv) states
(observed: 1.66 TB/chip of temporaries in the compiled step's memory
analysis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _chunk_scan(state, rkvw, u):
    """Scan one chunk; returns (final state, ys)."""
    def step(st, x):
        r_t, k_t, v_t, w_t = x
        kv = k_t[:, :, None] * v_t[:, None, :]            # (BH, dk, dv)
        y = jnp.einsum("bk,bkv->bv", r_t, st + u[:, :, None] * kv)
        return w_t[:, :, None] * st + kv, y

    return jax.lax.scan(step, state, rkvw)


def rwkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
          u: jax.Array, return_state: bool = False, chunk: int = 128):
    """r,k,w (BH,S,dk), v (BH,S,dv), u (BH,dk) -> y (BH,S,dv)
    (+ final state (BH,dk,dv) when ``return_state``)."""
    bh, s, dk = r.shape
    dv = v.shape[-1]
    args = tuple(jnp.swapaxes(x.astype(jnp.float32), 0, 1)
                 for x in (r, k, v, w))
    state0 = jnp.zeros((bh, dk, dv), jnp.float32)
    u32 = u.astype(jnp.float32)
    body = jax.checkpoint(functools.partial(_chunk_scan, u=u32))

    c = min(chunk, s)
    if s % c:            # irregular length: single checkpointed scan
        state, ys = body(state0, args)
        y = jnp.swapaxes(ys, 0, 1).astype(r.dtype)
        return (y, state) if return_state else y

    n = s // c
    chunked = tuple(x.reshape((n, c) + x.shape[1:]) for x in args)
    state, ys = jax.lax.scan(body, state0, chunked)
    ys = ys.reshape((s,) + ys.shape[2:])
    y = jnp.swapaxes(ys, 0, 1).astype(r.dtype)
    if return_state:
        return y, state
    return y
