"""RWKV-6 (Finch) WKV recurrence Pallas kernel — data-dependent decay.

Per (batch, head) the recurrent state is a (dk, dv) matrix:

    y_t = r_t . (S_{t-1} + (u * k_t) v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

with per-channel data-dependent decay w_t in (0,1) (Finch's contribution vs
RWKV-5's static decay) and a per-head bonus u for the current token.  The
state matrix lives in a VMEM scratch carried across sequence blocks (grid is
sequential over the S dimension).

Layouts: r,k,w (BH, S, dk), v (BH, S, dv), u (BH, dk) -> y (BH, S, dv).
``u`` rides as (BH, 1, dk) so its (1, 1, dk) block spans the array's last
two dims, as Mosaic requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..dispatch import compiler_params


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, st_ref, s_ref, *,
                  bs: int, n_blocks: int):
    @pl.when(pl.program_id(1) == 0)
    def _reset():
        s_ref[...] = jnp.zeros_like(s_ref)

    def body(t, state):
        r_t = r_ref[0, t, :].astype(jnp.float32)      # (dk,)
        k_t = k_ref[0, t, :].astype(jnp.float32)      # (dk,)
        v_t = v_ref[0, t, :].astype(jnp.float32)      # (dv,)
        w_t = w_ref[0, t, :].astype(jnp.float32)      # (dk,)
        u = u_ref[0, 0, :].astype(jnp.float32)        # (dk,)
        kv = k_t[:, None] * v_t[None, :]              # (dk, dv)
        y = jnp.sum((state + u[:, None] * kv) * r_t[:, None], axis=0)
        o_ref[0, t, :] = y.astype(o_ref.dtype)
        return w_t[:, None] * state + kv

    state = jax.lax.fori_loop(0, bs, body, s_ref[...])
    s_ref[...] = state

    @pl.when(pl.program_id(1) == n_blocks - 1)
    def _emit_state():
        st_ref[0] = state


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def rwkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
          u: jax.Array, *, bs: int = 128,
          interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Returns (y (BH,S,dv), final state (BH,dk,dv))."""
    bh, s, dk = r.shape
    dv = v.shape[-1]
    assert s % bs == 0, (s, bs)
    n_blocks = s // bs
    return pl.pallas_call(
        functools.partial(_rwkv6_kernel, bs=bs, n_blocks=n_blocks),
        grid=(bh, n_blocks),
        in_specs=[
            pl.BlockSpec((1, bs, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bs, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bs, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bs, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, dk), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, bs, dv), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, dk, dv), lambda i, j: (i, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((bh, s, dv), r.dtype),
                   jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
        compiler_params=compiler_params(),
    )(r, k, v, w, u[:, None, :])
