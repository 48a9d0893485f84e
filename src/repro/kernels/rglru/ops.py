"""Public wrapper for the RG-LRU recurrence kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import dispatch
from . import kernel, ref


def rglru(a: jax.Array, u: jax.Array, *, bs: int | None = None,
          impl: str | None = None) -> jax.Array:
    """h_t = a_t h_{t-1} + u_t over axis 1.  a, u: (B, S, D).  ``bs`` (the
    sequence block) defaults to the largest that fits VMEM at this D."""
    impl = impl or dispatch.current_impl()
    if impl == "xla":
        return ref.rglru(a, u)
    b, s, d = a.shape
    if bs is None:
        bs = kernel.block_rows(d, a.dtype.itemsize)
    bs_ = min(bs, s)
    pad = (-s) % bs_
    if pad:
        # zero-pad decay and input: padded steps hold h constant*0 + 0 — but
        # a=0 would RESET the state; pad at the END so real steps are done.
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
    out = kernel.rglru(a, u, bs=bs_,
                       interpret=(impl == "pallas_interpret"))
    return out[:, :s]
