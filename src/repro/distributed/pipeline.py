"""Pipeline parallelism over a mesh axis (the pod/SLR-assignment analogue).

GPipe-style micro-batched pipeline implemented with ``shard_map`` +
``ppermute`` (differentiable, so ``jax.grad`` through the schedule gives
pipeline-parallel backward for free; activation stash memory = GPipe).

The schedule runs S + M - 1 ticks for S stages and M microbatches; at each
tick a stage receives its predecessor's activation via collective_permute
and runs its layer block on the in-flight microbatch.  Bubble fraction
(S-1)/(S+M-1) — the cost model the stage-assignment solver (core/slr.py)
charges for choosing the pipeline role of the pod axis.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, mesh: Mesh, axis: str,
                   stage_params, x_micro: jax.Array) -> jax.Array:
    """Run a pipelined stack.

    stage_fn(params_stage, x) -> y : one stage's layer block.
    stage_params: pytree with leading dim = n_stages (sharded over
    ``axis``); x_micro (M, mb, ...) microbatched inputs (replicated).
    Returns (M, mb, ...) outputs of the LAST stage.
    """
    n_stages = mesh.shape[axis]

    def per_stage(params, xs):
        # params: (1, ...) slice for this stage; xs: full (M, mb, ...)
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        m = xs.shape[0]
        ticks = n_stages + m - 1
        buf = jnp.zeros_like(xs[0])                 # in-flight activation
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (when valid)
            inject = jnp.where(t < m, t, m - 1)
            x0 = xs[inject]
            cur = jnp.where(stage == 0, x0, buf)
            y = stage_fn(params, cur)
            # last stage emits microbatch t - (S-1)
            emit = t - (n_stages - 1)
            do_emit = jnp.logical_and(stage == n_stages - 1, emit >= 0)
            idx = jnp.clip(emit, 0, m - 1)
            outs = jnp.where(
                do_emit,
                outs.at[idx].set(y),
                outs)
            # send activation to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(ticks))
        # all stages hold ``outs``; only the last stage's is real — share it
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    return jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(stage_params, x_micro)


def stage_assignment_cost(n_stages: int, n_micro: int,
                          stage_flops: list[float],
                          peak_flops: float) -> float:
    """Analytic pipeline latency (the Eq. 12/13 schedule specialized to a
    chain): max-stage time dominates, (S-1) bubble ticks."""
    t_stage = max(stage_flops) / peak_flops
    return (n_stages + n_micro - 1) * t_stage
