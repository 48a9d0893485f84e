"""Jitted training step: loss -> grads -> AdamW, with sharding specs.

``make_train_step`` returns a jitted function with in/out shardings bound
to the mesh (donated params/opt-state buffers) — this is exactly the
callable the multi-pod dry-run lowers with ShapeDtypeStructs.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed import sharding as sh
from ..models import model as M
from .optimizer import AdamWConfig, OptState, adamw_update


def loss_fn(params: Any, cfg: M.ModelConfig, tokens: jax.Array,
            labels: jax.Array) -> jax.Array:
    hidden = M.forward(params, cfg, tokens)
    return M.lm_loss(params, cfg, hidden, labels)


def train_step(params: Any, opt_state: OptState, tokens: jax.Array,
               labels: jax.Array, *, cfg: M.ModelConfig,
               opt_cfg: AdamWConfig, microbatches: int = 1):
    """One optimizer step.  ``microbatches > 1`` splits the global batch
    and accumulates gradients in fp32 over a scan — the activation
    working set shrinks by the same factor (the §5.7 regeneration lever
    for OOM train cells; identical math up to accumulation order)."""
    if microbatches == 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, cfg, tokens,
                                                  labels)
    else:
        b = tokens.shape[0]
        assert b % microbatches == 0, (b, microbatches)
        tb = tokens.reshape((microbatches, b // microbatches)
                            + tokens.shape[1:])
        lb = labels.reshape((microbatches, b // microbatches)
                            + labels.shape[1:])

        def one(carry, tl):
            t, l = tl
            loss_i, g_i = jax.value_and_grad(loss_fn)(params, cfg, t, l)
            acc_l, acc_g = carry
            acc_g = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), acc_g, g_i)
            return (acc_l + loss_i, acc_g), None

        init = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))
        (loss_sum, gsum), _ = jax.lax.scan(one, init, (tb, lb))
        loss = loss_sum / microbatches
        grads = jax.tree.map(lambda g: g / microbatches, gsum)
    new_params, new_state, metrics = adamw_update(
        opt_cfg, params, grads, opt_state, decay=M.decay_mask(params))
    metrics = {**metrics, "loss": loss}
    return new_params, new_state, metrics


def make_train_step(mesh: Mesh, cfg: M.ModelConfig,
                    opt_cfg: AdamWConfig, params_shape: Any,
                    global_batch: int, seq_len: int,
                    microbatches: int = 1):
    """Build the pjit'd train step + its input shardings.

    Returns (jitted_fn, shardings dict) where shardings has entries
    params / opt_state / tokens / labels.
    """
    p_shard = sh.shard_params(mesh, params_shape)
    needs_master = any(x.dtype != jnp.float32
                       for x in jax.tree.leaves(params_shape))
    o_shard = OptState(
        step=NamedSharding(mesh, P()),
        m=p_shard, v=p_shard,
        master=p_shard if needs_master else None)
    extra = 1 if cfg.embed_input else 2
    t_shard = sh.tokens_sharding(mesh, global_batch,
                                 extra_dims=extra)
    l_shard = sh.tokens_sharding(mesh, global_batch, extra_dims=1)
    metric_shard = {k: NamedSharding(mesh, P())
                    for k in ("grad_norm", "lr", "loss")}

    def step(*args):
        # traced under the mesh: the model's activation constraints apply
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return train_step(*args, cfg=cfg, opt_cfg=opt_cfg,
                              microbatches=microbatches)

    jitted = jax.jit(
        step,
        in_shardings=(p_shard, o_shard, t_shard, l_shard),
        out_shardings=(p_shard, o_shard, metric_shard),
        donate_argnums=(0, 1),
    )
    shardings = {"params": p_shard, "opt_state": o_shard,
                 "tokens": t_shard, "labels": l_shard}
    return jitted, shardings

