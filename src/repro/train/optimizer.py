"""AdamW optimizer (hand-rolled — no optax in the container).

State mirrors the parameter pytree (m, v) in fp32; shardings follow the
parameter specs, giving ZeRO-style distribution of optimizer state for
free (params are 2D-sharded over (data, model)).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


class OptState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any
    # fp32 master weights when the model stores params in bf16
    # (mixed-precision recipe; None for fp32 params).
    master: Any = None


def init_opt_state(params: Any) -> OptState:
    def zeros(p):
        return jnp.zeros(p.shape, jnp.float32)

    needs_master = any(x.dtype != jnp.float32
                       for x in jax.tree.leaves(params))
    master = jax.tree.map(lambda p: p.astype(jnp.float32), params) \
        if needs_master else None
    return OptState(step=jnp.zeros((), jnp.int32),
                    m=jax.tree.map(zeros, params),
                    v=jax.tree.map(zeros, params),
                    master=master)


def lr_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    decay = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * decay


def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)))


def adamw_update(cfg: AdamWConfig, params: Any, grads: Any,
                 state: OptState, decay: Any = None
                 ) -> tuple[Any, OptState, dict]:
    """One AdamW step.  ``decay`` mirrors ``params`` with a bool per leaf:
    whether weight decay applies (default: every leaf of rank >= 2; a
    model that stacks its layers passes ``model.decay_mask``)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12))
    step = state.step + 1
    lr = lr_schedule(cfg, step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, master, decays):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mhat = m / b1c
        vhat = v / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        # decoupled weight decay (not on norm gains and biases)
        wd = cfg.weight_decay if decays else 0.0
        base = p.astype(jnp.float32) if master is None else master
        new_master = base * (1 - lr * wd) - lr * delta
        new_p = new_master.astype(p.dtype)
        return new_p, m, v, (None if master is None else new_master)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    flat_master = treedef.flatten_up_to(state.master) \
        if state.master is not None else [None] * len(flat_p)
    flat_d = treedef.flatten_up_to(decay) if decay is not None \
        else [p.ndim >= 2 for p in flat_p]
    out = [upd(p, g, m, v, mw, d)
           for p, g, m, v, mw, d in zip(flat_p, flat_g, flat_m, flat_v,
                                        flat_master, flat_d)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    new_master = treedef.unflatten([o[3] for o in out]) \
        if state.master is not None else None
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, OptState(step, new_m, new_v, new_master), metrics
