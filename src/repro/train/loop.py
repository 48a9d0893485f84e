"""Fault-tolerant training loop: data prefetch + jitted step + async
checkpointing + restart supervision + straggler monitoring."""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager
from ..data import DataConfig, PrefetchLoader, SyntheticLM
from ..ft import FailurePlan, run_with_restarts
from ..launch.mesh import make_mesh
from ..models import model as M
from .optimizer import AdamWConfig, init_opt_state
from .train_step import make_train_step

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainConfig:
    total_steps: int = 100
    checkpoint_every: int = 25
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    log_every: int = 10
    global_batch: int = 8
    seq_len: int = 128
    seed: int = 0
    max_restarts: int = 5


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any


def train(cfg: M.ModelConfig, tc: TrainConfig,
          opt_cfg: AdamWConfig | None = None, mesh=None,
          failure_plan: FailurePlan | None = None,
          on_metrics: Callable[[int, dict], None] | None = None):
    """Run training; returns (final TrainState, list of (step, loss))."""
    opt_cfg = opt_cfg or AdamWConfig(total_steps=tc.total_steps)
    if mesh is None:
        mesh = make_mesh((1, 1), ("data", "model"))
    key = jax.random.PRNGKey(tc.seed)
    params = M.init_params(cfg, key)
    opt_state = init_opt_state(params)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    step_fn, shardings = make_train_step(
        mesh, cfg, opt_cfg, shapes, tc.global_batch, tc.seq_len)

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=tc.seq_len,
                                  global_batch=tc.global_batch,
                                  seed=tc.seed))
    loader = PrefetchLoader(data)
    ckpt = CheckpointManager(tc.checkpoint_dir, keep=tc.keep_checkpoints)
    history: list[tuple[int, float]] = []

    state = TrainState(params=params, opt_state=opt_state)
    # Abstract template for restore (live arrays get donated/deleted).
    template = jax.tree.map(
        lambda x: np.zeros(x.shape, x.dtype),
        {"params": params, "opt_state": opt_state})

    def one_step(state: TrainState, step: int) -> TrainState:
        toks, labels = loader.next()
        t0 = time.monotonic()
        params, opt_state, metrics = step_fn(
            state.params, state.opt_state,
            jnp.asarray(toks), jnp.asarray(labels))
        loss = float(metrics["loss"])
        if not np.isfinite(loss):
            raise FloatingPointError(f"non-finite loss at step {step}")
        history.append((step, loss))
        if step % tc.log_every == 0 or step + 1 == tc.total_steps:
            log.info("step %d loss %.4f (%.0f ms)", step, loss,
                     1e3 * (time.monotonic() - t0))
        if on_metrics:
            on_metrics(step, {k: float(v) for k, v in metrics.items()})
        return TrainState(params=params, opt_state=opt_state)

    def save(state: TrainState, step: int) -> None:
        ckpt.save(step, {"params": state.params,
                         "opt_state": state.opt_state})

    def restore():
        restored, rstep = ckpt.restore(template)
        if restored is None:
            return None, None
        loader.seek(rstep + 1)
        return TrainState(params=jax.tree.map(jnp.asarray,
                                              restored["params"]),
                          opt_state=jax.tree.map(jnp.asarray,
                                                 restored["opt_state"])), \
            rstep

    final, stats = run_with_restarts(
        total_steps=tc.total_steps, state=state, step_fn=one_step,
        save_fn=save, restore_fn=restore,
        checkpoint_every=tc.checkpoint_every,
        max_restarts=tc.max_restarts, failure_plan=failure_plan)
    ckpt.wait()
    loader.close()
    return final, history, stats
