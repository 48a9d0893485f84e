"""Training on a mesh: a ``Trainer`` that holds the sharded state and runs
one step per call, and ``train``, its fault-tolerant loop (data prefetch,
async checkpointing, restart supervision).

The state is sharded from the first byte: parameters and optimizer state
are made (or placed) by jitted functions with the step's shardings as
their ``out_shardings``, so no device ever holds a whole weight.

Spans (``repro.obs``, category ``train``): ``train/step`` (one step on the
host: input placement, dispatch, and its ``train/sync`` child, the wait
for the step's metrics), ``train/data`` (waiting on the loader) and
``train/checkpoint`` (a save's stall).  Counters (the default registry):
``repro_train_steps_total`` and ``repro_train_tokens_total``.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable

import jax
import numpy as np

from ..checkpoint import CheckpointManager
from ..data import DataConfig, PrefetchLoader, SyntheticLM
from ..ft import FailurePlan, run_with_restarts
from ..launch.mesh import make_mesh
from ..models import model as M
from ..obs import default_registry, tracer
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


class Trainer:
    """One model's training state on one mesh, stepped one batch at a time.

    ``init`` makes the state from a key (``M.init_params``, or ``make``:
    any ``key -> params`` function) and ``load`` places given parameters;
    both leave every array in the step's sharding.  ``step`` runs one
    optimizer step on a ``(tokens, labels)`` batch and returns its metrics
    on the host."""

    def __init__(self, cfg: M.ModelConfig, *, global_batch: int,
                 seq_len: int, mesh=None, opt_cfg: AdamWConfig | None = None,
                 microbatches: int = 1):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else \
            make_mesh((1, 1), ("data", "model"))
        self.opt_cfg = opt_cfg or AdamWConfig()
        self.global_batch, self.seq_len = global_batch, seq_len
        self.tokens_per_step = global_batch * seq_len
        self.shapes = jax.eval_shape(functools.partial(M.init_params, cfg),
                                     jax.random.PRNGKey(0))
        self.step_fn, self.shardings = make_train_step(
            self.mesh, cfg, self.opt_cfg, self.shapes, global_batch, seq_len,
            microbatches)
        self._opt_init = jax.jit(init_opt_state,
                                 out_shardings=self.shardings["opt_state"])
        self.params = self.opt_state = None
        self._compiled = None
        reg = default_registry()
        self._steps = reg.counter("repro_train_steps_total",
                                  "optimizer steps completed")
        self._tokens = reg.counter("repro_train_tokens_total",
                                   "tokens trained")

    # -- state ---------------------------------------------------------------
    def init(self, key: jax.Array,
             make: Callable[[jax.Array], Any] | None = None) -> None:
        make = make or functools.partial(M.init_params, self.cfg)
        self.params = self.opt_state = None      # free the old state first
        self.params = jax.jit(
            make, out_shardings=self.shardings["params"])(key)
        self.opt_state = self._opt_init(self.params)

    def load(self, params: Any, opt_state: Any = None) -> None:
        self.params = jax.device_put(params, self.shardings["params"])
        self.opt_state = self._opt_init(self.params) if opt_state is None \
            else jax.device_put(opt_state, self.shardings["opt_state"])

    @property
    def state(self) -> TrainState:
        return TrainState(params=self.params, opt_state=self.opt_state)

    def template(self) -> tuple[dict, dict]:
        """Shapes and shardings of ``{"params", "opt_state"}``: what a
        restore reads into, with nothing allocated."""
        shapes = {"params": self.shapes,
                  "opt_state": jax.eval_shape(init_opt_state, self.shapes)}
        shardings = {"params": self.shardings["params"],
                     "opt_state": self.shardings["opt_state"]}
        return shapes, shardings

    # -- one step ------------------------------------------------------------
    def compiled(self):
        """The step compiled for its shardings (once, ahead of the first
        call): ``.as_text()`` names its collectives, ``.memory_analysis()``
        its bytes per device."""
        if self._compiled is None:
            shapes, shardings = self.template()
            sds = jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                  sharding=s),
                (shapes["params"], shapes["opt_state"]),
                (shardings["params"], shardings["opt_state"]))
            batch = tuple(
                jax.ShapeDtypeStruct((self.global_batch, self.seq_len),
                                     np.int32, sharding=self.shardings[k])
                for k in ("tokens", "labels"))
            self._compiled = self.step_fn.lower(*sds, *batch).compile()
        return self._compiled

    def step(self, tokens, labels, **span_args) -> dict:
        """One optimizer step; returns its metrics (``loss``,
        ``grad_norm``, ``lr``) as floats, after waiting for them."""
        with tracer().span("step", "train", **span_args):
            tokens = jax.device_put(tokens, self.shardings["tokens"])
            labels = jax.device_put(labels, self.shardings["labels"])
            self.params, self.opt_state, metrics = self.compiled()(
                self.params, self.opt_state, tokens, labels)
            with tracer().span("sync", "train", **span_args):
                metrics = {k: float(v) for k, v in metrics.items()}
        self._steps.inc()
        self._tokens.inc(self.tokens_per_step)
        return metrics


def train(cfg: M.ModelConfig, tc: TrainConfig,
          opt_cfg: AdamWConfig | None = None, mesh=None,
          failure_plan: FailurePlan | None = None,
          on_metrics: Callable[[int, dict], None] | None = None):
    """Run training; returns (final TrainState, list of (step, loss),
    restart stats)."""
    trainer = Trainer(cfg, global_batch=tc.global_batch, seq_len=tc.seq_len,
                      mesh=mesh,
                      opt_cfg=opt_cfg or AdamWConfig(
                          total_steps=tc.total_steps))
    trainer.init(jax.random.PRNGKey(tc.seed))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=tc.seq_len,
                                  global_batch=tc.global_batch,
                                  seed=tc.seed))
    loader = PrefetchLoader(data)
    ckpt = CheckpointManager(tc.checkpoint_dir, keep=tc.keep_checkpoints)
    template, shardings = trainer.template()
    history: list[tuple[int, float]] = []

    def one_step(state: TrainState, step: int) -> TrainState:
        trainer.params, trainer.opt_state = state.params, state.opt_state
        with tracer().span("data", "train", step=step):
            toks, labels = loader.next()
        t0 = time.monotonic()
        metrics = trainer.step(toks, labels, step=step)
        loss = metrics["loss"]
        if not np.isfinite(loss):
            raise FloatingPointError(f"non-finite loss at step {step}")
        history.append((step, loss))
        if step % tc.log_every == 0 or step + 1 == tc.total_steps:
            log.info("step %d loss %.4f (%.0f ms)", step, loss,
                     1e3 * (time.monotonic() - t0))
        if on_metrics:
            on_metrics(step, metrics)
        return trainer.state

    def save(state: TrainState, step: int) -> None:
        with tracer().span("checkpoint", "train", step=step):
            ckpt.save(step, {"params": state.params,
                             "opt_state": state.opt_state})

    def restore():
        restored, rstep = ckpt.restore(template, shardings)
        if restored is None:
            return None, None
        loader.seek(rstep + 1)
        return TrainState(params=restored["params"],
                          opt_state=restored["opt_state"]), rstep

    final, stats = run_with_restarts(
        total_steps=tc.total_steps, state=trainer.state, step_fn=one_step,
        save_fn=save, restore_fn=restore,
        checkpoint_every=tc.checkpoint_every,
        max_restarts=tc.max_restarts, failure_plan=failure_plan)
    ckpt.wait()
    loader.close()
    return final, history, stats
