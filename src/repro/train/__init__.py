from .optimizer import AdamWConfig, OptState, adamw_update, init_opt_state
from .train_step import make_train_step, train_step, loss_fn
from .loop import TrainConfig, Trainer, TrainState, train

__all__ = ["AdamWConfig", "OptState", "adamw_update", "init_opt_state",
           "make_train_step", "train_step", "loss_fn",
           "TrainConfig", "Trainer", "TrainState", "train"]
