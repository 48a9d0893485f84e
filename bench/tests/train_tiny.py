"""A tiny training cell for CPU tests: the Qwen2 family at a smoke size
(GQA 5:1, q/k/v bias, untied head, seeded weights), cut from a made-up
"published" size as the real cell is, run through the harness's whole
path on a 1x1 mesh, or on a 2x2 mesh of fake CPU devices."""
from __future__ import annotations

import copy
import time

from bench.harness import core

PUBLISHED = {"num_hidden_layers": 3, "vocab_size": 256}
CONFIG = {
    "entry": "model_train", "program_config": "qwen2-bench-tiny",
    "hidden_size": 160, "intermediate_size": 192, "num_attention_heads": 10,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 128,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "published": PUBLISHED,
    "program": {"mesh": {"data": 1, "model": 1}, "microbatches": 1,
                "optimizer": {"lr": 3e-4, "warmup_steps": 100, "b1": 0.9,
                              "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                              "grad_clip": 1.0}},
}
MIX = {"generator": "train_steps", "global_batch": 4, "seq_len": 32,
       "check_entries": 16}
#: From CPU readings of this cell, seeds 1, 2, 3 and 2**33 + 7 on 1x1 and
#: 2x2 meshes: the program's grad_err at most 0.053, update_err at most
#: 9.7e-4 and decay_err at most 0.121; the fp8 control's grad_err at least
#: 0.28; a state left unchanged, or a step that skips the decay of a
#: matrix or decays a bias, reads about 1.
LIMITS = {"grad_err": {"limit": 0.15, "control": "fp8"},
          "update_err": {"limit": 0.05, "control": "unchanged"},
          "decay_err": {"limit": 0.45, "control": "undecayed"}}


def program_config():
    from repro.models.model import ModelConfig
    return ModelConfig(name=CONFIG["program_config"],
                       n_layers=PUBLISHED["num_hidden_layers"], d_model=160,
                       n_heads=10, n_kv_heads=2, head_dim=16, d_ff=192,
                       vocab=PUBLISHED["vocab_size"], qkv_bias=True,
                       rope_theta=1e6, attn_impl="chunked", attn_chunk=8,
                       loss_chunk=32)


def run_cell(seed: int = 5, seconds: float = 0.5, trace: bool = False,
             mesh: tuple = (1, 1)) -> core.Run:
    """One whole run of the tiny cell on whatever JAX finds; the result is
    ``run.result``.  Needs ``program_config`` resolvable by name."""
    config, mix, lim = (copy.deepcopy(x) for x in (CONFIG, MIX, LIMITS))
    config["program"]["mesh"] = {"data": mesh[0], "model": mesh[1]}
    chips = mesh[0] * mesh[1]
    c = {"workload": {"name": "tiny.train", "chips": chips},
         "config": config, "mix": mix, "limits": lim,
         "end_to_end": [], "per_layer": []}
    run = core.Run(c, seed, seconds, trace, core.device_info(
        chips, require_tpu=False))
    run.result = core.measure(run, time.perf_counter())
    return run


def use_program_config(monkeypatch) -> None:
    import repro.configs
    real = repro.configs.get_config
    monkeypatch.setattr(
        repro.configs, "get_config",
        lambda name: program_config() if name == CONFIG["program_config"]
        else real(name))
