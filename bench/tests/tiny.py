"""Tiny cells for CPU tests: the harness's whole run path, without the
chip, at sizes a test run holds."""
from __future__ import annotations

import copy
import time

from bench.harness import core

MODEL = {
    "entry": "model_engine", "program_config": "qwen3-bench-tiny",
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
}
MODEL_MIX = {"generator": "offline_batches", "batch": 4,
             "prompt_lens": [8, 16], "new_tokens": 16, "max_len": 32,
             "check_requests": 6}

MM3 = {"entry": "plan_engine", "kind": "polybench", "kernel": "3mm",
       "extents": ["NI", "NJ", "NK", "NL", "NM"],
       "NI": 16, "NJ": 24, "NK": 32, "NL": 40, "NM": 48,
       "solver": {"mode": "prometheus", "workers": 1, "time_budget_s": 2.0}}
MM3_MIX = {"generator": "closed_loop", "clients": 1, "input_sets": 2,
           "check_requests": 4}

FFN = {"entry": "plan_engine", "kind": "swiglu_ffn", "hidden_size": 128,
       "intermediate_size": 256, "tokens_per_request": 16,
       "solver": {"mode": "prometheus", "workers": 1, "time_budget_s": 0.5},
       "batching": {"max_batch": 4}}
FFN_MIX = {"generator": "open_poisson", "rate_per_s": 200,
           "input_pool": 8, "check_requests": 8}

#: Limits set from tiny readings on the CPU, between the program's and
#: the control's: program seeds 1-6 read at most 0.0042 (model), seeds
#: 1-3 at most 0 (3mm) and 0.0037 (FFN); the control at least 0.040,
#: 8.1e-6 and 0.074.
CELLS = {
    "model": (MODEL, MODEL_MIX,
              {"logit_gap": {"limit": 0.015, "control": "fp8"}}),
    "mm3": (MM3, MM3_MIX, {"rel_err": {"limit": 2e-6, "control": "high"}}),
    "ffn": (FFN, FFN_MIX, {"rel_err": {"limit": 2e-2, "control": "fp8"}}),
}


def tiny_model_config():
    from repro.models.model import ModelConfig
    return ModelConfig(name=MODEL["program_config"], n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
                       vocab=256, qk_norm=True, rope_theta=1e6)


def run_cell(kind: str, seed: int = 5, seconds: float = 0.5,
             trace: bool = False) -> core.Run:
    """One whole run of a tiny cell on whatever JAX finds; the result is
    ``run.result``.  Needs the ``tiny`` fixture's environment."""
    config, mix, lim = (copy.deepcopy(x) for x in CELLS[kind])
    c = {"workload": {"name": f"tiny.{kind}", "chips": 1},
         "config": config, "mix": mix, "limits": lim,
         "end_to_end": [], "per_layer": []}
    run = core.Run(c, seed, seconds, trace, core.device_info(
        1, require_tpu=False))
    run.result = core.measure(run, time.perf_counter())
    return run
