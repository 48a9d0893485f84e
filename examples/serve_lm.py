"""Batched LM serving driver (deliverable b): prefill + decode engine with
slot-recycled batching, any assigned --arch at a reduced size.

    PYTHONPATH=src python examples/serve_lm.py --arch mixtral-8x7b \
        --batch 4 --new-tokens 32
"""
import argparse
import time

import jax
import numpy as np

from repro.codegen import enable_compile_cache
from repro.configs import get_config, list_archs
from repro.configs.base import smoke
from repro.models import model as M
from repro.serve.engine import Engine, ServeConfig, throughput_stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-dtype", default="bfloat16",
                    choices=["bfloat16", "int8", "float32"])
    args = ap.parse_args()
    enable_compile_cache()

    import dataclasses
    cfg = dataclasses.replace(smoke(get_config(args.arch)),
                              kv_cache_dtype=args.kv_dtype)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params,
                 ServeConfig(max_len=args.prompt_len + args.new_tokens,
                             temperature=args.temperature))

    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab, size=(args.batch, args.prompt_len)).astype(np.int32)
    # warmup (compile)
    eng.generate(prompts, max_new_tokens=2)
    t0 = time.monotonic()
    out = eng.generate(prompts, max_new_tokens=args.new_tokens)
    dt = time.monotonic() - t0
    stats = throughput_stats(args.batch * args.new_tokens, dt)
    print(f"arch={args.arch} kv={args.kv_dtype} "
          f"batch={args.batch} prompt={args.prompt_len} "
          f"new={args.new_tokens}")
    dev = jax.devices()[0]
    print(f"generated {out.shape} in {dt:.2f}s "
          f"-> {stats['tokens_per_s']:.1f} tok/s on {dev.platform} "
          f"({dev.device_kind})")
    print("sample:", out[0, :16].tolist())
    print("serve_lm OK")


if __name__ == "__main__":
    main()
