"""End-to-end LM training driver (deliverable b): fault-tolerant loop with
checkpointing, deterministic data, any assigned --arch at a reduced depth.

    # ~15M-param model, 300 steps (CPU-feasible):
    PYTHONPATH=src python examples/train_lm.py --steps 300

    # ~100M-param qwen-family model (larger budget):
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

    # smoke (CI): PYTHONPATH=src python examples/train_lm.py --steps 8 \
    #     --preset tiny

The same loop, step function and sharding rules the 512-chip dry-run
lowers — here jitted on the local device mesh.
"""
import argparse
import dataclasses

from repro.codegen import enable_compile_cache
from repro.configs import get_config, list_archs
from repro.configs.base import smoke
from repro.ft import FailurePlan
from repro.train.loop import TrainConfig, train
from repro.train.optimizer import AdamWConfig

PRESETS = {
    # name: (n_layers, d_model, heads, kv, d_ff, vocab) — ~param count
    "tiny": (2, 64, 2, 1, 128, 512),             # ~0.2M
    "15m": (4, 256, 4, 2, 1024, 8192),           # ~15M
    "100m": (8, 640, 10, 5, 2560, 16384),        # ~100M
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list_archs())
    ap.add_argument("--preset", default="15m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-failure-at", type=int, default=None,
                    help="test checkpoint-restart by failing at this step")
    args = ap.parse_args()
    enable_compile_cache()

    L, d, h, kv, ff, v = PRESETS[args.preset]
    base = smoke(get_config(args.arch))
    n_pat = len(base.pattern)
    cfg = dataclasses.replace(
        base, name=f"{args.arch}-{args.preset}",
        n_layers=max(n_pat, (L // n_pat) * n_pat), d_model=d, n_heads=h,
        n_kv_heads=kv, head_dim=d // h, d_ff=ff, vocab=v,
        d_rnn=d if base.d_rnn else 0, loss_chunk=args.batch * args.seq)
    tc = TrainConfig(total_steps=args.steps,
                     checkpoint_every=args.ckpt_every,
                     checkpoint_dir=args.ckpt_dir,
                     global_batch=args.batch, seq_len=args.seq,
                     log_every=10)
    opt = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 10),
                      total_steps=args.steps)
    plan = FailurePlan(at_steps=(args.inject_failure_at,)) \
        if args.inject_failure_at is not None else None

    import logging
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    from repro.models.model import init_params, param_count
    import jax
    n_params = param_count(init_params(cfg, jax.random.PRNGKey(0)))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")

    state, history, stats = train(cfg, tc, opt_cfg=opt,
                                  failure_plan=plan)
    first = history[0][1]
    last = min(l for _, l in history[-10:])
    print(f"loss {first:.4f} -> {last:.4f} over {len(history)} steps "
          f"({stats.restarts} restarts, {stats.replayed_steps} replayed)")
    assert last < first, "training must reduce loss"
    print("train_lm OK")


if __name__ == "__main__":
    main()
