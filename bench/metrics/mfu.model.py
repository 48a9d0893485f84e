"""mfu.model: model FLOPs of the prompt and greedy tokens of every request
completed in the window, over the window (host clock), as a share of the
chip's bf16 peak, in %.  Counts what the model needs (causal attention
over the visible positions, logits where a token is chosen), from the
configuration's shapes (``bench.harness.work``)."""
from bench.harness import work


def read(run):
    rec, cfg = run.record, run.config
    if not rec.batches or rec.window_s <= 0:
        return None
    new = run.mix["new_tokens"]
    rows = run.mix["batch"]
    flops = sum(rows * work.request_flops(cfg, b["prompt"], new)
                for b in rec.batches)
    chips = len(run.device["used"])
    return 100.0 * flops / rec.window_s / (chips * run.peaks["bf16_flops"])
