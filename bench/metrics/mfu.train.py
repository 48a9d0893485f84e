"""mfu.train: model FLOPs of the tokens trained in the window, over the
window (host clock), as a share of the chips' bf16 peak, in %.  The FLOPs
per token are ``bench.harness.train_work.token_flops`` (no recompute);
the tokens are the program's ``repro_train_tokens_total`` over the window,
read only when they agree with the steps the record completed."""
from bench.harness import train_work
from bench.harness.core import log


def read(run):
    rec = run.record
    tokens = run.counters.get("repro_train_tokens_total")
    if not tokens or rec.window_s <= 0:
        return None
    if tokens != rec.new_tokens:
        log(f"mfu.train: the program counted {tokens} tokens, the record "
            f"{rec.new_tokens}; not read")
        return None
    flops = tokens * train_work.token_flops(run.config, run.mix["seq_len"])
    chips = len(run.device["used"])
    return 100.0 * flops / rec.window_s / (chips * run.peaks["bf16_flops"])
