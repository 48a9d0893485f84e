"""mfu.plan: useful FLOPs of 3mm (2 (NI NK NJ + NJ NM NL + NI NJ NL), from
the configuration's shapes) times the requests the optimized path
completed, over the window (host clock), as a share of the chip's bf16
peak, in %.  3mm runs in float32 at ``precision=highest``, several bf16
passes per product, so this reads far below 100% by construction."""
from bench.harness import work


def read(run):
    rec = run.record
    if not rec.optimized or rec.window_s <= 0:
        return None
    flops = work.mm3_flops(run.config) * rec.optimized
    chips = len(run.device["used"])
    return 100.0 * flops / rec.window_s / (chips * run.peaks["bf16_flops"])
