"""contraction_roofline: the least time the chip could take for the
window's 3mm contractions (for each product, the larger of its FLOPs over
the bf16 peak and its unpadded operand and result bytes over the HBM
peak; ``bench.harness.work``) over the summed device time of the
contraction kernel's events in the profiler trace, in %.  3mm runs in
float32 at ``precision=highest``, several bf16 passes per product, so the
share of the bf16 peak reads low by construction."""
import re

from bench.harness import work
from bench.harness.core import log

#: The contraction kernel's Mosaic custom call: compiled for a v5e, the
#: instruction is named ``body.<n>`` after the Pallas body, and the
#: trace's operation events carry the instruction's name.
KERNEL = re.compile(r"^(body|kernel)(\.\d+)?$")


def read(run):
    t, rec = run.trace, run.record
    if t is None or not rec.completed:
        return None
    n, secs = t.op_seconds(lambda mod, op: KERNEL.match(op) is not None)
    if not n or secs <= 0:
        return None
    least, bounds = 0.0, []
    for c in work.mm3_contractions(run.config):
        s, bound = work.roofline_seconds(c["flops"], c["bytes"], run.peaks)
        least += s
        bounds.append(f"{c['name']} {bound}-bound")
    log(f"contraction_roofline: {n} kernel events, {secs:.6f}s for "
        f"{rec.completed} requests; {', '.join(bounds)}")
    return 100.0 * least * rec.completed / secs
