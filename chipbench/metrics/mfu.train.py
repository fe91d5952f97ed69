"""Model FLOP/s utilization of the train step, in %.

Forward and backward model operations per step (three times the forward's
products, causal attention counted once per query-key pair, recompute not
counted) over the train program's (``jit_step``) device time per step,
times the chips and the bf16 peak.
"""

from chipbench import counts
from chipbench import trace as tr

STEP = "jit_step"


def read(ctx):
    mods = tr.modules_named(ctx.trace, STEP)
    if not mods:
        return None
    r = ctx.run
    flops = counts.train_model_flops(ctx.dims, r["batch"], r["seq"])
    return 100.0 * flops * len(mods) / (
        sum(m.dur for m in mods) * ctx.chips * ctx.peaks["bf16_flops_per_s"])
