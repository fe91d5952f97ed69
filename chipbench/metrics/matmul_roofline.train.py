"""The matmul kernels' share of the bf16 peak in the train step, in %.

The weight products the step runs (each layer's forward twice under full
remat, the backward's two products per forward one, the head's three) over
the summed device time of the ``redmule_*`` kernel operations inside the
train program's runs, times the bf16 peak.  Attention's products are left
out of the operations and kept in the time where they run as these
kernels, so the share errs low.
"""

from chipbench import counts
from chipbench import trace as tr

STEP = "jit_step"


def read(ctx):
    mods = tr.modules_named(ctx.trace, STEP)
    if not mods:
        return None
    k = tr.kernel_s(tr.ops_within(ctx.trace, mods))
    if k <= 0:
        return None
    r = ctx.run
    flops = counts.train_matmul_flops(ctx.dims, r["batch"], r["seq"],
                                      r["remat"])
    return 100.0 * flops * len(mods) / (k * ctx.peaks["bf16_flops_per_s"])
