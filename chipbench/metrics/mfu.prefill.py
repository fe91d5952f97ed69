"""Prefill's share of the chip's peak, in %.

Model operations of the prompts prefilled in the window (from the shapes:
every layer's products over the prompt, causal attention, the head at the
last position) over the device time of the prefill program
(``jit_pre``) times the bf16 peak.
"""

from chipbench import counts
from chipbench import trace as tr

PREFILL = "jit_pre"


def read(ctx):
    mods = tr.modules_named(ctx.trace, PREFILL)
    lens = ctx.run["prefill_lens"]
    if not mods or len(mods) != len(lens):
        return None
    flops = sum(counts.prefill_flops(ctx.dims, n) for n in lens)
    return 100.0 * flops / (sum(m.dur for m in mods)
                            * ctx.peaks["bf16_flops_per_s"])
