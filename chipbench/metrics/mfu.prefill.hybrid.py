"""The hybrid's prefill share of the chip's peak, in %.

Model operations of the prompts prefilled in the window (from the shapes:
every layer's products over the prompt, causal attention on the attention
layers, the Mamba2 layers' SSD in its chunked form at the configuration's
chunk, the head at the last position) over the device time of the prefill
program (``jit_pre``) times the bf16 peak.
"""

from chipbench import counts_hybrid as ch
from chipbench import trace as tr
from chipbench.reference.granite_hybrid import Dims

PREFILL = "jit_pre"


def read(ctx):
    if "layer_types" not in ctx.conf:
        return None
    mods = tr.modules_named(ctx.trace, PREFILL)
    lens = ctx.run.get("prefill_lens", [])
    if not mods or len(mods) != len(lens):
        return None
    d, chunk = Dims.of(ctx.conf), ctx.conf["mamba_chunk_size"]
    flops = sum(ch.prefill_flops(d, n, chunk) for n in lens)
    return 100.0 * flops / (sum(m.dur for m in mods)
                            * ctx.peaks["bf16_flops_per_s"])
