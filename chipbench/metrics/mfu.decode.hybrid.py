"""The hybrid's whole decode step's share of its roofline, in %.

Per decode step of the window, the least time is the larger of its model
operations over the bf16 peak and its least bytes over the HBM bandwidth:
every weight once at the bf16 width, each active slot's Mamba2 state (fp32
SSM state and conv window of the 36 Mamba2 layers) read and written once,
and the valid key and value rows of the attention layers at the cache's
storage width, with the slots and their lengths from the driver's own
bookkeeping.  Their sum over the device time of the decode program
(``jit__decode``).
"""

from chipbench import counts_hybrid as ch
from chipbench import trace as tr
from chipbench.reference.granite_hybrid import Dims

DECODE = "jit__decode"


def read(ctx):
    if "layer_types" not in ctx.conf:
        return None
    mods = tr.modules_named(ctx.trace, DECODE)
    steps = ctx.run.get("decode_kv", [])
    if not mods or len(mods) != len(steps):
        return None
    d, pk = Dims.of(ctx.conf), ctx.peaks
    least = sum(max(ch.decode_flops(d, kv) / pk["bf16_flops_per_s"],
                    ch.decode_bytes(d, kv, ctx.run["kv_bytes"])
                    / pk["hbm_bytes_per_s"]) for kv in steps)
    return 100.0 * least / sum(m.dur for m in mods)
