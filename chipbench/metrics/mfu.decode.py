"""The whole decode step's share of its roofline, in %.

Per decode step of the window, the least time is the larger of its model
operations over the bf16 peak and its least bytes over the HBM bandwidth:
every matmul weight once at the bf16 operand width, and the valid key and
value rows at the cache's storage width, with each slot's length from the
driver's own bookkeeping.  Their sum over the device time of the decode
program (``jit__decode``).
"""

from chipbench import counts
from chipbench import trace as tr

DECODE = "jit__decode"


def read(ctx):
    mods = tr.modules_named(ctx.trace, DECODE)
    steps = ctx.run["decode_kv"]
    if not mods or len(mods) != len(steps):
        return None
    pk = ctx.peaks
    least = sum(max(counts.decode_flops(ctx.dims, kv) / pk["bf16_flops_per_s"],
                    counts.decode_bytes(ctx.dims, kv, ctx.run["kv_bytes"])
                    / pk["hbm_bytes_per_s"]) for kv in steps)
    return 100.0 * least / sum(m.dur for m in mods)
