"""The matmul kernels' share of their roofline in decode, in %.

Least bytes the decode step's weight products must read (every matmul
weight once, at the bf16 operand width) over the HBM bandwidth, against the
summed device time of the ``redmule_*`` kernel operations inside the decode
program's runs.  Attention's products on the cache are left out of the
bytes and kept in the time where they run as these kernels, so the share
errs low.
"""

from chipbench import counts
from chipbench import trace as tr

DECODE = "jit__decode"


def read(ctx):
    mods = tr.modules_named(ctx.trace, DECODE)
    steps = ctx.run["decode_kv"]
    if not mods or len(mods) != len(steps):
        return None
    k = tr.kernel_s(tr.ops_within(ctx.trace, mods))
    if k <= 0:
        return None
    pk = ctx.peaks
    least = len(steps) * counts.decode_weight_bytes(ctx.dims) \
        / pk["hbm_bytes_per_s"]
    return 100.0 * least / k
