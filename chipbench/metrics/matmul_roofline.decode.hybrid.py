"""The matmul kernels' share of their roofline in the hybrid's decode, in %.

Least bytes the decode step's weight products must read (every matmul
weight of the Mamba2, attention and MLP layers and the tied head once, at
the bf16 operand width) over the HBM bandwidth, against the summed device
time of the ``redmule_*`` kernel operations inside the decode program's
runs.  Attention's products on the cache are left out of the bytes and kept
in the time where they run as these kernels, so the share errs low.
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
    k = tr.kernel_s(tr.ops_within(ctx.trace, mods))
    if k <= 0:
        return None
    least = len(steps) * 2.0 * ch.matmul_params(Dims.of(ctx.conf)) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / k
