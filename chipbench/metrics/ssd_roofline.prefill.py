"""The linear-attention kernel's share of its roofline in prefill, in %.

The Mamba2 layers' SSD runs in the Engine's chunked linear-attention
kernel (``redmule_chunked_linear_attention``), one call per layer per
prefill.  Its least time for the prompts prefilled in the window is the
larger of the operations it runs (every chunk whole, the last one padded)
over the bf16 peak and its least bytes (fp32 C, B, dt x, the decay and the
output per head, the final state once) over the HBM bandwidth, against
the summed device time of the kernel's operations inside the prefill
program's runs.
"""

from chipbench import counts_hybrid as ch
from chipbench import trace as tr
from chipbench.reference.granite_hybrid import Dims

PREFILL = "jit_pre"
KERNEL = "redmule_chunked_linear_attention"


def read(ctx):
    if "layer_types" not in ctx.conf:
        return None
    mods = tr.modules_named(ctx.trace, PREFILL)
    lens = ctx.run.get("prefill_lens", [])
    if not mods or len(mods) != len(lens):
        return None
    k = sum(o.dur for o in tr.ops_within(ctx.trace, mods)
            if tr.op_kind(o.name) == KERNEL)
    if k <= 0:
        return None
    d, chunk, pk = Dims.of(ctx.conf), ctx.conf["mamba_chunk_size"], ctx.peaks
    least = sum(max(ch.ssd_kernel_flops(d, n, chunk) / pk["bf16_flops_per_s"],
                    ch.ssd_kernel_bytes(d, n, chunk) / pk["hbm_bytes_per_s"])
                for n in lens)
    return 100.0 * least / k
