"""Mean device-idle time between consecutive decode steps, in ms.

From the trace: for each pair of consecutive runs of the scheduler's
decode program (``jit__decode``) in the window, the time between them in
which no program ran on the device; pairs between which the driver waited
for an arrival (``cb.wait``) are left out.  It holds the host's work per
step: the logits' copy to the host, the argmax and the bookkeeping.
"""

from chipbench import trace as tr

DECODE = "jit__decode"


def read(ctx):
    t = ctx.trace
    mods = tr.modules_named(t, DECODE)
    waits = [s for s in t.host if s.name == "cb.wait"]
    allm = t.devices[0].modules
    gaps = []
    for a, b in zip(mods, mods[1:]):
        if any(w.start < b.start and w.end > a.end for w in waits):
            continue
        gaps.append((b.start - a.end) - tr.covered(allm, a.end, b.start))
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
