"""Record ``data/serve_spans.xplane.pb.gz``, a small traced serving run.

    python3 chipbench/tests/record_serve_spans.py [--out PATH]

One layer at yi-9b widths, 2 slots of 256 rows, an E4M3 cache, weights from
seed 1.  Three requests of 128 tokens, two new tokens each, are submitted at
once, so the third waits in the queue while the first two take the slots.
The window is marked as ``drivers/serve.py`` marks it
(``cb.window``, ``cb.submit``, ``cb.step``), with the profiler options of
``run.Tracer``; the program records its ``serve.*`` spans.  A host without a
TPU is refused.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import model  # noqa: E402
from chipbench import run as R  # noqa: E402
from chipbench import trace as tr  # noqa: E402

SLOTS, MAX_LEN, PROMPT, NEW, REQUESTS = 2, 256, 128, 2, 3


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", default=str(HERE / "data"
                                        / "serve_spans.xplane.pb.gz"))
    args = p.parse_args(argv)

    import jax
    import numpy as np
    from repro.serving import scheduler as S

    R.check_devices(1)
    conf = json.loads((ROOT / "chipbench" / "configs"
                       / "yi9b-serve-8l.json").read_text())
    conf["num_hidden_layers"] = 1
    dims = model.Dims.of(conf)
    weights = model.init_weights(1, dims)
    sched = S.Scheduler(weights, model.program_config(conf),
                        S.SchedulerConfig(
                            n_slots=SLOTS, max_len=MAX_LEN,
                            storage_dtype=conf["serving"]["kv_storage"]))
    rng = np.random.default_rng(1)

    def requests(first):
        return [S.Request(rid=first + i, arrival=sched.clock,
                          prompt=rng.integers(0, dims.vocab, PROMPT,
                                              dtype=np.int32),
                          max_new_tokens=NEW) for i in range(REQUESTS)]

    sched.submit(requests(-REQUESTS))     # warm every shape the window uses
    while sched.step():
        pass
    jax.block_until_ready(sched.cache)

    ann = jax.profiler.TraceAnnotation
    tracer = R.Tracer(True)
    tracer.start()
    with ann("cb.window"):
        with ann("cb.submit"):
            sched.submit(requests(0))
        busy = True
        while busy:
            with ann("cb.step"):
                busy = sched.step()
    tracer.stop()
    try:
        with open(tr.find_xplane(tracer.dir), "rb") as f, \
                gzip.open(args.out, "wb") as g:
            shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    host = next(p for p in tr.read_profile(args.out).planes
                if p.name == tr.HOST_PLANE)
    spans = [e for ln in host.lines for e in ln.events
             if e.name.startswith("serve.")]
    print(json.dumps({"out": args.out, "bytes": Path(args.out).stat().st_size,
                      "spans": len(spans),
                      "queued_ms": [dict(e.stats)["queued_ms"] for e in spans
                                    if e.name == S.SPAN_PREFILL]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
