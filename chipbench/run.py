"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are read from
``BENCHMARK.json`` and from files found by name: ``chipbench/configs/
<config>.json``, ``chipbench/traffic/<traffic>.json``, the driver
``chipbench/drivers/<kind>.py`` the mix names, the limits of the
correctness check ``chipbench/limits/<cell>.json`` and each per-layer
metric's reader ``chipbench/metrics/<metric>.py``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device`` and, traced, ``breakdown``;
``checks`` comes last, each compared number beside its limit, and the same
numbers end stderr.  A host whose JAX finds no TPU, or fewer chips than the
cell asks for, or a device missing from ``chipbench/peaks.json``, exits
non-zero with no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# the script's own directory would shadow the standard library's `trace`
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))


class Refused(SystemExit):
    """A run that must print no result."""

    def __init__(self, msg: str):
        super().__init__(f"chipbench: {msg}")


def load_json(path: Path):
    return json.loads(path.read_text())


def load_module(path: Path, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise Refused(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, key: str) -> list:
    return [m for m in bench[key]
            if cell in m.get("workloads", [cell])]


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def check_devices(chips: int):
    """The devices JAX finds; refuses anything but enough TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found platform "
                      f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX sees "
                      f"{len(devices)}")
    return devices


def enable_compile_cache() -> str:
    import jax
    from repro.launch import compile_cache

    where = compile_cache.enable()
    # every executable of the cell, small ones too, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class Tracer:
    """The profiler around the measured window, off unless asked for."""

    def __init__(self, on: bool):
        self.on, self.dir, self.trace = on, None, None

    def start(self):
        if not self.on:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def reduce(self):
        """Read the written trace once the run is over, and delete it."""
        from chipbench import trace as tr

        try:
            self.trace = tr.load(tr.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace


def cell_context(bench: dict, name: str, args, *, root: Path = ROOT,
                 data: Path = HERE, devices=None, peaks=None,
                 t_start: float = T_START):
    """What a driver needs for one run of cell ``name``, and the driver.

    ``data`` holds the ``traffic/`` and ``limits/`` files; the tests point
    it, and ``root`` under which configuration files are named, at small
    stand-ins and pass CPU ``devices`` and ``peaks``."""
    cell = find_cell(bench, name)
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = load_json(root / conf_entry["file"])
    mix = load_json(data / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(data / "limits" / f"{cell['name']}.json")
    driver = load_module(HERE / "drivers" / f"{mix['kind']}.py",
                         f"chipbench_driver_{mix['kind']}")
    if devices is None:
        devices = check_devices(cell["chips"])
        peaks = peaks_for(devices[0].device_kind)
        enable_compile_cache()

    from chipbench import model

    ctx = types.SimpleNamespace(
        args=args, cell=cell, conf=conf, mix=mix, limits=limits,
        dims=model.Dims.of(conf), devices=devices, peaks=peaks,
        t_start=t_start, tracer=Tracer(bool(args.trace)))
    return ctx, driver


def run_cell(bench: dict, name: str, args, **where) -> dict:
    """One run of cell ``name``; returns the result object.  ``where`` is
    passed to :func:`cell_context`."""
    ctx, driver = cell_context(bench, name, args, **where)
    cell, conf, mix, peaks = ctx.cell, ctx.conf, ctx.mix, ctx.peaks
    dev = ctx.devices[0]
    res = driver.run(ctx)
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": res["memory"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"]}
    if args.trace:
        from chipbench import trace as tr

        t = ctx.tracer.reduce()
        run_ctx = types.SimpleNamespace(
            trace=t, run=res["record"], dims=ctx.dims, conf=conf, mix=mix,
            peaks=peaks, chips=cell["chips"])
        metrics = {}
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 "chipbench_metric_" + m["name"])
            v = reader.read(run_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s(t), window_s=tr.window_s(t))
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = tr.breakdown(t)
    else:
        names = {m["name"]: m for m in cell_metrics(bench, cell["name"],
                                                    "end_to_end")}
        res["metrics"]["setup_s"] = res["setup_s"]
        out["metrics"] = {k: {"value": res["metrics"][k],
                              "unit": names[k]["unit"]}
                          for k in names if k in res["metrics"]}
        out["device"] = device
    out["checks"] = res["checks"]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    out = run_cell(load_json(ROOT / "BENCHMARK.json"), args.workload, args)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
