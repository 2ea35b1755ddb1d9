#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one cell, one traffic mix or
one per-layer metric is a file of its own, found by name:
`configs/<config>.json`, `workloads/<cell>.json`, `traffic/<traffic>.json`,
`layer_metrics/<metric>.json` (which names a module of `readers/`), and the
cell's `driver` names a module of `drivers/`. Adding one of them needs no
edit here.

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, and with `--trace 1` `breakdown`. With
`--trace 0` the metrics are the cell's end-to-end ones, with `--trace 1` its
per-layer ones. Exits non-zero, with no result line, without a TPU that
holds the cell's chips.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from drivers import common  # noqa: E402


@dataclasses.dataclass
class Record:
    """What a per-layer reader is given."""
    ctx: common.Ctx
    run: common.Run
    peaks: dict
    trace: object | None


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_for(bench: dict, group: str, cell: str) -> list[dict]:
    """The entries of `end_to_end` or `per_layer` that this cell reports: a
    metric without a `workloads` key belongs to every cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_layer_metric(name: str, rec: Record):
    """One per-layer metric through its own file and its own reader. A
    reader that finds nothing to read returns None."""
    spec = common.load_json("layer_metrics", f"{name}.json")
    reader = importlib.import_module(f"readers.{spec['reader']}")
    return reader.read(rec, **spec.get("args", {}))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def make_ctx(workload: str, seed: int, seconds: float, trace: bool
             ) -> common.Ctx:
    """Load the cell's files, refuse anything but its TPU chips, turn the
    persistent compile cache on and start counting compiles."""
    cell = common.load_json("workloads", f"{workload}.json")
    config = common.load_json("configs", f"{cell['config']}.json")
    from traffic import gen
    mix = gen.load_mix(cell["traffic"])
    device = common.require_device(cell["chips"])
    from distributedtraining_tpu.utils.platform import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"bench: cell {cell['name']} seed {seed} seconds {seconds} trace "
          f"{int(trace)}; compile cache {cache_dir}", flush=True)
    os.makedirs(common.WORK_DIR, exist_ok=True)
    return common.Ctx(cell=cell, config=config, mix=mix, seed=seed,
                      seconds=seconds, trace=trace, t_process=_T_PROCESS,
                      compiles=common.CompileListener(), device=device)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = load_benchmark()
    ctx = make_ctx(args.workload, args.seed, args.seconds, bool(args.trace))
    cell, compiles, device = ctx.cell, ctx.compiles, ctx.device
    peaks = common.peaks_for(device["kind"])
    driver = importlib.import_module(f"drivers.{cell['driver']}")
    run = driver.run(ctx)

    for chk in run.checks:
        print(chk.line(), flush=True)
    correct = all(c.ok for c in run.checks)
    print(f"bench: setup_s {run.setup_s:.3f} compile_s "
          f"{compiles.seconds:.3f} cache_hits {compiles.hits} "
          f"cache_misses {compiles.misses}", flush=True)

    values: dict = {}
    out_device = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed}
    if not ctx.trace:
        measured = dict(run.end_to_end, setup_s=run.setup_s)
        for m in metrics_for(bench, "end_to_end", cell["name"]):
            val = measured.get(m["name"])
            if val is not None and math.isfinite(val):
                values[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        from readers import xplane
        trace = xplane.load(run.trace_dir) if run.trace_dir else None
        rec = Record(ctx=ctx, run=run, peaks=peaks, trace=trace)
        for m in metrics_for(bench, "per_layer", cell["name"]):
            val = read_layer_metric(m["name"], rec)
            if val is not None and math.isfinite(val):
                values[m["name"]] = {"value": val, "unit": m["unit"]}
        if trace is not None:
            out_device["busy_s"] = xplane.busy_seconds(trace)
            out_device["window_s"] = trace.window_s
            result["breakdown"] = xplane.breakdown(trace)
            shutil.rmtree(run.trace_dir, ignore_errors=True)
    for name, v in values.items():
        print(f"bench: metric {name} = {v['value']!r} {v['unit']}",
              flush=True)
    result["metrics"] = values
    result["device"] = out_device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
