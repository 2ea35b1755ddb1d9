#!/usr/bin/env python3
"""Look at one trace by hand: run a cell with `--trace 1`'s traced slice and
print what the profiler wrote — planes, their lines, how many events each
holds and the operation names that took most time — then the reduction the
readers would make of it. Writes a small recording of the events (the form
`readers/xplane.from_events` takes) to `chiprun_out/trace_sample_<cell>.json`.

    python3 benchmarks/tools/trace_dump.py --workload <cell> --seed 1 --seconds 10
"""

from __future__ import annotations

import collections
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run_cell
from readers import xplane


def main(argv=None) -> int:
    args = run_cell.parse_args((argv or sys.argv[1:]) + ["--trace", "1"])
    ctx = run_cell.make_ctx(args.workload, args.seed, args.seconds, True)
    cell = ctx.cell
    run = importlib.import_module(f"drivers.{cell['driver']}").run(ctx)
    for chk in run.checks:
        print(chk.line())

    from jax.profiler import ProfileData
    path = xplane.find_xplane(run.trace_dir)
    print(f"trace: {path} ({os.path.getsize(path)} bytes)")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            by = collections.Counter()
            for ev in events:
                by[ev.name] += ev.duration_ns
            top = ", ".join(f"{n}={d / 1e6:.2f}ms"
                            for n, d in by.most_common(12))
            print(f"  line {line.name!r}: {len(events)} events; top: {top}")
    trace = xplane.load(run.trace_dir)
    print(f"window_s {trace.window_s:.4f} busy_s "
          f"{xplane.busy_seconds(trace):.4f}")
    print(json.dumps(xplane.breakdown(trace, top=25), indent=1))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    lo = trace.window[0]
    sample = {"device_ops": {p: [[n, s - lo, d] for n, s, d in evs[:3000]]
                             for p, evs in trace.device_ops.items()},
              "device_modules": {p: [[n, s - lo, d] for n, s, d in evs]
                                 for p, evs in trace.device_modules.items()},
              "host_spans": [[n, s - lo, d] for n, s, d
                             in trace.host_spans[:400]],
              "window": [0, trace.window[1] - lo]}
    with open(os.path.join(out, f"trace_sample_{cell['name']}.json"),
              "w") as f:
        json.dump(sample, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
