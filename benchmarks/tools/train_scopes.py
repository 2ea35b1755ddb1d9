#!/usr/bin/env python3
"""Where a train step's device time goes, by scope: run a train cell with
`--trace 1`'s traced slice (as `tools/trace_dump.py` does) and print the
whole table of `readers/trace_scope.py` — scope x pass (forward, remat's
re-run, backward), milliseconds a step and % of the step — then the twenty
largest instructions that no scope of the vocabulary covers. PERF.md §5's
train entries are written from this, not from a hand sum over an AOT compile.

    python3 benchmarks/tools/train_scopes.py --workload train-large-t1024 \
        --seed 1 --seconds 40

`--record <n>` also writes the first `n` whole runs' events with their
`op_name` to `chiprun_out/trace_scopes_<cell>.json` (the form
`benchmarks/tests/data/trace_train_scopes.json` was cut from).
"""

from __future__ import annotations

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
from readers import trace_scope, xplane

MODULE_PATTERN = r"^jit_train_step\("


def record(ops, runs, n: int) -> dict:
    """The first `n` whole runs as plain lists, times from the first run's
    start; an event's name cut to its own name and opcode."""
    runs = runs[:n]
    lo, hi = runs[0][0], runs[-1][1]

    def short(name: str) -> str:
        own, _, rest = name.partition(" = ")
        opcode = rest.split("(")[0].split(" ")[-1] if rest else ""
        return f"{own} = {opcode}(...)" if opcode else own

    return {"window": [0, hi - lo],
            "modules": [["jit_train_step(recorded)", a - lo, b - a]
                        for a, b in runs],
            "ops": [[short(name), start - lo, dur, op_name]
                    for name, start, dur, op_name in ops
                    if lo <= start < hi]}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    n_record = 0
    if "--record" in argv:
        i = argv.index("--record")
        n_record = int(argv[i + 1])
        del argv[i:i + 2]
    args = run_cell.parse_args(argv + ["--trace", "1"])
    ctx = run_cell.make_ctx(args.workload, args.seed, args.seconds, True)
    run = importlib.import_module(f"drivers.{ctx.cell['driver']}").run(ctx)
    for chk in run.checks:
        print(chk.line())
    # a cache HIT hands back the executable as it was compiled first, with
    # the metadata (scopes) it had then: the key leaves metadata out
    print(f"setup_s {run.setup_s:.1f} compile cache hits "
          f"{ctx.compiles.hits} misses {ctx.compiles.misses}")
    trace = xplane.load(run.trace_dir)
    rec = run_cell.Record(ctx=ctx, run=run, peaks={}, trace=trace)
    print(f"window_s {trace.window_s:.4f} busy_s "
          f"{xplane.busy_seconds(trace):.4f}")
    print(trace_scope.format_table(
        trace_scope.table_for(rec, module_pattern=MODULE_PATTERN)))
    if n_record:
        ops, modules = trace_scope.load(run.trace_dir)
        runs = trace_scope.whole_runs(modules, MODULE_PATTERN, *trace.window)
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        path = os.path.join(out, f"trace_scopes_{ctx.cell['name']}.json")
        with open(path, "w") as f:
            json.dump(record(ops, runs, n_record), f)
        print(f"recorded {n_record} runs to {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
