#!/usr/bin/env python3
"""Find a serve cell's knee once: one engine, warmed as the cell warms it,
offered the cell's mix at each of a few fixed rates for `--seconds` each
(with a full drain between rates). The knee is the highest rate at which
the backlog does not grow: nothing waits in the engine's queue when the
window closes and no more requests are in flight at its close than at its
half. The cell's traffic file then fixes `rate_rps` as a number.

    python3 benchmarks/tools/sweep.py --workload serve-large-chat \
        --rates 6,9,12,15,18,21 --seconds 20 --seed 1
"""

from __future__ import annotations

import argparse
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run_cell
from drivers import common, open_loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    ctx = run_cell.make_ctx(args.workload, args.seed, args.seconds, False)
    ctx.cell["drain_s"] = 120.0   # every rate starts from an empty engine
    mix, config = ctx.mix, ctx.config
    from traffic import gen
    spans = common.Spans()
    engine = open_loop.build_and_warm(ctx)
    print(f"sweep: set-up {time.perf_counter() - ctx.t_process:.1f}s",
          flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        ctx.mix = dict(mix, rate_rps=rate)
        # another seed per rate: other tokens on the mix's one schedule.
        # The engine's prefix cache outlives a window, so a prompt can
        # share its first token with an earlier rate's and take the
        # prefix-hit path (`compiles=1` on that line: read it with care)
        ctx.seed = args.seed + 1000 * (i + 1)
        schedule = gen.open_loop_requests(ctx.mix, ctx.seed, args.seconds,
                                          config["vocab_size"])
        ctx.compiles.mark()
        w = open_loop.serve_window(ctx, engine, schedule, spans,
                                   common.TraceSlice(ctx, spans))
        print(f"sweep: rate {rate} req/s compiles={ctx.compiles.since_mark()}"
              f" {open_loop.window_line(w)}", flush=True)
    engine.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
