#!/usr/bin/env python3
"""Read the two numbers every limit of `correct` is set from, on the chip,
at the cell's own size: the largest that sound runs of the program give, and
the smallest that the control gives. The control is the plain reference put
in the program's place and computed in the precision below the one the
configuration states (`--precision fp8` for bfloat16 compute).

    python3 benchmarks/tools/control.py --workload <cell> --seeds 11,12,13 \
        [--control-seeds 11,12,13] [--seconds 8] [--precision fp8]

Train cells need no measured window. Serve cells run a short one at the
cell's own load, long enough to finish the mix's longest requests. The
benchmark's own runs never run this; `tests/test_control.py` keeps it at a
size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run_cell
from drivers import common


def train_readings(ctx: common.Ctx, seeds, control_seeds, precision) -> list:
    from drivers import miner_steps
    from reference import gpt2 as reference
    out = []
    prog = miner_steps.Program(ctx)
    hp = dict(lr=prog.cfg.learning_rate, weight_decay=prog.cfg.weight_decay)
    mcfg = ctx.model_cfg()
    for seed in seeds:
        first = prog.first_steps(seed)
        prog.free()
        common.free_device_memory()
        ref = reference.train_reference(mcfg, seed, first["batches"], **hp)
        row = {"seed": seed, "sound": miner_steps.compare(first, ref)}
        if seed in control_seeds:
            # the control in the program's place, against the reference
            row["control"] = miner_steps.compare(
                reference.train_reference(mcfg, seed, first["batches"],
                                          precision=precision, **hp), ref)
            # the fault the first loss is there to catch: the second half
            # of the batch left out of the loss
            b = first["batches"][0]
            rows = np.arange(len(b["loss_mask"]))[:, None]
            half = dict(b, loss_mask=b["loss_mask"] * (rows < len(rows) // 2))
            half_loss = reference.train_reference(mcfg, seed, [half],
                                                  **hp)["losses"][0]
            row["half_batch"] = {"first_loss_gap": abs(
                half_loss - ref["losses"][0])}
        print(f"control: {json.dumps(row)}", flush=True)
        out.append(row)
    return out


def serve_readings(ctx: common.Ctx, seeds, control_seeds, precision) -> list:
    from drivers import open_loop
    from traffic import gen
    out = []
    spans = common.Spans()
    for seed in seeds:
        ctx.seed = seed
        engine = open_loop.build_and_warm(ctx, warm=False)
        schedule = gen.open_loop_requests(ctx.mix, seed, ctx.seconds,
                                          ctx.config["vocab_size"])
        w = open_loop.serve_window(ctx, engine, schedule, spans,
                                   common.TraceSlice(ctx, spans))
        sample = open_loop._sample_finished(
            [tr for tr in w["finished"] if tr.req.status == "done"], seed,
            ctx.cell["check"]["sample_requests"])
        engine.close()
        del engine, w
        common.free_device_memory()
        score = open_loop.score_served(
            ctx.model_cfg(), seed, sample, ctx.cell["engine"]["max_seq_len"],
            precision if seed in control_seeds else "float32")
        row = {"seed": seed, "tokens": score["tokens"],
               "sound": {"served_logit_gap": score["served_gap"],
                         "served_mean_gap": score["served_mean_gap"]}}
        if seed in control_seeds:
            row["control"] = {"served_logit_gap": score["control_gap"],
                              "served_mean_gap": score["control_mean_gap"]}
        print(f"control: {json.dumps(row)}", flush=True)
        out.append(row)
    return out


def summarize(rows: list) -> None:
    names = sorted({k for r in rows for k, v in r["sound"].items()
                    if isinstance(v, float)})
    for n in names:
        sound = [r["sound"][n] for r in rows]
        ctl = [r["control"][n] for r in rows if "control" in r]
        line = (f"control: {n}: sound max {max(sound)!r} over {len(sound)} "
                f"seeds")
        if ctl:
            line += (f"; control min {min(ctl)!r} over {len(ctl)} seeds; "
                     f"ratio {min(ctl) / max(max(sound), 1e-30):.2f}")
        print(line, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default=None)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--precision", default="fp8")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = (seeds if args.control_seeds is None else
                     [int(s) for s in args.control_seeds.split(",") if s])
    ctx = run_cell.make_ctx(args.workload, seeds[0], args.seconds, False)
    cell = ctx.cell
    read = train_readings if cell["driver"] == "miner_steps" \
        else serve_readings
    summarize(read(ctx, seeds, control_seeds, args.precision))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
