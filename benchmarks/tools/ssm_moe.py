#!/usr/bin/env python3
"""`tools/sweep.py` and `tools/control.py` for the cells of the driver
`open_loop_ssm_moe` (those tools import `open_loop` and GPT-2's reference
by name).

    python3 benchmarks/tools/ssm_moe.py sweep --workload serve-nemotron-chat \
        --rates 10,15,20,25,30 --seconds 20 --seed 1
    python3 benchmarks/tools/ssm_moe.py control --workload \
        serve-nemotron-chat --seeds 11,12,13 --seconds 10 \
        [--faults state_not_reset,pad_advances_state]

`sweep` finds the knee as `tools/sweep.py` does: one engine, warmed as the
cell warms it, the cell's mix at each rate with a full drain between.
`control` reads what every limit of `correct` is set from: for each seed a
short window at the cell's load, scored by the reference (the sound
reading) and by the float8 reference in the program's place (the control);
with `--faults`, the same window with the program broken underneath, by a
patch from here and never by a switch in the program:
`state_not_reset` makes a prefill ADD its state to what the slot's last
request left there instead of writing over it; `pad_advances_state` lets a
prefill bucket's padding rows feed the recurrence and the convolution's
tail (the live length is taken for the bucket's)."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run_cell  # noqa: E402
from drivers import common, open_loop, open_loop_ssm_moe as driver  # noqa: E402

FLOORS = (0.0, 0.0001, 0.0002, 0.0005, 0.001, 0.002)
FAULTS = ("state_not_reset", "pad_advances_state")


def sweep(args) -> int:
    from traffic import gen
    ctx = run_cell.make_ctx(args.workload, args.seed, args.seconds, False)
    ctx.cell["drain_s"] = 180.0   # every rate starts from an empty engine
    mix = ctx.mix
    spans = common.Spans()
    engine = driver.build_and_warm(ctx)
    print(f"sweep: set-up {time.perf_counter() - ctx.t_process:.1f}s",
          flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        ctx.mix = dict(mix, rate_rps=rate)
        ctx.seed = args.seed + 1000 * (i + 1)
        schedule = gen.open_loop_requests(ctx.mix, ctx.seed, args.seconds,
                                          ctx.config["vocab_size"])
        ctx.compiles.mark()
        w = driver.serve_window(ctx, engine, schedule, spans,
                                common.TraceSlice(ctx, spans))
        print(f"sweep: rate {rate} req/s compiles={ctx.compiles.since_mark()}"
              f" {driver.window_line(w)}", flush=True)
    engine.close()
    return 0


@contextlib.contextmanager
def fault(name: str | None):
    """The program with one mechanism broken, for the length of a window."""
    from distributedtraining_tpu.engine import kv_pool
    from distributedtraining_tpu.ops import ssm
    saved = (kv_pool.write_slot_state, ssm.ssd_prefill, ssm.causal_conv1d)
    if name == "state_not_reset":
        def keep(states, tails, inter, layers, slot):
            new_states, new_tails = kv_pool.sown_state(inter, layers)
            return (tuple(p.at[slot].add(x[0])
                          for p, x in zip(states, new_states)),
                    tuple(p.at[slot].set(x[0].astype(p.dtype))
                          for p, x in zip(tails, new_tails)))
        kv_pool.write_slot_state = keep
    elif name == "pad_advances_state":
        import jax.numpy as jnp

        def whole(live_len, T):
            return jnp.full_like(live_len, T)

        ssm.ssd_prefill = lambda x, dt, A, B, C, D, live_len, *a, **k: \
            saved[1](x, dt, A, B, C, D, whole(live_len, x.shape[1]), *a, **k)
        ssm.causal_conv1d = lambda u, w, b, live_len: \
            saved[2](u, w, b, whole(live_len, u.shape[1]))
    elif name is not None:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    try:
        yield
    finally:
        kv_pool.write_slot_state, ssm.ssd_prefill, ssm.causal_conv1d = saved


def _window_sample(ctx, fault_name: str | None) -> list:
    """One short window at the cell's load; the sampled finished
    requests."""
    from traffic import gen
    with fault(fault_name):
        # warmed as the cell warms it: a window that spends its seconds
        # compiling offers a request or two, each to a slot no request
        # has used, and the warm-up's requests leave their state in every
        # slot, as they do in the cell's own runs
        engine = driver.build_and_warm(ctx)
        schedule = gen.open_loop_requests(ctx.mix, ctx.seed, ctx.seconds,
                                          ctx.config["vocab_size"])
        spans = common.Spans()
        w = driver.serve_window(ctx, engine, schedule, spans,
                                common.TraceSlice(ctx, spans))
    sample = open_loop._sample_finished(
        [tr for tr in w["finished"] if tr.req.status == "done"], ctx.seed,
        ctx.cell["check"]["sample_requests"])
    engine.close()
    del engine, w
    common.free_device_memory()
    return sample


def control(args) -> int:
    from reference import nemotron_h as reference
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    ctx = run_cell.make_ctx(args.workload, seeds[0], args.seconds, False)
    mcfg = reference.model_cfg(ctx.config)
    floor = ctx.cell["check"]["margin_floor"]
    keys = ("served_gap", "served_gap_all", "served_mean_gap",
            "near_tie_share", "tokens")
    rows = []
    for seed in seeds:
        ctx.seed = seed
        score = driver.score_served(mcfg, seed, _window_sample(ctx, None),
                                    floor, args.precision)
        gaps, margins = score["arrays"]
        for f in FLOORS:         # what another margin floor would read
            clear = margins >= f
            print(f"control: seed {seed} floor {f}: near-tie share "
                  f"{1 - clear.mean():.4f} widest clear gap "
                  f"{gaps[clear].max() if clear.any() else 0.0:.4f} mean gap "
                  f"of the near ties "
                  f"{gaps[~clear].mean() if (~clear).any() else 0.0:.4f} of "
                  f"the clear {gaps[clear].mean() if clear.any() else 0:.5f}",
                  flush=True)
        row = {"seed": seed, "sound": {k: score[k] for k in keys}}
        if "control_gap" in score:
            row["control"] = {"served_gap": score["control_gap"],
                              "served_mean_gap": score["control_mean_gap"]}
        for name in faults:
            got = driver.score_served(mcfg, seed, _window_sample(ctx, name),
                                      floor)
            row[name] = {k: got[k] for k in keys}
        print(f"control: {json.dumps(row)}", flush=True)
        rows.append(row)
    for name in ("served_gap", "served_mean_gap", "near_tie_share"):
        line = (f"control: {name}: sound max "
                f"{max(r['sound'][name] for r in rows)!r}")
        for other in ["control"] + faults:
            vals = [r[other][name] for r in rows if name in r.get(other, {})]
            if vals:
                line += f"; {other} min {min(vals)!r}"
        print(line, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="what", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, default=20.0)
    s.add_argument("--seed", type=int, default=1)
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--seconds", type=float, default=10.0)
    c.add_argument("--precision", default="fp8")
    c.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    return {"sweep": sweep, "control": control}[args.what](args)


if __name__ == "__main__":
    raise SystemExit(main())
