#!/usr/bin/env python3
"""`tools/control.py` for the cells of the driver `miner_steps_lfm2_moe`
(that tool imports `miner_steps` and GPT-2's reference by name).

    python3 benchmarks/tools/lfm2_moe.py control --workload train-lfm2-t8192 \
        --seeds 11,12,13 --seconds 4 \
        [--faults conv_crosses_documents,elsewhere_rows_unmasked,bias_decayed]

Reads what every limit of the cell's `correct` is set from, and puts each
reading through the driver's own checks (`limit_checks`), so that every
row ends in the verdict a run of the cell would give: `correct`, or `NOT
correct` with the numbers that are outside their limits. For each seed: a
short window through the cell's own driver, compared with the float32
reference (the sound reading); the float8 reference in the program's place
(the control); and, with `--faults`, the same run with the program broken
underneath, by a patch from here to a copy of the function and never by a
switch in the program or an edit to the tree. The exit code is 0 when every
sound row is `correct` and every other row is not.

  conv_crosses_documents   the short convolution ignores `segment_ids`: a
                           packed document's first two positions read the
                           previous document's last two
  elsewhere_rows_unmasked  the rows of other chips' experts are masked in
                           the forward alone, as before PR 33: backward,
                           whatever the grouped product left in rows it
                           never wrote reaches `dh` and the router (on the
                           chip that is unwritten memory; `ragged_dot`, the
                           CPU's path, writes zeros there, so only a chip
                           run can show this one)
  bias_decayed             `expert_bias` is no buffer to the optimizer:
                           AdamW holds moments for it and decays it though
                           its gradient is zero (lr x wd x b a step)
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run_cell  # noqa: E402
from drivers import miner_steps_lfm2_moe as driver  # noqa: E402

FAULTS = ("conv_crosses_documents", "elsewhere_rows_unmasked", "bias_decayed")


@contextlib.contextmanager
def fault(name: str | None):
    """The program with one mechanism broken, for the length of a run."""
    import jax.numpy as jnp

    from distributedtraining_tpu.models import lfm2_moe
    from distributedtraining_tpu.ops import moe, ssm
    saved = (ssm.causal_conv1d, moe._held_rows, moe._weigh_held,
             lfm2_moe.Lfm2MoeConfig.is_buffer)
    if name == "conv_crosses_documents":
        ssm.causal_conv1d = lambda u, w, b, live_len, segment_ids=None: \
            saved[0](u, w, b, live_len, None)
    elif name == "elsewhere_rows_unmasked":
        moe._held_rows = lambda x, here, order: x
        moe._weigh_held = lambda y, w, here, order: jnp.where(
            jnp.take(here, order)[:, None],
            y.astype(jnp.float32) * w[:, None], 0.0)
    elif name == "bias_decayed":
        lfm2_moe.Lfm2MoeConfig.is_buffer = lambda self, path: False
    elif name is not None:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    try:
        yield
    finally:
        (ssm.causal_conv1d, moe._held_rows, moe._weigh_held,
         lfm2_moe.Lfm2MoeConfig.is_buffer) = saved


def judge(ctx, first: dict, ref: dict) -> dict:
    """The cell's numbers for one program run (or the control in its
    place) against the float32 reference, each through the driver's check,
    and the verdict."""
    checks = driver.limit_checks(ctx, first, ref)
    return {**{c.name: c.value for c in checks},
            "outside": [c.name for c in checks if not c.ok]}


def control(args) -> int:
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    ctx = run_cell.make_ctx(args.workload, seeds[0], args.seconds, False)
    rows = []
    for seed in seeds:
        ctx.seed = seed
        sound = driver.measure(ctx)
        ref = driver.follow(ctx, sound)
        row = {"seed": seed, "sound": judge(ctx, sound["first"], ref),
               "steps": sound["steps"],
               "control": judge(ctx, driver.follow(ctx, sound,
                                                   args.precision), ref)}
        for name in faults:
            with fault(name):
                row[name] = judge(ctx, driver.measure(ctx)["first"], ref)
        print(f"control: {json.dumps(row)}", flush=True)
        rows.append(row)
    as_wanted = True
    for kind in ["sound", "control"] + faults:
        for r in rows:
            out = r[kind]["outside"]
            as_wanted &= (not out) if kind == "sound" else bool(out)
            print(f"control: seed {r['seed']} {kind}: "
                  + (f"NOT correct: {', '.join(out)}" if out else "correct"),
                  flush=True)
    for number in (k for k in rows[0]["sound"] if k != "outside"):
        line = (f"control: {number}: sound max "
                f"{max(r['sound'][number] for r in rows)!r}")
        for other in ["control"] + faults:
            line += f"; {other} min {min(r[other][number] for r in rows)!r}"
        print(line, flush=True)
    return 0 if as_wanted else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="what", required=True)
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--seconds", type=float, default=4.0)
    c.add_argument("--precision", default="fp8")
    c.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    return {"control": control}[args.what](args)


if __name__ == "__main__":
    raise SystemExit(main())
