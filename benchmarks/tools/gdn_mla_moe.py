#!/usr/bin/env python3
"""`tools/sweep.py` and `tools/control.py` for the cells of the driver
`open_loop_gdn_mla_moe` (those tools import `open_loop` and GPT-2's
reference by name), and the compile for the chip that needs none.

    python3 benchmarks/tools/gdn_mla_moe.py aot --workload \
        serve-gigachat-reason
    python3 benchmarks/tools/gdn_mla_moe.py sweep --workload \
        serve-gigachat-reason --rates 3.5,4,4.5,5 --seconds 60 --seed 1
    python3 benchmarks/tools/gdn_mla_moe.py control --workload \
        serve-gigachat-reason --seeds 11,12 --seconds 10 \
        [--precisions bfloat16,fp8] [--faults no_decay,stale_state,no_gate]

`aot` compiles the cell's largest prefill and decode programs for a
DESCRIBED v5e (`JAX_PLATFORMS=cpu`; nothing runs) and prints their Mosaic
calls and their memory. `sweep` finds the knee as `tools/sweep.py` does:
one engine, warmed as the cell warms it, the cell's mix at each rate in
rising order with a full drain between, and no rate past the first that
closes with requests queued. A window opens on an empty engine and the
mix's longest answer takes about 30 s, so only a window of twice that
shows whether a rate is SUSTAINED: in flight at the close as at half.
`control` reads what every limit of `correct` is set from: for each seed
a short window at the cell's load, scored by the reference (the sound
reading) and, for each of `--precisions`, by the lower-precision reference
in the program's place (`bfloat16`: the state alone a step below what the
configuration states, the products as the program makes them; `fp8`:
float8 products too); with `--faults`, the same window with the program
broken underneath, by a patch from here and never
by a switch in the program: `no_decay` leaves `exp(g)` out of the delta
rule, prefill and decode (the state never forgets); `stale_state` makes a
prefill leave a re-used slot's state as the last request left it (the
convolution's tail is still written); `no_gate` leaves both mixers' output
gates out (`2 sigmoid(z)` and `sigmoid(u W_g)` read 1). Every reading is
put to the cell's own limits, and the line says by which it comes out
`correct: false`."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FLOORS = (0.0, 0.0002, 0.0005, 0.001, 0.002, 0.005)
FAULTS = ("no_decay", "stale_state", "no_gate")


def aot(args) -> int:
    """The cell's largest programs through the TPU's own compiler against
    a described v5e: Mosaic's verdict on the kernels at the published
    widths, and the device memory each program needs with weights, page
    pool and state pool."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from distributedtraining_tpu.engine import kv_pool, serve, serve_weights
    from distributedtraining_tpu.ops import delta_rule, mla_attention, moe
    from drivers import common, open_loop_gdn_mla_moe as driver

    jax.config.update("jax_enable_compilation_cache", False)
    for module in (delta_rule, mla_attention, moe):
        module._on_tpu = lambda: True
    one = SingleDeviceSharding(
        topologies.get_topology_desc("v5e:2x2", "tpu").devices[0])
    cell = common.load_json("workloads", f"{args.workload}.json")
    model, cfg = driver.make_model(
        common.load_json("configs", f"{cell['config']}.json"))
    e, w = cell["engine"], cell["warmup"]
    slots, P = e["max_slots"], e["page_size"]

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    base = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0))))
    tree = serve_weights.abstract(cfg, base)
    eng = serve.GenerationEngine(model, None, max_slots=slots, page_size=P,
                                 max_seq_len=e["max_seq_len"])
    eng._layers, eng._donate = serve._layer_keys(base), True
    caches = kv_pool.layer_caches(cfg, len(eng._layers))
    halves = tuple(
        tuple(sds((eng.pool_pages, P, width), cfg.compute_dtype())
              for _ in range(caches.count("kv")))
        for width in kv_pool.row_widths(cfg))
    n_state = caches.count("ssm")
    state = (tuple(sds((slots + 1, *cfg.ssm_state_shape), jnp.float32)
                   for _ in range(n_state)),
             tuple(sds((slots + 1, *cfg.ssm_tail_shape), cfg.compute_dtype())
                   for _ in range(n_state)))
    pages = max(w["decode_pages"] + w.get("decode_pages_grown", [])
                + w.get("decode_pages_far", []))
    tokens = max(w["prefill_tokens"])
    programs = {
        f"decode {slots} slots x {pages} pages":
            eng._decode_prog(slots, pages).__wrapped__.trace(
                tree, *halves, sds((slots, pages)), sds((slots,)),
                sds((slots,)), *state, sds((slots,))),
        f"prefill {tokens} tokens":
            eng._prefill_prog(tokens).__wrapped__.trace(
                tree, sds((1, tokens)), sds(()), *halves,
                sds((tokens // P,)), *state, sds(())),
    }
    for name, traced in programs.items():
        t0 = time.perf_counter()
        compiled = traced.lower(lowering_platforms=("tpu",)).compile()
        own = [ln.split(" = ")[0].strip()
               for ln in compiled.as_text().splitlines()
               if common.MOSAIC_CALL in ln]
        calls = {k: sum(bool(re.fullmatch(rf"%?{k}(\.\d+)?", n))
                        for n in own) for k in driver.KERNELS}
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"aot: {name}: compiled in {time.perf_counter() - t0:.1f}s; "
              f"Mosaic calls {json.dumps(calls)}; arguments "
              f"{m.argument_size_in_bytes} temporaries "
              f"{m.temp_size_in_bytes} in all {total} bytes = "
              f"{total / 2**30:.2f} GiB", flush=True)
    eng.close()
    return 0


def sweep(args) -> int:
    import run_cell
    from drivers import common, open_loop_gdn_mla_moe as driver
    from traffic import gen
    ctx = run_cell.make_ctx(args.workload, args.seed, args.seconds, False)
    ctx.cell["drain_s"] = 240.0   # every rate starts from an empty engine
    mix = ctx.mix
    spans = common.Spans()
    engine = driver.build_and_warm(ctx)
    print(f"sweep: set-up {time.perf_counter() - ctx.t_process:.1f}s",
          flush=True)
    for i, rate in enumerate(sorted(float(r)
                                    for r in args.rates.split(","))):
        ctx.mix = dict(mix, rate_rps=rate)
        ctx.seed = args.seed + 1000 * (i + 1)
        schedule = gen.open_loop_requests(ctx.mix, ctx.seed, args.seconds,
                                          ctx.config["vocab_size"])
        ctx.compiles.mark()
        w = driver.serve_window(ctx, engine, schedule, spans,
                                common.TraceSlice(ctx, spans))
        print(f"sweep: rate {rate} req/s compiles={ctx.compiles.since_mark()}"
              f" {driver.window_line(w)}", flush=True)
        if w["queued_at_close"]:
            break       # past the knee: a higher rate only queues more
    engine.close()
    return 0


@contextlib.contextmanager
def fault(name: str | None):
    """The program with one mechanism broken, for the length of a window."""
    import jax.numpy as jnp

    from distributedtraining_tpu.engine import kv_pool
    from distributedtraining_tpu.models import gigachat3_5
    from distributedtraining_tpu.ops import delta_rule
    saved = (delta_rule.delta_rule_prefill, delta_rule.gdn_decode_update,
             kv_pool.write_slot_state, gigachat3_5.output_gate)
    if name == "no_decay":
        delta_rule.delta_rule_prefill = \
            lambda q, k, v, g, *a, **kw: saved[0](
                q, k, v, jnp.zeros_like(g), *a, **kw)
        delta_rule.gdn_decode_update = \
            lambda state, slots, q, k, v, g, *a, **kw: saved[1](
                state, slots, q, k, v, jnp.zeros_like(g), *a, **kw)
    elif name == "stale_state":
        def keep(states, tails, inter, layers, slot):
            _, new_tails = kv_pool.sown_state(inter, layers)
            return states, tuple(p.at[slot].set(x[0].astype(p.dtype))
                                 for p, x in zip(tails, new_tails))
        kv_pool.write_slot_state = keep
    elif name == "no_gate":
        gigachat3_5.output_gate = lambda z, scale: 1.0
    elif name is not None:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    try:
        yield
    finally:
        (delta_rule.delta_rule_prefill, delta_rule.gdn_decode_update,
         kv_pool.write_slot_state, gigachat3_5.output_gate) = saved


def _window_sample(ctx, fault_name: str | None) -> list:
    """One short window at the cell's load; the sampled finished
    requests."""
    from drivers import common, open_loop, open_loop_gdn_mla_moe as driver
    from traffic import gen
    with fault(fault_name):
        # warmed over the cell's prefill and decode buckets: the warm-up's
        # requests leave their state in every slot, as they do in the
        # cell's own runs (the page rungs past the longest prompt, which a
        # short window's drain may reach, compile there: nothing is timed)
        engine = driver.build_and_warm(ctx, warm=False)
        open_loop._warm_up(ctx, engine)
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


def _verdict(reading: dict, limits: dict) -> str:
    """The reading under the cell's own limits, as `run_cell` would put
    it: which of them it passes, if any."""
    names = {"served_gap": "served_logit_gap",
             "served_mean_gap": "served_mean_gap",
             "near_tie_share": "near_tie_share"}
    over = [f"{limit} ({reading[key]:.4g} > {limits[limit]})"
            for key, limit in names.items()
            if key in reading and reading[key] > limits[limit]]
    return ("correct: false by " + ", ".join(over)) if over \
        else "correct: true"


def control(args) -> int:
    import run_cell
    from drivers import open_loop_gdn_mla_moe as driver
    from reference import gigachat3_5 as reference
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    precisions = [p for p in args.precisions.split(",") if p]
    ctx = run_cell.make_ctx(args.workload, seeds[0], args.seconds, False)
    mcfg = reference.model_cfg(ctx.config)
    floor, limits = ctx.cell["check"]["margin_floor"], ctx.cell["limits"]
    keys = ("served_gap", "served_gap_all", "served_mean_gap",
            "near_tie_share", "tokens")
    rows = []
    for seed in seeds:
        ctx.seed = seed
        row = {"seed": seed}
        if not args.faults_only:
            sample = _window_sample(ctx, None)
            score = driver.score_served(mcfg, seed, sample, floor)
            gaps, margins = score["arrays"]
            for f in FLOORS:         # what another margin floor would read
                clear = margins >= f
                print(f"control: seed {seed} floor {f}: near-tie share "
                      f"{1 - clear.mean():.4f} widest clear gap "
                      f"{gaps[clear].max() if clear.any() else 0.0:.4f} "
                      f"mean gap of the near ties "
                      f"{gaps[~clear].mean() if (~clear).any() else 0.0:.4f}"
                      f" of the clear "
                      f"{gaps[clear].mean() if clear.any() else 0:.5f}",
                      flush=True)
            row["sound"] = {k: score[k] for k in keys}
            for precision in precisions:
                low = driver.score_served(mcfg, seed, sample, floor,
                                          precision)
                row[precision] = {"served_gap": low["control_gap"],
                                  "served_mean_gap": low["control_mean_gap"]}
        for name in faults:
            got = driver.score_served(mcfg, seed, _window_sample(ctx, name),
                                      floor)
            row[name] = {k: got[k] for k in keys}
        for what, reading in row.items():
            if what != "seed":
                print(f"control: seed {seed} {what}: "
                      f"{_verdict(reading, limits)}", flush=True)
        print(f"control: {json.dumps(row)}", flush=True)
        rows.append(row)
    for name in ("served_gap", "served_mean_gap", "near_tie_share"):
        line = f"control: {name}:"
        for other in ["sound"] + precisions + faults:
            vals = [r[other][name] for r in rows if name in r.get(other, {})]
            if vals:
                pick, said = (max, "max") if other == "sound" \
                    else (min, "min")
                line += f" {other} {said} {pick(vals)!r};"
        print(line, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="what", required=True)
    a = sub.add_parser("aot")
    a.add_argument("--workload", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, default=60.0,
                   help="twice the mix's longest answer, at least")
    s.add_argument("--seed", type=int, default=1)
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--seconds", type=float, default=10.0)
    c.add_argument("--precisions", default="bfloat16,fp8")
    c.add_argument("--faults", default="")
    c.add_argument("--faults-only", action="store_true",
                   help="no sound window: the faults' readings alone")
    args = ap.parse_args(argv)
    return {"aot": aot, "sweep": sweep, "control": control}[args.what](args)


if __name__ == "__main__":
    raise SystemExit(main())
