#!/usr/bin/env python3
"""`tools/sweep.py` and `tools/control.py` for the cells of the driver
`sessions_kda_gqa_moe` (those tools import `open_loop` and GPT-2's
reference by name), and the compile for the chip that needs none.

    python3 benchmarks/tools/kda_gqa_moe.py aot --workload \
        serve-solar-sessions
    python3 benchmarks/tools/kda_gqa_moe.py sweep --workload \
        serve-solar-sessions --rates 1,1.5,2,2.5 --seconds 60 --seed 1
    python3 benchmarks/tools/kda_gqa_moe.py control --workload \
        serve-solar-sessions --seeds 11,12 --seconds 20 \
        [--precisions bfloat16,fp8] \
        [--faults stale_snapshot,mean_decay,beta_1,no_gate]

`aot` compiles the cell's largest decode, suffix-prefill and prefill
programs for a DESCRIBED v5e (`JAX_PLATFORMS=cpu`; nothing runs) and prints
their Mosaic calls and their memory. `sweep` finds the knee: one engine,
warmed and set up as the cell is (every session's history registered), the
cell's mix at each TURN rate in rising order with a full drain between, the
sessions growing from window to window as they would in one longer run,
and no rate past the first that closes with turns queued or waiting for a
session. `control` reads what every limit of `correct` is set from: for
each seed a short window at the cell's load, scored by the reference (the
sound reading) and, for each of `--precisions`, by the lower-precision
reference in the program's place (`bfloat16`: the state alone a step below
what the configuration states, the products as the program makes them;
`fp8`: float8 products too); with `--faults`, the same window with the
program broken underneath, by a patch from here and never by a switch in
the program: `stale_snapshot` makes a hit restore ANOTHER row of the
snapshot pool (another session's state under this session's pages);
`mean_decay` averages a head's log-decay over its channels, prefill and
decode (the scalar rule under this model's name); `beta_1` halves `beta`
(the rule without `kda_allow_neg_eigval`'s factor 2); `no_gate` leaves both
mixers' output gates out (they read 1). Every reading is put to the cell's
own limits, and the line says by which it comes out `correct: false`."""

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

from tools.gdn_mla_moe import FLOORS, _verdict as verdict  # noqa: E402

FAULTS = ("stale_snapshot", "mean_decay", "beta_1", "no_gate")


def aot(args) -> int:
    """The cell's largest programs through the TPU's own compiler against
    a described v5e: Mosaic's verdict on the kernels at the published
    widths (and on the page table's 2,560 scalar-prefetched entries a
    slot), and the device memory each program needs with weights, page
    pool, state pool and snapshot pool."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from distributedtraining_tpu.engine import kv_pool, serve, serve_weights
    from distributedtraining_tpu.ops import delta_rule, moe, paged_attention
    from drivers import common, sessions_kda_gqa_moe as driver

    jax.config.update("jax_enable_compilation_cache", False)
    for module in (delta_rule, paged_attention, moe):
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
    eng = serve.GenerationEngine(
        model, None, max_slots=slots, page_size=P,
        pool_pages=e["pool_pages"], max_seq_len=e["max_seq_len"],
        prefix_cache=e["prefix_cache"], snapshot_rows=e["snapshot_rows"],
        prefill_chunk=e["prefill_chunk"])
    eng._layers, eng._donate = serve._layer_keys(base), True
    caches = kv_pool.layer_caches(cfg, len(eng._layers))
    halves = tuple(
        tuple(sds((eng.pool_pages, P, width), cfg.compute_dtype())
              for _ in range(caches.count("kv")))
        for width in kv_pool.row_widths(cfg))
    n_state = caches.count("ssm")

    def state(rows):
        return (tuple(sds((rows, *cfg.ssm_state_shape), jnp.float32)
                      for _ in range(n_state)),
                tuple(sds((rows, *cfg.ssm_tail_shape), cfg.compute_dtype())
                      for _ in range(n_state)))

    pool, snaps = state(slots + 1), state(e["snapshot_rows"])
    held = sum(x.size * x.dtype.itemsize for half in snaps for x in half)
    pages, chunk = w["table_pages"], e["prefill_chunk"]
    suffix = max(w["suffix_tokens"])
    programs = {
        f"decode {slots} slots x {pages} pages":
            eng._decode_prog(slots, pages).__wrapped__.trace(
                tree, *halves, sds((slots, pages)), sds((slots,)),
                sds((slots,)), *pool, sds((slots,))),
        f"suffix prefill {suffix} tokens x {pages} pages":
            eng._prefill_ctx_prog(suffix, pages).__wrapped__.trace(
                tree, sds((1, suffix)), sds(()), sds(()), *halves,
                sds((1, pages)), *pool, sds(())),
        f"prefill {chunk} tokens":
            eng._prefill_prog(chunk).__wrapped__.trace(
                tree, sds((1, chunk)), sds(()), *halves,
                sds((chunk // P,)), *pool, sds(())),
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
              f"{total / 2**30:.2f} GiB, and beside it the snapshot pool "
              f"{held} bytes = {(total + held) / 2**30:.2f} GiB",
              flush=True)
    eng.close()
    return 0


def sweep(args) -> int:
    import run_cell
    from drivers import common, sessions_kda_gqa_moe as driver
    ctx = run_cell.make_ctx(args.workload, args.seed, args.seconds, False)
    ctx.cell["drain_s"] = 240.0   # every rate starts with nothing in flight
    mix = ctx.mix
    spans = common.Spans()
    engine = driver.build_and_warm(ctx)
    vocab = ctx.config["vocab_size"]
    histories, _ = driver.plan(mix, args.seed, args.seconds, vocab)
    sessions = driver._set_up_sessions(ctx, engine, histories)
    print(f"sweep: set-up {time.perf_counter() - ctx.t_process:.1f}s",
          flush=True)
    for i, rate in enumerate(sorted(float(r)
                                    for r in args.rates.split(","))):
        ctx.mix = dict(mix, rate_rps=rate)
        # another seed a rate: other messages on the same sessions
        _, turns = driver.plan(ctx.mix, args.seed + 1000 * (i + 1),
                               args.seconds, vocab)
        before = (engine.prefix_hits, engine.prefix_misses,
                  engine.prefix_snapshots_evicted)
        ctx.compiles.mark()
        w = driver.serve_window(ctx, engine, (sessions, turns), spans,
                                common.TraceSlice(ctx, spans))
        print(f"sweep: rate {rate} turns/s compiles="
              f"{ctx.compiles.since_mark()} hits/misses/evicted="
              f"{engine.prefix_hits - before[0]}/"
              f"{engine.prefix_misses - before[1]}/"
              f"{engine.prefix_snapshots_evicted - before[2]} waited="
              f"{w['waited_for_a_session']} free_pages={engine.pool.free} "
              f"mean_context="
              f"{sum(len(s.text) for s in sessions) // len(sessions)} "
              f"{driver.window_line(w)}", flush=True)
        if w["queued_at_close"] or w["waited_for_a_session"]:
            break       # past the knee: a higher rate only queues more
    engine.close()
    return 0


@contextlib.contextmanager
def fault(name: str | None):
    """The program with one mechanism broken, for the length of a window."""
    import jax.numpy as jnp

    from distributedtraining_tpu.engine import serve
    from distributedtraining_tpu.models import solar_open2
    from distributedtraining_tpu.ops import delta_rule
    saved = (delta_rule.delta_rule_prefill, delta_rule.gdn_decode_update,
             serve.GenerationEngine._copy_state_row, solar_open2.output_gate)

    def both(change):
        """`change(g, beta) -> (g, beta)` in prefill and decode alike."""
        def prefill(q, k, v, g, beta, *a, **kw):
            return saved[0](q, k, v, *change(g, beta), *a, **kw)

        def decode(state, slots, q, k, v, g, beta, *a, **kw):
            return saved[1](state, slots, q, k, v, *change(g, beta), *a,
                            **kw)

        delta_rule.delta_rule_prefill = prefill
        delta_rule.gdn_decode_update = decode

    if name == "mean_decay":
        both(lambda g, beta: (jnp.broadcast_to(
            jnp.mean(g, axis=-1, keepdims=True), g.shape), beta))
    elif name == "beta_1":
        both(lambda g, beta: (g, 0.5 * beta))
    elif name == "stale_snapshot":
        def other_row(self, which, src_row, dst_row):
            if which == "restore":
                src_row = (src_row + 1) % self._snapshot_rows
            return saved[2](self, which, src_row, dst_row)
        serve.GenerationEngine._copy_state_row = other_row
    elif name == "no_gate":
        solar_open2.output_gate = lambda z: jnp.ones(z.shape, jnp.float32)
    elif name is not None:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    try:
        yield
    finally:
        (delta_rule.delta_rule_prefill, delta_rule.gdn_decode_update,
         serve.GenerationEngine._copy_state_row,
         solar_open2.output_gate) = saved


def window_sample(ctx, fault_name: str | None) -> list:
    """One short window at the cell's load, set up as the cell is; the
    sampled finished turns."""
    from drivers import common, sessions_kda_gqa_moe as driver
    with fault(fault_name):
        # the programs are traced with the fault in place, so nothing of a
        # sound run's is reused; what is not warmed compiles as it is met
        # (nothing is timed)
        engine = driver.build_and_warm(ctx)
        histories, turns = driver.plan(ctx.mix, ctx.seed, ctx.seconds,
                                       ctx.config["vocab_size"])
        sessions = driver._set_up_sessions(ctx, engine, histories)
        spans = common.Spans()
        w = driver.serve_window(ctx, engine, (sessions, turns), spans,
                                common.TraceSlice(ctx, spans))
    sample = driver.sample_turns(
        [tr for tr in w["finished"] if tr.req.status == "done"], ctx.seed,
        ctx.cell["check"]["sample_requests"])
    engine.close()
    del engine, w, sessions
    common.free_device_memory()
    return sample


def control(args) -> int:
    import run_cell
    from drivers import sessions_kda_gqa_moe as driver
    from reference import solar_open2 as reference
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    precisions = [p for p in args.precisions.split(",") if p]
    ctx = run_cell.make_ctx(args.workload, seeds[0], args.seconds, False)
    mcfg = reference.model_cfg(ctx.config)
    floor, limits = ctx.cell["check"]["margin_floor"], ctx.cell["limits"]
    keys = ("served_gap", "served_gap_all", "served_mean_gap",
            "near_tie_share", "tokens", "longest_context")
    rows = []
    for seed in seeds:
        ctx.seed = seed
        row = {"seed": seed}
        if not args.faults_only:
            sample = window_sample(ctx, None)
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
            got = driver.score_served(mcfg, seed, window_sample(ctx, name),
                                      floor)
            row[name] = {k: got[k] for k in keys}
        for what, reading in row.items():
            if what != "seed":
                print(f"control: seed {seed} {what}: "
                      f"{verdict(reading, limits)}", flush=True)
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
    s.add_argument("--seconds", type=float, default=60.0)
    s.add_argument("--seed", type=int, default=1)
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--seconds", type=float, default=20.0)
    c.add_argument("--precisions", default="bfloat16,fp8")
    c.add_argument("--faults", default="")
    c.add_argument("--faults-only", action="store_true",
                   help="no sound window: the faults' readings alone")
    args = ap.parse_args(argv)
    return {"aot": aot, "sweep": sweep, "control": control}[args.what](args)


if __name__ == "__main__":
    raise SystemExit(main())
