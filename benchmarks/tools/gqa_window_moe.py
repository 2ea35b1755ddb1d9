#!/usr/bin/env python3
"""`tools/sweep.py` and `tools/control.py` for the cells of the driver
`open_loop_gqa_window_moe` (those tools import `open_loop` and GPT-2's
reference by name), and the compile for the chip that needs none.

    python3 benchmarks/tools/gqa_window_moe.py aot --workload \
        serve-trinity-mixed
    python3 benchmarks/tools/gqa_window_moe.py sweep --workload \
        serve-trinity-mixed --rates 2,3,4,5 --seconds 60 --seed 1
    python3 benchmarks/tools/gqa_window_moe.py control --workload \
        serve-trinity-mixed --seeds 11 [--precisions bfloat16,fp8] \
        [--faults no_window,window_off_by_one,...]

`aot` compiles the cell's largest decode, suffix-prefill and prefill
programs for a DESCRIBED v5e (`JAX_PLATFORMS=cpu`; nothing runs) and prints
their Mosaic calls and their memory. `sweep` finds the knee as
`tools/gdn_mla_moe.py`'s does (one warmed engine, the cell's mix at each
rate in rising order with a full drain between, no rate past the first that
closes queued). `control` reads what every limit of `correct` is set from:
for each seed the requests the cell's scoring would sample from that seed's schedule
(the longest prompt and `sample_requests - 1` drawn), served to completion
by an engine built as the cell builds it and scored by the reference (the
sound reading) and, for each of `--precisions`, by the lower-precision
reference in the program's place; with `--faults`, the same requests served
with the program broken underneath, by a patch from here and never by a
switch in the program:
  no_window          the sliding layers' mask left out (they see whatever
                     their group still holds)
  window_off_by_one  2,047 keys
  rope_everywhere    the rotation in the full-attention layer too
  no_qk_norm         q and k attended as projected
  no_gate            the attention's output gate reads 1
  no_mup             the lookup not multiplied by sqrt(hidden_size)
  stale_window_page  a page goes back behind the window and the table's
                     first row does not move on: every page of the group is
                     read as the one before it
Beside each serving the cell's probe of the window's edge
(`driver.window_edge_gap`) is read with the same fault in place. Every
reading is put to the cell's own limits, and the line says by which it
comes out `correct: false`."""

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

from tools.gdn_mla_moe import FLOORS  # noqa: E402

FAULTS = ("no_window", "window_off_by_one", "rope_everywhere", "no_qk_norm",
          "no_gate", "no_mup", "stale_window_page")


def verdict(reading: dict, limits: dict) -> str:
    """The reading under the cell's own limits, as `run_cell` would put
    it: which of them it passes, if any."""
    names = {"served_gap": "served_logit_gap",
             "served_mean_gap": "served_mean_gap",
             "near_tie_share": "near_tie_share",
             "window_edge_gap": "window_edge_gap"}
    over = [f"{limit} ({reading[key]:.4g} > {limits[limit]})"
            for key, limit in names.items()
            if key in reading and reading[key] > limits[limit]]
    return ("correct: false by " + ", ".join(over)) if over \
        else "correct: true"


def aot(args) -> int:
    """The cell's largest programs through the TPU's own compiler against
    a described v5e: Mosaic's verdict on both paged decode kernels at the
    published widths (the global layer's 2,112 scalar-prefetched table
    entries a slot, the window group's 136), and the device memory each
    program needs with the weights and the two page pools."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from distributedtraining_tpu.engine import kv_pool, serve, serve_weights
    from distributedtraining_tpu.ops import moe, paged_attention
    from drivers import common, open_loop_gqa_window_moe as driver

    jax.config.update("jax_enable_compilation_cache", False)
    for module in (paged_attention, moe):
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
        pool_pages=e["pool_pages"],
        window_pool_pages=e["window_pool_pages"],
        max_seq_len=e["max_seq_len"], prefix_cache=e["prefix_cache"],
        prefill_chunk=e["prefill_chunk"])
    eng._layers, eng._donate = serve._layer_keys(base), True
    eng._init_kv()
    caches = kv_pool.layer_caches(cfg, len(eng._layers))

    def pool(pages, kind):
        return tuple(
            tuple(sds((pages, P, width), cfg.compute_dtype())
                  for _ in range(caches.count(kind)))
            for width in kv_pool.row_widths(cfg))

    halves = pool(eng.pool_pages, "kv")
    window = pool(eng._window.total + 1, "kv_window")
    pages, chunk = w["table_pages"], e["prefill_chunk"]
    suffix = max(w["suffix_tokens"])
    narrow, wide = eng._window.decode_pages, eng._window.table_pages
    programs = {
        f"decode {slots} slots x {pages} + {narrow} pages":
            eng._decode_prog(slots, pages).__wrapped__.trace(
                tree, *halves, sds((slots, pages)), sds((slots,)),
                sds((slots,)), *window, sds((slots, narrow)), sds((slots,))),
        f"suffix prefill {suffix} tokens x {pages} + {wide} pages":
            eng._prefill_ctx_prog(suffix, pages).__wrapped__.trace(
                tree, sds((1, suffix)), sds(()), sds(()), *halves,
                sds((1, pages)), *window, sds((1, wide)), sds((1,))),
        f"prefill {chunk} tokens":
            eng._prefill_prog(chunk).__wrapped__.trace(
                tree, sds((1, chunk)), sds(()), *halves,
                sds((chunk // P,)), *window, sds((1, chunk // P)),
                sds((1,))),
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
    from drivers import common, open_loop_gqa_window_moe as driver
    from traffic import gen
    ctx = run_cell.make_ctx(args.workload, args.seed, args.seconds, False)
    ctx.cell["drain_s"] = 240.0   # every rate starts with nothing in flight
    mix = ctx.mix
    spans = common.Spans()
    engine = driver.build_and_warm(ctx)
    print(f"sweep: set-up {time.perf_counter() - ctx.t_process:.1f}s",
          flush=True)
    for i, rate in enumerate(sorted(float(r)
                                    for r in args.rates.split(","))):
        ctx.mix = dict(mix, rate_rps=rate)
        # another seed a rate: no prompt of one window opens like another's
        schedule = gen.open_loop_requests(
            ctx.mix, args.seed + 1000 * (i + 1), args.seconds,
            ctx.config["vocab_size"])
        ctx.compiles.mark()
        w = driver.serve_window(ctx, engine, schedule, spans,
                                common.TraceSlice(ctx, spans))
        print(f"sweep: rate {rate} req/s compiles="
              f"{ctx.compiles.since_mark()} free pages kv/window="
              f"{engine.pool.free}/{engine._window.free} "
              f"{driver.window_line(w)}", flush=True)
        if w["queued_at_close"]:
            break       # past the knee: a higher rate only queues more
    engine.close()
    return 0


@contextlib.contextmanager
def fault(name: str | None):
    """The program with one mechanism broken, for as long as its programs
    are traced and run."""
    import jax.numpy as jnp

    from distributedtraining_tpu.engine import kv_pool
    from distributedtraining_tpu.models import afmoe, family
    saved = (family.grouped_query_attention, family.paged_attention,
             family.causal_attention, afmoe.output_gate, afmoe.Afmoe.embed,
             kv_pool.WindowPages.release_behind)

    def attention(change):
        """`change(keywords) -> keywords` of every attention layer."""
        def layer(module, h, step, cfg, impl, gate=None, **kw):
            return saved[0](module, h, step, cfg, impl, gate,
                            **change(dict(kw), cfg))
        family.grouped_query_attention = layer

    def masks(change):
        """`change(window) -> window` where a layer's mask is made."""
        def paged(*a, window=None, **kw):
            return saved[1](*a, **kw, **change(window))

        def dense(*a, window=None, **kw):
            return saved[2](*a, **kw, **change(window))
        family.paged_attention, family.causal_attention = paged, dense

    if name == "no_window":
        masks(lambda window: {})
    elif name == "window_off_by_one":
        masks(lambda window: {} if window is None
              else {"window": window - 1})
    elif name == "rope_everywhere":
        attention(lambda kw, cfg: dict(kw, rope_theta=cfg.rope_theta))
    elif name == "no_qk_norm":
        attention(lambda kw, cfg: dict(kw, qk_norm=False))
    elif name == "no_gate":
        afmoe.output_gate = lambda z: jnp.ones(z.shape, jnp.float32)
    elif name == "no_mup":
        afmoe.Afmoe.embed = family.ServedDecoder.embed
    elif name == "stale_window_page":
        def stale(self, rid, newest):
            first = self.held[rid].first
            n = saved[5](self, rid, newest)
            self.held[rid].first = first    # the table's first row stays
            return n
        kv_pool.WindowPages.release_behind = stale
    elif name is not None:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    try:
        yield
    finally:
        (family.grouped_query_attention, family.paged_attention,
         family.causal_attention, afmoe.output_gate, afmoe.Afmoe.embed,
         kv_pool.WindowPages.release_behind) = saved


def sampled_requests(ctx) -> list:
    """(prompt, output length) of the requests the cell's scoring would
    sample if every request of this seed's schedule finished: the longest
    and `sample_requests - 1` drawn from the seed."""
    import numpy as np

    from traffic import gen
    schedule = gen.open_loop_requests(ctx.mix, ctx.seed, ctx.seconds,
                                      ctx.config["vocab_size"])
    order = sorted(range(len(schedule)), key=lambda i: -(
        len(schedule[i][1]) + schedule[i][2]))
    rng = np.random.default_rng([int(ctx.seed), 0xC4EC])
    k = ctx.cell["check"]["sample_requests"]
    pick = [order[0]] + [order[1:][j] for j in
                         rng.permutation(len(order) - 1)[:k - 1]]
    return [(schedule[i][1], schedule[i][2]) for i in pick]


def served_sample(ctx, fault_name: str | None, requests: list) -> list:
    """`requests` served to completion by an engine built as the cell
    builds it (nothing warmed: what is not compiled compiles as it is met,
    and nothing is timed), with the fault in place while its programs are
    traced. -> (prompt, served tokens) each."""
    from drivers import common, open_loop_gqa_window_moe as driver
    with fault(fault_name):
        engine = driver.build_and_warm(ctx, warm=False)
        w, e = ctx.cell["warmup"], ctx.cell["engine"]
        engine.declare_buckets(
            prefill_pages=[max(w["prefill_tokens"]) // e["page_size"]],
            suffix_pages=[max(w["suffix_tokens"]) // e["page_size"]],
            table_pages=[w["table_pages"]], decode_pages=[w["table_pages"]])
        reqs = [engine.submit(prompt, n) for prompt, n in requests]
        while not all(r.done_evt.is_set() for r in reqs):
            engine.step()
    engine.close()
    del engine
    common.free_device_memory()
    return [(list(r.prompt), list(r.tokens)) for r in reqs]


def edge_gap(ctx, fault_name: str | None) -> float:
    """The cell's probe of the window's edge with the fault in place."""
    from drivers import open_loop_gqa_window_moe as driver
    with fault(fault_name):
        _, pc = driver.make_model(ctx.config)
        try:
            return max(driver.window_edge_gap(pc, ctx.cell["engine"],
                                              ctx.seed).values())
        except AssertionError:      # the group ran short under the fault
            return float("inf")


def control(args) -> int:
    import run_cell
    from drivers import open_loop_gqa_window_moe as driver
    from reference import afmoe as reference
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    precisions = [p for p in args.precisions.split(",") if p]
    ctx = run_cell.make_ctx(args.workload, seeds[0], args.seconds, False)
    mcfg = reference.model_cfg(ctx.config)
    floor, limits = ctx.cell["check"]["margin_floor"], ctx.cell["limits"]
    keys = ("served_gap", "served_gap_all", "served_mean_gap",
            "near_tie_share", "tokens", "longest_context")
    # the readings a limit compares: a fault's are smallest, the sound largest
    compared = ("served_gap", "served_mean_gap", "near_tie_share",
                "window_edge_gap")
    rows = []
    for seed in seeds:
        ctx.seed = seed
        requests = sampled_requests(ctx)
        row = {"seed": seed}
        if not args.faults_only:
            sample = served_sample(ctx, None, requests)
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
            row["sound"] = dict({k: score[k] for k in keys},
                                window_edge_gap=edge_gap(ctx, None))
            for precision in precisions:
                low = driver.score_served(mcfg, seed, sample, floor,
                                          precision)
                row[precision] = {"served_gap": low["control_gap"],
                                  "served_mean_gap": low["control_mean_gap"]}
        for name in faults:
            got = driver.score_served(
                mcfg, seed, served_sample(ctx, name, requests), floor)
            row[name] = dict({k: got[k] for k in keys},
                             window_edge_gap=edge_gap(ctx, name))
            print(f"control: seed {seed} {name}: "
                  f"{verdict(row[name], limits)}", flush=True)
        for what, reading in row.items():
            if what != "seed" and what not in faults:
                print(f"control: seed {seed} {what}: "
                      f"{verdict(reading, limits)}", flush=True)
        print(f"control: {json.dumps(row)}", flush=True)
        rows.append(row)
    for name in compared:
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
    c.add_argument("--seconds", type=float, default=40.0,
                   help="the schedule's length the requests are taken from")
    c.add_argument("--precisions", default="fp8")
    c.add_argument("--faults", default="")
    c.add_argument("--faults-only", action="store_true",
                   help="no sound serving: the faults' readings alone")
    args = ap.parse_args(argv)
    return {"aot": aot, "sweep": sweep, "control": control}[args.what](args)


if __name__ == "__main__":
    raise SystemExit(main())
