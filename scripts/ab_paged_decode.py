#!/usr/bin/env python3
"""The paged decode kernel alone, on the chip, at the serve cells' shapes.

    chiprun -- python scripts/ab_paged_decode.py [--parent DIR] [--reps N]

Times `ops/paged_attention.paged_decode_attention` of this tree (and, with
`--parent`, of a second checkout: `git archive` of the parent commit under
`.chipwork/`) at the four head shapes the benchmark serves, bfloat16 pools,
with the live rows a traced run of each cell showed (`PERF.md` §5): most of
a bucket's rows dead, the live ones at the cell's context lengths. A time is
the device's: `reps` calls chained inside ONE jitted loop (each call's
output is the next one's query), the wall clock around it with
`block_until_ready`, the fastest of `--trials`, over `reps`. Beside it the
K and V bytes of the live tokens over the chip's 819 GB/s (what
`paged_decode_roofline` and its siblings count), and the widest distance
from the XLA spelling in blocks (`paged_suffix_attention`). `JAX_PLATFORMS=cpu ... --aot` compiles every shape for a
described v5e instead; nothing runs.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HBM_BYTES_PER_S = 819e9

# name: (rows, Hq, Hkv, D, page, table, window, live context lengths)
SHAPES = {
    "gpt2-large.1live": (8, 20, 20, 64, 16, 64, None, [300]),
    "gpt2-large.2live": (8, 20, 20, 64, 16, 64, None, [150, 600]),
    "gpt2-large.table16": (8, 20, 20, 64, 16, 16, None, [200]),
    "nemotron": (64, 32, 2, 128, 16, 128, None,
                 [100 + 120 * i for i in range(16)]),
    "solar": (64, 64, 8, 128, 16, 2560, None, [11000, 20000, 33000]),
    "trinity.full": (64, 32, 4, 128, 16, 2112, None, [700, 12000, 30000]),
    "trinity.window": (64, 32, 4, 128, 16, 136, 2048, [700, 2050, 2170]),
}


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _case(shape, seed=0):
    import jax.numpy as jnp
    import numpy as np
    B, Hq, Hkv, D, P, MP, window, lens = shape
    rng = np.random.default_rng(seed)
    live_pages = sum(-(-n // P) for n in lens)
    pool = 1 + live_pages
    bf16 = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), bf16)
    kp = jnp.asarray(rng.standard_normal((pool, P, Hkv * D)), bf16)
    vp = jnp.asarray(rng.standard_normal((pool, P, Hkv * D)), bf16)
    kn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), bf16)
    vn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), bf16)
    tables = np.zeros((B, MP), np.int32)       # dead entries: trash page 0
    seq = np.zeros((B,), np.int32)
    order, at = rng.permutation(live_pages) + 1, 0
    rows = rng.permutation(B)[:len(lens)]      # the live rows lie anywhere
    for row, n in zip(rows, lens):
        pages = -(-n // P)
        tables[row, :pages] = order[at:at + pages]
        seq[row], at = n, at + pages
    return (q, kp, vp, jnp.asarray(tables), jnp.asarray(seq), kn, vn), window


def _time(fn, args, reps, trials):
    import jax

    @jax.jit
    def chained(q, *rest):
        return jax.lax.fori_loop(
            0, reps, lambda _, x: fn(x, *rest).astype(x.dtype), q)

    chained(*args).block_until_ready()
    best = float("inf")
    for _ in range(trials):
        t = time.perf_counter()
        chained(*args).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return best / reps


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None,
                    help="a second checkout whose kernel is timed beside")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--only", default=None)
    ap.add_argument("--orders", action="store_true",
                    help="`landed` again with the query block's rows forced "
                         "a block a query row, and a block a K/V head")
    ap.add_argument("--chunk-rows", default="",
                    help="comma list: `landed` again with CHUNK_ROWS (and "
                         "CHUNK_BYTES to match) set to each, to place them")
    ap.add_argument("--aot", action="store_true")
    a = ap.parse_args()
    if a.aot:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtraining_tpu.ops import paged_attention as landed
    PLACED = {k: getattr(landed, k) for k in (
        "CHUNK_ROWS", "CHUNK_BYTES", "_query_block")}
    # a kernel: (module, the module attributes it is timed under)
    kernels = {"landed": (landed, {})}
    for rows in filter(None, a.chunk_rows.split(",")):
        kernels[f"landed.rows{rows}"] = (landed, {
            "CHUNK_ROWS": int(rows), "CHUNK_BYTES": 1 << 22})
    if a.orders:
        kernels["landed.by_query_row"] = (landed, {"_query_block": (
            lambda G, Hkv: (False, G, -(-Hkv // 8) * 8))})
        kernels["landed.by_head"] = (landed, {"_query_block": (
            lambda G, Hkv: (True, Hkv, -(-G // 8) * 8))})
    if a.parent:
        kernels["parent"] = (_load(os.path.join(
            a.parent, "distributedtraining_tpu/ops/paged_attention.py"),
            "distributedtraining_tpu.ops.parent_paged_attention"), {})
    if a.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.default_backend() != "tpu":
        print("no TPU: a time is the chip's to say (--aot compiles only)")
        return 2
    rows = []
    for name, shape in SHAPES.items():
        if a.only and a.only not in name:
            continue
        args, window = _case(shape)
        for label, (mod, under) in kernels.items():
            if mod is landed:
                for attr, value in {**PLACED, **under}.items():
                    setattr(mod, attr, value)
                mod._build_call.cache_clear()
            fn = (lambda *x, m=mod, w=window: m.paged_decode_attention(
                *x, **({} if w is None else {"window": w})))
            if a.aot:
                abstract = [jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                 sharding=one) for x in args]
                jax.jit(fn).trace(*abstract).lower(
                    lowering_platforms=("tpu",)).compile()
                rows.append({"shape": name, "kernel": label, "aot": "ok"})
                continue
            lens = shape[7]
            live = [min(n, window) if window else n for n in lens]
            live_bytes = sum(live) * 2 * args[1].shape[2] * 2
            # the blocked spelling: the gathered twin repeats a 40,960-row
            # context to every query head, 21 GB at solar's bucket
            twin = jax.jit(lambda *x, w=window: landed.paged_suffix_attention(
                *x, window=w))(*args)
            try:
                got = jax.jit(fn)(*args)
            except Exception as e:      # a chunk the chip's VMEM refuses
                rows.append({"shape": name, "kernel": label,
                             "refused": str(e)[:200]})
                continue
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - twin.astype(jnp.float32))))
            if not np.isfinite(err):
                raise SystemExit(f"{name} {label}: not finite")
            us = _time(fn, args, a.reps, a.trials) * 1e6
            rows.append({
                "shape": name, "kernel": label, "us_a_call": round(us, 2),
                "live_tokens": sum(live), "bytes_floor_us": round(
                    live_bytes / HBM_BYTES_PER_S * 1e6, 2),
                "roofline_pct": round(
                    100 * live_bytes / HBM_BYTES_PER_S / (us * 1e-6), 1),
                "max_abs_err_vs_twin": round(err, 4)})
        for r in rows[-len(kernels):]:
            print(json.dumps(r), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "ab_paged_decode.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
