#!/usr/bin/env python3
"""On the chip: the two spellings of the grouped expert product and of the
latent decode attention side by side, and the cut model's program times.

    chiprun -- python3 benchmarks/tools/ab_mla_moe.py [grouped] [mla] [engine]

`grouped`  jax.lax.ragged_dot against megablox gmm (a few tilings) at the
           decode shape (64 slots x 6 rows over 128 experts) and a prefill
           shape, with the bytes of the touched experts over the time
`mla`      the Pallas latent decode kernel against its XLA twin: widest
           difference and time
`engine`   kanana-2-30b-a3b-l8 with random weights through
           GenerationEngine: the time of prefill steps and of 64-slot
           decode steps
Writes what it prints to chiprun_out/ab_mla_moe.txt too."""

from __future__ import annotations

import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

LINES: list[str] = []


def say(*a):
    line = " ".join(str(x) for x in a)
    LINES.append(line)
    print(line, flush=True)


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n, out


def grouped():
    from distributedtraining_tpu.ops import moe
    G, E, F, K = 128, 2048, 768, 6
    key = jax.random.PRNGKey(0)
    w_gu = (0.02 * jax.random.normal(key, (G, E, 2 * F))).astype(jnp.bfloat16)
    w_d = (0.02 * jax.random.normal(key, (G, F, E))).astype(jnp.bfloat16)
    for n_tok in (64, 16, 2048):
        rng = np.random.default_rng(n_tok)
        choice = np.stack([rng.permutation(G)[:K] for _ in range(n_tok)])
        sizes = jnp.asarray(np.bincount(choice.reshape(-1), minlength=G),
                            jnp.int32)
        touched = int((np.asarray(sizes) > 0).sum())
        m = n_tok * K
        x = jax.random.normal(key, (m, E)).astype(jnp.bfloat16)
        a = jax.random.normal(key, (m, F)).astype(jnp.bfloat16)
        say(f"grouped: {n_tok} tokens x {K} = {m} rows, {touched} experts "
            f"touched")
        ref = None
        for label, tiling in (("ragged_dot", None),
                              ("gmm 128,512,768", (128, 512, 768)),
                              ("gmm 128,1024,768", (128, 1024, 768)),
                              ("gmm 128,2048,512", (128, 2048, 512)),
                              ("gmm 128,768,1024", (128, 768, 1024)),
                              ("gmm 64,1024,768", (64, 1024, 768)),
                              ("gmm 256,512,768", (256, 512, 768))):
            for name, lhs, rhs in (("gate_up", x, w_gu), ("down", a, w_d)):
                k, n = rhs.shape[1:]
                if tiling is None:
                    fn = jax.jit(lambda l, r, s: moe.grouped_matmul(
                        l, r, s, impl="ragged_dot"))
                else:
                    if m % tiling[0] or tiling[1] > k or tiling[2] > n \
                            or n % tiling[2]:
                        continue
                    from jax.experimental.pallas.ops.tpu import megablox
                    fn = jax.jit(lambda l, r, s, t=tiling: megablox.gmm(
                        l, r, s, l.dtype, t))
                try:
                    dt, out = timed(fn, lhs, rhs, sizes)
                except Exception as exc:  # a tiling the compiler refuses
                    say(f"  {label:18s} {name:8s} FAILED "
                        f"{type(exc).__name__}: {str(exc)[:120]}")
                    continue
                nbytes = touched * k * n * 2
                if tiling is None:
                    ref = dict(ref or {}, **{name: out})
                    diff = 0.0
                else:
                    diff = float(jnp.max(jnp.abs(
                        out.astype(jnp.float32)
                        - ref[name].astype(jnp.float32))))
                say(f"  {label:18s} {name:8s} {dt * 1e3:8.3f} ms  "
                    f"{nbytes / dt / 1e9:7.1f} GB/s of touched weights  "
                    f"max|diff| {diff:.4f}")


def mla():
    from distributedtraining_tpu.ops import mla_attention as M
    B, H, C, R, P = 64, 32, 512, 64, 16
    for mp, top in ((128, 2048), (256, 3072), (32, 512)):
        pool = B * mp + 1
        key = jax.random.split(jax.random.PRNGKey(mp), 8)
        dt = jnp.bfloat16
        c_pages = jax.random.normal(key[0], (pool, P, C)).astype(dt)
        kr = jax.random.normal(key[1], (pool, P, R)).astype(dt)
        kr_pages = jnp.pad(kr, ((0, 0), (0, 0), (0, 128 - R)))
        q_abs = (0.3 * jax.random.normal(key[2], (B, 1, H, C))).astype(dt)
        q_rope = (0.3 * jax.random.normal(key[3], (B, 1, H, R))).astype(dt)
        c_new = jax.random.normal(key[4], (B, 1, C)).astype(dt)
        kr_new = jax.random.normal(key[5], (B, 1, R)).astype(dt)
        rng = np.random.default_rng(mp)
        lens = jnp.asarray(rng.integers(1, top, B), jnp.int32)
        tables = jnp.asarray(
            1 + rng.permutation(B * mp).reshape(B, mp), jnp.int32)
        scale = 192 ** -0.5
        kern = jax.jit(lambda *a: M.mla_decode_attention(*a, scale))
        twin = jax.jit(lambda *a: M.mla_decode_reference(*a, scale))
        args = (q_abs, q_rope, c_pages, kr_pages, tables, lens, c_new,
                kr_new)
        tk, ok = timed(kern, *args)
        tt, ot = timed(twin, *args)
        diff = float(jnp.max(jnp.abs(ok.astype(jnp.float32)
                                     - ot.astype(jnp.float32))))
        live = int(jnp.sum(lens)) * (C + R) * 2
        say(f"mla: {B} slots x {mp} pages, {int(jnp.sum(lens))} live tokens:"
            f" kernel {tk * 1e3:.3f} ms ({live / tk / 1e9:.0f} GB/s of live"
            f" rows) twin {tt * 1e3:.3f} ms  max|kernel - twin| {diff:.4f}"
            f"  max|twin| {float(jnp.max(jnp.abs(ot))):.3f}")


def engine():
    from distributedtraining_tpu.engine.serve import GenerationEngine
    from distributedtraining_tpu.models import deepseek_v3 as ds
    from distributedtraining_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    model, cfg = ds.make_model("kanana-2-30b-a3b-l8")
    t0 = time.perf_counter()
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    say(f"engine: {sum(x.size for x in jax.tree_util.tree_leaves(params))} "
        f"parameters made in {time.perf_counter() - t0:.1f}s")
    eng = GenerationEngine(model, params, max_slots=64, page_size=16,
                           max_seq_len=4096, max_new_tokens=1024,
                           prefix_cache=True)
    rng = np.random.default_rng(0)
    for plen in (100, 200, 400, 800, 1600, 100, 200, 400, 800, 1600):
        eng.submit(rng.integers(0, cfg.vocab_size, plen).tolist(), 200)
        t0 = time.perf_counter()
        eng.step()
        say(f"engine: step with a prefill of {plen} tokens "
            f"{(time.perf_counter() - t0) * 1e3:.1f} ms")
    for _ in range(54):
        eng.submit(rng.integers(0, cfg.vocab_size, 700).tolist(), 200)
    times = []
    for _ in range(150):
        t0 = time.perf_counter()
        out = eng.step()
        times.append(((time.perf_counter() - t0) * 1e3, out["active"],
                      out["queued"]))
    for ms, active, queued in times[:60:6] + times[60::10]:
        say(f"engine: step {ms:.1f} ms active {active} queued {queued}")
    stats = jax.local_devices()[0].memory_stats() or {}
    say(f"engine: peak bytes in use {stats.get('peak_bytes_in_use')}")
    say("engine: decode programs", sorted(eng._decode_progs),
        "prefill", sorted(eng._prefill_progs))
    eng.close()


if __name__ == "__main__":
    what = sys.argv[1:] or ["grouped", "mla", "engine"]
    say("device", jax.devices()[0].device_kind)
    for name in what:
        try:
            {"grouped": grouped, "mla": mla, "engine": engine}[name]()
        except Exception as exc:
            import traceback
            say(f"{name}: FAILED {type(exc).__name__}: {exc}")
            say(traceback.format_exc()[-3000:])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ab_mla_moe.txt"), "w") as f:
        f.write("\n".join(LINES) + "\n")
