#!/usr/bin/env python3
"""A/B on the chip: the spellings of the routed layer's two moves back to
token order on the PREFIX path (ops/moe.py), alone, at `train-lfm2-t8192`'s
shapes: 16,384 tokens x 4 choices, 2,048 wide, a prefix of 20,480 of the
65,536 sorted rows, bfloat16 rows.

    chiprun -- python scripts/ab_moe_unsort.py

Forward (the un-sort and its sum over `k`, from `y` as it left the experts)
and backward (the dispatch's: a token's `k` cotangent rows summed), each as

  full_width  the parent's: forward `f32[N k, E]` gathered along the inverse
              permutation, re-tiled `[N, k, E]`, reduced; backward ONE
              gather of `[k, N, E]` planes, reduced over `k`
  planes      what ops/moe.py does: `k` gathers of `N` rows out of the
              bfloat16 source, weigh / mask / cast inside the pass that adds
  fold        ISSUE 48's first spelling: the prefix's rows sorted by token
              (a sort of `P` integers), gathered into that order, each
              token's adjacent rows folded into its first by `k - 1`
              shifted adds, `N` first rows gathered
  fold_f32    the same with the float32 rows written out before the fold
              (what XLA makes of `fold(weigh(...))`: it does not fuse a
              producer into a consumer that reads it at four row shifts)

on two routings: `one` (the cell's: one held row a token, three elsewhere)
and `mixed` (8 of 32 experts held, 0..4 rows a token). Prints the median of
20 calls in ms and whether the result is the full-width one's to the bit
(forward, `mixed` differs by a rounding here and there in every spelling
that adds the weighed rows in one pass: the chip's compiler pairs the
terms of the full-width reduce; `one` read bit-equal). PERF.md, PR 48 has
the readings."""

from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# SMALL=1: a rehearsal on the CPU
N, K, E, P, G, ROUTER = ((256, 4, 128, 384, 8, 32) if os.environ.get("SMALL")
                         else (16384, 4, 2048, 20480, 8, 32))


def routing(kind: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    if kind == "one":
        choice = rng.integers(G, ROUTER, (N, K))
        choice[np.arange(N), rng.integers(0, K, N)] = rng.integers(0, G, N)
    else:
        choice = np.stack([rng.permutation(ROUTER)[:K] for _ in range(N)])
    flat = choice.reshape(-1)
    here = flat < G
    order = np.argsort(np.where(here, flat, G), kind="stable")
    return jnp.asarray(here), jnp.asarray(order, jnp.int32), int(here.sum())


def by_token(order):
    """(sorted row, flat position) of the prefix's rows in token order,
    which slots hold the token of the slot d before, each token's first."""
    flat, rows = jax.lax.sort((order[:P], jnp.arange(P, dtype=order.dtype)),
                              num_keys=1)
    token = flat // K
    same = tuple(jnp.pad(token[d:] == token[:-d], (0, d))
                 for d in range(1, K))
    held = jnp.sum(jnp.argsort(order).reshape(N, K) < P, axis=1,
                   dtype=jnp.int32)
    first = jnp.cumsum(held) - held
    return rows, flat, same, jnp.where(held > 0, first, P)


def on(x, d):
    return jnp.pad(x[d:], ((0, d),) + ((0, 0),) * (x.ndim - 1))


def held_rows(x, here, at):
    return jnp.where(jnp.take(here, at)[..., None], x, 0.0)


def weigh(y, w, here, at):
    return held_rows(y.astype(jnp.float32) * w[:, None], here, at)


def fill(src, at):
    return jnp.take(src, at, axis=0, mode="fill", fill_value=0)


def folded(row, same, first):
    total = row(0)
    for d, s in enumerate(same, 1):
        total = total + jnp.where(s[:, None], row(d), 0.0)
    return fill(total, first)


# forward: y [P, E], w [N K] -> [N, E]
def fwd_full_width(y, w, here, order, index):
    rows = order[:P]
    out = fill(weigh(y, jnp.take(w, rows), here, rows), jnp.argsort(order))
    return jnp.sum(out.reshape(N, K, -1), axis=1).astype(y.dtype)


def fwd_planes(y, w, here, order, index):
    at, w = jnp.argsort(order).reshape(N, K), w.reshape(N, K)
    return functools.reduce(jnp.add, (
        weigh(fill(y, at[:, i]), w[:, i], here, jnp.arange(N) * K + i)
        for i in range(K))).astype(y.dtype)


def fwd_fold(y, w, here, order, index):
    rows, flat, same, first = index
    y, w = jnp.take(y, rows, axis=0, mode="clip"), jnp.take(w, flat)
    return folded(lambda d: weigh(on(y, d), on(w, d), here, on(flat, d)),
                  same, first).astype(y.dtype)


def fwd_fold_f32(y, w, here, order, index):
    rows, flat, same, first = index
    x = weigh(jnp.take(y, rows, axis=0, mode="clip"), jnp.take(w, flat),
              here, flat)
    return folded(lambda d: on(x, d), same, first).astype(y.dtype)


# backward of the dispatch: g [P, E] -> [N, E]
def bwd_full_width(g, w, here, order, index):
    g = fill(g, jnp.argsort(order).reshape(N, K).T)
    g = held_rows(g, here, jnp.arange(N * K).reshape(N, K).T)
    return jnp.sum(g.astype(jnp.float32), axis=0).astype(g.dtype)


def bwd_planes(g, w, here, order, index):
    at = jnp.argsort(order).reshape(N, K)
    return functools.reduce(jnp.add, (
        held_rows(fill(g, at[:, i]), here,
                  jnp.arange(N) * K + i).astype(jnp.float32)
        for i in range(K))).astype(g.dtype)


def bwd_fold(g, w, here, order, index):
    rows, flat, same, first = index
    g = jnp.take(g, rows, axis=0, mode="clip")
    return folded(lambda d: held_rows(on(g, d), here, on(flat, d)).astype(
        jnp.float32), same, first).astype(g.dtype)


def timed(fn, *args, n=20):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return out, float(np.median(times)) * 1e3


def main() -> int:
    print(f"device: {jax.devices()[0].device_kind}; N {N} k {K} E {E} P {P}")
    rng = np.random.default_rng(1)
    readings = {}
    for kind in ("one", "mixed"):
        here, order, held = routing(kind)
        y = jnp.asarray(rng.standard_normal((P, E)), jnp.bfloat16)
        w = jnp.asarray(rng.random((N * K,)), jnp.float32)
        index, ms = timed(by_token, order)
        _, sort_ms = timed(jnp.argsort, order)
        print(f"[{kind}] {held} held rows; the index by token {ms:.3f} ms, "
              f"of it argsort(order) {sort_ms:.3f}")
        for group in ((fwd_full_width, fwd_planes, fwd_fold, fwd_fold_f32),
                      (bwd_full_width, bwd_planes, bwd_fold)):
            want = None
            for fn in group:
                out, ms = timed(fn, y, w, here, order, index)
                want = out if want is None else want
                print(f"[{kind}] {fn.__name__}: {ms:.3f} ms; the full-width "
                      f"result to the bit: {bool(jnp.all(out == want))}")
                readings[f"{kind}.{fn.__name__}"] = round(ms, 3)
    print(json.dumps(readings))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
