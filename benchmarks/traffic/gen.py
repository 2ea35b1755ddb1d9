"""The one traffic generator. A mix is a data file in this directory
(`<traffic>.json`); this module turns it and `--seed` into inputs.

Every seed gets THE SAME work: sizes and arrival gaps are the quantiles of
the mix's distributions at (i + 0.5) / n. An open-loop mix also has ONE
ORDER for them, the same for every seed (`ORDER_SEED`; a mix may name
another under `order_seed`): `--seed` draws the token values, and with them
the weights' inputs, but not the schedule. Why: at the rates this system
sustains a window holds 56-96 requests, a window may not pass 51 s, and with
the order drawn from the seed six runs on the chip spread `ttft_p95_ms` by
9% (gpt2-large) and 122% (gpt2-xl), `itl_p95_ms` by 7% and
`serve_tokens_per_s` by 12%, where no bound may pass 10% (PERF.md, PR 23).
So the tails a cell reports are those of its one schedule, not of the mix
at large; PERF.md says so and shows the other orders' readings. Packed
training batches take their document order from the seed (their rate does
not depend on it).

Kinds:
  open_loop     requests (due time, prompt, output length) for a server
  packed_steps  packed training batches, as the program's packer shapes them
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ORDER_SEED = 23     # the one order of every open-loop mix that names none


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n whole sizes at the mid-quantiles of a clipped Pareto (or a
    constant)."""
    if dist["dist"] == "constant":
        return np.full((n,), int(dist["value"]), np.int64)
    if dist["dist"] != "pareto":
        raise ValueError(f"unknown size distribution {dist['dist']!r}")
    u = (np.arange(n) + 0.5) / n
    raw = dist["min"] * (1.0 - u) ** (-1.0 / dist["shape"])
    return np.clip(raw, dist["min"], dist["max"]).astype(np.int64)


def _tokens(rng: np.random.Generator, spec: dict, n: int, vocab: int
            ) -> np.ndarray:
    """n token ids below `vocab`: uniform, or Zipf with the ranks laid
    over the vocabulary by the seed (so there is a distribution to learn
    and every row of the embedding can be hit)."""
    if spec["dist"] == "uniform":
        return rng.integers(0, vocab, n, dtype=np.int64)
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {spec['dist']!r}")
    return spec["_perm"][np.searchsorted(spec["_cdf"], rng.random(n))]


def _prepare_tokens(spec: dict, rng: np.random.Generator, vocab: int) -> dict:
    spec = dict(spec)
    if spec["dist"] == "zipf":
        w = 1.0 / np.arange(1, vocab + 1) ** spec["exponent"]
        spec["_cdf"] = np.cumsum(w / w.sum())
        spec["_cdf"][-1] = 1.0
        spec["_perm"] = rng.permutation(vocab)
    return spec


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_rps"] * seconds)))


def open_loop_requests(mix: dict, seed: int, seconds: float, vocab: int
                       ) -> list[tuple[float, list[int], int]]:
    """(due_s, prompt, max_new_tokens), sorted by due time, all due inside
    [0, seconds). Poisson arrivals: the n gaps are the exponential's
    mid-quantiles, permuted by the order seed, scaled so that the
    last arrival falls inside the window. Prompt plus output never passes
    `max_total`. `seed` draws the tokens."""
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(int(mix.get("order_seed", ORDER_SEED)))
    n = n_requests(mix, seconds)
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u))
    due = np.cumsum(gaps)
    due *= seconds * (n / (n + 1.0)) / due[-1]
    plens = order.permutation(_quantiles(mix["prompt_tokens"], n))
    olens = order.permutation(_quantiles(mix["output_tokens"], n))
    olens = np.minimum(olens, mix["max_total"] - plens)
    spec = _prepare_tokens(mix["tokens"], rng, vocab)
    if mix.get("sharing", "none") != "none":
        raise ValueError("this generator knows only `sharing: none`")
    first = _first_tokens(seed, vocab)
    if n > vocab // 2:
        raise ValueError(f"{n} requests cannot all open differently")
    out = []
    for i in range(n):
        prompt = _tokens(rng, spec, int(plens[i]), vocab).tolist()
        prompt[0] = int(first[i])
        out.append((float(due[i]), prompt, int(olens[i])))
    return out


def _first_tokens(seed: int, vocab: int) -> np.ndarray:
    """A permutation of the vocabulary: request i of the window opens with
    its i-th entry, warm-up request j with its j-th from the end, so no two
    prompts of a run share even one leading token (`sharing: none` means
    none: the program's prefix cache counts a single shared token a hit)."""
    return np.random.default_rng([int(seed), 0xF125]).permutation(vocab)


def warmup_prompt(mix: dict, seed: int, index: int, length: int, vocab: int
                  ) -> list[int]:
    """A distinct random prompt for warm-up request `index`."""
    rng = np.random.default_rng([int(seed), 0x57A2, int(index)])
    out = rng.integers(0, vocab, int(length)).tolist()
    if mix.get("sharing", "none") == "none":
        out[0] = int(_first_tokens(seed, vocab)[-1 - index])
    return out


def packed_batches(mix: dict, seed: int, vocab: int):
    """Endless packed batches {input_ids, segment_ids, position_ids,
    loss_mask}, each [batch, seq_len], no two rows alike. Documents are
    cut from a cycle of `docs_per_cycle` lengths (the same multiset for
    every seed, permuted); a row is filled greedily and its last document
    is cut where the row ends, so no position is padding."""
    rng = np.random.default_rng(int(seed))
    B, T = int(mix["batch"]), int(mix["seq_len"])
    lens = rng.permutation(_quantiles(mix["doc_tokens"],
                                      int(mix["docs_per_cycle"])))
    spec = _prepare_tokens(mix["tokens"], rng, vocab)
    k = 0
    while True:
        ids = _tokens(rng, spec, B * T, vocab).astype(np.int32).reshape(B, T)
        seg = np.zeros((B, T), np.int32)
        pos = np.zeros((B, T), np.int32)
        mask = np.zeros((B, T), np.float32)
        for b in range(B):
            fill, s = 0, 0
            while fill < T:
                take = min(int(lens[k % len(lens)]), T - fill)
                k += 1
                seg[b, fill:fill + take] = s
                pos[b, fill:fill + take] = np.arange(take)
                # the packer's convention: 1.0 on a token whose successor
                # is in the same document
                mask[b, fill:fill + take - 1] = 1.0
                fill += take
                s += 1
        yield {"input_ids": ids, "segment_ids": seg, "position_ids": pos,
               "loss_mask": mask}
