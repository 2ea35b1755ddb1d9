"""Driver `open_loop_gqa_window_moe`: `open_loop`'s window for the AFMoE
family (windowed rotary attention layers beside global NoPE ones, every one
gated; routed + shared SwiGLU experts, all held), with short and long
prompts in ONE queue. The window, the drain and the sampling of finished
requests are `open_loop`'s own functions, the counting traced slice is
`open_loop_mla_moe`'s, the configuration's plain values
`open_loop_gdn_mla_moe`'s and the warm-up's stepping
`sessions_kda_gqa_moe`'s, all imported; what is this family's is here: the
engine build (the program's preset checked key by key against the
configuration file, the weights made layer by layer from the seed by the
family's reference), the DECLARED buckets' warm-up (long prompts run in
chunks of `prefill_chunk` over the two page groups), the scoring of what
was served by that reference, the probe of the window's edge, which served
logits cannot see (`window_edge_gap`), the check of which paths the largest decode
program lowered to, and what the two page groups held while the slice was
open.

`build_and_warm`, `serve_window` and `window_line` are exposed under
`open_loop`'s names, so a tool written against that driver takes this
one."""

from __future__ import annotations

import dataclasses
import re
import time

import numpy as np

from . import common, open_loop, open_loop_mla_moe
from .common import Check, Ctx, Run, check_le
from .open_loop import serve_window, window_line  # noqa: F401  (re-exported)
from .open_loop_gdn_mla_moe import _plain
from .sessions_kda_gqa_moe import _drive

COUNTERS = ("serve.moe.rows", "serve.moe.experts_touched",
            "serve.kv.window.live_tokens", "serve.kv.window.pages_released")
# leaves the program holds bare (no Dense or norm module around them), and
# those of them it holds in float32 whatever the parameters' dtype
_BARE_LEAVES = ("router", "expert_bias", "experts_gate_up", "experts_down")
_FLOAT32_LEAVES = ("router", "expert_bias")
# the program's own fields of its config: everything else is published
_PROGRAM_KEYS = ("dtype", "param_dtype", "logits_dtype", "attention_impl",
                 "vocab_multiple", "remat", "scan_blocks", "experts_held")
KERNELS = ("paged_window_decode_attention", "paged_decode_attention", "gmm")
PAD_MULTIPLE = 2048      # the reference's row block: few shapes to compile


def make_model(config: dict):
    """The program's model for a configuration file: every published key
    of the preset must stand in the file with the preset's value."""
    from distributedtraining_tpu.models import afmoe

    pc = afmoe.PRESETS[config["preset"]]
    for f in dataclasses.fields(pc):
        if f.name in _PROGRAM_KEYS:
            continue
        value = _plain(getattr(pc, f.name))
        if f.name not in config or config[f.name] != value:
            raise SystemExit(
                f"bench: FAIL: {config['name']}.{f.name} = "
                f"{config.get(f.name)!r} but preset {config['preset']} runs "
                f"{value!r}")
    if list(config.get("experts_held", (0, pc.num_experts))) != list(
            pc.experts_held):
        raise SystemExit("bench: FAIL: the experts held differ from the "
                         "preset's")
    if config["assumed"]["padded_vocab"] != pc.padded_vocab:
        raise SystemExit("bench: FAIL: padded_vocab differs from the preset")
    dt = config["dtypes"]
    if (dt["param"], dt["compute"], dt["logits"]) != (
            pc.param_dtype, pc.dtype, pc.logits_dtype):
        raise SystemExit("bench: FAIL: dtypes differ from the preset")
    return afmoe.make_model(pc)


def to_program_layer(leaves: dict) -> dict:
    """One layer of the reference's flat leaves -> the program's Flax
    subtree. The arrays are handed over; a leaf the program holds in
    float32 is widened (it is a bfloat16 number: nothing is rounded)."""
    import jax.numpy as jnp
    out = {}
    for name, x in leaves.items():
        if name.endswith(("_layernorm", "_norm")):
            out[name] = {"scale": x.astype(jnp.float32)}
        elif name in _BARE_LEAVES:
            out[name] = (x.astype(jnp.float32) if name in _FLOAT32_LEAVES
                         else x)
        else:
            out[name] = {"kernel": x}
    return out


def program_params(mcfg: dict, seed: int, dtype) -> dict:
    """The program's tree with the reference's weights, made in `dtype`
    layer by layer (they are bfloat16 numbers: nothing is rounded again)."""
    import jax.numpy as jnp
    from reference import afmoe as reference

    top = reference.top_weights(mcfg, seed, dtype)
    tree = {"embed_tokens": top["embed_tokens"], "lm_head": top["lm_head"],
            "norm": {"scale": top["norm"].astype(jnp.float32)}}
    for i in range(mcfg["num_hidden_layers"]):
        tree[f"layer_{i}"] = to_program_layer(
            reference.layer_weights(mcfg, seed, i, dtype))
    return tree


# ---------------------------------------------------------------------------
# engine, warm-up
# ---------------------------------------------------------------------------

def _build_engine(ctx: Ctx, params_hook=None):
    from distributedtraining_tpu.engine.serve import GenerationEngine
    from reference import afmoe as reference

    model, pc = make_model(ctx.config)
    params = program_params(reference.model_cfg(ctx.config), ctx.seed,
                            pc.storage_dtype())
    if params_hook is not None:
        params = params_hook(params)
    e = ctx.cell["engine"]
    return GenerationEngine(
        model, params, revision="bench", max_slots=e["max_slots"],
        page_size=e["page_size"], pool_pages=e["pool_pages"],
        window_pool_pages=e["window_pool_pages"],
        max_seq_len=e["max_seq_len"], max_new_tokens=e["max_new_tokens"],
        eos_id=None, prefix_cache=e["prefix_cache"],
        prefill_chunk=e["prefill_chunk"])


def _warm_up(ctx: Ctx, engine) -> None:
    """Compile exactly the cell's programs. The rungs are DECLARED to the
    engine first, so a need pads up to them whatever the order; then each
    program is met once: a cold prefill bucket by a prompt just past the
    next smaller rung, a suffix bucket by a prompt one chunk and a tail
    long (the tail just past the next smaller suffix rung), the decode
    buckets by short requests admitted in stages so that the active count
    just passes each slot rung in turn. The window group's table has one
    width a program family and needs no declaration."""
    from traffic import gen

    w, e = ctx.cell["warmup"], ctx.cell["engine"]
    P, vocab = e["page_size"], ctx.config["vocab_size"]
    chunk = e["prefill_chunk"]
    engine.declare_buckets(
        prefill_pages=[t // P for t in w["prefill_tokens"]],
        suffix_pages=[t // P for t in w["suffix_tokens"]],
        table_pages=[w["table_pages"]], decode_pages=[w["table_pages"]])
    n = 0

    def prompt(length: int) -> list:
        nonlocal n
        n += 1
        return gen.warmup_prompt(ctx.mix, ctx.seed, n, length, vocab)

    t0 = time.perf_counter()
    below = 0
    for t in sorted(w["prefill_tokens"]):
        _drive(engine, [engine.submit(prompt(below + 1), 1)], 8)
        below = t
    below = 0
    for t in sorted(w["suffix_tokens"]):
        # one cold chunk, then a continuation that needs rung t
        _drive(engine, [engine.submit(prompt(chunk + below + 1), 1)], 8)
        below = t
    t1 = time.perf_counter()
    stages = [open_loop._just_into(s, e["max_slots"])
              for s in sorted(w["decode_slots"])]
    reqs, active = [], 0
    for want in stages:
        # alive through every later stage's step, then done
        reqs += [engine.submit(prompt(3 * P), len(stages) + 2)
                 for _ in range(want - active)]
        active = want
        engine.step()
    _drive(engine, reqs, 16)
    print(f"bench: warm-up: prefill and suffix buckets {t1 - t0:.1f}s, "
          f"decode buckets {time.perf_counter() - t1:.1f}s, {n} requests, "
          f"compile+load {ctx.compiles.seconds:.1f}s so far", flush=True)


def build_and_warm(ctx: Ctx, warm: bool = True, params_hook=None):
    t0 = time.perf_counter()
    engine = _build_engine(ctx, params_hook)
    print(f"bench: engine built in {time.perf_counter() - t0:.1f}s",
          flush=True)
    if warm:
        _warm_up(ctx, engine)
    return engine


class Holdings:
    """The engine as `open_loop.serve_window` drives it, which also reads
    what the two page groups hold after every step taken while the traced
    slice is open: `held_pct` is the pages the window layers hold over the
    pages the global layer holds for the same live slots, a mean over
    those steps (100: nothing was released)."""

    def __init__(self, engine, trace_slice):
        self._engine, self._slice = engine, trace_slice
        self._ratios: list[float] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step(self) -> dict:
        out = self._engine.step()
        if self._slice.state == "on":
            kv, window, _ = self._engine.kv_holdings()
            if kv:
                self._ratios.append(window / kv)
        return out

    @property
    def held_pct(self) -> float | None:
        return (100.0 * sum(self._ratios) / len(self._ratios)
                if self._ratios else None)


# ---------------------------------------------------------------------------
# scoring
# ---------------------------------------------------------------------------

def score_served(mcfg: dict, seed: int, sample: list, margin_floor: float,
                 precision: str = "float32") -> dict:
    """The reference's full forward over each sampled request's prompt and
    served tokens, layer at a time; the readings are over the served
    tokens alone (the prefill's first-token row and every decode row): the
    widest gap by which a served token's logit lies below the reference's
    best, over the positions whose smallest routing margin (the 8th
    against the 9th `s + b`, over the expert layers) is at least
    `margin_floor`, the mean gap over all, and the share of positions
    under the floor."""
    from reference import afmoe as reference

    if not sample:
        return {"served_gap": 0.0, "served_mean_gap": 0.0, "tokens": 0,
                "requests": 0, "near_tie_share": 0.0, "longest_context": 0}
    seqs, spans = [], []
    for prompt, served in sample:
        seq = list(prompt) + list(served)
        spans.append((len(prompt) - 1, len(seq) - 1))
        seqs.append(seq + [0] * (-len(seq) % PAD_MULTIPLE))
    got = reference.score_sequences(mcfg, seed, seqs, spans, precision)
    gaps, margins = got["gaps"], got["margins"]
    clear = margins >= margin_floor
    out = {"served_gap": float(gaps[clear].max()) if clear.any() else 0.0,
           "served_gap_all": float(gaps.max()),
           "served_mean_gap": float(gaps.mean()),
           "near_tie_share": float(1.0 - clear.mean()),
           "tokens": int(len(gaps)), "requests": len(sample),
           "longest_context": max(len(p) for p, _ in sample),
           "arrays": (gaps, margins)}   # for tools/gqa_window_moe.py's table
    if "control_gaps" in got:
        ctl = got["control_gaps"]
        out.update(control_gap=float(ctl[clear].max()) if clear.any()
                   else 0.0, control_mean_gap=float(ctl.mean()))
    return out


def window_edge_gap(cfg, engine_cfg: dict, seed: int) -> dict:
    """The window group at the window's EDGE, which served logits cannot
    see (a sliding layer's softmax over 2,048 keys of normed random q and k
    is near uniform: one key more or less moves a logit less than bfloat16
    does). The group's own parts at the cell's geometry, without the
    model's layers around them: `kv_pool.WindowPages` driven as the engine
    drives it (a prompt written in chunks through `write_window_rows` over
    `tail`'s shifted table, pages given back at every chunk's end and at
    the decode step's growth), then what a sliding layer calls
    (`family.paged_attention(window=)`: on the chip the windowed Mosaic
    kernel for a decode step, the blocked suffix for a chunk), with TWO
    keys made dominant for each query: the last position inside its window
    (`newest - window + 1`, value +1) and the first outside it (`newest -
    window`, value -1). Against float64 attention under the rule written
    out here (`i - window < j <= i`). A window one key short sees neither
    (the output falls from +1 to about 0), one key long or none at all
    sees both (about 0), a table whose first row lags its pages reads
    other rows. -> the widest distance, for the decode step and the chunk."""
    import jax
    import jax.numpy as jnp

    from distributedtraining_tpu.engine import kv_pool
    from distributedtraining_tpu.models import family

    Hq, Hkv, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    W, P = cfg.sliding_window, engine_cfg["page_size"]
    chunk, dtype = engine_cfg["prefill_chunk"], cfg.compute_dtype()
    rng = np.random.default_rng([int(seed), 0xED6E])
    # the decode queries' positions: inside the first window; the inside
    # edge on a page's first row (the outside key's page has gone back),
    # mid-page, on its last row. Then the chunk's: T rows from `ahead`.
    newest = [W // 2, W - 1 + 5 * P, 3 * W - 1 + P // 2, 2 * W + P - 2]
    T = min(chunk, 256)
    ahead = 2 * W + P // 2 + 1
    lengths = [n + 1 for n in newest] + [ahead + T]
    group = kv_pool.WindowPages(1 + len(lengths) * kv_pool.window_table_pages(
        W, chunk, P), P, W, chunk)
    group.pools = kv_pool.make_pool(1, group.total + 1, P,
                                    (Hkv * D, Hkv * D), dtype)

    def rounded(x):         # what the pool's dtype holds, as float64
        return np.asarray(jnp.asarray(x, dtype).astype(jnp.float32),
                          np.float64)

    ks = [rounded(rng.standard_normal((n, Hkv, D))) for n in lengths]
    vs = [rounded(rng.standard_normal((n, Hkv, D))) for n in lengths]
    # one query a K/V head (its group's query heads all ask the same), so
    # that a key can be made dominant for every head at once
    qs = [rng.choice([-1.0, 1.0], (1, Hkv, D)) for _ in newest] \
        + [rng.choice([-1.0, 1.0], (T, Hkv, D))]
    for k, v, q, first in zip(ks, vs, qs, newest + [ahead]):
        for t in {0, len(q) - 1}:
            for j, value in ((first + t - W + 1, 1.0),
                             (first + t - W, -1.0)):
                if j >= 0:
                    k[j], v[j] = 2.0 * q[t], value

    @jax.jit
    def write(win, k, v, pos, valid):
        inter = {"probe": {"kv_cache": ((k, v),)}}
        return kv_pool.write_window_rows(win, inter, ["probe"], pos,
                                         valid)[0]

    def cast(x):
        return jnp.asarray(x, dtype)

    for rid, (k, v, q) in enumerate(zip(ks, vs, qs)):
        cached = len(k) - len(q)    # a query's own row is handed in fresh
        assert group.admit(rid, cached)
        for lo in range(0, cached, chunk):
            hi = min(lo + chunk, cached)
            assert group.extend(rid, hi)
            rows = np.zeros((2, 1, chunk, Hkv, D))
            rows[0, 0, :hi - lo], rows[1, 0, :hi - lo] = k[lo:hi], v[lo:hi]
            pos = lo + np.arange(chunk, dtype=np.int32)[None, :]
            group.pools = write(group.tail([rid]), cast(rows[0]),
                                cast(rows[1]), pos, pos < hi)
            group.release_behind(rid, hi)

    def attend(rids, width, q, k_new, v_new, lens):
        win = group.tail(rids, width)
        (k_pages,), (v_pages,), tables, starts = win

        @jax.jit
        def run(k_pages, v_pages, q, k_new, v_new):
            return family.paged_attention(
                q, k_pages, v_pages, tables, lens - starts, k_new, v_new,
                window=W)
        heads = jnp.repeat(cast(q), Hq // Hkv, axis=2)
        return np.asarray(run(k_pages, v_pages, heads, cast(k_new),
                              cast(v_new)).astype(jnp.float32), np.float64)

    def expected(k, v, q, first):
        out = np.zeros((len(q), Hkv, D))
        for t in range(len(q)):
            i = first + t
            lo = max(0, i - W + 1)
            s = np.einsum("hd,jhd->hj", q[t], k[lo:i + 1]) * D ** -0.5
            p = np.exp(s - s.max(-1, keepdims=True))
            out[t] = np.einsum("hj,jhd->hd", p / p.sum(-1, keepdims=True),
                               v[lo:i + 1])
        return np.repeat(out, Hq // Hkv, axis=1)

    steps = list(range(len(newest)))
    for rid in steps:               # a decode step's growth
        group.release_behind(rid, newest[rid])
        assert group.extend(rid, newest[rid])
    got = attend(steps, group.decode_pages, np.stack(qs[:-1]),
                 np.stack([k[-1:] for k in ks[:-1]]),
                 np.stack([v[-1:] for v in vs[:-1]]),
                 np.asarray(newest, np.int32))
    want = np.stack([expected(k, v, q, n)
                     for k, v, q, n in zip(ks, vs, qs, newest)])
    decode = float(np.abs(got - want).max())
    rid = len(newest)
    assert group.extend(rid, ahead + T)
    got = attend([rid], 0, qs[-1][None], ks[-1][None, ahead:],
                 vs[-1][None, ahead:], np.asarray([ahead], np.int32))
    suffix = float(np.abs(got[0] - expected(ks[-1], vs[-1], qs[-1],
                                            ahead)).max())
    for rid in range(len(lengths)):
        group.release(rid)
    group.check_held([])
    return {"decode": decode, "suffix": suffix}


def decode_paths(engine) -> dict:
    """Which paths the largest decode program the engine compiled took:
    Mosaic calls of the windowed and the plain paged decode attention and
    of the grouped expert product, by their instructions' own names in the
    COMPILED program. Compiled from the persistent cache, after the
    window. Reads `_decode_progs` as `open_loop._decode_mosaic_calls` does
    (the engine has no public listing of its programs yet)."""
    if not engine._decode_progs:
        return {}
    (slots, pages), prog = max(engine._decode_progs.items())
    k_pages, v_pages = engine._kv
    text = prog.lower(
        engine._params, k_pages, v_pages,
        np.zeros((slots, pages), np.int32), np.zeros((slots,), np.int32),
        np.zeros((slots,), np.int32),
        *engine._window.tail([], engine._window.decode_pages, slots)
    ).compile().as_text()
    own = [ln.split(" = ")[0].strip().removeprefix("ROOT ")
           for ln in text.splitlines() if common.MOSAIC_CALL in ln]
    return {k: sum(bool(re.fullmatch(rf"%?{k}(\.\d+)?", name))
                   for name in own) for k in KERNELS}


class CountingSlice(open_loop_mla_moe.CountingSlice):
    """`open_loop_mla_moe.CountingSlice` over this family's counters:
    `moe` holds what the programs inside the slice counted."""

    @staticmethod
    def _read() -> dict:
        from distributedtraining_tpu.utils import obs
        reg = obs.registry()
        out = {}
        for name in COUNTERS:
            c = reg.peek(name)
            out[name] = float(c.value) if c is not None else 0.0
        return out


def run(ctx: Ctx) -> Run:
    from distributedtraining_tpu.utils import obs
    from reference import afmoe as reference
    from traffic import gen

    cell = ctx.cell
    spans = common.Spans()
    trace_slice = CountingSlice(ctx, spans)
    engine = build_and_warm(ctx)
    schedule = gen.open_loop_requests(ctx.mix, ctx.seed, ctx.seconds,
                                      ctx.config["vocab_size"])
    print(f"bench: window offers {len(schedule)} requests at "
          f"{ctx.mix['rate_rps']} req/s, "
          f"{sum(len(p) for _, p, _ in schedule)} prompt tokens, longest "
          f"{max(len(p) for _, p, _ in schedule)}", flush=True)
    if ctx.trace:
        obs.configure(common.NullSink(), role="server")
    # the selection bias is balanced by the REFERENCE's forward while the
    # weights are made: its seconds are the reference's, as the scoring's
    # after the window are, and no set-up of the system's
    balancing_s = reference.balancing_seconds()
    print(f"bench: selection bias balanced by the reference in "
          f"{balancing_s:.1f}s, kept out of setup_s", flush=True)
    setup_s = time.perf_counter() - ctx.t_process - balancing_s
    ctx.compiles.mark()
    watched = Holdings(engine, trace_slice)
    w = serve_window(ctx, watched, schedule, spans, trace_slice)
    compiles_in_window = ctx.compiles.since_mark()
    peak = common.memory_peak_bytes()
    obs_snap = common.obs_snapshot(obs) if ctx.trace else {}
    if ctx.trace:
        obs.reset()
    print(f"bench: serve {window_line(w)}", flush=True)
    print(f"bench: page groups at the close: kv free {engine.pool.free} of "
          f"{engine.pool.total}, window free {engine._window.free} of "
          f"{engine._window.total}; window pages over kv pages in the "
          f"slice {watched.held_pct}", flush=True)

    finished, left = w.pop("finished"), w.pop("left")
    bad_status = sum(1 for tr in finished if tr.req.status != "done"
                     or len(tr.req.tokens) != tr.req.max_new_tokens)
    failed = bad_status + len(left)
    sample = open_loop._sample_finished(
        [tr for tr in finished if tr.req.status == "done"], ctx.seed,
        cell["check"]["sample_requests"])
    paths = decode_paths(engine)
    held_pct, program_cfg = watched.held_pct, engine.cfg

    engine.close()
    del engine, watched, finished, left
    common.free_device_memory()
    edge = window_edge_gap(program_cfg, cell["engine"], ctx.seed)
    print(f"bench: window edge: widest distance from float64 attention "
          f"{edge}", flush=True)
    t_ref = time.perf_counter()
    score = score_served(reference.model_cfg(ctx.config), ctx.seed, sample,
                         cell["check"]["margin_floor"])
    print(f"bench: reference scored {score['tokens']} served tokens of "
          f"{score['requests']} requests (longest context "
          f"{score['longest_context']}) in "
          f"{time.perf_counter() - t_ref:.1f}s; widest gap over all "
          f"positions {score.get('served_gap_all', 0.0)!r}", flush=True)

    limits = cell["limits"]
    checks = [
        check_le("served_logit_gap", score["served_gap"],
                 limits["served_logit_gap"],
                 f"widest over the greedy tokens of {score['requests']} "
                 f"requests whose routing margin is >= "
                 f"{cell['check']['margin_floor']}"),
        check_le("served_mean_gap", score["served_mean_gap"],
                 limits["served_mean_gap"],
                 f"mean over all {score['tokens']}"),
        check_le("near_tie_share", score["near_tie_share"],
                 limits["near_tie_share"],
                 "share of those positions under the margin"),
        check_le("window_edge_gap", max(edge.values()),
                 limits["window_edge_gap"],
                 "the window group's parts with the keys at the window's "
                 "edge made dominant"),
        Check("sample_tokens", score["tokens"], cell["check"]["min_tokens"],
              score["tokens"] >= cell["check"]["min_tokens"]),
        Check("sample_longest_context", score["longest_context"],
              cell["check"]["min_longest_context"],
              score["longest_context"]
              > cell["check"]["min_longest_context"],
              "a sampled request on a context past this many tokens"),
        check_le("compiles_in_window", compiles_in_window, 0),
    ]
    for name, want in cell["engine"]["expect_paths"].items():
        got = paths.get(name, -1)
        checks.append(Check(f"decode_path.{name}", got, want, got == want,
                            "Mosaic calls in the largest decode program"))
    e2e = {"serve_tokens_per_s": w["tokens_in_window"] / w["window_s"]}
    moe = trace_slice.moe
    stats = dict(w, obs=obs_snap,
                 window_held_pct=held_pct,
                 traced_moe_rows=moe.get(COUNTERS[0], 0.0),
                 traced_moe_experts=moe.get(COUNTERS[1], 0.0),
                 traced_window_live_tokens=moe.get(COUNTERS[2], 0.0),
                 traced_window_pages_released=moe.get(COUNTERS[3], 0.0))
    return Run(setup_s=setup_s, end_to_end=e2e, attempted=w["offered"],
               failed=failed, checks=checks, stats=stats,
               memory_peak_bytes=peak, window_s=w["window_s"],
               trace_dir=trace_slice.result_dir())
