"""Driver `sessions_kda_gqa_moe`: multi-turn SESSIONS through one
`GenerationEngine` with the prefix cache on, for the Solar-Open2 family
(per-channel delta-rule layers beside gated NoPE grouped-query attention,
routed SwiGLU experts of which the chip holds a share).

The traffic kind `sessions` is generated here (`plan`; `traffic/gen.py`
gives the quantiles and the order seed, and is not edited). At SET-UP every
session's history is submitted through the engine and left registered:
pages and a state snapshot. The timed window offers TURNS on one Poisson
schedule; a turn goes to the session that has waited longest among those
whose last answer is complete, its prompt is the session's WHOLE text so
far (history, earlier messages, the engine's own earlier answers) plus a
new message, so every admission in the window is a prefix hit: a snapshot
restore and a short continuation over thousands of cached tokens. Every
turn is timed from when it was DUE.

What is this family's is here too, as in `open_loop_gdn_mla_moe`: the
engine build (the program's preset checked key by key against the
configuration file, the weights made layer by layer from the seed by the
family's reference), the declared buckets' warm-up, the scoring of what was
served by that reference over the session's whole text, the check of which
paths the largest decode program lowered to, and the traced slice's sums of
the program's counters.

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
from .open_loop import window_line  # noqa: F401  (re-exported)
from .open_loop_gdn_mla_moe import _plain

COUNTERS = ("serve.moe.rows", "serve.moe.rows_elsewhere",
            "serve.moe.experts_touched", "serve.kda.slot_steps")
STATE_GAUGE = "serve.kda.state_bytes"
# leaves the program holds bare (no Dense module around them)
_BARE_LEAVES = ("conv1d_weight", "A_log", "dt_bias", "o_norm", "router",
                "e_score_correction_bias", "experts_gate_up",
                "experts_down")
# leaves the program holds in float32 whatever the parameters' dtype
_FLOAT32_LEAVES = ("conv1d_weight", "A_log", "dt_bias", "o_norm", "router",
                   "e_score_correction_bias")
# the program's own fields of its config: everything else is published
_PROGRAM_KEYS = ("dtype", "param_dtype", "logits_dtype", "attention_impl",
                 "vocab_multiple", "remat", "scan_blocks", "experts_held",
                 "chunk_size", "kda_low_rank")
KERNELS = ("gdn_decode_update", "paged_decode_attention", "gmm")
PAD_MULTIPLE = 2048      # the reference's row block: few shapes to compile


def make_model(config: dict):
    """The program's model for a configuration file: every published key
    of the preset must stand in the file with the preset's value. The
    file's `n_routed_experts` counts the experts HELD here; the router's
    width is the published count beside it."""
    from distributedtraining_tpu.models import solar_open2

    pc = solar_open2.PRESETS[config["preset"]]
    want = {f.name: _plain(getattr(pc, f.name))
            for f in dataclasses.fields(pc) if f.name not in _PROGRAM_KEYS}
    want["n_routed_experts"] = pc.experts_held[1]
    for name, value in want.items():
        if name not in config or config[name] != value:
            raise SystemExit(
                f"bench: FAIL: {config['name']}.{name} = "
                f"{config.get(name)!r} but preset {config['preset']} runs "
                f"{value!r}")
    if (config.get("published", {}).get("n_routed_experts",
                                        config["n_routed_experts"])
            != pc.n_routed_experts
            or list(config.get("experts_held", (0, pc.n_routed_experts)))
            != list(pc.experts_held)):
        raise SystemExit("bench: FAIL: the experts held differ from the "
                         "preset's share")
    assumed = config["assumed"]
    if (assumed["padded_vocab"], assumed["kda_low_rank"]) != (
            pc.padded_vocab, pc.kda_low_rank):
        raise SystemExit("bench: FAIL: padded_vocab or kda_low_rank "
                         "differs from the preset")
    dt = config["dtypes"]
    if (dt["param"], dt["compute"], dt["logits"]) != (
            pc.param_dtype, pc.dtype, pc.logits_dtype):
        raise SystemExit("bench: FAIL: dtypes differ from the preset")
    return solar_open2.make_model(pc)


def to_program_layer(leaves: dict) -> dict:
    """One layer of the reference's flat leaves -> the program's Flax
    subtree. The arrays are handed over; a leaf the program holds in
    float32 is widened (it is a bfloat16 number: nothing is rounded)."""
    import jax.numpy as jnp
    out = {}
    for name, x in leaves.items():
        if name.endswith("_norm") and name != "o_norm":
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
    from reference import solar_open2 as reference

    top = reference.top_weights(mcfg, seed, dtype)
    tree = {"embed_tokens": top["embed_tokens"], "lm_head": top["lm_head"],
            "norm": {"scale": top["norm"].astype(jnp.float32)}}
    for i in range(mcfg["num_hidden_layers"]):
        tree[f"layer_{i}"] = to_program_layer(
            reference.layer_weights(mcfg, seed, i, dtype))
    return tree


# ---------------------------------------------------------------------------
# the traffic kind `sessions`
# ---------------------------------------------------------------------------

def plan(mix: dict, seed: int, seconds: float, vocab: int
         ) -> tuple[list, list]:
    """(histories, turns) of one run. `histories[s]` is session s's
    tokens before the window; `turns[i] = (due_s, message tokens, answer
    length)`, sorted by due time, all due inside [0, seconds). Sizes and
    gaps are the mid-quantiles of the mix's distributions in ONE order for
    every seed (`traffic/gen.py` says why); `seed` draws the token values
    alone. No two sessions share a leading token: nothing is shared
    BETWEEN sessions."""
    from traffic import gen

    if mix["kind"] != "sessions" or mix.get("sharing", "none") != "none":
        raise ValueError("this driver generates `kind: sessions` with "
                         "nothing shared between sessions")
    order = np.random.default_rng(int(mix.get("order_seed", gen.ORDER_SEED)))
    S = int(mix["sessions"])
    hist = order.permutation(gen._quantiles(mix["history_tokens"], S))
    n = gen.n_requests(mix, seconds)
    u = (np.arange(n) + 0.5) / n
    due = np.cumsum(order.permutation(-np.log1p(-u)))
    due *= seconds * (n / (n + 1.0)) / due[-1]
    msgs = order.permutation(gen._quantiles(mix["message_tokens"], n))
    outs = order.permutation(gen._quantiles(mix["output_tokens"], n))
    first = gen._first_tokens(seed, vocab)
    histories = []
    for s in range(S):
        rng = np.random.default_rng([int(seed), 0x5E55, s])
        toks = rng.integers(0, vocab, int(hist[s])).tolist()
        toks[0] = int(first[s])
        histories.append(toks)
    turns = []
    for i in range(n):
        rng = np.random.default_rng([int(seed), 0x7A1C, i])
        turns.append((float(due[i]),
                      rng.integers(0, vocab, int(msgs[i])).tolist(),
                      int(outs[i])))
    return histories, turns


# ---------------------------------------------------------------------------
# engine, warm-up, set-up
# ---------------------------------------------------------------------------

def _build_engine(ctx: Ctx, params_hook=None):
    from distributedtraining_tpu.engine.serve import GenerationEngine
    from reference import solar_open2 as reference

    model, pc = make_model(ctx.config)
    params = program_params(reference.model_cfg(ctx.config), ctx.seed,
                            pc.storage_dtype())
    if params_hook is not None:
        params = params_hook(params)
    e = ctx.cell["engine"]
    return GenerationEngine(
        model, params, revision="bench", max_slots=e["max_slots"],
        page_size=e["page_size"], pool_pages=e["pool_pages"],
        max_seq_len=e["max_seq_len"], max_new_tokens=e["max_new_tokens"],
        eos_id=None, prefix_cache=e["prefix_cache"],
        snapshot_rows=e["snapshot_rows"], prefill_chunk=e["prefill_chunk"])


def _drive(engine, reqs: list, steps: int) -> None:
    for _ in range(steps):
        if all(r.done_evt.is_set() for r in reqs):
            return
        engine.step()
    raise SystemExit("bench: FAIL: a warm-up request did not finish")


def _warm_up(ctx: Ctx, engine) -> None:
    """Compile exactly the cell's programs. The rungs are DECLARED to the
    engine first, so a need pads up to them whatever the order; then each
    program is met once: the cold prefill bucket and the suffix buckets
    by prompts one chunk and a tail long (the tail just past the next
    smaller suffix rung), the decode buckets by short requests admitted
    in stages so that the active count just passes each slot rung in
    turn, the snapshot's restore and the page copy by one prompt that
    extends an earlier one. What the warm-up cached is then forgotten."""
    from traffic import gen

    w, e = ctx.cell["warmup"], ctx.cell["engine"]
    P, vocab = e["page_size"], ctx.config["vocab_size"]
    chunk = e["prefill_chunk"]
    engine.declare_buckets(
        prefill_pages=[chunk // P],
        suffix_pages=[t // P for t in w["suffix_tokens"]],
        table_pages=[w["table_pages"]], decode_pages=[w["table_pages"]])
    n = 0

    def prompt(length: int) -> list:
        nonlocal n
        n += 1
        return gen.warmup_prompt(ctx.mix, ctx.seed, n, length, vocab)

    t0 = time.perf_counter()
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
    t2 = time.perf_counter()
    # a turn on top of a registered prompt that ended inside a page: the
    # snapshot's restore, the page's copy, a suffix over cached pages
    base = prompt(5 * P + 3)
    first = engine.submit(base, 2)
    _drive(engine, [first], 8)
    _drive(engine, [engine.submit(base + list(first.tokens) + prompt(P), 1)],
           8)
    if engine.prefix_hits != 1:
        raise SystemExit("bench: FAIL: the warm-up's second turn did not "
                         "hit the prefix cache")
    engine.flush_prefix_cache()
    print(f"bench: warm-up: prefill and suffix buckets {t1 - t0:.1f}s, "
          f"decode buckets {t2 - t1:.1f}s, restore and copy "
          f"{time.perf_counter() - t2:.1f}s, {n} requests, compile+load "
          f"{ctx.compiles.seconds:.1f}s so far", flush=True)


@dataclasses.dataclass
class _Session:
    text: list                  # every token so far: the next prompt's head
    idle_since: float           # when its last answer was complete
    busy: bool = False
    turns: int = 0


def _set_up_sessions(ctx: Ctx, engine, histories: list) -> list:
    """Every session's history through the engine, to completion and left
    registered (pages and snapshot). One token is asked for (a request
    must ask for one) and thrown away: the session's text is its history."""
    t0 = time.perf_counter()
    sessions = []
    for s, hist in enumerate(histories):
        _drive(engine, [engine.submit(hist, 1)], 8)
        # set-up order is the first turns' order: the longest wait first
        sessions.append(_Session(text=list(hist), idle_since=float(
            s - len(histories))))
    tokens = sum(len(h) for h in histories)
    print(f"bench: set-up: {len(histories)} histories, {tokens} tokens "
          f"prefilled and registered in {time.perf_counter() - t0:.1f}s; "
          f"free pages {engine.pool.free} of {engine.pool.total}, "
          f"snapshots evicted {engine.prefix_snapshots_evicted}",
          flush=True)
    return sessions


def build_and_warm(ctx: Ctx, warm: bool = True, params_hook=None):
    t0 = time.perf_counter()
    engine = _build_engine(ctx, params_hook)
    print(f"bench: engine built in {time.perf_counter() - t0:.1f}s",
          flush=True)
    if warm:
        _warm_up(ctx, engine)
    return engine


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Turn:
    req: object
    due: float
    submitted: float
    session: _Session
    seen: int = 0
    times: list = dataclasses.field(default_factory=list)


def serve_window(ctx: Ctx, engine, schedule: tuple, spans, trace_slice
                 ) -> dict:
    """Offer the turns of `schedule = (sessions, turns)` on the wall clock
    for `ctx.seconds`, then drain for at most the cell's `drain_s`.
    Everything the window saw, under `open_loop.serve_window`'s names."""
    sessions, turns = schedule
    clock = time.perf_counter
    cap = ctx.mix["max_total"]
    inflight: list[_Turn] = []
    finished: list[_Turn] = []
    step_ms: list[float] = []
    w = {"tokens_in_window": 0, "traced_live_tokens": 0, "active_sum": 0,
         "inflight_at_half": 0, "queued_at_half": 0, "prompt_tokens": 0,
         "waited_for_a_session": 0}
    nxt = 0

    def account(t: float, in_window: bool) -> int:
        still, live = [], 0
        for tr in inflight:
            n = len(tr.req.tokens)
            if n > tr.seen:
                tr.times.extend([t] * (n - tr.seen))
                if in_window:
                    w["tokens_in_window"] += n - tr.seen
                tr.seen = n
            if tr.req.done_evt.is_set():
                finished.append(tr)
                tr.session.text = list(tr.req.prompt) + list(tr.req.tokens)
                tr.session.busy = False
                tr.session.idle_since = t
            else:
                still.append(tr)
                live += (len(tr.req.prompt) + n) if n else 0
        inflight[:] = still
        return live

    def next_session(message: list, n_new: int):
        """The session that has waited longest among those whose last
        answer is complete and that the turn still fits."""
        fit = [s for s in sessions if not s.busy
               and len(s.text) + len(message) + n_new <= cap]
        return min(fit, key=lambda s: s.idle_since) if fit else None

    t0 = clock()
    while True:
        now = clock() - t0
        trace_slice.poll(now)
        if now >= ctx.seconds:
            break
        if now < ctx.seconds / 2:
            w["inflight_at_half"] = len(inflight)
            w["queued_at_half"] = engine.queue_depth
        with spans("bench.submit"):
            while nxt < len(turns) and turns[nxt][0] <= now:
                due, message, n_new = turns[nxt]
                session = next_session(message, n_new)
                if session is None:
                    w["waited_for_a_session"] += 1
                    break           # every session is mid-answer: wait
                session.busy = True
                session.turns += 1
                prompt = session.text + message
                w["prompt_tokens"] += len(prompt)
                inflight.append(_Turn(engine.submit(prompt, n_new), due,
                                      now, session))
                nxt += 1
        if not inflight:
            with spans("bench.wait_arrival"):
                due = turns[nxt][0] if nxt < len(turns) else ctx.seconds
                time.sleep(max(0.0, min(due, ctx.seconds) - now))
            continue
        with spans("bench.engine_step"):
            out = engine.step()
        t = clock() - t0
        step_ms.append(out["step_ms"])
        w["active_sum"] += out["active"]
        with spans("bench.account"):
            live = account(t, t <= ctx.seconds)
        if trace_slice.state == "on":
            w["traced_live_tokens"] += live
    w["window_s"] = clock() - t0 - trace_slice.overhead_s
    trace_slice.stop()
    w["offered"] = nxt
    w["backlog_at_close"] = len(inflight)
    w["queued_at_close"] = engine.queue_depth

    # bounded drain: turns due in the window may finish; nothing new
    t_drain = clock()
    while inflight and clock() - t_drain < ctx.cell["drain_s"]:
        engine.step()
        account(clock() - t0, False)
    w["drain_s"] = clock() - t_drain
    every = finished + inflight
    w["ttft_ms"] = [(tr.times[0] - tr.due) * 1e3 for tr in every if tr.times]
    w["itl_ms"] = [(b - a) * 1e3 for tr in every
                   for a, b in zip(tr.times, tr.times[1:])]
    w["late_ms"] = [(tr.submitted - tr.due) * 1e3 for tr in every]
    w["step_ms"] = step_ms
    w["finished"], w["left"] = finished, inflight
    w["slots_busy_pct"] = (100.0 * w.pop("active_sum") / max(1, len(step_ms))
                           / ctx.cell["engine"]["max_slots"])
    return w


# ---------------------------------------------------------------------------
# scoring
# ---------------------------------------------------------------------------

def sample_turns(finished: list, seed: int, k: int) -> list:
    """The finished turn on the longest context and k - 1 more drawn from
    the seed: (prompt, served tokens) each."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -len(finished[i].req.prompt))
    pick, rest = [order[0]], order[1:]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    if rest:
        pick += [rest[j] for j in rng.permutation(len(rest))[:k - 1]]
    return [(list(finished[i].req.prompt), list(finished[i].req.tokens))
            for i in pick]


def score_served(mcfg: dict, seed: int, sample: list, margin_floor: float,
                 precision: str = "float32") -> dict:
    """The reference's full forward over each sampled turn's WHOLE text
    (the session's history, every earlier message and answer, this turn's
    message and what was served), layer at a time; the readings are over
    the served tokens alone (the turn's first-token row and every decode
    row): the widest gap by which a served token's logit lies below the
    reference's best, over the positions whose smallest routing margin
    (the 8th against the 9th `s + b`, over the layers) is at least
    `margin_floor`, the mean gap over all, and the share of positions
    under the floor."""
    from reference import solar_open2 as reference

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
           "arrays": (gaps, margins)}   # for tools/kda_gqa_moe.py's table
    if "control_gaps" in got:
        ctl = got["control_gaps"]
        out.update(control_gap=float(ctl[clear].max()) if clear.any()
                   else 0.0, control_mean_gap=float(ctl.mean()))
    return out


def decode_paths(engine) -> dict:
    """Which paths the largest decode program the engine compiled took:
    Mosaic calls of the state update, of the paged decode attention and of
    the grouped expert product, by their instructions' own names in the
    COMPILED program. Compiled from the persistent cache, after the
    window. Reads `_decode_progs` as `open_loop._decode_mosaic_calls` does
    (the engine has no public listing of its programs yet)."""
    if not engine._decode_progs:
        return {}
    (slots, pages), prog = max(engine._decode_progs.items())
    k_pages, v_pages = engine._kv
    text = prog.lower(engine._params, k_pages, v_pages,
                      np.zeros((slots, pages), np.int32),
                      np.zeros((slots,), np.int32),
                      np.zeros((slots,), np.int32),
                      *engine._slot_state(np.zeros((slots,), np.int32))
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
    from reference import solar_open2 as reference

    cell = ctx.cell
    spans = common.Spans()
    trace_slice = CountingSlice(ctx, spans)
    engine = build_and_warm(ctx)
    histories, turns = plan(ctx.mix, ctx.seed, ctx.seconds,
                            ctx.config["vocab_size"])
    sessions = _set_up_sessions(ctx, engine, histories)
    print(f"bench: window offers {len(turns)} turns at "
          f"{ctx.mix['rate_rps']} turns/s to {len(sessions)} sessions",
          flush=True)
    if ctx.trace:
        obs.configure(common.NullSink(), role="server")
    balancing_s = reference.balancing_seconds()
    print(f"bench: selection bias balanced by the reference in "
          f"{balancing_s:.1f}s, kept out of setup_s", flush=True)
    setup_s = time.perf_counter() - ctx.t_process - balancing_s
    before = (engine.prefix_hits, engine.prefix_misses,
              engine.prefix_tokens_saved, engine.prefix_snapshots_evicted)
    ctx.compiles.mark()
    w = serve_window(ctx, engine, (sessions, turns), spans, trace_slice)
    compiles_in_window = ctx.compiles.since_mark()
    hits, misses, saved, evicted = (
        now - was for now, was in zip(
            (engine.prefix_hits, engine.prefix_misses,
             engine.prefix_tokens_saved, engine.prefix_snapshots_evicted),
            before))
    peak = common.memory_peak_bytes()
    obs_snap = common.obs_snapshot(obs) if ctx.trace else {}
    state_bytes = None
    if ctx.trace:
        gauge = obs.registry().peek(STATE_GAUGE)
        state_bytes = float(gauge.value) if gauge is not None else None
        obs.reset()
    print(f"bench: serve {window_line(w)}", flush=True)
    print(f"bench: prefix cache in the window: hits {hits} misses {misses} "
          f"tokens saved {saved} of {w['prompt_tokens']} prompt tokens, "
          f"snapshots evicted {evicted}; turns that waited for a session "
          f"{w['waited_for_a_session']}; free pages {engine.pool.free}",
          flush=True)

    finished, left = w.pop("finished"), w.pop("left")
    bad_status = sum(1 for tr in finished if tr.req.status != "done"
                     or len(tr.req.tokens) != tr.req.max_new_tokens)
    failed = bad_status + len(left)
    sample = sample_turns(
        [tr for tr in finished if tr.req.status == "done"], ctx.seed,
        cell["check"]["sample_requests"])
    paths = decode_paths(engine)

    engine.close()
    del engine, finished, left, sessions
    common.free_device_memory()
    t_ref = time.perf_counter()
    score = score_served(reference.model_cfg(ctx.config), ctx.seed, sample,
                         cell["check"]["margin_floor"])
    print(f"bench: reference scored {score['tokens']} served tokens of "
          f"{score['requests']} turns (longest context "
          f"{score['longest_context']}) in "
          f"{time.perf_counter() - t_ref:.1f}s; widest gap over all "
          f"positions {score.get('served_gap_all', 0.0)!r}", flush=True)

    limits = cell["limits"]
    checks = [
        check_le("served_logit_gap", score["served_gap"],
                 limits["served_logit_gap"],
                 f"widest over the greedy tokens of {score['requests']} "
                 f"turns whose routing margin is >= "
                 f"{cell['check']['margin_floor']}"),
        check_le("served_mean_gap", score["served_mean_gap"],
                 limits["served_mean_gap"],
                 f"mean over all {score['tokens']}"),
        check_le("near_tie_share", score["near_tie_share"],
                 limits["near_tie_share"],
                 "share of those positions under the margin"),
        Check("sample_tokens", score["tokens"], cell["check"]["min_tokens"],
              score["tokens"] >= cell["check"]["min_tokens"]),
        Check("sample_longest_context", score["longest_context"],
              cell["check"]["min_longest_context"],
              score["longest_context"]
              > cell["check"]["min_longest_context"],
              "a sampled turn on a context past this many tokens"),
        Check("turns_served_on_a_hit", hits, w["offered"],
              hits == w["offered"], "every turn of the window"),
        check_le("cold_prefills_in_window", misses, 0),
        check_le("snapshots_evicted_in_window", evicted, 0),
        check_le("compiles_in_window", compiles_in_window, 0),
    ]
    for name, want in cell["engine"]["expect_paths"].items():
        got = paths.get(name, -1)
        checks.append(Check(f"decode_path.{name}", got, want, got == want,
                            "Mosaic calls in the largest decode program"))
    e2e = {"serve_tokens_per_s": w["tokens_in_window"] / w["window_s"]}
    moe = trace_slice.moe
    routed = moe.get(COUNTERS[0], 0.0) + moe.get(COUNTERS[1], 0.0)
    stats = dict(w, obs=obs_snap,
                 kda_state_mb=state_bytes / 1e6 if state_bytes else None,
                 prefix_saved_tokens_pct=(
                     100.0 * saved / w["prompt_tokens"]
                     if w["prompt_tokens"] else None),
                 moe_share_here_pct=(100.0 * moe.get(COUNTERS[0], 0.0)
                                     / routed if routed else None),
                 traced_moe_rows=moe.get(COUNTERS[0], 0.0),
                 traced_moe_rows_elsewhere=moe.get(COUNTERS[1], 0.0),
                 traced_moe_experts=moe.get(COUNTERS[2], 0.0),
                 traced_kda_slot_steps=moe.get(COUNTERS[3], 0.0))
    return Run(setup_s=setup_s, end_to_end=e2e, attempted=w["offered"],
               failed=failed, checks=checks, stats=stats,
               memory_peak_bytes=peak, window_s=w["window_s"],
               trace_dir=trace_slice.result_dir())
