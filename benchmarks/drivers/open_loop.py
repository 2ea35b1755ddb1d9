"""Driver `open_loop`: one `GenerationEngine` as `neurons/server.py` builds
it, with the benchmark's own thread submitting what is due and calling
`engine.step()` on the wall clock. Every request is timed from when it was
DUE, so a stall charges the requests behind it.

The load is fixed in the cell (`rate_rps` of its traffic mix); nothing is
searched for. After the window a bounded drain lets requests that were due
in it finish; then the engine is freed and the plain reference scores a
sample of what was served."""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from . import common
from .common import Check, Ctx, Run, check_le


@dataclasses.dataclass
class _Tracked:
    req: object
    due: float
    submitted: float
    seen: int = 0
    times: list = dataclasses.field(default_factory=list)


def _build_engine(ctx: Ctx):
    from distributedtraining_tpu.engine.serve import GenerationEngine
    from reference import gpt2 as reference

    from .program import make_model, to_program_tree

    model, _ = make_model(ctx.config)
    params = to_program_tree(reference.init_weights(ctx.model_cfg(),
                                                    ctx.seed))
    e = ctx.cell["engine"]
    # the arguments neurons/server.py passes, with its defaults (drain
    # swaps, request traces on, no drafter, unified phase, no EOS: the
    # output lengths are the mix's)
    return GenerationEngine(
        model, params, revision="bench", max_slots=e["max_slots"],
        page_size=e["page_size"], max_seq_len=e["max_seq_len"],
        max_new_tokens=e["max_new_tokens"], eos_id=None,
        prefix_cache=e["prefix_cache"])


def _rungs(top: int) -> list[int]:
    """The engine's bucket ladder: powers of two below `top`, then `top`."""
    out, b = [], 1
    while b < top:
        out.append(b)
        b *= 2
    return out + [top]


def _just_into(bucket: int, top: int) -> int:
    """The smallest need that the ladder rounds up to `bucket`."""
    rungs = _rungs(top)
    i = rungs.index(bucket)
    return rungs[i - 1] + 1 if i else 1


def _warm_up(ctx: Ctx, engine) -> int:
    """Compile exactly the cell's programs, smallest first (the engine's
    ladder pads up to whatever is compiled already, so the order decides
    which programs exist). Each is reached by requests shaped to need it:
    a prefill bucket by one prompt just past the next smaller rung; the
    decode buckets of one page rung by requests long enough to need it,
    admitted in stages so that the active count just passes each slot rung
    in turn (one decode step at each). PERF.md lists a public warm-up of a
    declared bucket set for a later PR."""
    from traffic import gen

    w, e = ctx.cell["warmup"], ctx.cell["engine"]
    P = e["page_size"]
    pages_top = e["max_seq_len"] // P
    vocab = ctx.config["vocab_size"]
    n = 0

    def submit(count: int, length: int, n_new: int) -> list:
        nonlocal n
        reqs = []
        for _ in range(count):
            reqs.append(engine.submit(
                gen.warmup_prompt(ctx.mix, ctx.seed, n, length, vocab),
                n_new))
            n += 1
        return reqs

    def finish(reqs: list) -> None:
        for _ in range(16):
            if all(r.done_evt.is_set() for r in reqs):
                return
            engine.step()
        raise SystemExit("bench: FAIL: a warm-up request did not finish")

    t0 = time.perf_counter()
    for t in sorted(w["prefill_tokens"]):
        finish(submit(1, (_just_into(t // P, pages_top) - 1) * P + 1, 1))
    t1 = time.perf_counter()
    stages = [_just_into(s, e["max_slots"]) for s in sorted(w["decode_slots"])]
    for pages in sorted(w["decode_pages"]):
        length = (_just_into(pages, pages_top) - 1) * P + 1
        reqs, active = [], 0
        for want in stages:
            # alive through every later stage's step, then done
            reqs += submit(want - active, length, len(stages) + 2)
            active = want
            engine.step()
        finish(reqs)
    print(f"bench: warm-up: prefill buckets {t1 - t0:.1f}s, decode buckets "
          f"{time.perf_counter() - t1:.1f}s, {n} requests, compile+load "
          f"{ctx.compiles.seconds:.1f}s so far", flush=True)
    return n


def _gaps_below_best(ref_logits, tokens, vocab: int):
    """For every position t of one padded sequence: how far the reference
    logit of `tokens[t + 1]` lies below the reference's best at t.
    ref_logits [1, T, V'], tokens [T] -> [T - 1]."""
    import jax.numpy as jnp
    rows = ref_logits[0, :-1, :vocab]
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, tokens[1:, None], axis=-1)[:, 0]


def score_served(mcfg: dict, seed: int, sample: list, pad_to: int,
                 precision: str = "float32") -> dict:
    """The reference, once over each sampled prompt with its served tokens
    (padded to one length, so one program). Returns the widest gap by which
    a served token's logit lies below the reference's best, and, for the
    controls, the same reading for the tokens a lower precision puts
    first at the same positions."""
    import functools

    import jax
    import jax.numpy as jnp

    from reference import gpt2 as reference

    vocab = mcfg["vocab_size"]
    ref = reference.Reference(mcfg)
    low = (reference.Reference(mcfg, precision)
           if precision != "float32" else None)
    gaps = jax.jit(functools.partial(_gaps_below_best, vocab=vocab))
    low_first = jax.jit(lambda lg: jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.argmax(lg[0, :-1, :vocab], axis=-1).astype(jnp.int32)]))
    weights = reference.init_weights(mcfg, seed)
    served_gaps, low_gaps = [], []
    for prompt, served in sample:
        ids = np.zeros((1, pad_to), np.int32)
        seq = list(prompt) + list(served)
        ids[0, :len(seq)] = seq
        lo, hi = len(prompt) - 1, len(seq) - 1
        logits = ref.logits(weights, ids)
        served_gaps.append(np.asarray(
            gaps(logits, jnp.asarray(ids[0])))[lo:hi])
        if low is not None:
            first = low_first(low.logits(weights, ids))
            low_gaps.append(np.asarray(gaps(logits, first))[lo:hi])
    out = _gap_stats(served_gaps, "served")
    out.update(_gap_stats(low_gaps, "control"))
    return dict(out, tokens=int(sum(len(g) for g in served_gaps)),
                requests=len(sample))


def _gap_stats(gaps: list, name: str) -> dict:
    """The widest gap, and the mean gap (steadier: the widest depends on
    whether a near-tie falls into the sample)."""
    if not gaps:
        return {f"{name}_gap": 0.0, f"{name}_mean_gap": 0.0}
    every = np.concatenate(gaps)
    return {f"{name}_gap": float(every.max()),
            f"{name}_mean_gap": float(every.mean())}


def _sample_finished(finished: list, seed: int, k: int) -> list:
    """The longest finished request and k-1 more drawn from the seed."""
    import numpy as np
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i].req.prompt) + len(finished[i].req.tokens)))
    pick = [order[0]]
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    if rest:
        pick += [rest[j] for j in rng.permutation(len(rest))[:k - 1]]
    return [(list(finished[i].req.prompt), list(finished[i].req.tokens))
            for i in pick]


def build_and_warm(ctx: Ctx, warm: bool = True):
    """`warm=False` (tools only) lets programs compile as they are met."""
    t0 = time.perf_counter()
    engine = _build_engine(ctx)
    print(f"bench: engine built in {time.perf_counter() - t0:.1f}s",
          flush=True)
    if warm:
        _warm_up(ctx, engine)
    return engine


def serve_window(ctx: Ctx, engine, schedule: list, spans, trace_slice
                 ) -> dict:
    """Offer `schedule` on the wall clock for `ctx.seconds`, then drain for
    at most the cell's `drain_s`. Everything the window saw, as lists."""
    clock = time.perf_counter
    inflight: list[_Tracked] = []
    finished: list[_Tracked] = []
    step_ms: list[float] = []
    w = {"tokens_in_window": 0, "traced_live_tokens": 0, "active_sum": 0,
         "inflight_at_half": 0, "queued_at_half": 0}
    nxt = 0

    def account(t: float, in_window: bool) -> int:
        """Stamp the tokens this step emitted; returns the live KV tokens
        (prompt + generated) of the sequences still decoding."""
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
            else:
                still.append(tr)
                live += (len(tr.req.prompt) + n) if n else 0
        inflight[:] = still
        return live

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
            while nxt < len(schedule) and schedule[nxt][0] <= now:
                due, prompt, n_new = schedule[nxt]
                inflight.append(_Tracked(engine.submit(prompt, n_new), due,
                                         now))
                nxt += 1
        if not inflight:
            with spans("bench.wait_arrival"):
                due = schedule[nxt][0] if nxt < len(schedule) else ctx.seconds
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

    # bounded drain: requests due in the window may finish; nothing new
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


def _dist(vals: list) -> str:
    if not vals:
        return "n=0"
    qs = " ".join(f"p{q}={common.percentile(vals, q):.2f}"
                  for q in (50, 80, 90, 95, 99))
    return f"{qs} mean={sum(vals) / len(vals):.2f} n={len(vals)}"


def window_line(w: dict) -> str:
    return (f"window {w['window_s']:.3f}s offered={w['offered']} "
            f"inflight half/close={w['inflight_at_half']}/"
            f"{w['backlog_at_close']} queued half/close="
            f"{w['queued_at_half']}/{w['queued_at_close']} "
            f"drain={w['drain_s']:.2f}s left_after_drain={len(w['left'])} "
            f"steps={len(w['step_ms'])} tokens/s="
            f"{w['tokens_in_window'] / w['window_s']:.2f} "
            f"slots_busy={w['slots_busy_pct']:.1f}% | step_ms "
            f"{_dist(w['step_ms'])} | ttft_ms {_dist(w['ttft_ms'])} | "
            f"itl_ms {_dist(w['itl_ms'])} | generator_late_ms "
            f"{_dist(w['late_ms'])}")


def run(ctx: Ctx) -> Run:
    from distributedtraining_tpu.utils import obs
    from traffic import gen

    cell = ctx.cell
    spans = common.Spans()
    trace_slice = common.TraceSlice(ctx, spans)
    engine = build_and_warm(ctx)
    schedule = gen.open_loop_requests(ctx.mix, ctx.seed, ctx.seconds,
                                      ctx.config["vocab_size"])
    print(f"bench: window offers {len(schedule)} requests at "
          f"{ctx.mix['rate_rps']} req/s", flush=True)
    if ctx.trace:
        obs.configure(common.NullSink(), role="server")
    setup_s = time.perf_counter() - ctx.t_process
    ctx.compiles.mark()
    w = serve_window(ctx, engine, schedule, spans, trace_slice)
    compiles_in_window = ctx.compiles.since_mark()
    peak = common.memory_peak_bytes()
    obs_snap = common.obs_snapshot(obs) if ctx.trace else {}
    if ctx.trace:
        obs.reset()
    print(f"bench: serve {window_line(w)}", flush=True)

    finished, left = w.pop("finished"), w.pop("left")
    bad_status = sum(1 for tr in finished if tr.req.status != "done"
                     or len(tr.req.tokens) != tr.req.max_new_tokens)
    # the load is below the knee: a request that was due in the window
    # and is not complete after the drain has failed
    failed = bad_status + len(left)
    sample = _sample_finished(
        [tr for tr in finished if tr.req.status == "done"], ctx.seed,
        cell["check"]["sample_requests"])
    mosaic = _decode_mosaic_calls(engine)

    engine.close()
    del engine, finished, left
    common.free_device_memory()
    t_ref = time.perf_counter()
    score = score_served(ctx.model_cfg(), ctx.seed, sample,
                         cell["engine"]["max_seq_len"])
    print(f"bench: reference scored {score['tokens']} served tokens of "
          f"{score['requests']} requests in "
          f"{time.perf_counter() - t_ref:.1f}s", flush=True)

    want = cell["engine"]["expect_paged_kernel"]
    checks = [
        check_le("served_logit_gap", score["served_gap"],
                 cell["limits"]["served_logit_gap"],
                 f"widest over {score['tokens']} greedy tokens of "
                 f"{score['requests']} requests"),
        check_le("served_mean_gap", score["served_mean_gap"],
                 cell["limits"]["served_mean_gap"], "mean over the same"),
        Check("sample_tokens", score["tokens"],
              cell["check"]["min_tokens"],
              score["tokens"] >= cell["check"]["min_tokens"]),
        check_le("compiles_in_window", compiles_in_window, 0),
        Check("paged_kernel_calls", mosaic, 1 if want else 0,
              (mosaic > 0) == want,
              "tpu_custom_call in the largest decode program"),
    ]
    e2e = {"serve_tokens_per_s": w["tokens_in_window"] / w["window_s"]}
    if w["ttft_ms"]:
        e2e["ttft_p95_ms"] = common.percentile(w["ttft_ms"], 95)
    if w["itl_ms"]:
        e2e["itl_p95_ms"] = common.percentile(w["itl_ms"], 95)
    return Run(setup_s=setup_s, end_to_end=e2e, attempted=w["offered"],
               failed=failed, checks=checks, stats=dict(w, obs=obs_snap),
               memory_peak_bytes=peak, window_s=w["window_s"],
               trace_dir=trace_slice.result_dir())


def _decode_mosaic_calls(engine) -> int:
    """`tpu_custom_call`s in the lowered text of the largest decode program
    the engine compiled: which attention path the decode steps took. The
    engine has no public handle on its programs, so this one reads
    `_decode_progs` and the arrays it is called with; a refactor that moves
    them must move this too (PERF.md, for the tracing issue: a public
    listing of compiled buckets)."""
    if not engine._decode_progs:
        return -1
    (slots, pages), prog = max(engine._decode_progs.items())
    k_pages, v_pages = engine._kv
    text = prog.lower(engine._params, k_pages, v_pages,
                      np.zeros((slots, pages), np.int32),
                      np.zeros((slots,), np.int32),
                      np.zeros((slots,), np.int32)).as_text()
    return text.count(common.MOSAIC_CALL)
