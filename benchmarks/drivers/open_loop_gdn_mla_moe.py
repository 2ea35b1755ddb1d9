"""Driver `open_loop_gdn_mla_moe`: `open_loop`'s window for the
GigaChat-3.5 family (gated delta-rule layers beside latent attention,
routed SwiGLU experts of which the chip holds a share). The window, the
drain, the warm-up, the sampling of finished requests are `open_loop`'s
own functions and the grown page rungs' warm-up is `open_loop_mla_moe`'s,
imported as `open_loop_ssm_moe` imports them; what is this family's is
here: the engine build (the program's preset checked key by key against
the configuration file, the held experts against the file's share, the
weights made layer by layer from the seed by the family's reference), the
warm-up of a page rung that lies TWO doublings past the longest prompt,
the scoring of what was served by that reference with the same share, the
check of which state-update, latent-attention and expert paths the largest
decode program lowered to, and the traced slice's sums of the program's
counters beside `traced_live_tokens`.

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

COUNTERS = ("serve.moe.rows", "serve.moe.rows_elsewhere",
            "serve.moe.experts_touched", "serve.gdn.slot_steps")
STATE_GAUGE = "serve.gdn.state_bytes"
# leaves the program holds bare (no Dense module around them)
_BARE_LEAVES = ("conv1d_weight", "A_log", "dt_bias", "o_norm", "kv_b_proj",
                "router", "e_score_correction_bias", "experts_gate_up",
                "experts_down")
# leaves the program holds in float32 whatever the parameters' dtype
_FLOAT32_LEAVES = ("conv1d_weight", "A_log", "dt_bias", "o_norm", "router",
                   "e_score_correction_bias")
# the program's own fields of its config: everything else is published
_PROGRAM_KEYS = ("dtype", "param_dtype", "logits_dtype", "attention_impl",
                 "vocab_multiple", "remat", "scan_blocks", "experts_held",
                 "chunk_size")
KERNELS = ("gdn_decode_update", "mla_decode_attention", "gmm")


def _plain(value):
    """A preset's value as a configuration file holds it: a tuple of
    pairs (the published `rope_scaling` group) as an object, any other
    tuple as a list."""
    if isinstance(value, tuple):
        if value and all(isinstance(v, tuple) and len(v) == 2
                         for v in value):
            return dict(value)
        return list(value)
    return value


def make_model(config: dict):
    """The program's model for a configuration file: every published key
    of the preset must stand in the file with the preset's value. The
    file's `n_routed_experts` counts the experts HELD here; the router's
    width is the published count beside it."""
    from distributedtraining_tpu.models import gigachat3_5

    pc = gigachat3_5.PRESETS[config["preset"]]
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
    if config["assumed"]["padded_vocab"] != pc.padded_vocab:
        raise SystemExit("bench: FAIL: padded_vocab differs from the preset")
    dt = config["dtypes"]
    if (dt["param"], dt["compute"], dt["logits"]) != (
            pc.param_dtype, pc.dtype, pc.logits_dtype):
        raise SystemExit("bench: FAIL: dtypes differ from the preset")
    return gigachat3_5.make_model(pc)


def to_program_layer(leaves: dict) -> dict:
    """One layer of the reference's flat leaves -> the program's Flax
    subtree. The arrays are handed over; a leaf the program holds in
    float32 is widened (it is a bfloat16 number: nothing is rounded)."""
    import jax.numpy as jnp
    out = {}
    for name, x in leaves.items():
        if name.endswith("_norm") and name != "o_norm":
            out[name] = {"w": x.astype(jnp.float32)}
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
    from reference import gigachat3_5 as reference

    top = reference.top_weights(mcfg, seed, dtype)
    tree = {"embed_tokens": top["embed_tokens"], "lm_head": top["lm_head"],
            "norm": {"w": top["norm"].astype(jnp.float32)}}
    for i in range(mcfg["num_hidden_layers"]):
        tree[f"layer_{i}"] = to_program_layer(
            reference.layer_weights(mcfg, seed, i, dtype))
    return tree


def _build_engine(ctx: Ctx, params_hook=None):
    from distributedtraining_tpu.engine.serve import GenerationEngine
    from reference import gigachat3_5 as reference

    model, pc = make_model(ctx.config)
    params = program_params(reference.model_cfg(ctx.config), ctx.seed,
                            pc.storage_dtype())
    if params_hook is not None:
        params = params_hook(params)
    e = ctx.cell["engine"]
    return GenerationEngine(
        model, params, revision="bench", max_slots=e["max_slots"],
        page_size=e["page_size"], max_seq_len=e["max_seq_len"],
        max_new_tokens=e["max_new_tokens"], eos_id=None,
        prefix_cache=e["prefix_cache"])


def _warm_far(ctx: Ctx, engine, first_index: int) -> int:
    """The decode buckets of the page rungs under `decode_pages_far`:
    rungs more than one doubling past the longest prompt, which no prompt
    of the mix reaches at its first decode step. Requests with the mix's
    longest prompt DECODE their way there, the largest slot stage's count
    of them together, and then finish in groups, a token apart, so that
    the active count falls through every slot rung's stage while all are
    in the rung: one decode step at each bucket, no prefill bucket that
    the mix does not need."""
    from traffic import gen

    w, e = ctx.cell["warmup"], ctx.cell["engine"]
    P, vocab = e["page_size"], ctx.config["vocab_size"]
    stages = [open_loop._just_into(s, e["max_slots"])
              for s in sorted(w["decode_slots"])]
    prompt_len = max(w["prefill_tokens"])
    n = first_index
    t0 = time.perf_counter()
    for pages in sorted(w.get("decode_pages_far", [])):
        # positions a request holds when its pages first pad up to `pages`
        into = (open_loop._just_into(pages, e["max_seq_len"] // P) - 1) * P
        steps = into + 1 - prompt_len + 2      # decode steps to be there
        reqs = []
        # the group that leaves stage i for stage i - 1 finishes i tokens
        # after the first group
        for i, want in enumerate(reversed(stages)):
            below = stages[len(stages) - 2 - i] if i + 1 < len(stages) else 0
            for _ in range(want - below):
                reqs.append(engine.submit(gen.warmup_prompt(
                    ctx.mix, ctx.seed, n, prompt_len, vocab),
                    steps + 1 + i))
                n += 1
        for _ in range(steps + len(stages) + 16 + len(reqs)):
            if all(r.done_evt.is_set() for r in reqs):
                break
            engine.step()
        else:
            raise SystemExit("bench: FAIL: a warm-up request did not finish")
    print(f"bench: warm-up: far page rungs {time.perf_counter() - t0:.1f}s, "
          f"{n - first_index} requests", flush=True)
    return n


def build_and_warm(ctx: Ctx, warm: bool = True, params_hook=None):
    t0 = time.perf_counter()
    engine = _build_engine(ctx, params_hook)
    print(f"bench: engine built in {time.perf_counter() - t0:.1f}s",
          flush=True)
    if warm:
        n = open_loop._warm_up(ctx, engine)
        open_loop_mla_moe._warm_grown(ctx, engine, n)
        # _warm_grown numbers its prompts on from `n` and returns nothing:
        # it submits the largest slot stage's count for every grown rung
        w, e = ctx.cell["warmup"], ctx.cell["engine"]
        n += len(w.get("decode_pages_grown", [])) * open_loop._just_into(
            max(w["decode_slots"]), e["max_slots"])
        _warm_far(ctx, engine, n)
    return engine


def score_served(mcfg: dict, seed: int, sample: list, margin_floor: float,
                 precision: str = "float32", pad_multiple: int = 512
                 ) -> dict:
    """`open_loop_ssm_moe.score_served` with this family's reference: one
    pass, layer at a time, over each sampled prompt with its served
    tokens; the widest gap over the positions whose smallest routing
    margin (the 8th against the 9th `s + b`, over the expert layers) is at
    least `margin_floor`, the mean gap over all, and the share of
    positions under the floor."""
    from reference import gigachat3_5 as reference

    if not sample:
        return {"served_gap": 0.0, "served_mean_gap": 0.0, "tokens": 0,
                "requests": 0, "near_tie_share": 0.0}
    longest = max(len(p) + len(s) for p, s in sample)
    pad_to = -(-longest // pad_multiple) * pad_multiple
    ids = np.zeros((len(sample), pad_to), np.int32)
    spans = []
    for b, (prompt, served) in enumerate(sample):
        seq = list(prompt) + list(served)
        ids[b, :len(seq)] = seq
        spans.append((len(prompt) - 1, len(seq) - 1))
    got = reference.score_sequences(mcfg, seed, ids, precision)

    def served_only(key):
        return np.concatenate([got[key][b, lo:hi]
                               for b, (lo, hi) in enumerate(spans)])

    gaps, margins = served_only("gaps"), served_only("margins")
    clear = margins >= margin_floor
    out = {"served_gap": float(gaps[clear].max()) if clear.any() else 0.0,
           "served_gap_all": float(gaps.max()),
           "served_mean_gap": float(gaps.mean()),
           "near_tie_share": float(1.0 - clear.mean()),
           "tokens": int(len(gaps)), "requests": len(sample),
           "arrays": (gaps, margins)}   # for tools/gdn_mla_moe.py's table
    if "control_gaps" in got:
        ctl = served_only("control_gaps")
        out.update(control_gap=float(ctl[clear].max()) if clear.any()
                   else 0.0, control_mean_gap=float(ctl.mean()))
    return out


def decode_paths(engine) -> dict:
    """Which paths the largest decode program the engine compiled took:
    Mosaic calls of the delta-rule state update, of the latent decode
    kernel and of the grouped expert product, by their instructions' own
    names in the COMPILED program. Compiled from the persistent cache,
    after the window. Reads `_decode_progs` as
    `open_loop._decode_mosaic_calls` does (the engine has no public
    listing of its programs yet)."""
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
    from reference import gigachat3_5 as reference
    from traffic import gen

    cell = ctx.cell
    spans = common.Spans()
    trace_slice = CountingSlice(ctx, spans)
    engine = build_and_warm(ctx)
    schedule = gen.open_loop_requests(ctx.mix, ctx.seed, ctx.seconds,
                                      ctx.config["vocab_size"])
    print(f"bench: window offers {len(schedule)} requests at "
          f"{ctx.mix['rate_rps']} req/s", flush=True)
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
    w = serve_window(ctx, engine, schedule, spans, trace_slice)
    compiles_in_window = ctx.compiles.since_mark()
    peak = common.memory_peak_bytes()
    obs_snap = common.obs_snapshot(obs) if ctx.trace else {}
    state_bytes = None
    if ctx.trace:
        gauge = obs.registry().peek(STATE_GAUGE)
        state_bytes = float(gauge.value) if gauge is not None else None
        obs.reset()
    print(f"bench: serve {window_line(w)}", flush=True)

    finished, left = w.pop("finished"), w.pop("left")
    bad_status = sum(1 for tr in finished if tr.req.status != "done"
                     or len(tr.req.tokens) != tr.req.max_new_tokens)
    failed = bad_status + len(left)
    sample = open_loop._sample_finished(
        [tr for tr in finished if tr.req.status == "done"], ctx.seed,
        cell["check"]["sample_requests"])
    paths = decode_paths(engine)

    engine.close()
    del engine, finished, left
    common.free_device_memory()
    t_ref = time.perf_counter()
    score = score_served(reference.model_cfg(ctx.config), ctx.seed, sample,
                         cell["check"]["margin_floor"])
    print(f"bench: reference scored {score['tokens']} served tokens of "
          f"{score['requests']} requests in "
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
        Check("sample_tokens", score["tokens"], cell["check"]["min_tokens"],
              score["tokens"] >= cell["check"]["min_tokens"]),
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
                 gdn_state_mb=state_bytes / 1e6 if state_bytes else None,
                 moe_share_here_pct=(100.0 * moe.get(COUNTERS[0], 0.0)
                                     / routed if routed else None),
                 traced_moe_rows=moe.get(COUNTERS[0], 0.0),
                 traced_moe_rows_elsewhere=moe.get(COUNTERS[1], 0.0),
                 traced_moe_experts=moe.get(COUNTERS[2], 0.0),
                 traced_gdn_slot_steps=moe.get(COUNTERS[3], 0.0))
    return Run(setup_s=setup_s, end_to_end=e2e, attempted=w["offered"],
               failed=failed, checks=checks, stats=stats,
               memory_peak_bytes=peak, window_s=w["window_s"],
               trace_dir=trace_slice.result_dir())
