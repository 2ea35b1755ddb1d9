"""Driver `open_loop_mla_moe`: `open_loop`'s window for the DeepSeek-V3
family (latent attention, routed experts). The window, the drain, the
warm-up, the sampling of finished requests and the gap statistics are
`open_loop`'s own functions, imported; what is this family's is here: the
engine build (the program's preset checked key by key against the
configuration file, the weights made layer by layer from the seed by the
family's reference), the scoring of what was served by that reference,
the check of which attention and expert paths the largest decode program
lowered to, and the traced slice's sums of routed rows and touched experts
beside `traced_live_tokens`.

`build_and_warm`, `serve_window` and `window_line` are exposed under
`open_loop`'s names, so a tool written against that driver takes this
one."""

from __future__ import annotations

import dataclasses
import re
import time

import numpy as np

from . import common, open_loop
from .common import Check, Ctx, Run, check_le
from .open_loop import serve_window, window_line  # noqa: F401  (re-exported)

MOE_COUNTERS = ("serve.moe.rows", "serve.moe.experts_touched")
_BARE_LEAVES = ("kv_b_proj", "router", "e_score_correction_bias",
                "experts_gate_up", "experts_down")
# the program's own fields of its config: everything else is published
_PROGRAM_KEYS = ("dtype", "param_dtype", "logits_dtype", "attention_impl",
                 "vocab_multiple", "remat", "scan_blocks")


def make_model(config: dict):
    """The program's model for a configuration file: every published key
    of the preset must stand in the file with the preset's value."""
    from distributedtraining_tpu.models import deepseek_v3

    pc = deepseek_v3.PRESETS[config["preset"]]
    for f in dataclasses.fields(pc):
        if f.name in _PROGRAM_KEYS:
            continue
        if f.name not in config or config[f.name] != getattr(pc, f.name):
            raise SystemExit(
                f"bench: FAIL: {config['name']}.{f.name} = "
                f"{config.get(f.name)!r} but preset {config['preset']} runs "
                f"{getattr(pc, f.name)!r}")
    if config["assumed"]["padded_vocab"] != pc.padded_vocab:
        raise SystemExit("bench: FAIL: padded_vocab differs from the preset")
    dt = config["dtypes"]
    if (dt["param"], dt["compute"], dt["logits"]) != (
            pc.param_dtype, pc.dtype, pc.logits_dtype):
        raise SystemExit("bench: FAIL: dtypes differ from the preset")
    return deepseek_v3.make_model(pc)


def to_program_layer(leaves: dict) -> dict:
    """One layer of the reference's flat leaves -> the program's Flax
    subtree. The arrays are handed over, not copied."""
    out = {}
    for name, x in leaves.items():
        if name.endswith("layernorm"):
            out[name] = {"scale": x}
        elif name in _BARE_LEAVES:
            out[name] = x
        else:
            out[name] = {"kernel": x}
    return out


def program_params(mcfg: dict, seed: int, dtype) -> dict:
    """The program's tree with the reference's weights, made in `dtype`
    layer by layer (they are bfloat16 numbers: nothing is rounded again).
    The selection bias stays float32, as the program holds it."""
    import jax.numpy as jnp
    from reference import deepseek_v3 as reference

    top = reference.top_weights(mcfg, seed, dtype)
    tree = {"embed_tokens": top["embed_tokens"], "lm_head": top["lm_head"],
            "norm": {"scale": top["norm"]}}
    for i in range(mcfg["num_hidden_layers"]):
        layer = to_program_layer(reference.layer_weights(mcfg, seed, i,
                                                         dtype))
        if "e_score_correction_bias" in layer:
            layer["e_score_correction_bias"] = layer[
                "e_score_correction_bias"].astype(jnp.float32)
        tree[f"layer_{i}"] = layer
    return tree


def _build_engine(ctx: Ctx, params_hook=None):
    from distributedtraining_tpu.engine.serve import GenerationEngine
    from reference import deepseek_v3 as reference

    model, pc = make_model(ctx.config)
    params = program_params(reference.model_cfg(ctx.config), ctx.seed,
                            pc.storage_dtype())
    if params_hook is not None:      # tools/mla_moe.py's faults
        params = params_hook(params)
    e = ctx.cell["engine"]
    return GenerationEngine(
        model, params, revision="bench", max_slots=e["max_slots"],
        page_size=e["page_size"], max_seq_len=e["max_seq_len"],
        max_new_tokens=e["max_new_tokens"], eos_id=None,
        prefix_cache=e["prefix_cache"])


def _warm_grown(ctx: Ctx, engine, first_index: int) -> None:
    """The decode buckets of the page rungs under `decode_pages_grown`:
    rungs that a request reaches by GROWING (prompt plus output), past
    the longest prompt. `open_loop._warm_up` shapes a rung by a prompt
    one token past the rung below, and would compile a prefill bucket
    here that no request of the mix needs. A prompt that FILLS the rung
    below exactly needs its next page at its first decode step, so it
    reaches the rung through a prefill bucket the mix has. Staged over
    the slot rungs as `_warm_up` stages them."""
    from traffic import gen

    w, e = ctx.cell["warmup"], ctx.cell["engine"]
    vocab = ctx.config["vocab_size"]
    stages = [open_loop._just_into(s, e["max_slots"])
              for s in sorted(w["decode_slots"])]
    n = first_index
    t0 = time.perf_counter()
    for pages in sorted(w.get("decode_pages_grown", [])):
        length = (pages // 2) * e["page_size"]
        reqs, active = [], 0
        for want in stages:
            for _ in range(want - active):
                reqs.append(engine.submit(gen.warmup_prompt(
                    ctx.mix, ctx.seed, n, length, vocab), len(stages) + 2))
                n += 1
            active = want
            engine.step()
        for _ in range(16):
            if all(r.done_evt.is_set() for r in reqs):
                break
            engine.step()
        else:
            raise SystemExit("bench: FAIL: a warm-up request did not finish")
    print(f"bench: warm-up: grown page rungs {time.perf_counter() - t0:.1f}s,"
          f" {n - first_index} requests", flush=True)


def build_and_warm(ctx: Ctx, warm: bool = True, params_hook=None):
    t0 = time.perf_counter()
    engine = _build_engine(ctx, params_hook)
    print(f"bench: engine built in {time.perf_counter() - t0:.1f}s",
          flush=True)
    if warm:
        _warm_grown(ctx, engine, open_loop._warm_up(ctx, engine))
    return engine


def score_served(mcfg: dict, seed: int, sample: list, margin_floor: float,
                 precision: str = "float32", pad_multiple: int = 512
                 ) -> dict:
    """The reference, layer at a time, over each sampled prompt with its
    served tokens (padded to one length). The widest and the mean gap by
    which a served token's reference logit lies below the reference's
    best; positions whose smallest routing margin over the layers is
    under `margin_floor` (the router's near-ties: a rounding flips the
    k-th expert there, and the reference then follows another model) are
    counted, and left out of the WIDEST gap only."""
    from reference import deepseek_v3 as reference

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
    gaps = np.concatenate([got["gaps"][b, lo:hi]
                           for b, (lo, hi) in enumerate(spans)])
    margins = np.concatenate([got["margins"][b, lo:hi]
                              for b, (lo, hi) in enumerate(spans)])
    clear = margins >= margin_floor
    out = {"served_gap": float(gaps[clear].max()) if clear.any() else 0.0,
           "served_gap_all": float(gaps.max()),
           "served_mean_gap": float(gaps.mean()),
           "near_tie_share": float(1.0 - clear.mean()),
           "tokens": int(len(gaps)), "requests": len(sample),
           "arrays": (gaps, margins)}       # for tools/mla_moe.py's table
    if "control_gaps" in got:
        ctl = np.concatenate([got["control_gaps"][b, lo:hi]
                              for b, (lo, hi) in enumerate(spans)])
        out.update(control_gap=float(ctl[clear].max()) if clear.any()
                   else 0.0, control_mean_gap=float(ctl.mean()))
    return out


def decode_paths(engine) -> dict:
    """Which paths the largest decode program the engine compiled took:
    Mosaic calls of the latent decode kernel and of the grouped expert
    product, by their instructions' own names in the COMPILED program
    (the lowered text holds a jitted kernel's body once, however many
    layers call it). Compiled from the persistent cache, after the
    window. Reads `_decode_progs` as `open_loop._decode_mosaic_calls`
    does (the engine has no public listing of its programs yet)."""
    if not engine._decode_progs:
        return {}
    (slots, pages), prog = max(engine._decode_progs.items())
    k_pages, v_pages = engine._kv
    text = prog.lower(engine._params, k_pages, v_pages,
                      np.zeros((slots, pages), np.int32),
                      np.zeros((slots,), np.int32),
                      np.zeros((slots,), np.int32)).compile().as_text()
    own = [ln.split(" = ")[0].strip().removeprefix("ROOT ")
           for ln in text.splitlines() if common.MOSAIC_CALL in ln]
    return {"mla_decode_attention": sum(
                "mla_decode_attention" in name for name in own),
            "gmm": sum(bool(re.fullmatch(r"%?gmm(\.\d+)?", name))
                       for name in own)}


class CountingSlice(common.TraceSlice):
    """The traced slice, which also reads the program's routed-expert
    counters when it opens and when it closes: `moe` holds what the
    programs inside the slice routed (rows, experts touched, summed over
    layers and runs)."""

    def __init__(self, ctx: Ctx, spans: common.Spans):
        super().__init__(ctx, spans)
        self.moe: dict = {}
        self._opened: dict = {}

    @staticmethod
    def _read() -> dict:
        from distributedtraining_tpu.utils import obs
        reg = obs.registry()
        out = {}
        for name in MOE_COUNTERS:
            c = reg.peek(name)
            out[name] = float(c.value) if c is not None else 0.0
        return out

    def poll(self, t: float) -> None:
        was = self.state
        super().poll(t)
        if was == "before" and self.state == "on":
            self._opened = self._read()

    def stop(self) -> None:
        if self.state == "on":
            now = self._read()
            self.moe = {k: now[k] - self._opened.get(k, 0.0) for k in now}
        super().stop()


def run(ctx: Ctx) -> Run:
    from distributedtraining_tpu.utils import obs
    from reference import deepseek_v3 as reference
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
    if w["ttft_ms"]:
        e2e["ttft_p95_ms"] = common.percentile(w["ttft_ms"], 95)
    if w["itl_ms"]:
        e2e["itl_p95_ms"] = common.percentile(w["itl_ms"], 95)
    stats = dict(w, obs=obs_snap,
                 traced_moe_rows=trace_slice.moe.get(MOE_COUNTERS[0], 0.0),
                 traced_moe_experts=trace_slice.moe.get(MOE_COUNTERS[1], 0.0))
    return Run(setup_s=setup_s, end_to_end=e2e, attempted=w["offered"],
               failed=failed, checks=checks, stats=stats,
               memory_peak_bytes=peak, window_s=w["window_s"],
               trace_dir=trace_slice.result_dir())
