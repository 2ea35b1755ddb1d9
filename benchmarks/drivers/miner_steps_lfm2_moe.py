"""Driver `miner_steps_lfm2_moe`: `miner_steps` for the LFM2-MoE family.
The window's feed, the role's arguments, the comparison of losses and of
every leaf's norms (`compare`, over `worst_leaf_gap`) and the loop's
release (`Program.free`) are `miner_steps`'s own, imported; what is here
is what differs: the family's reference (`reference/lfm2_moe.py`), the
mapping between its layout and the program's tree, the checks the family
adds (the first gradient of the median small leaf by its DISTANCE from the
reference's, the one number that tells a lower precision from a sound run;
the buffer `expert_bias` bit-equal after the steps and without moments;
the kernels of BOTH Mosaic families in the step) and what its per-layer
metrics read (the routed layers' counters over the traced steps, the
documents' own lengths).

`miner_steps.Program.__init__`, `first_steps`, `_leaf_norms` and `run` name
GPT-2's preset table, leaf names and reference inside their bodies, so
those bodies cannot be inherited and their few shared lines stand here
again with the family's in their place (PERF.md section 7, (b): one `run`
that takes the family's pieces as arguments is an edit to that file, a
`benchmark` PR's)."""

from __future__ import annotations

import itertools
import math
import statistics
import time
import types

import numpy as np

from . import common, miner_steps
from .common import Check, Ctx, Run, check_le
from .miner_steps import ADAM_B1, BEYOND_ANY_WINDOW, _WindowFeed, compare

# the reference's leaf -> the program's path inside a layer
_LAYER_LEAVES = {
    "operator_norm": ("operator_norm", "scale"),
    "ffn_norm": ("ffn_norm", "scale"),
    "conv.in_proj": ("in_proj", "kernel"),
    "conv.conv": ("conv_weight",),
    "conv.out_proj": ("out_proj", "kernel"),
    "self_attn.q_proj": ("q_proj", "kernel"),
    "self_attn.k_proj": ("k_proj", "kernel"),
    "self_attn.v_proj": ("v_proj", "kernel"),
    "self_attn.out_proj": ("out_proj", "kernel"),
    "self_attn.q_layernorm": ("q_layernorm", "scale"),
    "self_attn.k_layernorm": ("k_layernorm", "scale"),
    "feed_forward.w1": ("w1", "kernel"),
    "feed_forward.w3": ("w3", "kernel"),
    "feed_forward.w2": ("w2", "kernel"),
    "feed_forward.gate": ("router",),
    "feed_forward.expert_bias": ("expert_bias",),
    "feed_forward.experts_in": ("experts_in",),
    "feed_forward.experts_down": ("experts_down",),
}
_ENDS = {"embed_tokens": ("embed_tokens",),
         "embedding_norm": ("norm_f", "scale")}
_CHECKED_SIZES = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_dense_layers", "num_attention_heads",
    "num_key_value_heads", "conv_L_cache", "conv_bias",
    "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
    "routed_scaling_factor", "norm_eps", "rope_theta",
    "max_position_embeddings")
# elements: the leaves whose first gradient is compared whole (a router's
# [2048, 32], the taps, a gain); each sums over every token of the step
SMALL_LEAF = 65536
COUNTERS = ("train.moe.rows", "train.moe.rows_elsewhere",
            "train.moe.rows_fullest_expert", "train.moe.experts_touched")


def check_config(config: dict):
    """Every published size in the file must be the preset's own: a file
    that says one thing while the preset runs another is refused. Returns
    the preset."""
    from distributedtraining_tpu.models import lfm2_moe

    pc = lfm2_moe.PRESETS[config["preset"]]
    whole = lfm2_moe.PRESETS["lfm2-8b-a1b"]
    want = {k: getattr(pc, k) for k in _CHECKED_SIZES}
    want.update(layer_types=list(pc.layer_types),
                num_experts=pc.experts_held[1],
                experts_held=list(pc.experts_held),
                vocab_held=list(pc.vocab_held))
    for key, val in want.items():
        if config[key] != val:
            raise SystemExit(f"bench: FAIL: {config['name']}.{key} = "
                             f"{config[key]!r} but preset "
                             f"{config['preset']} runs {val!r}")
    pub = config.get("published", {})
    if pub.get("num_experts", pc.num_experts) != pc.num_experts:
        raise SystemExit("bench: FAIL: the router's width differs from the "
                         "preset's num_experts")
    for key in ("num_hidden_layers", "num_dense_layers", "vocab_size"):
        if key in pub and pub[key] != getattr(whole, key):
            raise SystemExit(f"bench: FAIL: published.{key} is not the "
                             "uncut preset's")
    if pub.get("layer_types", list(whole.layer_types)) != list(
            whole.layer_types):
        raise SystemExit("bench: FAIL: published.layer_types is not the "
                         "uncut preset's")
    if config.get("assumed", {}).get("padded_vocab",
                                     pc.padded_vocab) != pc.padded_vocab:
        raise SystemExit("bench: FAIL: padded_vocab differs from the preset")
    dt = config["dtypes"]
    if (dt["param"], dt["compute"], dt["logits"]) != (
            pc.param_dtype, pc.dtype, pc.logits_dtype):
        raise SystemExit("bench: FAIL: dtypes differ from the preset")
    return pc


def to_program_tree(weights: dict) -> dict:
    """Reference layout -> the program's Flax tree. The arrays are handed
    over, not copied."""
    tree: dict = {}

    def put(path, leaf):
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf

    for name, path in _ENDS.items():
        put(path, weights[name])
    for i, blk in enumerate(weights["layers"]):
        for leaf, x in blk.items():
            put((f"layer_{i}",) + _LAYER_LEAVES[leaf], x)
    return tree


def _leaf_norms(tree, minus=None, select=lambda path: True,
                mixers: tuple = ()) -> dict:
    """{reference name: norm} of a (sub)tree of the program's state, or of
    its difference from `minus` (reduced leaf by leaf, never held)."""
    import jax
    import jax.numpy as jnp

    def norm(x, y=None):
        x = x.astype(jnp.float32) if y is None else x - y
        return jnp.sqrt(jnp.sum(jnp.square(x)))

    trees = (tree,) if minus is None else (tree, minus)
    norms = jax.jit(lambda *t: jax.tree_util.tree_map(norm, *t))(*trees)
    out = {}
    for path, val in jax.tree_util.tree_leaves_with_path(
            jax.device_get(norms)):
        if select(path):
            out[program_leaf_name(path, mixers)] = float(val)
    return out


def program_leaf_name(path, mixers: tuple) -> str:
    """A key path of the program's tree (or of its optimizer state, which
    mirrors it) -> the reference's name. `mixers` is the configuration's
    `layer_types`: `out_proj` belongs to the layer's one mixer."""
    keys = tuple(k.key for k in path
                 if isinstance(getattr(k, "key", None), str))
    start = next(i for i, k in enumerate(keys)
                 if k.startswith("layer_") or k in ("embed_tokens", "norm_f"))
    keys = keys[start:]
    for name, p in _ENDS.items():
        if keys == p:
            return name
    i = int(keys[0][6:])
    prefix = "conv." if mixers[i] == "conv" else "self_attn."
    for leaf, p in _LAYER_LEAVES.items():
        if p == keys[1:] and (p != ("out_proj", "kernel")
                              or leaf.startswith(prefix)):
            return f"layers.{i}.{leaf}"
    raise KeyError(keys)


def _is_mu(path) -> bool:
    return any(getattr(k, "name", None) == "mu" for k in path)


def doc_square_sum(segment_ids: np.ndarray) -> float:
    """Sum over a batch's documents of (their length)^2: what causal
    attention inside documents needs, twice over."""
    total = 0.0
    for row in np.asarray(segment_ids):
        total += float(np.sum(np.square(np.bincount(row).astype(np.float64))))
    return total


def direction_gap(prog: dict, ref: dict) -> tuple[float, str]:
    """The DISTANCE between the program's first gradient of a small leaf
    and the reference's, over the reference's norm of it: the median
    leaf's, and which leaf is worst. A norm hardly moves under roundings
    that cancel; a distance adds them up, in every leaf alike, so the
    median leaf tells a lower precision from a sound run, whose flipped
    experts reach the routed layers' own small leaves (a router, the norm
    before it) and leave the median alone."""
    gaps = {k: float(np.linalg.norm(np.asarray(prog[k], np.float64) - g)
                     / np.linalg.norm(g)) for k, g in ref.items()}
    worst = max(gaps, key=gaps.get)
    return (statistics.median(gaps.values()),
            f"{worst} {gaps[worst]:.4g} of {len(gaps)}")


class Program(miner_steps.Program):
    """The miner's loop as the role composes it, and its first steps."""

    def __init__(self, ctx: Ctx):
        from distributedtraining_tpu.config import RunConfig
        from distributedtraining_tpu.engine import MinerLoop
        from neurons.common import build

        check_config(ctx.config)
        self.ctx = ctx
        self.cfg = cfg = RunConfig.from_args(
            "miner", miner_steps._miner_argv(ctx))
        self.c = build(cfg)
        self.loop = MinerLoop(
            self.c.engine, self.c.transport, cfg.hotkey,
            send_interval=BEYOND_ANY_WINDOW,
            check_update_interval=BEYOND_ANY_WINDOW,
            metrics=None, log_every=cfg.log_every,
            keep_optimizer_on_pull=cfg.keep_optimizer_on_pull,
            push_async=cfg.push_async,
            push_queue_depth=cfg.push_queue_depth)
        self.feed = None
        self.mixers = tuple(ctx.config["layer_types"])
        self.batches_made = 0
        self.doc_sq = 0.0

    def first_steps(self, seed: int) -> dict:
        """As `miner_steps.Program.first_steps`, with the family's weights
        and names; besides, whether the buffer is bit-equal to the base's
        after the steps."""
        import jax
        import jax.numpy as jnp

        from distributedtraining_tpu.data import prefetch
        from reference import lfm2_moe as reference
        from traffic import gen

        ctx, loop = self.ctx, self.loop
        n = ctx.cell["driver_args"]["check_steps"]
        tree = to_program_tree(reference.init_weights(
            reference.model_cfg(ctx.config), seed))
        loop.bootstrap(params=tree)
        del tree
        kept: list = []

        def source():
            for batch in gen.packed_batches(ctx.mix, seed,
                                            ctx.config["vocab_size"]):
                if len(kept) < n:
                    kept.append(batch)
                self.batches_made += 1
                self.doc_sq += doc_square_sum(batch["segment_ids"])
                yield batch

        self.feed = prefetch(source(), depth=self.cfg.prefetch_depth)
        losses, grad, small = [], {}, {}
        for i in range(n):
            loop.run(itertools.islice(self.feed, 1))
            losses.append(loop.report.last_loss)
            if i == 0:
                grad = {k: v / (1.0 - ADAM_B1) for k, v in _leaf_norms(
                    loop.state.opt_state, select=_is_mu,
                    mixers=self.mixers).items()}
                small = {
                    program_leaf_name(path, self.mixers):
                        np.asarray(x) / (1.0 - ADAM_B1)
                    for path, x in jax.tree_util.tree_leaves_with_path(
                        loop.state.opt_state)
                    if _is_mu(path) and x.size <= SMALL_LEAF}
        change = _leaf_norms(loop.state.params, loop.base_params,
                             mixers=self.mixers)
        moved = [float(jnp.max(jnp.abs(
            loop.state.params[k]["expert_bias"]
            - loop.base_params[k]["expert_bias"])))
            for k in loop.state.params
            if "expert_bias" in loop.state.params[k]]
        # a buffer has no moments: a first moment under its name means the
        # optimizer took it for a parameter (at a small learning rate the
        # decay itself, lr x wd x b a step, hides under float32's rounding)
        moments = [k for k in grad if k.endswith("expert_bias")]
        for k in moments:
            del grad[k]
        return {"losses": losses, "grad_norms": grad, "change_norms": change,
                "grads": small, "batches": kept, "buffer_moved": max(moved),
                "buffer_moments": len(moments)}


def _counted(obs) -> dict:
    """What the routed layers counted so far, as the registry has it (a
    step's counts reach it with the fetch of a loss)."""
    out = {}
    for name in COUNTERS:
        c = obs.registry().peek(name)
        out[name] = float(c.value) if c is not None else 0.0
    return out


def _moe_stats(counted: dict, held: int, prefix: str = "") -> dict:
    """Nothing where the program counted nothing (a parent without the
    counters). Steps that computed NO row here (a share trained alone
    routes its rows away: PERF.md section 6, PR 33) read a share of 0 and,
    there being no fullest expert, a ratio of 0."""
    rows, away, fullest, touched = (counted[name] for name in COUNTERS)
    if not rows + away:
        return {}
    return {f"{prefix}moe_rows": rows, f"{prefix}moe_experts": touched,
            f"{prefix}moe_share_here_pct": 100.0 * rows / (rows + away),
            f"{prefix}moe_fullest_over_mean":
                fullest / (rows / held) if rows else 0.0}


def _run_window(ctx: Ctx, prog: Program, spans, trace_slice) -> dict:
    """The window through `MinerLoop.run`. An untraced run is one call, as
    `miner_steps` makes it. A traced run ends the loop once where the slice
    is due (`run` returns on its last loss's fetch, with which the steps'
    counters reach the registry), reads the counters, and runs the slice as
    a second call under the profiler: what the trace's readers credit is
    then what exactly the traced steps counted, whatever the routing did
    earlier in the window. The device idles for the one dispatch between
    the two calls."""
    from distributedtraining_tpu.utils import obs

    loop, head_s, wait_s, before = prog.loop, 0.0, 0.0, {}
    seconds = ctx.seconds
    if ctx.trace:
        head = _WindowFeed(prog.feed, trace_slice.start_at, spans,
                           types.SimpleNamespace(poll=lambda t: None))
        loop.run(head)
        head._close_dispatch()
        head_s, wait_s = time.perf_counter() - head.t0, head.data_wait_s
        before = _counted(obs)
        seconds, trace_slice.start_at = seconds - trace_slice.start_at, 0.0
    window = _WindowFeed(prog.feed, seconds, spans, trace_slice)
    report = loop.run(window)            # returns on the last loss's fetch
    window_s = (head_s + time.perf_counter() - window.t0
                - trace_slice.overhead_s)
    window._close_dispatch()
    trace_slice.stop()
    out = {"report": report, "window_s": window_s,
           "data_wait_s": wait_s + window.data_wait_s, "moe": {}}
    if ctx.trace:
        held = ctx.config["experts_held"][1]
        whole = _counted(obs)
        out["moe"] = {
            **_moe_stats(whole, held),
            **_moe_stats({k: whole[k] - before[k] for k in whole}, held,
                         "traced_")}
    return out


def measure(ctx: Ctx) -> dict:
    """The program's part of a run: set-up with the first steps, the
    window, and what the step's lowered text holds. The program's state is
    freed when this returns."""
    import jax

    from distributedtraining_tpu.utils import obs

    d = ctx.cell["driver_args"]
    spans = common.Spans()
    trace_slice = common.TraceSlice(ctx, spans)
    prog = Program(ctx)
    loop = prog.loop
    try:
        first = prog.first_steps(ctx.seed)
        steps_before = loop.report.steps
        jax.block_until_ready(loop.state.params)

        if ctx.trace:       # the program's registry, for the window only
            obs.configure(common.NullSink(), role="miner")
        setup_s = time.perf_counter() - ctx.t_process
        ctx.compiles.mark()
        w = _run_window(ctx, prog, spans, trace_slice)
        report, window_s = w["report"], w["window_s"]
        steps = report.steps - steps_before
        stats = {"steps": steps, "window_s": window_s,
                 "tokens_per_step": ctx.mix["batch"] * ctx.mix["seq_len"],
                 "batch": ctx.mix["batch"], "seq_len": ctx.mix["seq_len"],
                 "data_wait_pct": 100.0 * w["data_wait_s"] / window_s,
                 "doc_sq_per_step": prog.doc_sq / max(prog.batches_made, 1),
                 "obs": common.obs_snapshot(obs) if ctx.trace else {},
                 **w["moe"]}
        if "moe_rows" in stats and steps:
            stats["moe_rows_per_step"] = stats["moe_rows"] / steps
        # which kernels the step holds, from its lowered text
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (loop.state, prog.c.engine.place_batch(first["batches"][0])))
        lowered = prog.c.engine.train_step.lower(*abstract).as_text()
        calls = {name: lowered.count(name) for name in d["expect_kernels"]}
        return {"first": first, "setup_s": setup_s, "window_s": window_s,
                "steps": steps, "last_loss": report.last_loss,
                "compiles_in_window": ctx.compiles.since_mark(),
                "peak": common.memory_peak_bytes(), "stats": stats,
                "calls": calls, "trace_slice": trace_slice,
                "lr": prog.cfg.learning_rate, "wd": prog.cfg.weight_decay}
    finally:
        if ctx.trace:
            obs.reset()
        prog.free()
        del loop, prog
        common.free_device_memory()


def follow(ctx: Ctx, got: dict, precision: str = "float32") -> dict:
    """The plain reference over the same first batches, from the seed's
    weights."""
    from reference import lfm2_moe as reference

    t_ref = time.perf_counter()
    ref = reference.train_reference(
        reference.model_cfg(ctx.config), ctx.seed, got["first"]["batches"],
        lr=got["lr"], weight_decay=got["wd"], precision=precision,
        keep_grads_up_to=SMALL_LEAF)
    print(f"bench: reference ({precision}) followed "
          f"{len(got['first']['batches'])} steps in "
          f"{time.perf_counter() - t_ref:.1f}s", flush=True)
    return ref


def limit_checks(ctx: Ctx, first: dict, ref: dict) -> list:
    """The numbers of `correct` that have a limit, for a program's first
    steps (or the control's, put in its place) against the reference's."""
    lim, got = ctx.cell["limits"], compare(first, ref)
    direction, worst = direction_gap(first["grads"], ref["grads"])
    return [
        check_le("first_loss_gap", got["first_loss_gap"],
                 lim["first_loss_gap"],
                 f"program {first['losses']} reference {ref['losses']}"),
        check_le("later_loss_gap", got["later_loss_gap"],
                 lim.get("later_loss_gap", math.inf),
                 "" if "later_loss_gap" in lim else "reported, not held: "
                 "the cell gives it no limit (its limits_why)"),
        check_le("grad_norm_gap", got["grad_norm_gap"], lim["grad_norm_gap"],
                 f"worst leaf {got['worst_leaves'][0]}"),
        check_le("grad_direction_gap", direction, lim["grad_direction_gap"],
                 f"the median small leaf; the worst is {worst}"),
        check_le("change_norm_gap", got["change_norm_gap"],
                 lim["change_norm_gap"],
                 f"worst leaf {got['worst_leaves'][1]}"),
        check_le("buffer_moved", first.get("buffer_moved", 0.0), 0.0,
                 "expert_bias against the base's, widest element"),
        check_le("buffer_moments", first.get("buffer_moments", 0), 0,
                 "first moments the optimizer holds for expert_bias"),
    ]


def run(ctx: Ctx) -> Run:
    d = ctx.cell["driver_args"]
    m = measure(ctx)
    first, steps, last_loss = m["first"], m["steps"], m["last_loss"]
    ref = follow(ctx, m)

    finite = math.isfinite(last_loss)
    checks = limit_checks(ctx, first, ref) + [
        Check("last_loss_below_first", last_loss, first["losses"][0],
              finite and last_loss < first["losses"][0],
              f"after {steps} window steps"),
        check_le("compiles_in_window", m["compiles_in_window"], 0),
    ]
    for name, least in d["expect_kernels"].items():
        checks.append(Check(f"kernel_calls.{name}", m["calls"][name], least,
                            m["calls"][name] >= least,
                            "in the lowered train step"))
    window_s = m["window_s"]
    rate = steps * m["stats"]["tokens_per_step"] / window_s
    print(f"bench: train window {window_s:.3f}s steps={steps} "
          f"step_ms={1e3 * window_s / max(steps, 1):.2f} "
          f"last_loss={last_loss:.4f}", flush=True)
    s = m["stats"]
    if "moe_share_here_pct" in s:
        print(f"bench: routed rows computed here: {s['moe_share_here_pct']:.2f}"
              f"% over the window, {s.get('traced_moe_share_here_pct', 0.0):.2f}"
              "% over the traced steps (25 at the seed)", flush=True)
    return Run(setup_s=m["setup_s"],
               end_to_end={"train_tokens_per_s": rate},
               attempted=steps, failed=0 if finite else steps,
               checks=checks, stats=m["stats"], memory_peak_bytes=m["peak"],
               window_s=window_s, trace_dir=m["trace_slice"].result_dir())
