"""Driver `miner_steps`: the miner as `neurons.common.build` composes it,
driven through `MinerLoop.run` over a batch iterator that ends when the
window does. No push and no checkpoint inside the window.

Set-up builds ONE loop (the compiled step with its state), drives it from
the seed through its first `check_steps` steps through the same call and
feed the window uses, and hands that same loop to the window. After the
window the state is freed and the plain reference follows those first steps
from the seed's weights (`reference/gpt2.train_reference`)."""

from __future__ import annotations

import itertools
import math
import os
import statistics
import time

from . import common
from .common import Check, Ctx, Run, check_le

BEYOND_ANY_WINDOW = 1.0e9     # seconds: no push, pull or checkpoint fires
ADAM_B1 = 0.9                 # optax.adamw's default, which the role uses


def _miner_argv(ctx: Ctx) -> list[str]:
    d, mix = ctx.cell["driver_args"], ctx.mix
    argv = ["--model", ctx.config["preset"], "--backend", "memory",
            "--chain", "local", "--hotkey", "bench_miner",
            "--work-dir", os.path.join(common.WORK_DIR, "miner"),
            "--dataset", "synthetic", "--tokenizer", "byte",
            "--batch-size", str(mix["batch"]), "--seq-len",
            str(mix["seq_len"]),
            "--send-interval", str(BEYOND_ANY_WINDOW),
            "--checkpoint-interval", "0", "--self-eval-interval", "0"]
    argv += ["--remat"] if d["remat"] else ["--no-remat"]
    return argv + list(d.get("extra_argv", []))


def worst_leaf_gap(prog: dict, ref: dict) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:4]}")
    med = statistics.median(ref.values())
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def _leaf_norms(tree, minus=None, select=lambda path: True) -> dict:
    """{release name: norm} of a (sub)tree of the program's state, or of
    its difference from `minus` (reduced leaf by leaf, never held)."""
    import jax
    import jax.numpy as jnp

    from .program import release_name

    def norm(x, y=None):
        x = x.astype(jnp.float32) if y is None else x - y
        return jnp.sqrt(jnp.sum(jnp.square(x)))

    trees = (tree,) if minus is None else (tree, minus)
    norms = jax.jit(lambda *t: jax.tree_util.tree_map(norm, *t))(*trees)
    out = {}
    for path, val in jax.tree_util.tree_leaves_with_path(
            jax.device_get(norms)):
        if select(path):
            out[release_name(path)] = float(val)
    return out


def _is_mu(path) -> bool:
    return any(getattr(k, "name", None) == "mu" for k in path)


class _WindowFeed:
    """The window's batch iterator: ends when the window does, starts the
    traced slice when it is due, times the wait for each batch, and names
    the host's two states for the trace — waiting for the next batch, and
    everything `MinerLoop.run` does between two batches (the train-step
    dispatch)."""

    def __init__(self, feed, seconds, spans, trace_slice):
        self.feed, self.seconds = feed, seconds
        self.spans, self.slice = spans, trace_slice
        self.t0 = time.perf_counter()
        self.data_wait_s = 0.0
        self._dispatch = None

    def __iter__(self):
        return self

    def _close_dispatch(self):
        if self._dispatch is not None:
            self._dispatch.__exit__(None, None, None)
            self._dispatch = None

    def __next__(self):
        self._close_dispatch()
        t = time.perf_counter() - self.t0
        self.slice.poll(t)
        if t >= self.seconds:
            raise StopIteration
        t_wait = time.perf_counter()
        with self.spans("bench.next_batch"):
            batch = next(self.feed)
        self.data_wait_s += time.perf_counter() - t_wait
        if self.spans.on:
            self._dispatch = self.spans("bench.train_step_dispatch")
            self._dispatch.__enter__()
        return batch


class Program:
    """The miner's loop as the role composes it, and its first steps."""

    def __init__(self, ctx: Ctx):
        from distributedtraining_tpu.config import RunConfig
        from distributedtraining_tpu.engine import MinerLoop
        from neurons.common import build

        from .program import make_model

        make_model(ctx.config)       # the file's sizes are the preset's
        self.ctx = ctx
        self.cfg = cfg = RunConfig.from_args("miner", _miner_argv(ctx))
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

    def first_steps(self, seed: int) -> dict:
        """Weights from the seed, then `check_steps` steps through the
        window's own call (`MinerLoop.run`) and feed (the role's prefetch
        over the generator). Returns each step's loss, the norm of every
        leaf of the first gradient as the optimizer got it (Adam's first
        moment after one step is (1 - b1) g), the norm of every leaf's
        change over the steps, and the batches."""
        from distributedtraining_tpu.data import prefetch
        from reference import gpt2 as reference
        from traffic import gen

        from .program import to_program_tree

        ctx, loop = self.ctx, self.loop
        n = ctx.cell["driver_args"]["check_steps"]
        tree = to_program_tree(reference.init_weights(ctx.model_cfg(), seed))
        loop.bootstrap(params=tree)
        del tree
        kept: list = []

        def source():
            for batch in gen.packed_batches(ctx.mix, seed,
                                            ctx.config["vocab_size"]):
                if len(kept) < n:
                    kept.append(batch)
                yield batch

        self.feed = prefetch(source(), depth=self.cfg.prefetch_depth)
        losses, grad = [], {}
        for i in range(n):
            loop.run(itertools.islice(self.feed, 1))
            losses.append(loop.report.last_loss)
            if i == 0:
                grad = {k: v / (1.0 - ADAM_B1) for k, v in _leaf_norms(
                    loop.state.opt_state, select=_is_mu).items()}
        change = _leaf_norms(loop.state.params, loop.base_params)
        return {"losses": losses, "grad_norms": grad,
                "change_norms": change, "batches": kept}

    def free(self) -> None:
        if self.feed is not None:
            self.feed.close()
            self.feed = None
        self.loop.state = None
        self.loop.base_params = None


def compare(first: dict, ref: dict) -> dict:
    """The three numbers `correct` compares for a train cell."""
    grad_gap, grad_leaf = worst_leaf_gap(first["grad_norms"],
                                         ref["grad_norms"])
    chg_gap, chg_leaf = worst_leaf_gap(first["change_norms"],
                                       ref["change_norms"])
    gaps = [abs(a - b) for a, b in zip(first["losses"], ref["losses"])]
    return {"first_loss_gap": gaps[0], "later_loss_gap": max(gaps[1:]),
            "grad_norm_gap": grad_gap, "change_norm_gap": chg_gap,
            "worst_leaves": [grad_leaf, chg_leaf]}


def run(ctx: Ctx) -> Run:
    import jax

    from distributedtraining_tpu.utils import obs
    from reference import gpt2 as reference

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
        window = _WindowFeed(prog.feed, ctx.seconds, spans, trace_slice)
        report = loop.run(window)        # returns on the last loss's fetch
        window_s = time.perf_counter() - window.t0 - trace_slice.overhead_s
        window._close_dispatch()
        trace_slice.stop()
        steps = report.steps - steps_before
        last_loss = report.last_loss
        compiles_in_window = ctx.compiles.since_mark()
        tokens_per_step = ctx.mix["batch"] * ctx.mix["seq_len"]
        peak = common.memory_peak_bytes()
        stats = {"steps": steps, "window_s": window_s,
                 "tokens_per_step": tokens_per_step,
                 "batch": ctx.mix["batch"], "seq_len": ctx.mix["seq_len"],
                 "data_wait_pct": 100.0 * window.data_wait_s / window_s,
                 "obs": common.obs_snapshot(obs) if ctx.trace else {}}
        # which attention path the step holds, from its lowered text
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (loop.state, prog.c.engine.place_batch(first["batches"][0])))
        mosaic = prog.c.engine.train_step.lower(*abstract).as_text().count(
            common.MOSAIC_CALL)
    finally:
        if ctx.trace:
            obs.reset()
        prog.free()

    # the program's state is freed: let the reference follow the steps
    lr, wd = prog.cfg.learning_rate, prog.cfg.weight_decay
    del loop, prog, abstract
    common.free_device_memory()
    t_ref = time.perf_counter()
    ref = reference.train_reference(ctx.model_cfg(), ctx.seed,
                                    first["batches"], lr=lr, weight_decay=wd)
    print(f"bench: reference followed {len(first['batches'])} steps in "
          f"{time.perf_counter() - t_ref:.1f}s", flush=True)

    lim, got = ctx.cell["limits"], compare(first, ref)
    finite = math.isfinite(last_loss)
    checks = [
        check_le("first_loss_gap", got["first_loss_gap"],
                 lim["first_loss_gap"],
                 f"program {first['losses']} reference {ref['losses']}"),
        check_le("later_loss_gap", got["later_loss_gap"],
                 lim["later_loss_gap"]),
        check_le("grad_norm_gap", got["grad_norm_gap"], lim["grad_norm_gap"],
                 f"worst leaf {got['worst_leaves'][0]}"),
        check_le("change_norm_gap", got["change_norm_gap"],
                 lim["change_norm_gap"],
                 f"worst leaf {got['worst_leaves'][1]}"),
        Check("last_loss_below_first", last_loss, first["losses"][0],
              finite and last_loss < first["losses"][0],
              f"after {steps} window steps"),
        check_le("compiles_in_window", compiles_in_window, 0),
        Check("flash_kernel_calls", mosaic, d["expect_mosaic_min"],
              mosaic >= d["expect_mosaic_min"],
              "tpu_custom_call in the lowered train step"),
    ]
    rate = steps * tokens_per_step / window_s
    print(f"bench: train window {window_s:.3f}s steps={steps} "
          f"step_ms={1e3 * window_s / max(steps, 1):.2f} "
          f"last_loss={last_loss:.4f}", flush=True)
    return Run(setup_s=setup_s,
               end_to_end={"train_tokens_per_s": rate},
               attempted=steps, failed=0 if finite else steps,
               checks=checks, stats=stats, memory_peak_bytes=peak,
               window_s=window_s, trace_dir=trace_slice.result_dir())
