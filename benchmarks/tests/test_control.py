"""`correct` has to be a comparison that can fail. Two kinds of proof, at a
size a test run can hold (the chip readings at the cells' own sizes are in
PERF.md):

1. the control — the plain reference put in the program's place, computed
   in float8 where the configuration states bfloat16 — reads outside what
   sound runs read (`tools/control.py` is what reads both on the chip);
2. a whole run with the timed path broken underneath comes out
   `correct: false`.
"""

import json
import sys

import pytest


def _run(run_cell, capsys, argv):
    rc = run_cell.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def _argv(cell, seed=3):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", "0"]


def test_train_control_reads_outside_the_sound_runs(tiny_checkout):
    from drivers import common, miner_steps
    from reference import gpt2 as reference
    import run_cell
    cell = common.load_json("workloads", "train-tiny.json")
    from traffic import gen
    ctx = common.Ctx(cell=cell, config=common.load_json("configs",
                                                        "tiny.json"),
                     mix=gen.load_mix(cell["traffic"]), seed=1, seconds=1.0,
                     trace=False, t_process=0.0,
                     compiles=common.CompileListener(),
                     device={"count": 1})
    prog = miner_steps.Program(ctx)
    sound, control = [], []
    for seed in (1, 2, 3):
        first = prog.first_steps(seed)
        prog.free()
        kw = dict(lr=prog.cfg.learning_rate,
                  weight_decay=prog.cfg.weight_decay)
        ref = reference.train_reference(ctx.model_cfg(), seed,
                                        first["batches"], **kw)
        low = reference.train_reference(ctx.model_cfg(), seed,
                                        first["batches"], precision="fp8",
                                        **kw)
        sound.append(miner_steps.compare(first, ref))
        control.append(miner_steps.compare(low, ref))
    lim = cell["limits"]
    for s in sound:
        assert all(s[k] <= lim[k] for k in lim), s
    for c in control:       # fails one of the cell's numbers, not each
        assert any(c[k] > lim[k] for k in lim), c
    assert min(c["first_loss_gap"] for c in control) > 2 * max(
        s["first_loss_gap"] for s in sound)


def test_serve_control_is_read_at_the_served_positions(tiny_checkout):
    """At `tiny` (two layers) float8 rarely changes which token is first,
    so this keeps the machinery running rather than a limit: over three
    seeds the control is read at the served positions and is never below
    the sound reading. The readings that separate the two are the chip's, at
    the cells' own sizes (PERF.md section 6)."""
    from drivers import common, open_loop
    from traffic import gen
    cell = common.load_json("workloads", "serve-tiny.json")
    spans = common.Spans()
    scores = []
    for seed in (1, 2, 3):
        ctx = common.Ctx(cell=cell,
                         config=common.load_json("configs", "tiny.json"),
                         mix=gen.load_mix(cell["traffic"]), seed=seed,
                         seconds=3.0, trace=False, t_process=0.0,
                         compiles=common.CompileListener(),
                         device={"count": 1})
        engine = open_loop.build_and_warm(ctx, warm=False)
        w = open_loop.serve_window(
            ctx, engine, gen.open_loop_requests(ctx.mix, seed, 3.0, 512),
            spans, common.TraceSlice(ctx, spans))
        sample = open_loop._sample_finished(w["finished"], seed, 8)
        engine.close()
        scores.append(open_loop.score_served(ctx.model_cfg(), seed, sample,
                                             128, "fp8"))
    for s in scores:
        assert s["tokens"] >= 60
        assert s["served_gap"] <= cell["limits"]["served_logit_gap"]
        assert s["control_mean_gap"] >= s["served_mean_gap"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tiny_checkout, capsys, monkeypatch):
    from distributedtraining_tpu.engine import MinerLoop
    orig = MinerLoop._train_one

    def frozen(self, batch):
        import jax
        before = jax.tree_util.tree_map(lambda x: x.copy(),
                                        self.state.params)   # it is donated
        m = orig(self, batch)
        self.state = self.state.replace(params=before)
        return m

    monkeypatch.setattr(MinerLoop, "_train_one", frozen)
    rc, res, out = _run(tiny_checkout, capsys, _argv("train-tiny"))
    assert rc == 0 and res["correct"] is False, "\n".join(out)
    assert any("check change_norm_gap" in line and "FAIL" in line
               for line in out)


def test_part_of_the_batch_left_out_is_not_correct(tiny_checkout, capsys,
                                                   monkeypatch):
    from distributedtraining_tpu.engine import TrainEngine
    orig = TrainEngine.place_batch

    def half(self, batch):
        batch = dict(batch)
        mask = batch["loss_mask"].copy()
        mask[mask.shape[0] // 2:] = 0.0
        batch["loss_mask"] = mask
        return orig(self, batch)

    monkeypatch.setattr(TrainEngine, "place_batch", half)
    rc, res, out = _run(tiny_checkout, capsys, _argv("train-tiny"))
    assert rc == 0 and res["correct"] is False, "\n".join(out)
    assert any("check first_loss_gap" in line and "FAIL" in line for line in out)


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tiny_checkout, capsys, monkeypatch):
    from distributedtraining_tpu.engine.serve import GenerationEngine
    orig = GenerationEngine._emit

    def altered(self, slot, tok):
        return orig(self, slot, (tok + 1) % self.cfg.vocab_size)

    monkeypatch.setattr(GenerationEngine, "_emit", altered)
    rc, res, out = _run(tiny_checkout, capsys, _argv("serve-tiny"))
    assert rc == 0 and res["correct"] is False, "\n".join(out)
    assert any("check served_logit_gap" in line and "FAIL" in line
               for line in out)
