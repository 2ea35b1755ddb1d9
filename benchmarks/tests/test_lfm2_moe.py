"""The LFM2-MoE family's part of the benchmark, on the CPU at the program's
`tiny-lfm2` preset: its kernel arithmetic against hand counts, its readers
on synthetic traces, `run_cell.py` end to end through the driver
`miner_steps_lfm2_moe` from a temporary copy (new files only), and
`correct` shown to be a comparison that can fail: the float8 control and
the faults this family's mechanisms invite read outside what sound runs
read. (The program against the reference, leaf by leaf: the repository's
own tests/test_lfm2_moe.py.)"""

import json
import sys
import types

import pytest

import conftest


@pytest.fixture
def lfm2_checkout(tmp_path, monkeypatch):
    import tiny_lfm2_moe
    root = tiny_lfm2_moe.copy_with_tiny(tmp_path)
    saved = list(sys.path)
    run_cell = conftest._load_run_cell(root)
    from drivers import common
    monkeypatch.setattr(common, "require_device", lambda chips: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(common, "WORK_DIR", str(tmp_path / "work"))
    yield run_cell
    sys.path[:] = saved
    conftest._load_run_cell(conftest.ROOT)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cell_config() -> dict:
    from drivers import common
    return common.load_json("configs", "lfm2-8b-a1b-l5-e8-v16k.json")


# -- kernel arithmetic -------------------------------------------------------

def test_dense_parameters_against_a_hand_count():
    from readers import kernel_math_lfm2_moe as km
    c = _cell_config()
    assert km.expert_params(2048, 1792) == 11_010_048
    # the head 16,384 x 2,048; layer 0: in_proj 2,048 x 6,144 + out_proj
    # 2,048^2 and the dense FFN 3 x 2,048 x 7,168; layer 1: q and out
    # 2,048^2 each, k and v 2,048 x 512 each, the router 2,048 x 32;
    # layers 2-4: the convolution's two matrices and the router
    assert km.dense_matmul_params(c) == (
        33_554_432 + (16_777_216 + 44_040_192) + (10_485_760 + 65_536)
        + 3 * (16_777_216 + 65_536)) == 155_451_392
    assert km.attention_layers(c) == 1
    # the issue's count: with uniform routing a token has 4 rows here
    assert round((km.dense_matmul_params(c) + 4 * 11_010_048) / 1e6,
                 1) == 199.5


def test_step_operations_against_a_hand_count():
    from readers import kernel_math_lfm2_moe as km
    c = _cell_config()
    # one document a row: 2 x 8,192^2
    flops = km.train_step_flops(c, 16_384, 65_536, 2 * 8192 ** 2)
    assert flops == (6 * 155_451_392 * 16_384 + 6 * 11_010_048 * 65_536
                     + 6 * 2 * 8192 ** 2 * 2048)
    assert round(flops / 1e12, 2) == 21.26       # 107.9 ms at the peak
    # documents of 1,024: an eighth of the attention
    less = km.train_step_flops(c, 16_384, 65_536, 16 * 1024 ** 2)
    assert flops - less == 6 * 2048 * (2 * 8192 ** 2 - 16 * 1024 ** 2)


def test_grouped_product_work_against_a_hand_count():
    from readers import kernel_math_lfm2_moe as km
    # one step: 65,536 rows through 3 matrices, 3 products each; the 32
    # touched stacks (8 experts x 4 layers) read twice and written once
    ops, nbytes = km.moe_train_work(65_536, 32, 2048, 1792)
    assert ops == 3 * 2 * 65_536 * 11_010_048 == 4_329_327_034_368
    assert nbytes == 3 * 32 * 11_010_048 * 2 == 2_113_929_216
    # compute bounds it: 21.98 ms against 2.58 ms of traffic
    assert km.roofline_seconds(ops, nbytes, PEAKS) == ops / 197e12
    # a step whose routing left 2,096 rows to 9 experts of the 32: the
    # touched experts' bytes bound it (0.73 ms against 0.70 of products)
    ops, nbytes = km.moe_train_work(2_096, 9, 2048, 1792)
    assert nbytes == 3 * 9 * 11_010_048 * 2 == 594_542_592
    assert km.roofline_seconds(ops, nbytes, PEAKS) == nbytes / 819e9
    assert ops / 197e12 < nbytes / 819e9


def test_packed_attention_work_against_a_hand_count():
    from readers import kernel_math_lfm2_moe as km
    doc_sq = 2 * 8192 ** 2
    ops, nbytes = km.flash_packed_work(doc_sq, 16_384, 32, 8, 64, False)
    assert ops == 2 * doc_sq * 2048 == 549_755_813_888
    # q and o of 32 heads, k and v of 8, bfloat16
    assert nbytes == 16_384 * 64 * 2 * (2 * 32 + 2 * 8) == 167_772_160
    ops_b, nbytes_b = km.flash_packed_work(doc_sq, 16_384, 32, 8, 64, True)
    assert ops_b == 5 * doc_sq * 2048 and nbytes_b == 2 * nbytes
    # the kernel_math convention at equal heads: 2 x B x T^2 x E
    from readers import kernel_math
    same, _ = kernel_math.flash_attention_call(2, 8192, 2048, False)
    assert same == ops


# -- the readers -------------------------------------------------------------

def _rec(events, modules, stats, config):
    from readers import xplane
    trace = xplane.from_events({"/device:TPU:0": events}, [],
                               {"/device:TPU:0": modules})
    return types.SimpleNamespace(
        trace=trace, peaks=PEAKS,
        ctx=types.SimpleNamespace(config=config, device={"count": 1}),
        run=types.SimpleNamespace(stats=stats))


GMM = "%gmm.4 = bf16[65536,3584] custom-call(%a, %b)"
TGMM = "%tgmm.2 = bf16[8,2048,3584] custom-call(%a, %b)"
FWD = "%flash_mha_fwd_segmented_residuals.1 = bf16[32,8192,64] " \
      "custom-call(%q)"
DKV = "%flash_mha_dkv_segmented_no_residuals.1 = bf16[32,8192,64] " \
      "custom-call(%q)"
USER = "%fusion.9 = bf16[65536,1792] fusion(%gmm.4)"
STEP = ("jit_train_step(1)", 0, 100_000_000)


@pytest.mark.parametrize("model, pattern", [
    ("moe_train", "^%?t?gmm(\\.\\d+)?$"), ("flash_packed", "flash")])
def test_readers_read_nothing_without_their_kernel_or_counters(model,
                                                               pattern):
    from readers import trace_kernel_lfm2_moe as reader
    kw = dict(pattern=pattern, model=model, module_pattern="train_step")
    other = ("%fusion.1 = f32[8] fusion(%p)", 0, 1000)
    c = _cell_config()
    stats = {"traced_moe_rows": 65_536.0, "traced_moe_experts": 32.0,
             "doc_sq_per_step": 1e8, "tokens_per_step": 16_384}
    assert reader.read(_rec([other], [STEP], stats, c), **kw) is None
    # the kernels but no counters (a parent that counts nothing)
    events = [(GMM, 0, 1000), (FWD, 1000, 1000)]
    assert reader.read(_rec(events, [STEP], {"tokens_per_step": 16_384}, c),
                       **kw) is None
    # no run of the step in the slice
    assert reader.read(_rec(events, [], stats, c), **kw) is None


def test_readers_take_the_shares_from_trace_and_counters():
    from readers import trace_kernel_lfm2_moe as reader, train_lfm2_moe
    c = _cell_config()
    doc_sq = 2.0 * 4096 ** 2 * 2
    # the traced steps' own counts: 1.1 steps' rows and touched experts
    stats = {"moe_rows_per_step": 65_536.0, "doc_sq_per_step": doc_sq,
             "traced_moe_rows": 1.1 * 65_536.0, "traced_moe_experts": 35.0,
             "tokens_per_step": 16_384, "steps": 10, "window_s": 2.5}
    events = [(GMM, 0, 30_000_000), (USER, 30_000_000, 5_000_000),
              (TGMM, 40_000_000, 14_000_000), (FWD, 60_000_000, 6_000_000),
              (DKV, 70_000_000, 10_000_000)]
    # the slice is the events' extent, 80 ms: 0.8 of one run of the step
    # lies inside it and 0.3 of the next
    modules = [STEP, ("jit_train_step(1)", 50_000_000, 100_000_000)]
    rec = _rec(events, modules, stats, c)
    steps = 1.1
    got = reader.read(rec, pattern="^%?t?gmm(\\.\\d+)?$", model="moe_train",
                      module_pattern="train_step")
    assert got == pytest.approx(
        100 * steps * (4_329_327_034_368 / 197e12) / 44e-3)
    got = reader.read(rec, pattern="flash", model="flash_packed",
                      module_pattern="train_step")
    least = (2 * doc_sq * 2048 / 197e12) + (5 * doc_sq * 2048 / 197e12)
    assert got == pytest.approx(100 * steps * least / 16e-3)
    from readers import kernel_math_lfm2_moe as km
    assert train_lfm2_moe.read(rec, what="mfu_pct") == pytest.approx(
        100 * km.train_step_flops(c, 16_384, 65_536.0, doc_sq) * 10 / 2.5
        / 197e12)
    assert train_lfm2_moe.read(
        _rec(events, modules, {"steps": 10, "window_s": 2.5,
                               "tokens_per_step": 16_384}, c),
        what="mfu_pct") is None


# -- the base's bias holds the share's load ------------------------------------

@pytest.mark.parametrize("seed", [3, 2**31 + 17, 2146666613])
def test_base_bias_holds_a_quarter_of_the_rows_here_whatever_s_is(seed):
    """The cell's configuration, its routed layers' `expert_bias` as the
    reference makes it: whatever the scores (uniform, or collapsed onto
    experts elsewhere, or onto held ones), every token chooses the three
    pinned experts elsewhere and ONE held expert, the one with its largest
    s; and the seed draws which three."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import lfm2_moe as reference
    cfg = reference.model_cfg(_cell_config())
    first, count = cfg["experts_held"]
    G, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    key = reference.seed_key(seed)
    pinned_by_layer = []
    for i in range(cfg["num_dense_layers"], cfg["num_hidden_layers"]):
        name = f"layers.{i}.feed_forward.expert_bias"
        b = np.asarray(reference._make_leaf(key, name, (G,), "bias", cfg))
        plain = np.asarray(reference._make_leaf(
            key, name, (G,), "bias", dict(cfg, bias_tiers=None)))
        tiers = np.round(b - plain, 6)
        assert set(tiers[first:first + count]) == {2.0}
        assert sorted(tiers[first + count:])[-4:] == [0.0, 4.0, 4.0, 4.0]
        pinned = set(np.flatnonzero(tiers == 4.0))
        pinned_by_layer.append(tuple(sorted(pinned)))
        u = jax.random.uniform(jax.random.fold_in(key, i), (4096, G))
        for s in (u, u.at[:, first + count:].set(0.999999),
                  u.at[:, :first + count].set(1e-6),
                  jnp.where(jnp.arange(G) < count, 0.999999, 1e-6) * u):
            choice = np.asarray(jax.lax.top_k(s + b, k)[1])
            here = (choice >= first) & (choice < first + count)
            assert (here.sum(-1) == 1).all()
            assert all(set(row[~h]) == pinned
                       for row, h in zip(choice[:64], here[:64]))
            best = first + np.argmax(np.asarray(s)[:, first:first + count]
                                     + b[first:first + count], -1)
            assert (choice[here] == best).all()
    assert len(set(pinned_by_layer)) > 1        # each layer its own lot


def test_program_computes_the_held_share_of_a_tiered_base_in_every_step():
    """The PROGRAM on a tiered base, at toy widths with 2 of 8 experts held
    and 2 a token: through five steps of the role's AdamW at a rate that
    moves the router far (0.05 a step), each routed layer computes exactly
    one row a token here and leaves one to the other chips, and the buffer
    stays the base's."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    import tiny_lfm2_moe
    from distributedtraining_tpu.engine.train import (TrainEngine,
                                                      default_optimizer)
    from distributedtraining_tpu.models import lfm2_moe
    from drivers import miner_steps_lfm2_moe as driver
    from reference import lfm2_moe as reference

    pc = dataclasses.replace(lfm2_moe.PRESETS["tiny-lfm2"],
                             experts_held=(0, 2))
    config = dict(tiny_lfm2_moe.config(), num_experts=2, experts_held=[0, 2])
    config["assumed"] = dict(config["assumed"], expert_bias_tiers={
        "held": 2.0, "elsewhere": 4.0, "elsewhere_count": 1})
    tree = driver.to_program_tree(reference.init_weights(
        reference.model_cfg(config), 2**31 + 5))
    model, _ = lfm2_moe.make_model(pc)
    engine = TrainEngine(model, optimizer=default_optimizer(
        0.05, is_buffer=pc.is_buffer))
    state = engine.init_state(params=tree)
    rng = np.random.default_rng(0)
    routed = pc.num_hidden_layers - pc.num_dense_layers
    for _ in range(5):
        ids = rng.integers(0, 512, (2, 64), dtype=np.int32)
        state, m = engine.train_step(state, engine.place_batch(
            {"input_ids": ids}))
        assert int(m["train.moe.rows"]) == 2 * 64 * routed
        assert int(m["train.moe.rows_elsewhere"]) == 2 * 64 * routed
    for i in range(pc.num_dense_layers, pc.num_hidden_layers):
        assert jnp.array_equal(state.params[f"layer_{i}"]["expert_bias"],
                               tree[f"layer_{i}"]["expert_bias"])


def test_tiers_that_cannot_hold_the_choice_are_refused():
    from reference import lfm2_moe as reference
    cfg = reference.model_cfg(_cell_config())
    key = reference.seed_key(1)
    for bad in ({"held": 0.5, "elsewhere": 4.0, "elsewhere_count": 3},
                {"held": 2.0, "elsewhere": 2.5, "elsewhere_count": 3},
                {"held": 2.0, "elsewhere": 4.0, "elsewhere_count": 4}):
        with pytest.raises(ValueError, match="cannot hold the choice"):
            reference._bias_tiers(key, dict(cfg, bias_tiers=bad))


# -- the cell, rehearsed -----------------------------------------------------

def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_train_cell_runs_and_is_correct(lfm2_checkout, capsys, trace):
    rc = lfm2_checkout.main(["--workload", "train-tiny-lfm2", "--seed",
                             str(2**31 + 17), "--seconds", "2", "--trace",
                             str(trace)])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    assert res["failed"] == 0 and res["attempted"] > 0
    if trace == 0:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    else:
        m = res["metrics"]
        # the routed layers' counters left the step and were read: all 8
        # experts are held at this size, so every routed row is here
        assert m["train.moe.share_here_pct"]["value"] == 100.0
        # Zipf tokens at toy widths route unevenly: only the sign is held
        assert 1.0 <= m["train.moe.fullest_over_mean"]["value"] < 8.0
        assert m["train.moe.mfu_pct"]["value"] > 0
        assert "train.step_ms" in m and "train.data_wait_pct" in m
    for name in ("first_loss_gap", "grad_norm_gap", "grad_direction_gap",
                 "change_norm_gap", "buffer_moved", "buffer_moments",
                 "kernel_calls.call @gmm", "compiles_in_window"):
        assert any(ln.startswith(f"bench: check {name}") for ln in lines)


def test_steps_without_a_row_here_read_zero_not_nothing():
    from drivers import miner_steps_lfm2_moe as driver
    names = driver.COUNTERS
    assert driver._moe_stats(dict.fromkeys(names, 0.0), 8) == {}
    got = driver._moe_stats(dict(zip(names, (0.0, 4096.0, 0.0, 0.0))), 8,
                            "traced_")
    assert got == {"traced_moe_rows": 0.0, "traced_moe_experts": 0.0,
                   "traced_moe_share_here_pct": 0.0,
                   "traced_moe_fullest_over_mean": 0.0}


def test_file_that_disagrees_with_the_preset_is_refused(lfm2_checkout):
    from drivers import miner_steps_lfm2_moe as driver
    import tiny_lfm2_moe
    with pytest.raises(SystemExit, match="moe_intermediate_size"):
        driver.check_config(dict(tiny_lfm2_moe.config(),
                                 moe_intermediate_size=64))
    with pytest.raises(SystemExit, match="experts_held"):
        driver.check_config(dict(tiny_lfm2_moe.config(),
                                 experts_held=[0, 4]))
    with pytest.raises(SystemExit, match="router's width"):
        driver.check_config(dict(tiny_lfm2_moe.config(),
                                 published={"num_experts": 32}))


# -- `correct` can fail ------------------------------------------------------

def test_control_and_faults_read_outside_the_sound_runs(lfm2_checkout,
                                                        capsys):
    from tools import lfm2_moe
    rc = lfm2_moe.main(["control", "--workload", "train-tiny-lfm2",
                        "--seeds", "3,4", "--seconds", "1", "--faults",
                        ",".join(lfm2_moe.FAULTS)])
    # `ragged_dot`, the CPU's grouped product, writes zeros where the
    # chip's kernel writes nothing, and all 8 experts are held here: that
    # fault shows on the chip alone (PERF.md section 6, PR 33), so here
    # the tool finds one row that is not as wanted and says so
    assert rc == 1
    out = capsys.readouterr().out.splitlines()
    rows = [json.loads(ln[len("control: "):]) for ln in out
            if ln.startswith("control: {")]
    assert len(rows) == 2
    for r in rows:
        # the verdicts are the driver's own checks'
        assert r["sound"]["outside"] == [], r["sound"]
        assert f"control: seed {r['seed']} sound: correct" in out
        # each fails one of the cell's numbers, not each
        for other in ("control", "conv_crosses_documents", "bias_decayed"):
            assert 0 < len(r[other]["outside"]) < 7, (other, r)
        # the lower precision is told by the gradients' distance
        assert "grad_direction_gap" in r["control"]["outside"]
        assert r["bias_decayed"]["buffer_moved"] > 0
        assert r["bias_decayed"]["buffer_moments"] == 4
        assert r["bias_decayed"]["outside"] == ["buffer_moved",
                                                "buffer_moments"]
        assert r["elsewhere_rows_unmasked"]["outside"] == []
        assert (f"control: seed {r['seed']} control: NOT correct: "
                + ", ".join(r["control"]["outside"])) in out
