"""The Solar-Open2 family's part of the benchmark, on the CPU at the
program's `tiny-solar` preset: its kernel arithmetic against hand counts,
its readers, the sessions generator (the same work for every seed, other
tokens), the share test on the reference's own layer, `run_cell.py` end to
end through the driver `sessions_kda_gqa_moe` from a temporary copy (new
files only), and `correct` shown to be a comparison that can fail: the
float8 control with its bfloat16 state and the four faults this mechanism
invites read outside what sound runs read."""

import json
import sys
import types

import numpy as np
import pytest

import conftest


@pytest.fixture
def solar_checkout(tmp_path, monkeypatch):
    import tiny_kda_gqa_moe
    root = tiny_kda_gqa_moe.copy_with_tiny(tmp_path)
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


# -- kernel arithmetic -------------------------------------------------------

def test_channel_delta_rule_update_work_against_a_hand_count():
    from readers import kernel_math_kda_gqa_moe as km
    # one slot's state in one layer: 64 heads x 128 x 128 float32
    assert km.kda_state_bytes(64, 128) == 4_194_304
    # a 64-slot decode step of the 3 delta-rule layers: 192 slot-steps,
    # each state read once and written once, and the step's q, k, v and
    # 128 decays a head (4 x 8,192 float32) with 64 betas beside it
    ops, nbytes = km.kda_decode_work(192, 64, 128)
    assert nbytes == 192 * (2 * 4_194_304 + 4 * 8192 * 4 + 64 * 4) \
        == 1_635_827_712
    assert ops == 7 * 192 * 1_048_576
    # bandwidth bounds it: 2.0 ms against 0.007 ms of arithmetic
    assert km.roofline_seconds(ops, nbytes, PEAKS) == nbytes / 819e9


def test_grouped_query_paged_decode_bytes_against_a_hand_count():
    from readers import kernel_math_kda_gqa_moe as km
    # 32 live slots at 12,000 cached tokens each, ONE attention layer of
    # the four: a K and a V row of 8 heads x 128 bfloat16 a token
    assert km.gqa_paged_decode_bytes(384_000, 8, 128, 1) \
        == 384_000 * 2 * 1024 * 2 == 1_572_864_000
    # the 64 query heads share what the 8 K/V heads read
    assert km.gqa_paged_decode_bytes(1, 8, 128, 1) == 4096


def test_held_swiglu_expert_work_at_this_configurations_widths():
    """`moe_held_swiglu_roofline` is read with the accepted expert cells'
    count (three matrices an expert)."""
    from readers import kernel_math_mla_moe as km
    assert km.expert_params(4096, 1280) == 15_728_640
    # a 32-slot decode step of one layer: 256 routed rows of which an
    # eighth, 32, fall to the 40 experts held, 22 of them touched
    ops, nbytes = km.moe_experts_work(32, 22, 4096, 1280)
    assert ops == 2 * 32 * 15_728_640
    assert nbytes == 22 * 15_728_640 * 2 == 692_060_160


def _rec(events, stats, config=None):
    from readers import xplane
    trace = xplane.from_events({"/device:TPU:0": events}, [])
    return types.SimpleNamespace(
        trace=trace, peaks=PEAKS,
        ctx=types.SimpleNamespace(config=config or {}),
        run=types.SimpleNamespace(stats=stats, window_s=40.0))


KERNEL_LINES = {
    "kda_decode": "%gdn_decode_update.2 = (f32[64,4,16,128], "
                  "f32[65,64,128,128]) custom-call(%p)",
    "moe_swiglu": "%gmm.1 = bf16[128,4096] custom-call(%p)",
    "gqa_paged": "%paged_decode_attention.5 = bf16[64,8,1024] "
                 "custom-call(%p)"}
# each through its OWN file of `layer_metrics/` (reader, pattern, model)
METRICS = {"kda_decode": "kda_decode_roofline",
           "moe_swiglu": "moe_held_swiglu_roofline",
           "gqa_paged": "gqa_paged_decode_roofline"}
CONFIG = {"linear_attn_config": {"num_heads": 64, "head_dim": 128,
                                 "num_kv_heads": None,
                                 "short_conv_kernel_size": 4},
          "hidden_size": 4096, "moe_intermediate_size": 1280,
          "num_key_value_heads": 8, "head_dim": 128, "gqa_layers": [0],
          "num_hidden_layers": 4}


def _read(model, rec):
    import run_cell
    return run_cell.read_layer_metric(METRICS[model], rec)


@pytest.mark.parametrize("model", sorted(METRICS))
def test_reader_reads_nothing_without_its_kernel_or_counters(model):
    other = ("%fusion.1 = f32[8] fusion(%p)", 0, 1000)
    assert _read(model, _rec([other], {}, CONFIG)) is None
    # the kernel ran, the program reported no counter: nothing to credit
    kernel = (KERNEL_LINES[model], 0, 1000)
    assert _read(model, _rec([kernel], {}, CONFIG)) is None
    # another family's configuration (the parent's cells): nothing to read
    stats = {"traced_kda_slot_steps": 4.0, "traced_live_tokens": 9.0}
    if model != "moe_swiglu":
        assert _read(model, _rec([kernel], stats, {"hidden_size": 8})) is None
    rec = _rec([kernel], stats, CONFIG)
    rec.trace = None
    assert _read(model, rec) is None


def test_reader_takes_the_shares_from_trace_and_counters():
    user = "%fusion.9 = f32[64,64,128] fusion(%gdn_decode_update.2)"
    rec = _rec([(KERNEL_LINES["kda_decode"], 0, 4_000_000),
                (user, 4_000_000, 500_000),
                (KERNEL_LINES["moe_swiglu"], 5_000_000, 2_000_000),
                (KERNEL_LINES["gqa_paged"], 8_000_000, 50_000_000)],
               {"traced_kda_slot_steps": 192.0, "traced_moe_rows": 32.0,
                "traced_moe_experts": 22.0,
                "traced_live_tokens": 384_000.0}, CONFIG)
    assert _read("kda_decode", rec) == pytest.approx(
        100 * (1_635_827_712 / 819e9) / 4e-3)
    assert _read("moe_swiglu", rec) == pytest.approx(
        100 * (692_060_160 / 819e9) / 2e-3)
    assert _read("gqa_paged", rec) == pytest.approx(
        100 * (1_572_864_000 / 819e9) / 50e-3)


def test_the_host_and_histogram_metrics_read_the_drivers_stats():
    import run_cell
    other = [("%fusion.1 = f32[8] fusion(%p)", 0, 1000)]
    rec = _rec(other, {"kda_state_mb": 818.5, "prefix_saved_tokens_pct": 95.5,
                    "obs": {"serve.prefix.restore_ms": {
                        "count": 3, "sum": 6.0, "p50": 1.9, "p95": 2.4}}})
    read = run_cell.read_layer_metric
    assert read("serve.kda.state_mb", rec) == 818.5
    assert read("serve.prefix.saved_tokens_pct", rec) == 95.5
    assert read("serve.prefix.restore_ms_p50", rec) == 1.9
    empty = _rec(other, {})
    for name in ("serve.kda.state_mb", "serve.prefix.saved_tokens_pct",
                 "serve.prefix.restore_ms_p50"):
        assert read(name, empty) is None


# -- the sessions generator ---------------------------------------------------

def test_sessions_are_the_same_work_for_every_seed_and_other_tokens():
    from drivers import sessions_kda_gqa_moe as driver
    from traffic import gen
    mix = gen.load_mix("sessions-solar")
    assert mix["kind"] == "sessions" and mix["sessions"] == 64
    a_hist, a_turns = driver.plan(mix, 3, 40.0, 24576)
    b_hist, b_turns = driver.plan(mix, 2**31 + 9, 40.0, 24576)
    # lengths and order are the mix's, not the seed's
    assert [len(h) for h in a_hist] == [len(h) for h in b_hist]
    assert [(d, len(m), n) for d, m, n in a_turns] \
        == [(d, len(m), n) for d, m, n in b_turns]
    # the tokens are the seed's
    assert a_hist[0] != b_hist[0] and a_turns[0][1] != b_turns[0][1]
    assert driver.plan(mix, 3, 40.0, 24576)[0][5] == a_hist[5]
    # the mix's sizes: histories 4,096-32,768 (a clipped Pareto, mean
    # about 11k, 708,050 tokens in all), messages 64-512, answers 128-512
    lens = [len(h) for h in a_hist]
    assert (min(lens), max(lens), sum(lens)) == (4122, 32768, 708_050)
    assert sum(n > 16384 for n in lens) == 12
    assert all(64 <= len(m) <= 512 and 128 <= n <= 512
               for _, m, n in a_turns)
    assert min(lens) >= 4096
    assert len(a_turns) == round(mix["rate_rps"] * 40)
    due = [d for d, _, _ in a_turns]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 40.0
    # every id lies in the held slice, and no two sessions open alike
    assert max(max(h) for h in a_hist) < 24576
    assert len({h[0] for h in a_hist}) == 64
    with pytest.raises(ValueError, match="sessions"):
        driver.plan(dict(mix, kind="open_loop"), 3, 40.0, 24576)


# -- the share, on the reference's own layer ---------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    import jax.numpy as jnp
    import tiny_kda_gqa_moe
    from reference import solar_open2 as reference
    cfg = reference.model_cfg(tiny_kda_gqa_moe.config())
    assert cfg["experts_held"] == (0, 8) and cfg["n_routed_experts"] == 8
    w = reference.layer_weights(cfg, 5, 2)
    h = jnp.asarray(np.random.default_rng(2).normal(
        size=(18, cfg["hidden_size"])), jnp.float32)
    whole, _ = reference.experts(w, h, cfg, "float32")
    shared = reference._swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                               w["shared_down_proj"], "float32")
    routed = np.asarray(whole - shared)
    scale = np.abs(routed).max()
    total = np.zeros_like(routed)
    for r in range(8):
        share = dict(w, experts_gate_up=w["experts_gate_up"][r:r + 1],
                     experts_down=w["experts_down"][r:r + 1])
        part, _ = reference.experts(share, h, cfg, "float32", held=(r, 1))
        part = np.asarray(part - shared)
        assert np.abs(part - routed).max() > 0.1 * scale
        total += part
    assert np.max(np.abs(total - routed)) < 2e-3 * scale


# -- the driver, end to end --------------------------------------------------

def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_sessions_cell_runs_and_is_correct(solar_checkout, capsys, trace):
    rc = solar_checkout.main(["--workload", "serve-tiny-solar", "--seed",
                              str(2**31 + 5), "--seconds", "4", "--trace",
                              str(trace)])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    assert res["failed"] == 0 and res["attempted"] == 8
    if trace == 0:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    else:
        # the program's gauge: 3 layers x 5 rows of state and tail
        mb = res["metrics"]["serve.kda.state_mb"]["value"]
        assert mb == pytest.approx(3 * 5 * (2 * 128 * 128 * 4
                                            + 3 * 768 * 4) / 1e6)
        saved = res["metrics"]["serve.prefix.saved_tokens_pct"]["value"]
        assert 60.0 < saved < 100.0
        assert res["metrics"]["serve.prefix.restore_ms_p50"]["value"] > 0
        for name in ("serve.slots_busy_pct", "serve.idle_pct.outside_step",
                     "setup.compile_s", "setup.cache_misses",
                     "serve.moe.rows_per_expert"):
            assert name in res["metrics"], name
        share = res["metrics"].get("serve.moe.share_here_pct")
        assert share is None or share["value"] == 100.0
    for name in ("served_logit_gap", "near_tie_share",
                 "sample_longest_context", "turns_served_on_a_hit",
                 "cold_prefills_in_window", "snapshots_evicted_in_window",
                 "decode_path.gdn_decode_update", "compiles_in_window"):
        assert any(ln.startswith(f"bench: check {name}") and " ok" in ln
                   for ln in lines), name
    assert any("histories" in ln and "registered" in ln for ln in lines)


def test_file_that_disagrees_with_the_preset_is_refused(solar_checkout):
    from drivers import sessions_kda_gqa_moe as driver
    import tiny_kda_gqa_moe
    with pytest.raises(SystemExit, match="num_experts_per_tok"):
        driver.make_model(dict(tiny_kda_gqa_moe.config(),
                               num_experts_per_tok=2))
    cfg = tiny_kda_gqa_moe.config()
    del cfg["linear_attn_config"]
    with pytest.raises(SystemExit, match="linear_attn_config"):
        driver.make_model(cfg)
    with pytest.raises(SystemExit, match="experts held"):
        driver.make_model(dict(tiny_kda_gqa_moe.config(),
                               experts_held=[2, 6]))


def test_the_cells_files_say_what_the_issue_asked():
    from drivers import common, sessions_kda_gqa_moe as driver
    config = common.load_json("configs",
                              "solar-open2-250b-l4-e40-v24k.json")
    _, pc = driver.make_model(config)
    assert pc.experts_held == (0, 40) and pc.vocab_size == 24576
    for key, value in config["published"].items():
        assert key in config["reduced"] and config[key] != value
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "linear_attn_config", "num_experts_per_tok"):
        assert key not in config["reduced"]
    assert config["parameters"]["held_here"] == 3_308_353_344
    for reading in ("kda_low_rank_why", "gates", "decay", "beta", "conv",
                    "attention", "router"):
        assert {"key", "taken", "not_taken"} <= set(
            config["assumed"][reading])
    cell = common.load_json("workloads", "serve-solar-sessions.json")
    e = cell["engine"]
    assert (e["max_slots"], e["max_seq_len"], e["page_size"],
            e["prefix_cache"]) == (64, 40960, 16, True)
    assert e["expect_paths"] == {"gdn_decode_update": 3,
                                 "paged_decode_attention": 1, "gmm": 8}
    assert cell["check"]["min_longest_context"] == 16384
    mix = common.load_json("traffic", "sessions-solar.json")
    assert mix["max_total"] == e["max_seq_len"]
    assert mix["history_tokens"] == {"dist": "pareto", "min": 4096,
                                     "max": 32768, "shape": 1.2}
    assert mix["message_tokens"] == {"dist": "pareto", "min": 64,
                                     "max": 512, "shape": 1.2}
    assert mix["output_tokens"] == {"dist": "pareto", "min": 128,
                                    "max": 512, "shape": 1.5}


# -- `correct` can fail ------------------------------------------------------

def test_control_and_faults_read_outside_the_sound_runs(solar_checkout,
                                                        capsys):
    from tools import kda_gqa_moe
    faults = "stale_snapshot,mean_decay,beta_1,no_gate"
    rc = kda_gqa_moe.main(["control", "--workload", "serve-tiny-solar",
                           "--seeds", "3", "--seconds", "3", "--faults",
                           faults])
    assert rc == 0
    lines = capsys.readouterr().out.splitlines()
    rows = [json.loads(ln[len("control: "):]) for ln in lines
            if ln.startswith("control: {")]
    assert sum("sound: correct: true" in ln for ln in lines) == 1
    for other in ["fp8"] + faults.split(","):
        assert sum(f" {other}: correct: false by " in ln
                   for ln in lines) == 1, other
    import tiny_kda_gqa_moe
    lim = tiny_kda_gqa_moe.CELL["limits"]
    (r,) = rows
    assert r["sound"]["served_gap"] <= lim["served_logit_gap"]
    assert r["sound"]["served_mean_gap"] <= lim["served_mean_gap"]
    assert {"served_gap", "served_mean_gap"} <= set(r["bfloat16"])
    for other in ["fp8"] + faults.split(","):
        # fails one of the cell's numbers, not each
        assert (r[other]["served_gap"] > lim["served_logit_gap"]
                or r[other]["served_mean_gap"] > lim["served_mean_gap"]
                ), (other, r)
