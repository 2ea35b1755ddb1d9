"""The AFMoE family's part of the benchmark, on the CPU at the program's
`tiny-trinity` preset: its kernel arithmetic against hand counts, its
readers, `run_cell.py` end to end through the driver
`open_loop_gqa_window_moe` from a temporary copy (new files only), and
`correct` shown to be a comparison that can fail: the float8 control and
the seven faults this mechanism invites read outside what sound runs
read, and the probe of the window's edge reads what served logits
cannot."""

import json
import sys
import types

import pytest

import conftest


@pytest.fixture
def trinity_checkout(tmp_path, monkeypatch):
    import tiny_gqa_window_moe
    root = tiny_gqa_window_moe.copy_with_tiny(tmp_path)
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

def test_window_decode_bytes_against_a_hand_count():
    from readers import kernel_math_gqa_window_moe as km
    # a K row and a V row of 4 heads x 128 bfloat16: 2,048 bytes a token
    assert km.kv_row_bytes(4, 128) == 2048
    # 64 live slots, every one past the window: 2,048 tokens a slot a
    # step, in each of the 4 sliding layers
    assert km.gqa_window_decode_bytes(64 * 2048, 4, 128, 4) \
        == 64 * 2048 * 2048 * 4 == 1_073_741_824
    # a slot at 300 tokens counts 300, not 2,048
    assert km.gqa_window_decode_bytes(300, 4, 128, 4) == 300 * 2048 * 4
    assert km.roofline_seconds(0.0, 1_073_741_824, PEAKS) \
        == 1_073_741_824 / 819e9


def test_full_decode_bytes_against_a_hand_count():
    from readers import kernel_math_gqa_window_moe as km
    # 32 live slots at 12,000 cached tokens each, ONE global layer
    assert km.gqa_full_decode_bytes(384_000, 4, 128, 1) \
        == 384_000 * 2048 == 786_432_000


# -- the readers -------------------------------------------------------------

def _rec(events, stats, config=None):
    from readers import xplane
    trace = xplane.from_events({"/device:TPU:0": events}, [])
    return types.SimpleNamespace(
        trace=trace, peaks=PEAKS,
        ctx=types.SimpleNamespace(config=config or {}),
        run=types.SimpleNamespace(stats=stats, window_s=40.0))


KERNEL_LINES = {
    "window": "%paged_window_decode_attention.3 = bf16[64,8,512] "
              "custom-call(%p)",
    "full": "%paged_decode_attention.5 = bf16[64,8,512] custom-call(%p)",
    "experts": "%gmm.1 = bf16[512,2048] custom-call(%p)"}
METRICS = {"window": "gqa_window_decode_roofline",
           "full": "gqa_full_decode_roofline",
           "experts": "moe_experts_roofline"}
CONFIG = {"layer_types": ["sliding_attention"] * 4 + ["full_attention"],
          "hidden_size": 2048, "moe_intermediate_size": 1024,
          "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 5}


def _read(model, rec):
    import run_cell
    return run_cell.read_layer_metric(METRICS[model], rec)


@pytest.mark.parametrize("model", ["window", "full"])
def test_reader_reads_nothing_without_its_kernel_or_counters(model):
    other = ("%fusion.1 = f32[8] fusion(%p)", 0, 1000)
    assert _read(model, _rec([other], {}, CONFIG)) is None
    # the kernel ran, the program reported no counter: nothing to credit
    kernel = (KERNEL_LINES[model], 0, 1000)
    assert _read(model, _rec([kernel], {}, CONFIG)) is None
    # another family's configuration (the parent's cells): nothing to read
    stats = {"traced_window_live_tokens": 4.0, "traced_live_tokens": 9.0}
    assert _read(model, _rec([kernel], stats, {"hidden_size": 8})) is None
    rec = _rec([kernel], stats, CONFIG)
    rec.trace = None
    assert _read(model, rec) is None


def test_the_two_kernels_are_told_apart_by_their_own_names():
    """`paged_decode_attention`'s pattern must not read the windowed
    kernel's events, nor the other way round."""
    rec = _rec([(KERNEL_LINES["window"], 0, 8_000_000),
                (KERNEL_LINES["full"], 8_000_000, 50_000_000),
                (KERNEL_LINES["experts"], 60_000_000, 2_000_000)],
               {"traced_window_live_tokens": 131_072.0,
                "traced_live_tokens": 384_000.0, "traced_moe_rows": 512.0,
                "traced_moe_experts": 400.0}, CONFIG)
    assert _read("window", rec) == pytest.approx(
        100 * (1_073_741_824 / 819e9) / 8e-3)
    assert _read("full", rec) == pytest.approx(
        100 * (786_432_000 / 819e9) / 50e-3)
    # an expert of three 2048 x 1024 matrices, read once where touched
    assert _read("experts", rec) == pytest.approx(
        100 * (400 * 3 * 2048 * 1024 * 2 / 819e9) / 2e-3)


def test_the_host_metric_reads_the_drivers_stats():
    import run_cell
    other = [("%fusion.1 = f32[8] fusion(%p)", 0, 1000)]
    read = run_cell.read_layer_metric
    assert read("serve.kv.window_held_pct",
                _rec(other, {"window_held_pct": 41.5})) == 41.5
    assert read("serve.kv.window_held_pct", _rec(other, {})) is None
    assert read("serve.kv.window_held_pct",
                _rec(other, {"window_held_pct": None})) is None


# -- the cell, rehearsed -----------------------------------------------------

def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_mixed_cell_runs_and_is_correct(trinity_checkout, capsys, trace):
    rc = trinity_checkout.main(["--workload", "serve-tiny-trinity", "--seed",
                                str(2**31 + 5), "--seconds", "4", "--trace",
                                str(trace)])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    assert res["failed"] == 0 and res["attempted"] == 12
    if trace == 0:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    else:
        held = res["metrics"]["serve.kv.window_held_pct"]["value"]
        assert 0.0 < held < 100.0
        for name in ("serve.slots_busy_pct", "serve.idle_pct.outside_step",
                     "setup.compile_s", "setup.cache_misses",
                     "serve.moe.rows_per_expert"):
            assert name in res["metrics"], name
    for name in ("served_logit_gap", "served_mean_gap", "near_tie_share",
                 "window_edge_gap", "sample_longest_context",
                 "compiles_in_window",
                 "decode_path.paged_window_decode_attention",
                 "decode_path.paged_decode_attention", "decode_path.gmm"):
        assert any(ln.startswith(f"bench: check {name}") and " ok" in ln
                   for ln in lines), name
    assert any("page groups at the close" in ln for ln in lines)


def test_file_that_disagrees_with_the_preset_is_refused(trinity_checkout):
    from drivers import open_loop_gqa_window_moe as driver
    import tiny_gqa_window_moe
    with pytest.raises(SystemExit, match="sliding_window"):
        driver.make_model(dict(tiny_gqa_window_moe.config(),
                               sliding_window=16))
    cfg = tiny_gqa_window_moe.config()
    del cfg["layer_types"]
    with pytest.raises(SystemExit, match="layer_types"):
        driver.make_model(cfg)
    with pytest.raises(SystemExit, match="experts held"):
        driver.make_model(dict(tiny_gqa_window_moe.config(),
                               experts_held=[2, 6]))


def test_the_cells_files_say_what_the_issue_asked():
    from drivers import common, open_loop_gqa_window_moe as driver
    config = common.load_json("configs", "trinity-mini-l5.json")
    _, pc = driver.make_model(config)
    assert pc.experts_held == (0, 128) and pc.vocab_size == 200192
    for key, value in config["published"].items():
        assert key in config["reduced"] and config[key] != value
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "intermediate_size", "num_experts", "num_experts_per_tok",
                "vocab_size", "sliding_window"):
        assert key not in config["reduced"]
    assert config["parameters"]["held_here"] == 4_241_534_720
    for reading in ("mup", "window", "rotary", "gate", "router"):
        assert {"key", "taken", "not_taken"} <= set(
            config["assumed"][reading])
    cell = common.load_json("workloads", "serve-trinity-mixed.json")
    e = cell["engine"]
    assert (e["max_slots"], e["max_seq_len"], e["page_size"],
            e["prefix_cache"], e["prefill_chunk"]) == (64, 33792, 16, False,
                                                       1024)
    assert e["window_pool_pages"] == 1 + 64 * 194
    assert e["expect_paths"] == {"paged_window_decode_attention": 4,
                                 "paged_decode_attention": 1, "gmm": 8}
    assert cell["check"]["min_longest_context"] == 8192
    assert cell["warmup"]["table_pages"] == 33792 // 16
    mix = common.load_json("traffic", "mixed-trinity.json")
    assert mix["kind"] == "open_loop" and mix["sharing"] == "none"
    assert mix["max_total"] == e["max_seq_len"]
    assert mix["prompt_tokens"] == {"dist": "pareto", "min": 256,
                                    "max": 32768, "shape": 0.7}
    assert mix["output_tokens"] == {"dist": "pareto", "min": 64,
                                    "max": 1024, "shape": 1.2}
    assert mix["tokens"] == {"dist": "uniform"}
    with open(conftest.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    (entry,) = [w for w in bench["workloads"]
                if w["name"] == "serve-trinity-mixed"]
    assert entry["chips"] == 1 and entry["why"] == cell["why"]
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in bench[g]
                if "serve-trinity-mixed" in m.get("workloads", ())}
    assert reported == {
        "serve_tokens_per_s", "serve.slots_busy_pct",
        "serve.idle_pct.outside_step", "serve.moe.rows_per_expert",
        "setup.compile_s", "setup.cache_misses", "moe_experts_roofline",
        "gqa_window_decode_roofline", "gqa_full_decode_roofline",
        "serve.kv.window_held_pct"}


# -- `correct` can fail ------------------------------------------------------

@pytest.mark.parametrize("fault, low, high", [
    (None, 0.0, 1e-5), ("window_off_by_one", 0.5, 9.0),
    ("no_window", 0.5, 9.0), ("stale_window_page", 0.5, float("inf")),
    ("no_gate", 0.0, 1e-5)])
def test_the_window_edge_probe_sees_one_key_more_or_less(fault, low, high):
    """`window_edge_gap` drives the window group's own parts (the
    allocator, the shifted table, the rows' write, the windowed attention)
    with the keys at the window's edge made dominant: sound, and under a
    fault that is none of the group's, it reads float32 rounding; with one
    key less, the mask left out or a table whose first row lags its pages,
    it reads the planted values' size."""
    from distributedtraining_tpu.models import afmoe
    from drivers import open_loop_gqa_window_moe as driver
    from tools import gqa_window_moe
    pc = afmoe.PRESETS["tiny-trinity"]
    with gqa_window_moe.fault(fault):
        try:
            got = driver.window_edge_gap(
                pc, {"page_size": 4, "prefill_chunk": 8}, 2**31 + 9)
        except AssertionError:      # the group ran short under the fault
            got = {"decode": float("inf")}
    assert low <= max(got.values()) <= high, got


def test_control_and_faults_read_outside_the_sound_runs(trinity_checkout,
                                                        capsys):
    from tools import gqa_window_moe
    faults = ",".join(gqa_window_moe.FAULTS)
    rc = gqa_window_moe.main(["control", "--workload", "serve-tiny-trinity",
                              "--seeds", "3", "--seconds", "4", "--faults",
                              faults])
    assert rc == 0
    lines = capsys.readouterr().out.splitlines()
    rows = [json.loads(ln[len("control: "):]) for ln in lines
            if ln.startswith("control: {")]
    assert sum("sound: correct: true" in ln for ln in lines) == 1
    for other in ["fp8"] + faults.split(","):
        assert sum(f" {other}: correct: false by " in ln
                   for ln in lines) == 1, other
    import tiny_gqa_window_moe
    lim = tiny_gqa_window_moe.CELL["limits"]
    (r,) = rows
    assert r["sound"]["served_gap"] <= lim["served_logit_gap"]
    assert r["sound"]["served_mean_gap"] <= lim["served_mean_gap"]
    assert r["sound"]["longest_context"] > 40
