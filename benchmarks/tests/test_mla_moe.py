"""The DeepSeek-V3 family's part of the benchmark, on the CPU at the
program's `tiny-kanana` preset: its kernel arithmetic against hand counts,
its reference against a second, literal spelling and against itself layer
at a time, `run_cell.py` end to end through the driver `open_loop_mla_moe`
from a temporary copy (new files only), and `correct` shown to be a
comparison that can fail: the float8 control and two faults read outside
what sound runs read."""

import json
import sys

import numpy as np
import pytest

import conftest


@pytest.fixture
def kanana_checkout(tmp_path, monkeypatch):
    import tiny_mla_moe
    root = tiny_mla_moe.copy_with_tiny(tmp_path)
    saved = list(sys.path)
    run_cell = conftest._load_run_cell(root)
    from drivers import common
    monkeypatch.setattr(common, "require_device", lambda chips: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(common, "WORK_DIR", str(tmp_path / "work"))
    yield run_cell
    sys.path[:] = saved
    conftest._load_run_cell(conftest.ROOT)


# -- kernel arithmetic -------------------------------------------------------

def test_expert_work_against_a_hand_count():
    from readers import kernel_math_mla_moe as km
    # one expert of kanana-2: three matrices of 2048 x 768
    assert km.expert_params(2048, 768) == 4_718_592
    # a 64-slot decode step of one layer: 384 rows, 122 experts touched
    ops, nbytes = km.moe_experts_work(384, 122, 2048, 768)
    assert ops == 2 * 384 * 4_718_592 == 3_623_878_656
    assert nbytes == 122 * 4_718_592 * 2 == 1_151_336_448
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # the weight read bounds it: 1.406 ms against 0.018 ms of products
    assert km.roofline_seconds(ops, nbytes, peaks) == nbytes / 819e9
    # the ridge: 240 rows an expert
    ops, nbytes = km.moe_experts_work(128 * 241, 128, 2048, 768)
    assert km.roofline_seconds(ops, nbytes, peaks) == ops / 197e12


def test_latent_decode_bytes_against_a_hand_count():
    from readers import kernel_math_mla_moe as km
    # 1,000 live tokens, 576 values of 2 bytes, 8 layers
    assert km.mla_decode_bytes(1000, 512, 64, 8) == 9_216_000


def test_reader_reads_nothing_without_its_kernel_or_counters():
    from readers import trace_kernel_mla_moe as reader, xplane
    import types
    op = ("%fusion.1 = f32[8] fusion(%p)", 0, 1000)
    trace = xplane.from_events({"/device:TPU:0": [op]}, [])
    rec = types.SimpleNamespace(
        trace=trace, peaks={}, ctx=types.SimpleNamespace(config={}),
        run=types.SimpleNamespace(stats={}))
    assert reader.read(rec, pattern="^%?gmm(\\.\\d+)?$",
                       model="moe_experts") is None
    rec.trace = None
    assert reader.read(rec, pattern="mla_decode_attention",
                       model="mla_decode") is None


def test_reader_takes_the_share_from_trace_and_counters():
    from readers import trace_kernel_mla_moe as reader, xplane
    import types
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    config = {"hidden_size": 2048, "moe_intermediate_size": 768,
              "kv_lora_rank": 512, "qk_rope_head_dim": 64,
              "num_hidden_layers": 8}
    gmm = "%gmm.3 = bf16[384,1536] custom-call(%a, %b)"
    user = "%fusion.9 = bf16[384,1536] fusion(%gmm.3)"      # not the kernel
    mla = "%mla_decode_attention.1 = bf16[64,32,512] custom-call(%q)"
    trace = xplane.from_events({"/device:TPU:0": [
        (gmm, 0, 2_000_000), (user, 2_000_000, 500_000),
        (mla, 3_000_000, 100_000)]}, [])
    rec = types.SimpleNamespace(
        trace=trace, peaks=peaks, ctx=types.SimpleNamespace(config=config),
        run=types.SimpleNamespace(stats={
            "traced_moe_rows": 384.0, "traced_moe_experts": 122.0,
            "traced_live_tokens": 5000}))
    got = reader.read(rec, pattern="^%?gmm(\\.\\d+)?$", model="moe_experts")
    assert got == pytest.approx(100 * (1_151_336_448 / 819e9) / 2e-3)
    got = reader.read(rec, pattern="mla_decode_attention",
                      model="mla_decode")
    assert got == pytest.approx(100 * (5000 * 576 * 2 * 8 / 819e9) / 1e-4)


# -- the reference -----------------------------------------------------------

def _tiny_cfg():
    import tiny_mla_moe
    from reference import deepseek_v3 as reference
    return reference.model_cfg(tiny_mla_moe.config())


def _literal_layer(w, x, cfg):
    """Layer forward in numpy float64, experts as a Python loop over
    tokens and chosen experts, attention one query at a time."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    T, E = x.shape
    H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    Dn, Dr, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]

    def rms(v, g):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + eps) * g

    def rope(v, t):
        out = v.copy()
        for i in range(Dr // 2):
            a = t / cfg["rope_theta"] ** (2 * i / Dr)
            x1, x2 = v[2 * i], v[2 * i + 1]
            out[2 * i] = x1 * np.cos(a) - x2 * np.sin(a)
            out[2 * i + 1] = x2 * np.cos(a) + x1 * np.sin(a)
        return out

    def silu(v):
        return v / (1 + np.exp(-v))

    h = rms(x, w["input_layernorm"])
    q = (h @ w["q_proj"]).reshape(T, H, Dn + Dr)
    kv_a = h @ w["kv_a_proj_with_mqa"]
    c = rms(kv_a[:, :C], w["kv_a_layernorm"])
    k_r = np.stack([rope(kv_a[t, C:], t) for t in range(T)])
    kv = (c @ w["kv_b_proj"]).reshape(T, H, Dn + Dv)
    attn = np.zeros((T, H, Dv))
    for t in range(T):
        for hd in range(H):
            qr = rope(q[t, hd, Dn:], t)
            s = np.array([q[t, hd, :Dn] @ kv[u, hd, :Dn] + qr @ k_r[u]
                          for u in range(t + 1)]) / np.sqrt(Dn + Dr)
            p = np.exp(s - s.max())
            p /= p.sum()
            attn[t, hd] = p @ kv[:t + 1, hd, Dn:]
    x = x + attn.reshape(T, H * Dv) @ w["o_proj"]
    h = rms(x, w["post_attention_layernorm"])
    if "router" not in w:
        return x + (silu(h @ w["gate_proj"]) * (h @ w["up_proj"])) \
            @ w["down_proj"]
    F, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    out = x + (silu(h @ w["shared_gate_proj"]) * (h @ w["shared_up_proj"])) \
        @ w["shared_down_proj"]
    for t in range(T):
        s = 1 / (1 + np.exp(-(h[t] @ w["router"])))
        choice = np.argsort(-(s + w["e_score_correction_bias"]))[:k]
        weights = s[choice] / s[choice].sum() * cfg["routed_scaling_factor"]
        for e, we in zip(choice, weights):
            gu = h[t] @ w["experts_gate_up"][e]
            out[t] += we * ((silu(gu[:F]) * gu[F:]) @ w["experts_down"][e])
    return out


def test_reference_agrees_with_a_literal_spelling():
    from reference import deepseek_v3 as reference
    cfg = _tiny_cfg()
    weights = reference.init_weights(cfg, 3)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 12))
    ref = reference.Reference(cfg)
    x, pos = ref.embed(weights, ids)
    want = np.asarray(x[0], np.float64)
    for w in weights["layers"]:
        x, _ = ref.layer(w, x, pos)
        want = _literal_layer(w, want, cfg)
        # float32 at `highest` against float64: rounding of sums only
        assert np.max(np.abs(np.asarray(x[0]) - want)) < 2e-5
    # the selection bias took part: without it some choice differs
    flat = np.asarray(x[0])
    h = flat / np.sqrt(np.mean(flat * flat, -1, keepdims=True) + 1e-6)
    w = {k: np.asarray(v) for k, v in weights["layers"][-1].items()}
    s = 1 / (1 + np.exp(-(h @ w["router"])))
    k = cfg["num_experts_per_tok"]
    with_b = np.sort(np.argsort(-(s + w["e_score_correction_bias"]))[:, :k])
    without = np.sort(np.argsort(-s)[:, :k])
    assert (with_b != without).any()


def test_layer_at_a_time_is_the_whole_model():
    from reference import deepseek_v3 as reference
    cfg = _tiny_cfg()
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (3, 24))
    ref = reference.Reference(cfg)
    logits, margin = ref.logits(reference.init_weights(cfg, 9), ids,
                                with_margin=True)
    x, margin2, top = ref.hidden_layerwise(9, ids)
    # the same jitted layers on the same numbers, one sequence at a time
    assert np.max(np.abs(np.asarray(ref.head(top, x) - logits))) < 1e-5
    assert np.allclose(np.asarray(margin), np.asarray(margin2), atol=1e-6)
    got = reference.score_sequences(cfg, 9, ids)
    assert got["gaps"].shape == got["margins"].shape == (3, 23)
    assert (got["gaps"] >= 0).all()
    # weights are bfloat16 numbers held in float32, whatever the dtype asked
    w32 = reference.layer_weights(cfg, 9, 1)
    w16 = reference.layer_weights(cfg, 9, 1, "bfloat16")
    for name in w32:
        assert (np.asarray(w16[name].astype("float32"))
                == np.asarray(w32[name])).all(), name


# -- the driver, end to end --------------------------------------------------

def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell_runs_and_is_correct(kanana_checkout, capsys, trace):
    rc = kanana_checkout.main(["--workload", "serve-tiny-kanana", "--seed",
                               str(2**31 + 5), "--seconds", "3", "--trace",
                               str(trace)])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    assert res["failed"] == 0 and res["attempted"] == 24
    if trace == 0:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    else:
        # the routed layer's counters came through the program's registry
        assert res["metrics"]["serve.moe.rows_per_expert"]["value"] > 0
        assert "serve.slots_busy_pct" in res["metrics"]
    for name in ("served_logit_gap", "near_tie_share",
                 "decode_path.gmm", "compiles_in_window"):
        assert any(ln.startswith(f"bench: check {name}") for ln in lines)


def test_file_that_disagrees_with_the_preset_is_refused(kanana_checkout):
    from drivers import open_loop_mla_moe
    import tiny_mla_moe
    with pytest.raises(SystemExit, match="num_experts_per_tok"):
        open_loop_mla_moe.make_model(
            dict(tiny_mla_moe.config(), num_experts_per_tok=2))
    cfg = tiny_mla_moe.config()
    del cfg["kv_lora_rank"]
    with pytest.raises(SystemExit, match="kv_lora_rank"):
        open_loop_mla_moe.make_model(cfg)


# -- `correct` can fail ------------------------------------------------------

def test_control_and_faults_read_outside_the_sound_runs(kanana_checkout,
                                                        capsys):
    from tools import mla_moe
    rc = mla_moe.main(["control", "--workload", "serve-tiny-kanana",
                       "--seeds", "3,4", "--seconds", "2", "--faults",
                       "no_bias,no_fresh_row"])
    assert rc == 0
    rows = [json.loads(ln[len("control: "):])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("control: {")]
    import tiny_mla_moe
    lim = tiny_mla_moe.CELL["limits"]
    assert len(rows) == 2
    for r in rows:
        assert r["sound"]["served_gap"] <= lim["served_logit_gap"]
        assert r["sound"]["served_mean_gap"] <= lim["served_mean_gap"]
        assert r["sound"]["near_tie_share"] <= lim["near_tie_share"]
        for other in ("control", "no_bias", "no_fresh_row"):
            # fails one of the cell's numbers, not each
            assert (r[other]["served_gap"] > lim["served_logit_gap"]
                    or r[other]["served_mean_gap"] > lim["served_mean_gap"]
                    ), (other, r)
