"""The GigaChat-3.5 family's part of the benchmark, on the CPU at the
program's `tiny-gigachat` preset: its kernel arithmetic against hand
counts, its reference against a second, literal spelling and against
itself layer at a time, the share test (the sixteen shares' partial expert
sums add up to the uncut layer), `run_cell.py` end to end through the
driver `open_loop_gdn_mla_moe` from a temporary copy (new files only), and
`correct` shown to be a comparison that can fail: the float8 control with
its bfloat16 state and the three faults this mechanism invites read
outside what sound runs read."""

import json
import sys
import types

import numpy as np
import pytest

import conftest


@pytest.fixture
def gigachat_checkout(tmp_path, monkeypatch):
    import tiny_gdn_mla_moe
    root = tiny_gdn_mla_moe.copy_with_tiny(tmp_path)
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

def test_delta_rule_update_work_against_a_hand_count():
    from readers import kernel_math_gdn_mla_moe as km
    # one slot's state in one layer: 64 value heads x 128 x 128 float32
    assert km.gdn_state_bytes(64, 128, 128) == 4_194_304
    # a 64-slot decode step of the 4 linear layers: 256 slot-steps, each
    # state read once and written once
    ops, nbytes = km.gdn_decode_work(256, 64, 128, 128)
    assert nbytes == 256 * 2 * 4_194_304 == 2_147_483_648
    assert ops == 7 * 256 * 1_048_576
    # bandwidth bounds it: 2.62 ms against 0.0095 ms of arithmetic
    assert km.roofline_seconds(ops, nbytes, PEAKS) == nbytes / 819e9


def test_held_swiglu_expert_work_against_a_hand_count():
    """`moe_held_swiglu_roofline` is read with the accepted expert cell's
    count (three matrices an expert): here at this configuration's
    widths."""
    from readers import kernel_math_mla_moe as km
    # one expert: three matrices of 7168 x 2048
    assert km.expert_params(7168, 2048) == 44_040_192
    # a 64-slot decode step of one layer: 64 x 8 = 512 routed rows of which
    # a sixteenth, 32, fall to the 16 experts held, 14 of them touched
    ops, nbytes = km.moe_experts_work(32, 14, 7168, 2048)
    assert ops == 2 * 32 * 44_040_192 == 2_818_572_288
    assert nbytes == 14 * 44_040_192 * 2 == 1_233_125_376
    assert km.roofline_seconds(ops, nbytes, PEAKS) == nbytes / 819e9


def test_hybrid_latent_decode_bytes_against_a_hand_count():
    from readers import kernel_math_gdn_mla_moe as km, kernel_math_mla_moe
    # 64 slots at 1,500 cached tokens each, ONE latent layer of the five
    assert km.mla_hybrid_decode_bytes(96_000, 512, 64, 1) \
        == 96_000 * 576 * 2 == 110_592_000
    # the accepted reader's count multiplies by every layer: 5 times this
    assert kernel_math_mla_moe.mla_decode_bytes(96_000, 512, 64, 5) \
        == 5 * 110_592_000


def _rec(events, stats, config=None):
    from readers import xplane
    trace = xplane.from_events({"/device:TPU:0": events}, [])
    return types.SimpleNamespace(
        trace=trace, peaks=PEAKS,
        ctx=types.SimpleNamespace(config=config or {}),
        run=types.SimpleNamespace(stats=stats))


KERNEL_LINES = {
    "gdn_decode": "%gdn_decode_update.2 = (f32[64,4,16,128], "
                  "f32[65,64,128,128]) custom-call(%p)",
    "moe_swiglu": "%gmm.1 = bf16[128,4096] custom-call(%p)",
    "mla_hybrid": "%mla_decode_attention.5 = bf16[64,64,512] "
                  "custom-call(%p)"}
# each through its OWN file of `layer_metrics/` (reader, pattern, model)
METRICS = {"gdn_decode": "gdn_decode_roofline",
           "moe_swiglu": "moe_held_swiglu_roofline",
           "mla_hybrid": "mla_hybrid_decode_roofline"}
CONFIG = {"linear_num_value_heads": 64, "linear_key_head_dim": 128,
          "linear_value_head_dim": 128, "hidden_size": 7168,
          "moe_intermediate_size": 2048, "kv_lora_rank": 512,
          "qk_rope_head_dim": 64, "full_attention_layers": [1],
          "num_hidden_layers": 5}


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
    stats = {"traced_gdn_slot_steps": 4.0, "traced_live_tokens": 9.0}
    if model != "moe_swiglu":
        assert _read(model, _rec([kernel], stats, {"hidden_size": 8})) is None
    rec = _rec([kernel], stats, CONFIG)
    rec.trace = None
    assert _read(model, rec) is None


def test_reader_takes_the_shares_from_trace_and_counters():
    user = "%fusion.9 = f32[64,64,128] fusion(%gdn_decode_update.2)"
    rec = _rec([(KERNEL_LINES["gdn_decode"], 0, 4_000_000),
                (user, 4_000_000, 500_000),
                (KERNEL_LINES["moe_swiglu"], 5_000_000, 2_000_000),
                (KERNEL_LINES["mla_hybrid"], 8_000_000, 250_000)],
               {"traced_gdn_slot_steps": 256.0, "traced_moe_rows": 32.0,
                "traced_moe_experts": 14.0, "traced_live_tokens": 96_000.0},
               CONFIG)
    assert _read("gdn_decode", rec) == pytest.approx(
        100 * (2_147_483_648 / 819e9) / 4e-3)
    assert _read("moe_swiglu", rec) == pytest.approx(
        100 * (1_233_125_376 / 819e9) / 2e-3)
    assert _read("mla_hybrid", rec) == pytest.approx(
        100 * (110_592_000 / 819e9) / 0.25e-3)


# -- the reference -----------------------------------------------------------

def _tiny_cfg():
    import tiny_gdn_mla_moe
    from reference import gigachat3_5 as reference
    return reference.model_cfg(tiny_gdn_mla_moe.config())


def _literal_layer(w, x, cfg, i):
    """One block in numpy float64: the delta rule as a Python loop over
    time and heads, the convolution tap by tap, attention one query at a
    time, the experts as a loop over tokens and chosen experts."""
    import math
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    T, E = x.shape
    eps, gw = cfg["rms_norm_eps"], cfg["layernorm_gating_weight"]
    L = cfg["swiglu_limit"]

    def sig(v):
        return 1 / (1 + np.exp(-v))

    def norm(v, p):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + eps) \
            * (gw * sig(p))

    def silu(v):
        return v * sig(v)

    def swiglu(v, gate, up, down):
        return (silu(np.minimum(v @ gate, L)) * np.clip(v @ up, -L, L)) \
            @ down

    u = norm(x, w["pre_mixer_norm"])
    if "kv_b_proj" in w:
        H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
        Dn, Dr, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        yarn = dict(cfg["rope_scaling"])
        orig, theta = yarn["original_max_position_embeddings"], \
            cfg["rope_theta"]

        def corr(turns):
            return Dr * math.log(orig / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(corr(yarn["beta_fast"])), 0)
        high = min(math.ceil(corr(yarn["beta_slow"])), Dr - 1)
        inv = np.zeros(Dr // 2)
        for p in range(Dr // 2):
            plain = theta ** (-2 * p / Dr)
            ramp = min(max((p - low) / max(high - low, 1e-3), 0), 1)
            inv[p] = plain / yarn["factor"] * ramp + plain * (1 - ramp)
        m = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1
        scale = m * m / math.sqrt(Dn + Dr)

        def rope(vec, t):
            out = vec.copy()
            for p in range(Dr // 2):
                a, b = vec[2 * p], vec[2 * p + 1]
                c, s = math.cos(t * inv[p]), math.sin(t * inv[p])
                out[2 * p], out[2 * p + 1] = a * c - b * s, b * c + a * s
            return out

        q = (norm(u @ w["q_a_proj"], w["q_a_norm"]) @ w["q_b_proj"]
             ).reshape(T, H, Dn + Dr)
        kv_a = u @ w["kv_a_proj_with_mqa"]
        c_lat = norm(kv_a[:, :C], w["kv_a_norm"])
        k_r = np.stack([rope(kv_a[t, C:], t) for t in range(T)])
        kv = (c_lat @ w["kv_b_proj"]).reshape(T, H, Dn + Dv)
        attn = np.zeros((T, H, Dv))
        for t in range(T):
            for hd in range(H):
                q_r = rope(q[t, hd, Dn:], t)
                s = np.array([q[t, hd, :Dn] @ kv[j, hd, :Dn] + q_r @ k_r[j]
                              for j in range(t + 1)]) * scale
                p = np.exp(s - s.max())
                p /= p.sum()
                attn[t, hd] = p @ kv[:t + 1, hd, Dn:]
        y = (attn.reshape(T, H * Dv) * sig(u @ w["o_gate_proj"])) \
            @ w["o_proj"]
    else:
        Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
        K = cfg["linear_conv_kernel_dim"]
        conv_dim = 2 * Hk * dk + Hv * dv
        qkvz, ba = u @ w["in_proj_qkvz"], u @ w["in_proj_ba"]
        qkv, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
        conv = np.zeros_like(qkv)
        for t in range(T):
            for k in range(K):
                src = t - (K - 1) + k
                if src >= 0:
                    conv[t] += w["conv1d_weight"][k] * qkv[src]
        act = silu(conv)
        beta = sig(ba[:, :Hv])
        g = -np.exp(w["A_log"]) * np.log1p(np.exp(ba[:, Hv:]
                                                  + w["dt_bias"]))
        o = np.zeros((T, Hv, dv))
        S = np.zeros((Hv, dk, dv))
        for t in range(T):
            for hd in range(Hv):
                kh = hd // (Hv // Hk)
                qv = act[t, kh * dk:(kh + 1) * dk]
                kv_ = act[t, Hk * dk + kh * dk:Hk * dk + (kh + 1) * dk]
                qv = qv / np.sqrt(qv @ qv + 1e-6) / math.sqrt(dk)
                kv_ = kv_ / np.sqrt(kv_ @ kv_ + 1e-6)
                vv = act[t, 2 * Hk * dk + hd * dv:
                         2 * Hk * dk + (hd + 1) * dv]
                S[hd] = np.exp(g[t, hd]) * S[hd]
                r = (vv - S[hd].T @ kv_) * beta[t, hd]
                S[hd] = S[hd] + np.outer(kv_, r)
                o[t, hd] = S[hd].T @ qv
        o = o / np.sqrt(np.mean(o * o, -1, keepdims=True)
                        + cfg["linear_attn_o_norm_eps"])
        o = o * (gw * sig(w["o_norm"])) * (
            cfg["linear_sigmoid_gate_scale"] * sig(z.reshape(T, Hv, dv)))
        y = o.reshape(T, Hv * dv) @ w["out_proj"]
    x = x + norm(y, w["post_mixer_norm"])
    u = norm(x, w["pre_ffn_norm"])
    if "router" not in w:
        y = swiglu(u, w["gate_proj"], w["up_proj"], w["down_proj"])
    else:
        first, count = cfg["experts_held"]
        k, F = cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]
        y = swiglu(u, w["shared_gate_proj"], w["shared_up_proj"],
                   w["shared_down_proj"])
        for t in range(T):
            s = sig(u[t] @ w["router"])
            choice = np.argsort(-(s + w["e_score_correction_bias"]))[:k]
            weights = s[choice] / (s[choice].sum() + 1e-20) \
                * cfg["routed_scaling_factor"]
            for e, we in zip(choice, weights):
                if first <= e < first + count:
                    gu = w["experts_gate_up"][e - first]
                    y[t] += we * swiglu(u[t], gu[:, :F], gu[:, F:],
                                        w["experts_down"][e - first])
    return x + norm(y, w["post_ffn_norm"])


def test_reference_agrees_with_a_literal_spelling():
    from reference import gigachat3_5 as reference
    cfg = _tiny_cfg()
    weights = reference.init_weights(cfg, 3)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 10))
    ref = reference.Reference(cfg)
    x = ref.embed(weights, ids)
    want = np.asarray(x[0], np.float64)
    kinds = set()
    for i, w in enumerate(weights["layers"]):
        x, _ = ref.layer(w, x)
        want = _literal_layer(w, want, cfg, i)
        kinds.add(("kv_b_proj" in w, "router" in w))
        # float32 at `highest` against float64: rounding of sums only
        assert np.max(np.abs(np.asarray(x[0]) - want)) \
            < 4e-6 * max(1.0, np.abs(want).max()), i
    assert kinds == {(False, False), (True, True), (False, True)}


def test_layer_at_a_time_is_the_whole_model():
    from reference import gigachat3_5 as reference
    cfg = _tiny_cfg()
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (3, 24))
    ref = reference.Reference(cfg)
    logits, margin = ref.logits(reference.init_weights(cfg, 9), ids,
                                with_margin=True)
    x, margin2, top = ref.hidden_layerwise(9, ids)
    # three sequences at once against one at a time: other float32 sums
    assert np.max(np.abs(np.asarray(ref.head(top, x) - logits))) < 1e-4
    assert np.allclose(np.asarray(margin), np.asarray(margin2), atol=1e-5)
    got = reference.score_sequences(cfg, 9, ids)
    assert got["gaps"].shape == got["margins"].shape == (3, 23)
    assert (got["gaps"] >= 0).all() and np.isfinite(got["margins"]).all()
    # weights are bfloat16 numbers held in float32, whatever the dtype asked
    for i in (0, 1, 4):
        w32 = reference.layer_weights(cfg, 9, i)
        w16 = reference.layer_weights(cfg, 9, i, "bfloat16")
        for name in w32:
            assert (np.asarray(w16[name].astype("float32"))
                    == np.asarray(w32[name])).all(), name
    # the linear mixer's own initialisers
    m = reference.layer_weights(cfg, 9, 0)
    a = np.exp(np.asarray(m["A_log"]))
    assert (a > 0.99).all() and (a < 16.1).all()
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert (dt > 0.0009).all() and (dt < 0.11).all()
    assert np.abs(np.asarray(m["pre_mixer_norm"])).max() < 0.1


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share test at this size: eight chips hold 1 of the 8 experts
    each. Their partial routed sums, with the shared expert counted once,
    add up to the layer with all 8 experts; and no share is the whole (the
    cut leaves something out)."""
    import jax.numpy as jnp
    from reference import gigachat3_5 as reference
    cfg = _tiny_cfg()
    assert cfg["experts_held"] == (0, 8) and cfg["n_routed_experts"] == 8
    w = reference.layer_weights(cfg, 5, 2)              # a routed layer
    h = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 9, cfg["hidden_size"])), jnp.float32)
    whole, _ = reference.experts(w, h, cfg, "float32")
    shared = reference._swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                               w["shared_down_proj"], cfg["swiglu_limit"],
                               "float32")
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
    # float32 sums in another order, and the two subtractions' rounding
    assert np.max(np.abs(total - routed)) < 2e-3 * scale


# -- the driver, end to end --------------------------------------------------

def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell_runs_and_is_correct(gigachat_checkout, capsys, trace):
    rc = gigachat_checkout.main(["--workload", "serve-tiny-gigachat",
                                 "--seed", str(2**31 + 5), "--seconds", "3",
                                 "--trace", str(trace)])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    assert res["failed"] == 0 and res["attempted"] == 24
    if trace == 0:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    else:
        # the program's gauge and the slice's counters came through
        mb = res["metrics"]["serve.gdn.state_mb"]["value"]
        assert mb == pytest.approx(4 * 5 * (4 * 128 * 128 * 4
                                            + 3 * 1024 * 4) / 1e6)
        # the accepted metrics whose lists the cell joins are read too
        for name in ("serve.slots_busy_pct", "serve.idle_pct.outside_step",
                     "setup.compile_s", "setup.cache_misses",
                     "serve.moe.rows_per_expert"):
            assert name in res["metrics"], name
        # all 8 experts are held at this size: every routed row is here
        share = res["metrics"].get("serve.moe.share_here_pct")
        assert share is None or share["value"] == 100.0
    for name in ("served_logit_gap", "near_tie_share",
                 "decode_path.gdn_decode_update", "compiles_in_window"):
        assert any(ln.startswith(f"bench: check {name}") for ln in lines)
    assert any("far page rungs" in ln for ln in lines)


def test_file_that_disagrees_with_the_preset_is_refused(gigachat_checkout):
    from drivers import open_loop_gdn_mla_moe as driver
    import tiny_gdn_mla_moe
    with pytest.raises(SystemExit, match="num_experts_per_tok"):
        driver.make_model(dict(tiny_gdn_mla_moe.config(),
                               num_experts_per_tok=2))
    cfg = tiny_gdn_mla_moe.config()
    del cfg["linear_num_key_heads"]
    with pytest.raises(SystemExit, match="linear_num_key_heads"):
        driver.make_model(cfg)
    with pytest.raises(SystemExit, match="rope_scaling"):
        driver.make_model(dict(tiny_gdn_mla_moe.config(),
                               rope_scaling={"type": "yarn", "factor": 4}))
    with pytest.raises(SystemExit, match="experts held"):
        driver.make_model(dict(tiny_gdn_mla_moe.config(),
                               experts_held=[2, 6]))


def test_the_cells_configuration_is_the_programs_preset():
    """The real file against the real preset, key by key, as every run of
    the cell checks it; and against the catalog's published numbers where
    the file does not list a key as reduced."""
    from drivers import common, open_loop_gdn_mla_moe as driver
    config = common.load_json(
        "configs", "gigachat3.5-432b-a28b-l5-e16-v16k.json")
    _, pc = driver.make_model(config)
    assert pc.experts_held == (0, 16) and pc.vocab_size == 16032
    for key, value in config["published"].items():
        assert key in config["reduced"] and config[key] != value
    for key in ("hidden_size", "q_lora_rank", "kv_lora_rank",
                "linear_num_value_heads", "moe_intermediate_size",
                "intermediate_size", "num_experts_per_tok"):
        assert key not in config["reduced"]
    assert config["parameters"] == 4_733_099_008
    for reading in ("norm", "block", "linear_gate", "linear_keys",
                    "gated_attention", "router", "swiglu_limit"):
        assert {"key", "taken", "not_taken"} <= set(
            config["assumed"][reading])


# -- `correct` can fail ------------------------------------------------------

def test_control_and_faults_read_outside_the_sound_runs(gigachat_checkout,
                                                        capsys):
    from tools import gdn_mla_moe
    rc = gdn_mla_moe.main(["control", "--workload", "serve-tiny-gigachat",
                           "--seeds", "3,4", "--seconds", "2", "--faults",
                           "no_decay,stale_state,no_gate"])
    assert rc == 0
    lines = capsys.readouterr().out.splitlines()
    rows = [json.loads(ln[len("control: "):]) for ln in lines
            if ln.startswith("control: {")]
    # every reading is put to the cell's own limits, and says so
    assert sum("sound: correct: true" in ln for ln in lines) == 2
    for other in ("fp8", "no_decay", "stale_state", "no_gate"):
        assert sum(f" {other}: correct: false by " in ln
                   for ln in lines) == 2, other
    import tiny_gdn_mla_moe
    lim = tiny_gdn_mla_moe.CELL["limits"]
    assert len(rows) == 2
    for r in rows:
        assert r["sound"]["served_gap"] <= lim["served_logit_gap"]
        assert r["sound"]["served_mean_gap"] <= lim["served_mean_gap"]
        assert r["sound"]["near_tie_share"] <= lim["near_tie_share"]
        assert {"served_gap", "served_mean_gap"} <= set(r["bfloat16"])
        for other in ("fp8", "no_decay", "stale_state", "no_gate"):
            # fails one of the cell's numbers, not each
            assert (r[other]["served_gap"] > lim["served_logit_gap"]
                    or r[other]["served_mean_gap"] > lim["served_mean_gap"]
                    ), (other, r)
