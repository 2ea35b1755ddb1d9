"""The Nemotron-H family's part of the benchmark, on the CPU at the
program's `tiny-nemotron-h` preset: its kernel arithmetic against hand
counts, its reference against a second, literal spelling and against
itself layer at a time, the share test (the four shares' partial expert
sums add up to the uncut layer), `run_cell.py` end to end through the
driver `open_loop_ssm_moe` from a temporary copy (new files only), and
`correct` shown to be a comparison that can fail: the float8 control and
the two faults this mechanism invites read outside what sound runs read."""

import json
import sys
import types

import numpy as np
import pytest

import conftest


@pytest.fixture
def nemotron_checkout(tmp_path, monkeypatch):
    import tiny_ssm_moe
    root = tiny_ssm_moe.copy_with_tiny(tmp_path)
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

def test_state_update_work_against_a_hand_count():
    from readers import kernel_math_ssm_moe as km
    # one slot's state in one layer: 128 heads x 64 x 128 float32
    assert km.ssm_state_bytes(128, 64, 128) == 4_194_304
    # a 64-slot decode step of the 5 layers: 320 slot-steps, each state
    # read once and written once
    ops, nbytes = km.ssm_decode_work(320, 128, 64, 128)
    assert nbytes == 320 * 2 * 4_194_304 == 2_684_354_560
    assert ops == 5 * 320 * 1_048_576
    # bandwidth bounds it: 3.28 ms against 0.009 ms of arithmetic
    assert km.roofline_seconds(ops, nbytes, PEAKS) == nbytes / 819e9


def test_latent_expert_work_against_a_hand_count():
    from readers import kernel_math_ssm_moe as km
    # one latent expert: two matrices of 1024 x 2688, no gate
    assert km.latent_expert_params(1024, 2688) == 5_505_024
    # a 64-slot decode step of one layer: 352 rows here, 120 experts touched
    ops, nbytes = km.moe_latent_work(352, 120, 1024, 2688)
    assert ops == 2 * 352 * 5_505_024 == 3_875_536_896
    assert nbytes == 120 * 5_505_024 * 2 == 1_321_205_760
    assert km.roofline_seconds(ops, nbytes, PEAKS) == nbytes / 819e9


def _rec(events, stats, config=None):
    from readers import xplane
    trace = xplane.from_events({"/device:TPU:0": events}, [])
    return types.SimpleNamespace(
        trace=trace, peaks=PEAKS,
        ctx=types.SimpleNamespace(config=config or {}),
        run=types.SimpleNamespace(stats=stats))


@pytest.mark.parametrize("model, pattern", [
    ("ssm_decode", "^%?ssm_decode_update(\\.\\d+)?$"),
    ("moe_latent", "^%?gmm(\\.\\d+)?$")])
def test_reader_reads_nothing_without_its_kernel_or_counters(model, pattern):
    from readers import trace_kernel_ssm_moe as reader
    other = ("%fusion.1 = f32[8] fusion(%p)", 0, 1000)
    assert reader.read(_rec([other], {}), pattern=pattern,
                       model=model) is None
    kernel = {"ssm_decode": "%ssm_decode_update.2 = f32[8] custom-call(%p)",
              "moe_latent": "%gmm.1 = bf16[8] custom-call(%p)"}[model]
    # the kernel ran, the program reported no counter: nothing to credit
    assert reader.read(_rec([(kernel, 0, 1000)], {}), pattern=pattern,
                       model=model) is None
    rec = _rec([(kernel, 0, 1000)], {})
    rec.trace = None
    assert reader.read(rec, pattern=pattern, model=model) is None


def test_reader_takes_the_shares_from_trace_and_counters():
    from readers import trace_kernel_ssm_moe as reader
    config = {"mamba_num_heads": 128, "mamba_head_dim": 64,
              "ssm_state_size": 128, "moe_latent_size": 1024,
              "moe_intermediate_size": 2688}
    ssm = "%ssm_decode_update.3 = (f32[64,8,64,16], f32[65,128,64,128]) " \
          "custom-call(%a)"
    user = "%fusion.9 = f32[64,128,64] fusion(%ssm_decode_update.3)"
    gmm = "%gmm.4 = bf16[1408,2688] custom-call(%a, %b)"
    rec = _rec([(ssm, 0, 4_000_000), (user, 4_000_000, 500_000),
                (gmm, 5_000_000, 2_000_000)],
               {"traced_ssm_slot_steps": 320.0, "traced_moe_rows": 352.0,
                "traced_moe_experts": 120.0}, config)
    got = reader.read(rec, pattern="^%?ssm_decode_update(\\.\\d+)?$",
                      model="ssm_decode")
    assert got == pytest.approx(100 * (2_684_354_560 / 819e9) / 4e-3)
    got = reader.read(rec, pattern="^%?gmm(\\.\\d+)?$", model="moe_latent")
    assert got == pytest.approx(100 * (1_321_205_760 / 819e9) / 2e-3)


# -- the reference -----------------------------------------------------------

def _tiny_cfg():
    import tiny_ssm_moe
    from reference import nemotron_h as reference
    return reference.model_cfg(tiny_ssm_moe.config())


def _literal_layer(w, x, cfg):
    """One block in numpy float64: the recurrence as a Python loop over
    time and heads, the convolution tap by tap, attention one query at a
    time, the experts as a loop over tokens and chosen experts."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    T, E = x.shape
    eps = cfg["norm_eps"]

    def rms(v, g):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + eps) * g

    def silu(v):
        return v / (1 + np.exp(-v))

    h = rms(x, w["norm"])
    if "in_proj" in w:
        H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
        d_inner = H * P
        conv_dim = d_inner + 2 * G * N
        proj = h @ w["in_proj"]
        z, xbc, dt = (proj[:, :d_inner], proj[:, d_inner:d_inner + conv_dim],
                      proj[:, d_inner + conv_dim:])
        conv = np.zeros_like(xbc)
        for t in range(T):
            for k in range(K):
                src = t - (K - 1) + k
                if src >= 0:
                    conv[t] += w["conv1d_weight"][k] * xbc[src]
        act = silu(conv + w["conv1d_bias"])
        dt = np.log1p(np.exp(dt + w["dt_bias"]))
        a = -np.exp(w["A_log"])
        y = np.zeros((T, H, P))
        state = np.zeros((H, P, N))
        for t in range(T):
            for hd in range(H):
                g = hd // (H // G)
                xs = act[t, hd * P:(hd + 1) * P]
                b = act[t, d_inner + g * N:d_inner + (g + 1) * N]
                c = act[t, d_inner + G * N + g * N:
                        d_inner + G * N + (g + 1) * N]
                state[hd] = (np.exp(dt[t, hd] * a[hd]) * state[hd]
                             + dt[t, hd] * np.outer(xs, b))
                y[t, hd] = state[hd] @ c + w["D"][hd] * xs
        gated = (y.reshape(T, d_inner) * silu(z)).reshape(T, G, -1)
        gated = gated / np.sqrt(np.mean(gated * gated, -1, keepdims=True)
                                + eps)
        return x + (gated.reshape(T, d_inner) * w["mixer_norm"]) \
            @ w["out_proj"]
    if "q_proj" in w:
        Hq, Hkv, Dh = (cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
        q = (h @ w["q_proj"]).reshape(T, Hq, Dh)
        k = (h @ w["k_proj"]).reshape(T, Hkv, Dh)
        v = (h @ w["v_proj"]).reshape(T, Hkv, Dh)
        attn = np.zeros((T, Hq, Dh))
        for t in range(T):
            for hd in range(Hq):
                kv = hd // (Hq // Hkv)
                s = np.array([q[t, hd] @ k[u, kv] for u in range(t + 1)]
                             ) / np.sqrt(Dh)
                p = np.exp(s - s.max())
                p /= p.sum()
                attn[t, hd] = p @ v[:t + 1, kv]
        return x + attn.reshape(T, Hq * Dh) @ w["o_proj"]
    first, count = cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    relu2 = lambda v: np.square(np.maximum(v, 0))           # noqa: E731
    out = x + relu2(h @ w["shared_up_proj"]) @ w["shared_down_proj"]
    for t in range(T):
        s = 1 / (1 + np.exp(-(h[t] @ w["router"])))
        choice = np.argsort(-(s + w["e_score_correction_bias"]))[:k]
        weights = s[choice] / (s[choice].sum() + 1e-20) \
            * cfg["routed_scaling_factor"]
        x_l = h[t] @ w["latent_in"]
        acc = np.zeros_like(x_l)
        for e, we in zip(choice, weights):
            if first <= e < first + count:
                acc += we * (relu2(x_l @ w["experts_up"][e - first])
                             @ w["experts_down"][e - first])
        out[t] += acc @ w["latent_out"]
    return out


def test_reference_agrees_with_a_literal_spelling():
    from reference import nemotron_h as reference
    cfg = _tiny_cfg()
    weights = reference.init_weights(cfg, 3)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 12))
    ref = reference.Reference(cfg)
    x = ref.embed(weights, ids)
    want = np.asarray(x[0], np.float64)
    kinds = set()
    for i, w in enumerate(weights["layers"]):
        x, _ = ref.layer(w, x)
        want = _literal_layer(w, want, cfg)
        kinds.add(cfg["hybrid_override_pattern"][i])
        # float32 at `highest` against float64: rounding of sums only,
        # on a residual stream that grows to ~15 at these signal sizes
        assert np.max(np.abs(np.asarray(x[0]) - want)) \
            < 4e-6 * max(1.0, np.abs(want).max()), i
    assert kinds == set("M*E")


def test_layer_at_a_time_is_the_whole_model():
    from reference import nemotron_h as reference
    cfg = _tiny_cfg()
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (3, 24))
    ref = reference.Reference(cfg)
    logits, margin = ref.logits(reference.init_weights(cfg, 9), ids,
                                with_margin=True)
    x, margin2, top = ref.hidden_layerwise(9, ids)
    assert np.max(np.abs(np.asarray(ref.head(top, x) - logits))) < 1e-5
    assert np.allclose(np.asarray(margin), np.asarray(margin2), atol=1e-6)
    got = reference.score_sequences(cfg, 9, ids)
    assert got["gaps"].shape == got["margins"].shape == (3, 23)
    assert (got["gaps"] >= 0).all() and np.isfinite(got["margins"]).all()
    # weights are bfloat16 numbers held in float32, whatever the dtype asked
    for i in (0, 1, 7):
        w32 = reference.layer_weights(cfg, 9, i)
        w16 = reference.layer_weights(cfg, 9, i, "bfloat16")
        for name in w32:
            assert (np.asarray(w16[name].astype("float32"))
                    == np.asarray(w32[name])).all(), name
    # the state-space layer's own initialisers
    m = reference.layer_weights(cfg, 9, 0)
    assert (np.asarray(m["D"]) == 1).all()
    a = np.exp(np.asarray(m["A_log"]))
    assert (a > 0.99).all() and (a < 16.1).all()
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert (dt > 0.0009).all() and (dt < 0.11).all()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: four chips hold 2 of the 8 experts each. Their
    partial routed sums, each through `latent_out`, with the shared expert
    counted once, add up to the layer with all 8 experts; and no share is
    the whole (the cut leaves something out)."""
    import jax.numpy as jnp
    from reference import nemotron_h as reference
    cfg = _tiny_cfg()
    assert cfg["experts_held"] == (0, 8) and cfg["n_routed_experts"] == 8
    w = reference.layer_weights(cfg, 5, 1)                  # an E layer
    h = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 9, cfg["hidden_size"])), jnp.float32)
    whole, _ = reference.experts(w, h, cfg, "float32")
    shared = reference._relu2(h, w["shared_up_proj"], w["shared_down_proj"],
                              "float32")
    routed = np.asarray(whole - shared)     # small beside the shared part
    scale = np.abs(routed).max()
    total = np.zeros_like(routed)
    for r in range(4):
        share = dict(w, experts_up=w["experts_up"][2 * r:2 * r + 2],
                     experts_down=w["experts_down"][2 * r:2 * r + 2])
        part, _ = reference.experts(share, h, cfg, "float32",
                                    held=(2 * r, 2))
        part = np.asarray(part - shared)
        assert np.abs(part).max() > 0.05 * scale
        assert np.abs(part - routed).max() > 0.1 * scale
        total += part
    # float32 sums in another order, and the two subtractions' rounding
    assert np.max(np.abs(total - routed)) < 2e-3 * scale


# -- the driver, end to end --------------------------------------------------

def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell_runs_and_is_correct(nemotron_checkout, capsys, trace):
    rc = nemotron_checkout.main(["--workload", "serve-tiny-nemotron",
                                 "--seed", str(2**31 + 5), "--seconds", "3",
                                 "--trace", str(trace)])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    assert res["failed"] == 0 and res["attempted"] == 24
    if trace == 0:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    else:
        # the program's gauge and the slice's counters came through
        mb = res["metrics"]["serve.ssm.state_mb"]["value"]
        assert mb == pytest.approx(5 * 5 * (8 * 16 * 128 * 4
                                            + 3 * 640 * 4) / 1e6)
        assert "serve.slots_busy_pct" in res["metrics"]
        # all 8 experts are held at this size: every routed row is here
        share = res["metrics"].get("serve.moe.share_here_pct")
        assert share is None or share["value"] == 100.0
    for name in ("served_logit_gap", "near_tie_share",
                 "decode_path.ssm_decode_update", "compiles_in_window"):
        assert any(ln.startswith(f"bench: check {name}") for ln in lines)


def test_file_that_disagrees_with_the_preset_is_refused(nemotron_checkout):
    from drivers import open_loop_ssm_moe
    import tiny_ssm_moe
    with pytest.raises(SystemExit, match="num_experts_per_tok"):
        open_loop_ssm_moe.make_model(
            dict(tiny_ssm_moe.config(), num_experts_per_tok=2))
    cfg = tiny_ssm_moe.config()
    del cfg["ssm_state_size"]
    with pytest.raises(SystemExit, match="ssm_state_size"):
        open_loop_ssm_moe.make_model(cfg)
    with pytest.raises(SystemExit, match="experts held"):
        open_loop_ssm_moe.make_model(
            dict(tiny_ssm_moe.config(), experts_held=[2, 6]))


# -- `correct` can fail ------------------------------------------------------

def test_control_and_faults_read_outside_the_sound_runs(nemotron_checkout,
                                                        capsys):
    from tools import ssm_moe
    rc = ssm_moe.main(["control", "--workload", "serve-tiny-nemotron",
                       "--seeds", "3,4", "--seconds", "2", "--faults",
                       "state_not_reset,pad_advances_state"])
    assert rc == 0
    rows = [json.loads(ln[len("control: "):])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("control: {")]
    import tiny_ssm_moe
    lim = tiny_ssm_moe.CELL["limits"]
    assert len(rows) == 2
    for r in rows:
        assert r["sound"]["served_gap"] <= lim["served_logit_gap"]
        assert r["sound"]["served_mean_gap"] <= lim["served_mean_gap"]
        assert r["sound"]["near_tie_share"] <= lim["near_tie_share"]
        for other in ("control", "state_not_reset", "pad_advances_state"):
            # fails one of the cell's numbers, not each
            assert (r[other]["served_gap"] > lim["served_logit_gap"]
                    or r[other]["served_mean_gap"] > lim["served_mean_gap"]
                    ), (other, r)
