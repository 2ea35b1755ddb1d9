"""The GigaChat-3.5 family on the serving path (models/gigachat3_5.py,
ops/delta_rule.py, the latent attention it shares with models/deepseek_v3.py,
the held SwiGLU experts of ops/moe.py, and the TWO kinds of cache that meet
in one engine: a per-slot delta-rule state beside a latent page pool), at
the `tiny-gigachat` preset with float32 parameters and compute, so that
what separates program and reference is the ORDER of float32 sums (the
chunked WY form against the token-by-token recurrence, sorted grouped
products against a dense masked sum, the absorbed paged form against the
expanded dense one). The weights are drawn at the signal sizes of the
published widths (matrix std 0.21 at hidden 64 = 0.02 at 7168).

The reference is the benchmark's own plain one
(benchmarks/reference/gigachat3_5.py), which imports nothing of the
program; its weights are the program's through the benchmark driver's own
conversion."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import (kv_pool, serve, serve_weights,
                                            speculative)
from distributedtraining_tpu.models import (deepseek_v3, family, family_of,
                                            gigachat3_5 as gc, gpt2)
from distributedtraining_tpu.ops import moe

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
TOL = 2e-4
CUT = "gigachat3.5-432b-a28b-l5-e16-v16k"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's reference and driver modules, imported as the
    benchmark imports them."""
    sys.path.insert(0, _BENCH)
    try:
        from drivers import open_loop_gdn_mla_moe as driver
        from reference import gigachat3_5 as reference
        yield reference, driver
    finally:
        sys.path.remove(_BENCH)
        for name in [m for m in sys.modules
                     if m.split(".")[0] in ("drivers", "reference")]:
            del sys.modules[name]


def _config(pc, driver):
    return dict({f.name: driver._plain(getattr(pc, f.name))
                 for f in dataclasses.fields(pc)},
                assumed={"padded_vocab": pc.padded_vocab,
                         "matrix_std": 0.21})


@pytest.fixture(scope="module")
def tiny(bench):
    reference, driver = bench
    pc = gc.PRESETS["tiny-gigachat"]
    mcfg = reference.model_cfg(_config(pc, driver))
    model, _ = gc.make_model(pc)
    params = driver.program_params(mcfg, 7, jnp.float32)
    return model, pc, params, mcfg, reference.init_weights(mcfg, 7)


def _engine(tiny, **kw):
    model, _, params, _, _ = tiny
    kw = dict(dict(max_slots=4, page_size=8, max_seq_len=128,
                   max_new_tokens=32), **kw)
    return serve.GenerationEngine(model, params, **kw)


def _prompts(pc, lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, pc.vocab_size, n).tolist() for n in lengths]


def _served_gap(reference, tiny, prompt, out):
    """How far the served tokens lie below the reference's own greedy pick
    over ONE full pass of prompt + served tokens."""
    _, pc, _, mcfg, weights = tiny
    seq = np.asarray([prompt + out])
    rows = np.asarray(reference.Reference(mcfg).logits(weights, seq))[
        0, :, :pc.vocab_size]
    lo, n = len(prompt) - 1, len(out)
    served = rows[np.arange(lo, lo + n), out]
    return float(np.max(rows[lo:lo + n].max(-1) - served))


# -- program against reference ----------------------------------------------

def test_full_forward_matches_the_reference(bench, tiny):
    reference, _ = bench
    model, pc, params, mcfg, weights = tiny
    ids = np.random.default_rng(0).integers(0, pc.vocab_size, (2, 150))
    want = reference.Reference(mcfg).logits(weights, ids)
    got = model.apply({"params": params}, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.max(jnp.abs(got - want))) <= TOL


def test_prefill_of_a_padded_bucket_then_decode_through_both_caches(bench,
                                                                    tiny):
    """Slots at different lengths, none a whole bucket or a whole chunk,
    five requests over four slots so that one is admitted into a used
    slot: every served token is within rounding of the reference's own
    greedy pick over a FULL pass of prompt + served tokens, 32 decode
    steps on."""
    reference, _ = bench
    model, pc, params, mcfg, weights = tiny
    eng = _engine(tiny, debug_invariants=True)
    prompts = _prompts(pc, (5, 23, 9, 40, 17))
    outs = eng.generate(prompts, 32)
    for prompt, out in zip(prompts, outs):
        assert _served_gap(reference, tiny, prompt, out) <= TOL
    # what the pools hold: LATENT pages (c, and k_r stored in whole lane
    # tiles) for the one full-attention layer, a float32 state and a tail
    # for each of the four linear layers
    k_pages, v_pages = eng._kv
    assert len(k_pages) == len(v_pages) == 1
    assert (k_pages[0].shape[-1], v_pages[0].shape[-1]) == (128, 128)
    assert pc.cache_row_widths == (32, 8)
    states, tails = eng._ssm
    assert [s.shape for s in states] == [(4 + 1, 4, 128, 128)] * 4
    assert [t.shape for t in tails] == [(4 + 1, 3, 1024)] * 4
    assert states[0].dtype == jnp.float32
    # every row was let go with its last request's state in it
    assert sorted(eng._state_free) == [0, 1, 2, 3] and not eng._state_of
    eng.close()


def test_slots_admitted_finished_and_reused_serve_the_references_tokens(
        bench, tiny, monkeypatch):
    """No row of the state pool is ever zeroed: the prefill writes over
    whatever the last request left. One slot, so every request after the
    first lands on a used row; and the test fails if the prefill does not
    overwrite it."""
    reference, _ = bench
    _, pc, _, _, _ = tiny
    long_one, short_one, third = _prompts(pc, (60, 7, 21), seed=2)
    used = _engine(tiny, max_slots=1)
    used.generate([long_one], 32)
    assert float(jnp.max(jnp.abs(used._ssm[0][0][0]))) > 1e-3   # left there
    for prompt in (short_one, third):
        out = used.generate([prompt], 24)[0]
        assert _served_gap(reference, tiny, prompt, out) <= TOL
    used.close()

    def keep(states, tails, inter, layers, slot):       # the fault
        return states, tails

    monkeypatch.setattr(kv_pool, "write_slot_state", keep)
    stale = _engine(tiny, max_slots=1)
    stale.generate([long_one], 32)
    out = stale.generate([short_one], 24)[0]
    stale.close()
    assert _served_gap(reference, tiny, short_one, out) > 100 * TOL


def test_preemption_regenerates_the_same_tokens(tiny):
    """A pool too small for three long generations preempts the youngest:
    its state row goes back, and its re-prefill makes the same tokens."""
    _, pc, _, _, _ = tiny
    prompts = _prompts(pc, (30, 30, 30), seed=3)
    roomy = _engine(tiny, max_new_tokens=48)
    want = roomy.generate(prompts, 48)
    roomy.close()
    tight = _engine(tiny, max_new_tokens=48, pool_pages=1 + 16 + 8,
                    debug_invariants=True)
    got = tight.generate(prompts, 48)
    tight.close()
    assert got == want


def test_what_each_layer_caches_is_stated_per_layer():
    pc = gc.PRESETS[CUT]
    assert pc.layer_caches == ("ssm", "kv", "ssm", "ssm", "ssm")
    assert pc.ssm_state_shape == (64, 128, 128)
    assert pc.ssm_tail_shape == (3, 16384)
    assert pc.cache_row_widths == (512, 64)
    assert kv_pool.row_widths(pc) == (512, 128)
    assert kv_pool.has_recurrent_state(pc)
    assert kv_pool.state_name(pc) == "gdn"
    assert kv_pool.state_name(gpt2.PRESETS["gpt2-774m"]) == "ssm"
    assert pc.padded_vocab == 16128 and pc.experts_held == (0, 16)
    whole = gc.PRESETS["gigachat3.5-432b-a28b"]
    assert [whole.layer_caches.count(k) for k in ("ssm", "kv")] == [30, 10]
    assert whole.first_k_dense_replace == 3
    with pytest.raises(ValueError, match="full_attention_layers"):
        dataclasses.replace(pc, full_attention_layers=(7,))
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(pc, experts_held=(250, 16))
    with pytest.raises(ValueError, match="gated_attention"):
        dataclasses.replace(pc, gated_attention=False)
    # the family beside it still refuses what it does not write, and says
    # what a config would have to state, not which model's file it is
    with pytest.raises(ValueError, match="q_lora_rank, rope_scaling"):
        dataclasses.replace(deepseek_v3.PRESETS["tiny-kanana"],
                            q_lora_rank=24, rope_scaling={"type": "yarn"})


def test_parameter_count_of_the_cut_is_the_files():
    import json
    with open(os.path.join(_BENCH, "configs", f"{CUT}.json")) as f:
        config = json.load(f)
    model, _ = gc.make_model(CUT)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    n = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))
    assert n == config["parameters"] == 4_733_099_008


# -- the pieces the family brings -------------------------------------------

def test_yarn_frequencies_and_scale_against_a_hand_count():
    pc = gc.PRESETS[CUT]
    inv = np.asarray(family.yarn_inv_freq(64, 100000.0,
                                          dict(pc.rope_scaling)))
    plain = 1.0 / 100000.0 ** (np.arange(0, 64, 2) / 64)
    # the correction range over 32,768 positions: pairs under 14 turn more
    # than 32 times and keep their frequency, pairs from 24 turn less than
    # once and are divided by 8, a ramp of tenths between
    assert np.allclose(inv[:15], plain[:15], rtol=1e-6)
    assert np.allclose(inv[24:], plain[24:] / 8, rtol=1e-6)
    assert np.allclose(inv[19], plain[19] * (0.5 + 0.5 / 8), rtol=1e-6)
    m = 0.1 * math.log(8) + 1
    assert pc.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert pc.softmax_scale == pytest.approx(0.105304, rel=1e-5)


def test_the_norm_is_one_at_zero_and_a_scaled_sigmoid_elsewhere():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 64))
    norm = gc.ZeroCentredNorm(1e-6, 2.0)
    unit = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    at_zero = norm.apply({"params": {"w": jnp.zeros((64,))}}, x)
    assert float(jnp.max(jnp.abs(at_zero - unit))) < 1e-6
    w = jnp.linspace(-3, 3, 64)
    got = norm.apply({"params": {"w": w}}, x)
    assert float(jnp.max(jnp.abs(got - unit * 2 / (1 + jnp.exp(-w))))) < 1e-6


def test_the_clamp_holds_the_gate_under_and_the_up_half_inside_the_limit():
    gate = jnp.asarray([-20.0, 3.0, 12.0, 50.0])
    up = jnp.asarray([-30.0, 4.0, 11.0, -9.0])
    got = moe.clamped_swiglu(gate, up, 10.0)
    want = jax.nn.silu(jnp.minimum(gate, 10.0)) * jnp.clip(up, -10.0, 10.0)
    assert float(jnp.max(jnp.abs(got - want))) == 0.0
    plain = moe.clamped_swiglu(gate, up, None)
    assert float(jnp.max(jnp.abs(plain - jax.nn.silu(gate) * up))) == 0.0
    assert float(jnp.max(jnp.abs(plain - got))) > 100.0


def _share_case(n=48, total=16, k=4, E=32, F=24, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = 4.0 * jax.random.normal(key[0], (n, E))
    w_in = jax.random.normal(key[1], (total, E, 2 * F))
    w_down = 0.3 * jax.random.normal(key[2], (total, F, E))
    choice = jnp.argsort(jax.random.uniform(key[3], (n, total)),
                         axis=-1)[:, :k].astype(jnp.int32)
    weights = jax.random.uniform(key[4], (n, k))
    return h, choice, weights, w_in, w_down


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The share test: sixteen chips hold one SwiGLU expert each, clamped.
    Their partial routed sums, the shared expert counted once (it is added
    outside `routed_experts`, whole, on every chip), add up to the layer
    that holds all sixteen; and no share is the whole."""
    h, choice, weights, w_in, w_down = _share_case()
    whole, stats = moe.routed_experts(h, choice, weights, w_in, w_down,
                                      swiglu_limit=10.0)
    unclamped, _ = moe.routed_experts(h, choice, weights, w_in, w_down)
    assert float(jnp.max(jnp.abs(whole - unclamped))) > 1.0   # it clamps
    assert int(stats["moe_rows"]) == 48 * 4
    total, rows, elsewhere = jnp.zeros_like(whole), 0, 0
    for first in range(16):
        part, st = moe.routed_experts(
            h, choice, weights, w_in[first:first + 1],
            w_down[first:first + 1], held=(first, 1), swiglu_limit=10.0)
        assert float(jnp.max(jnp.abs(part - whole))) > 1e-2   # a true cut
        total = total + part
        rows += int(st["moe_rows"])
        elsewhere += int(st["moe_rows_elsewhere"])
        assert int(st["moe_experts_touched"]) <= 1
    scale = float(jnp.max(jnp.abs(whole)))
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * scale
    assert rows == 192 and elsewhere == 15 * 192


def test_a_share_of_the_model_is_the_references_share(bench):
    """The cut as the cell runs it: the program holding experts 2..5 of 8
    against the reference holding the same share (a chosen expert that is
    not held adds nothing, in both)."""
    reference, driver = bench
    pc = dataclasses.replace(gc.PRESETS["tiny-gigachat"],
                             experts_held=(2, 4))
    config = dict(_config(pc, driver), n_routed_experts=4,
                  published={"n_routed_experts": 8}, experts_held=[2, 4])
    mcfg = reference.model_cfg(config)
    assert mcfg["n_routed_experts"] == 8 and mcfg["experts_held"] == (2, 4)
    model, _ = gc.make_model(pc)
    params = driver.program_params(mcfg, 11, jnp.float32)
    assert params["layer_2"]["experts_down"].shape[0] == 4
    ids = np.random.default_rng(4).integers(0, pc.vocab_size, (2, 40))
    want = reference.Reference(mcfg).logits(
        reference.init_weights(mcfg, 11), ids)
    got = model.apply({"params": params}, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(got - want))) <= TOL


def test_the_selection_bias_is_balanced_and_the_programs_is_the_references(
        bench, tiny):
    """The bias is not drawn: it is the rest point of the lineage's
    aux-loss-free rule on a calibration batch from the seed, so that the
    held experts' share of the rows is not the seed's lot. On that batch
    every expert gets its share; the program holds the same float32
    numbers."""
    reference, driver = bench
    _, pc, params, mcfg, weights = tiny
    w = weights["layers"][2]
    assert w["e_score_correction_bias"].dtype == jnp.float32
    assert (np.asarray(params["layer_2"]["e_score_correction_bias"])
            == np.asarray(w["e_score_correction_bias"])).all()
    again = reference.layer_weights(mcfg, 7, 2, jnp.bfloat16)
    assert again["e_score_correction_bias"].dtype == jnp.float32
    assert (np.asarray(again["e_score_correction_bias"])
            == np.asarray(w["e_score_correction_bias"])).all()
    # a skewed score table: one expert favoured by every row
    rng = np.random.default_rng(0)
    scores = jax.nn.sigmoid(jnp.asarray(
        rng.normal(size=(4096, 8)) + np.linspace(-1.5, 1.5, 8), jnp.float32))

    def loads(b):
        _, idx = jax.lax.top_k(scores + b, 3)
        return np.bincount(np.asarray(idx).ravel(), minlength=8) / 1536

    assert loads(0.0).max() > 1.5 and loads(0.0).min() < 0.5
    balanced = loads(reference.balance_bias(scores, 3))
    assert balanced.max() < 1.02 and balanced.min() > 0.98


# -- who refuses, who takes it ----------------------------------------------

def test_drafter_and_kv_export_refuse_with_the_sentence(tiny):
    model, pc, params, _, _ = tiny
    gmodel, gcfg = gpt2.make_model("tiny")
    # the prefix cache is no longer among them: it keeps snapshots of the
    # state (tests/test_prefix_state.py)
    serve.GenerationEngine(model, params, max_slots=2, page_size=8,
                           max_seq_len=32, prefix_cache=True)
    for kw in ({"draft": object()},
               {"phase": "prefill", "kv_exporter": object()},
               {"phase": "decode", "kv_adopter": object()}):
        with pytest.raises(ValueError) as err:
            serve.GenerationEngine(model, params, max_slots=2, page_size=8,
                                   max_seq_len=32, **kw)
        assert str(err.value) == kv_pool.RECURRENT_STATE_REASON
    # both reasons apply; each names what the config states, no model file
    for reason in (kv_pool.RECURRENT_STATE_REASON,
                   kv_pool.LATENT_CACHE_REASON):
        assert "models/" not in reason
    assert "layer_caches" in kv_pool.RECURRENT_STATE_REASON
    assert "cache_row_widths" in kv_pool.LATENT_CACHE_REASON
    for draft, target in ((gmodel, pc), (model, gcfg), (model, pc)):
        assert speculative.compat_reason(draft, target) in (
            kv_pool.RECURRENT_STATE_REASON, kv_pool.LATENT_CACHE_REASON)
    with pytest.raises(ValueError, match="latent row"):
        kv_pool.kv_head_geometry(pc)


def test_common_build_takes_the_sixth_family(tmp_path):
    from distributedtraining_tpu.config import RunConfig
    from distributedtraining_tpu.utils import flight
    from neurons import common

    assert family_of("tiny-gigachat") is gc
    assert family_of(CUT) is gc
    cfg = RunConfig.from_args("server", [
        "--backend", "local", "--work-dir", str(tmp_path), "--model",
        "tiny-gigachat", "--dataset", "synthetic", "--hotkey", "hotkey_0",
        "--dp", "1"])
    try:
        comps = common.build(cfg)
        assert isinstance(comps.model, gc.GigaChat35)
        assert comps.model_cfg is gc.PRESETS["tiny-gigachat"]
    finally:
        flight.reset()


def test_serving_tree_keeps_the_float32_leaves_float32():
    """`rounds_first` at the cell's dtypes (bfloat16 parameters and
    compute): a float32 base rounds its matrices, and `A_log`, `dt_bias`,
    the convolution, every norm's parameter, the router and its selection
    bias stay float32."""
    pc = dataclasses.replace(gc.PRESETS["tiny-gigachat"], dtype="bfloat16")
    model, _ = gc.make_model(pc)
    base = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    tree = serve_weights.abstract(pc, base)
    flat = {"/".join(str(k.key) for k in path): a.dtype for path, a
            in jax.tree_util.tree_flatten_with_path(tree)[0]}
    stay = [k for k, d in flat.items() if d == jnp.float32]
    assert {k.split("/")[-1] for k in stay} == {
        "A_log", "dt_bias", "conv1d_weight", "o_norm", "w", "router",
        "e_score_correction_bias"}
    assert all(d == jnp.bfloat16 for k, d in flat.items() if k not in stay)
    assert flat["layer_1/kv_b_proj"] == jnp.bfloat16
    assert flat["layer_2/experts_gate_up"] == jnp.bfloat16
    assert flat["embed_tokens"] == flat["lm_head"] == jnp.bfloat16


def test_a_state_carried_in_bfloat16_is_told_from_float32(bench, tiny):
    """The control the cell's limits rest on: the reference with its state
    rounded to bfloat16 after every position (and nothing else lowered)
    reads logits that differ from the float32 reference's by far more than
    program and reference differ."""
    reference, _ = bench
    _, pc, _, mcfg, weights = tiny
    ids = np.random.default_rng(6).integers(0, pc.vocab_size, (1, 96))
    want = reference.Reference(mcfg).logits(weights, ids)
    low = reference.Reference(mcfg, "bfloat16").logits(weights, ids)
    assert float(jnp.max(jnp.abs(low - want))) > 50 * TOL


# -- counters ----------------------------------------------------------------

def test_state_and_share_counters_ride_the_token_fetch_only_with_a_sink(
        tiny):
    from distributedtraining_tpu.utils import obs

    class Sink:
        def log(self, *_a, **_k):
            pass

        def close(self):
            pass

    model, pc, params, _, _ = tiny
    eng = _engine(tiny, max_slots=2, max_new_tokens=4)
    try:
        eng.generate([[1, 2, 3]], 4)
        assert obs.registry().peek("serve.gdn.slot_steps") is None
        obs.configure(Sink(), role="server")
        eng.generate([[4, 5, 6, 7, 8]], 4)
        reg = obs.registry()
        # 3 decode steps of one live slot in each of the 4 linear layers
        # (a bucket's empty slot is not counted; a prefill counts none)
        assert reg.peek("serve.gdn.slot_steps").value == 3 * 4
        assert reg.peek("serve.ssm.slot_steps") is None
        k, expert_layers = pc.num_experts_per_tok, 4
        # all 8 experts are held at this size: nothing is left elsewhere
        assert reg.peek("serve.moe.rows").value \
            == (5 + 3) * k * expert_layers
        assert reg.peek("serve.moe.rows_elsewhere").value == 0
        assert reg.peek("serve.gdn.state_bytes").value == sum(
            x.nbytes for half in eng._ssm for x in half)
        assert reg.peek("serve.ssm.state_bytes") is None
    finally:
        obs.reset()
        eng.close()
