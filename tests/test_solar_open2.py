"""The Solar-Open2 family on the serving path (models/solar_open2.py, the
per-channel delta rule of ops/delta_rule.py, gated grouped-query attention
with no position term over the paged K/V pool, the held SwiGLU experts of
ops/moe.py, and the TWO kinds of cache that meet in one engine AND in one
prefix cache: a per-slot state with its snapshots beside K/V pages), at
the `tiny-solar` preset with float32 parameters and compute, so that what
separates program and reference is the ORDER of float32 sums (the chunked
WY form, from zero or from a snapshot, against the token-by-token
recurrence; sorted grouped products against a dense masked sum; paged
blocks against dense scores). The weights are drawn at the signal sizes of
the published widths (matrix std 0.16 at hidden 64 = 0.02 at 4096).

The reference is the benchmark's own plain one
(benchmarks/reference/solar_open2.py), which imports nothing of the
program; its weights are the program's through the benchmark driver's own
conversion."""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import kv_pool, serve
from distributedtraining_tpu.models import family_of, solar_open2 as so
from distributedtraining_tpu.ops import moe

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
TOL = 2e-4
CUT = "solar-open2-250b-l4-e40-v24k"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's reference and driver modules, imported as the
    benchmark imports them."""
    sys.path.insert(0, _BENCH)
    try:
        from drivers import sessions_kda_gqa_moe as driver
        from reference import solar_open2 as reference
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
                         "kda_low_rank": pc.kda_low_rank,
                         "matrix_std": 0.16})


@pytest.fixture(scope="module")
def tiny(bench):
    reference, driver = bench
    pc = so.PRESETS["tiny-solar"]
    mcfg = reference.model_cfg(_config(pc, driver))
    model, _ = so.make_model(pc)
    params = driver.program_params(mcfg, 7, jnp.float32)
    return model, pc, params, mcfg, reference.init_weights(mcfg, 7)


def _engine(tiny, **kw):
    model, _, params, _, _ = tiny
    kw = dict(dict(max_slots=4, page_size=8, max_seq_len=256,
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


def test_the_reference_in_row_blocks_is_the_reference_whole(bench, tiny,
                                                            monkeypatch):
    """The blocks that let a 40k-token session fit the chip change no
    number: the recurrence's carry and the convolution's rows cross a
    block's edge, the attention's queries read every earlier key."""
    reference, _ = bench
    _, pc, _, mcfg, weights = tiny
    ids = np.random.default_rng(5).integers(0, pc.vocab_size, (1, 90))
    whole = reference.Reference(mcfg).logits(weights, ids)
    monkeypatch.setattr(reference, "ROW_BLOCK", 32)
    monkeypatch.setattr(reference, "SCORE_BYTES", 4 * 4 * 90 * 16)
    blocked = reference.Reference(mcfg).logits(weights, ids)
    assert float(jnp.max(jnp.abs(blocked - whole))) <= 2e-5


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
    # what the pools hold: K/V pages of 2 heads x 16 for the one attention
    # layer, a float32 state and a tail for each of the three others
    k_pages, v_pages = eng._kv
    assert len(k_pages) == len(v_pages) == 1
    assert (k_pages[0].shape[-1], v_pages[0].shape[-1]) == (32, 32)
    states, tails = eng._ssm
    assert [s.shape for s in states] == [(4 + 1, 2, 128, 128)] * 3
    assert [t.shape for t in tails] == [(4 + 1, 3, 768)] * 3
    assert states[0].dtype == jnp.float32
    assert sorted(eng._state_free) == [0, 1, 2, 3] and not eng._state_of
    eng.close()


def test_a_session_through_the_prefix_cache_serves_the_references_tokens(
        bench, tiny):
    """Three turns of one session beside another session's: every turn
    after the first is a HIT (a snapshot restored, the suffix continued
    over the cached pages in chunks of 16), and what it serves is the
    reference's pick over the session's WHOLE text."""
    reference, _ = bench
    _, pc, _, _, _ = tiny
    eng = _engine(tiny, prefix_cache=True, prefill_chunk=16,
                  debug_invariants=True)
    (text, other) = _prompts(pc, (37, 29), seed=9)
    eng.generate([other], 8)
    for turn in range(3):
        out = eng.generate([text], 12)[0]
        assert _served_gap(reference, tiny, text, out) <= TOL
        assert eng.prefix_hits == turn
        text = text + out + _prompts(pc, (21,), seed=20 + turn)[0]
    assert eng.prefix_tokens_saved == 37 + (37 + 12 + 21)
    eng.close()


def test_what_each_layer_caches_is_stated_per_layer():
    pc = so.PRESETS[CUT]
    assert pc.layer_caches == ("kv", "ssm", "ssm", "ssm")
    assert pc.ssm_state_shape == (64, 128, 128)
    assert pc.ssm_tail_shape == (3, 24576)
    assert kv_pool.row_widths(pc) == (1024, 1024)
    assert kv_pool.kv_head_geometry.__name__     # refuses below
    assert kv_pool.has_recurrent_state(pc)
    assert kv_pool.state_name(pc) == "kda"
    assert pc.padded_vocab == 24576 and pc.experts_held == (0, 40)
    assert family_of(CUT) is so and family_of("tiny-solar") is so
    whole = so.PRESETS["solar-open2-250b"]
    assert [whole.layer_caches.count(k) for k in ("ssm", "kv")] == [36, 12]
    assert whole.gqa_layers == tuple(range(0, 48, 4))
    for change, named in (({"gqa_layers": (7,)}, "gqa_layers"),
                          ({"experts_held": (300, 40)}, "experts_held"),
                          ({"use_rope": True}, "use_rope"),
                          ({"use_gqa_gate": False}, "use_gqa_gate"),
                          ({"kda_allow_neg_eigval": False},
                           "kda_allow_neg_eigval"),
                          ({"kda_use_full_proj": True}, "kda_use_full_proj"),
                          ({"first_k_dense_replace": 1},
                           "first_k_dense_replace")):
        with pytest.raises(ValueError, match=named):
            dataclasses.replace(pc, **change)
    with pytest.raises(ValueError, match="recurrent state per slot"):
        kv_pool.kv_head_geometry(pc)


def test_parameter_count_of_the_cut_is_the_issues_table():
    with open(os.path.join(_BENCH, "configs", f"{CUT}.json")) as f:
        table = json.load(f)["parameters"]
    model, _ = so.make_model(CUT)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))

    def count(tree):
        return sum(math.prod(x.shape)
                   for x in jax.tree_util.tree_leaves(tree))

    def mixer(layer):
        return count({k: v for k, v in layer.items() if not (
            k.startswith(("experts_", "shared_", "router", "e_score"))
            or k.endswith("_norm") and k != "o_norm")})

    assert mixer(shapes["layer_1"]) == table["delta_mixer"] == 137_732_288
    assert mixer(shapes["layer_0"]) == table["attention_mixer"] \
        == 109_051_904
    ffn = count({k: v for k, v in shapes["layer_2"].items() if k.startswith(
        ("experts_", "shared_", "router", "e_score"))})
    assert ffn == table["ffn_held"] == 646_185_280
    layers = sum(count(shapes[f"layer_{i}"]) for i in range(4))
    assert layers == table["four_layers"] == 3_107_022_656
    assert count(shapes) - layers == table["embedding_head_final_norm"] \
        == 201_330_688
    assert count(shapes) == table["held_here"] == 3_308_353_344


def test_the_configuration_file_keeps_every_published_number(bench):
    """The catalog row's `config`, key for key, but for the keys listed
    under `reduced`; and the driver takes the file for the preset."""
    _, driver = bench
    with open(os.path.join(_BENCH, "configs", f"{CUT}.json")) as f:
        config = json.load(f)
    row = {"partial_rotary_factor": 1, "hidden_size": 4096,
           "num_hidden_layers": 48, "num_attention_heads": 64,
           "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
           "intermediate_size": 10240, "moe_intermediate_size": 1280,
           "rms_norm_eps": 1e-05, "rope_theta": 10000,
           "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
           "gqa_interval": 3, "n_routed_experts": 320,
           "n_shared_experts": 1, "routed_scaling_factor": 1,
           "num_experts_per_tok": 8}
    for key, value in row.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert config["model_type"] == "solar_open2"
    for key in ("tie_word_embeddings", "use_rope", "kda_use_full_proj"):
        assert config[key] is False
    for key in ("use_gqa_gate", "kda_allow_neg_eigval", "norm_topk_prob"):
        assert config[key] is True
    assert sorted(config["reduced"]) == ["gqa_layers", "n_routed_experts",
                                         "num_hidden_layers", "vocab_size"]
    driver.make_model(config)
    with pytest.raises(SystemExit, match="head_dim"):
        driver.make_model(dict(config, head_dim=64))


# -- the share ---------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(bench):
    """The share test: eight chips hold 2 of 16 SwiGLU experts each.
    Their partial routed sums, with the shared expert counted ONCE (it is
    added whole on every chip), add up to what the uncut reference gives
    for the whole expert layer; and no share is the whole."""
    reference, _ = bench
    mcfg = {"hidden_size": 32, "moe_intermediate_size": 24,
            "n_routed_experts": 16, "num_experts_per_tok": 4,
            "norm_topk_prob": True, "routed_scaling_factor": 1,
            "experts_held": (0, 16)}
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    E, F, G = 32, 24, 16
    w = {"router": jax.random.normal(key[0], (E, G)),
         "e_score_correction_bias": 0.1 * jax.random.normal(key[1], (G,)),
         "experts_gate_up": jax.random.normal(key[2], (G, E, 2 * F)),
         "experts_down": 0.3 * jax.random.normal(key[3], (G, F, E)),
         "shared_gate_proj": jax.random.normal(key[4], (E, F)),
         "shared_up_proj": jax.random.normal(key[5], (E, F)),
         "shared_down_proj": 0.3 * jax.random.normal(key[6], (F, E))}
    h = jax.random.normal(key[7], (48, E))
    whole, _ = reference.experts(w, h, mcfg, "float32")
    shared = reference._swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                               w["shared_down_proj"], "float32")
    choice, weights = moe.route(h, w["router"], w["e_score_correction_bias"],
                                4, 1, True)
    total = shared
    for first in range(0, 16, 2):
        part, st = moe.routed_experts(
            h, choice, weights, w["experts_gate_up"][first:first + 2],
            w["experts_down"][first:first + 2], held=(first, 2))
        assert float(jnp.max(jnp.abs(part + shared - whole))) > 1e-2
        # the reference holding the same share gives the same part
        ref_part, _ = reference.experts(
            dict(w, experts_gate_up=w["experts_gate_up"][first:first + 2],
                 experts_down=w["experts_down"][first:first + 2]),
            h, mcfg, "float32", held=(first, 2))
        assert float(jnp.max(jnp.abs(part + shared - ref_part))) <= 1e-4
        total = total + part
    scale = float(jnp.max(jnp.abs(whole)))
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * scale


def test_a_share_of_the_model_is_the_references_share(bench):
    """The cut as the cell runs it: the program holding experts 2..5 of 8
    against the reference holding the same share (a chosen expert that is
    not held adds nothing, in both)."""
    reference, driver = bench
    pc = dataclasses.replace(so.PRESETS["tiny-solar"], experts_held=(2, 4))
    config = dict(_config(pc, driver), n_routed_experts=4,
                  published={"n_routed_experts": 8}, experts_held=[2, 4])
    mcfg = reference.model_cfg(config)
    assert mcfg["n_routed_experts"] == 8 and mcfg["experts_held"] == (2, 4)
    model, _ = so.make_model(pc)
    params = driver.program_params(mcfg, 11, jnp.float32)
    assert params["layer_2"]["experts_down"].shape[0] == 4
    ids = np.random.default_rng(4).integers(0, pc.vocab_size, (2, 40))
    want = reference.Reference(mcfg).logits(
        reference.init_weights(mcfg, 11), ids)
    got = model.apply({"params": params}, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(got - want))) <= TOL


# -- the mechanisms, each seen by the comparison ----------------------------

@pytest.mark.parametrize("fault", ["mean_decay", "beta_1", "no_gate"])
def test_each_mechanism_left_out_is_seen_by_the_reference(bench, tiny,
                                                          fault):
    """What the tolerance must not hide: the decay a channel (against
    its mean a head), the factor 2 of beta, the output gates. Broken by
    the benchmark tool's own patches, the full forward leaves the
    reference by far more than rounding."""
    reference, _ = bench
    sys.path.insert(0, _BENCH)
    try:
        from tools import kda_gqa_moe as tool
    finally:
        sys.path.remove(_BENCH)
    model, pc, params, mcfg, weights = tiny
    ids = np.random.default_rng(0).integers(0, pc.vocab_size, (1, 60))
    want = reference.Reference(mcfg).logits(weights, ids)
    with tool.fault(fault):
        got = model.apply({"params": params}, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(got - want))) > 100 * TOL


def test_the_selection_bias_is_the_references(bench, tiny):
    reference, _ = bench
    _, _, params, mcfg, weights = tiny
    w = weights["layers"][2]
    assert w["e_score_correction_bias"].dtype == jnp.float32
    assert float(jnp.max(jnp.abs(w["e_score_correction_bias"]))) > 0
    assert (np.asarray(params["layer_2"]["e_score_correction_bias"])
            == np.asarray(w["e_score_correction_bias"])).all()


def test_scopes_are_in_the_lowered_serve_programs(tiny):
    eng = _engine(tiny)
    eng.generate(_prompts(tiny[1], (9,)), 2)
    (key, prog), = eng._decode_progs.items()
    k_pages, v_pages = eng._kv
    text = prog.lower(eng._params, k_pages, v_pages,
                      np.zeros(key, np.int32), np.zeros(key[:1], np.int32),
                      np.zeros(key[:1], np.int32),
                      *eng._slot_state(np.zeros(key[:1], np.int32))
                      ).as_text(debug_info=True)
    for scope in ("solar.kda", "solar.gqa", "solar.moe_ffn", "kda.decode",
                  "moe.route", "moe.experts"):
        assert scope in text, scope
    eng.close()
