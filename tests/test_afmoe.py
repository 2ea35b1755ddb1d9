"""The AFMoE family on the serving path (models/afmoe.py: windowed rotary
attention layers beside a global NoPE one, every one gated and with a norm a
head of q and k; a leading dense FFN, then routed + shared SwiGLU experts;
four norms a block; a muP multiplier on the lookup) and the TWO page groups
that meet in one engine (engine/kv_pool.py: the global layer's pages for
ever, the window layers' pages given back behind the window), at the
`tiny-trinity` preset (window 8, 1 dense + 4 expert layers `[s, s, s, s,
f]`, 8 experts, 2 a token) with float32 parameters and compute, so that
what separates program and reference is the ORDER of float32 sums (sorted
grouped products against a dense masked sum; paged blocks and a shifted
table against dense masked scores). The weights are drawn at the signal
sizes of the published widths (matrix std 0.11 at hidden 64 = 0.02 at 2048).

The reference is the benchmark's own plain one
(benchmarks/reference/afmoe.py), which imports nothing of the program; its
weights are the program's through the benchmark driver's own conversion."""

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
from distributedtraining_tpu.models import afmoe, family_of
from distributedtraining_tpu.ops import moe

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
# float32 both sides: what is left is the order of sums through five
# layers and a head at |logit| of a few units
TOL = 2e-4
CUT = "trinity-mini-l5"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's reference, driver and tool modules, imported as the
    benchmark imports them."""
    sys.path.insert(0, _BENCH)
    try:
        from drivers import open_loop_gqa_window_moe as driver
        from reference import afmoe as reference
        from tools import gqa_window_moe as tool
        yield reference, driver, tool
    finally:
        sys.path.remove(_BENCH)
        for name in [m for m in sys.modules if m.split(".")[0] in (
                "drivers", "reference", "tools")]:
            del sys.modules[name]


def _config(pc, driver):
    return dict({f.name: driver._plain(getattr(pc, f.name))
                 for f in dataclasses.fields(pc)},
                assumed={"padded_vocab": pc.padded_vocab,
                         "matrix_std": 0.11})


@pytest.fixture(scope="module")
def tiny(bench):
    reference, driver, _ = bench
    pc = afmoe.PRESETS["tiny-trinity"]
    mcfg = reference.model_cfg(_config(pc, driver))
    model, _ = afmoe.make_model(pc)
    params = driver.program_params(mcfg, 7, jnp.float32)
    return model, pc, params, mcfg, reference.init_weights(mcfg, 7)


def _engine(tiny, **kw):
    model, _, params, _, _ = tiny
    kw = dict(dict(max_slots=4, page_size=4, max_seq_len=128,
                   max_new_tokens=16), **kw)
    return serve.GenerationEngine(model, params, **kw)


def _prompts(pc, lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, pc.vocab_size, n).tolist() for n in lengths]


def _rows(reference, tiny, seq):
    _, pc, _, mcfg, weights = tiny
    return np.asarray(reference.Reference(mcfg).logits(
        weights, np.asarray([seq])))[0, :, :pc.vocab_size]


# -- program against reference ----------------------------------------------

@pytest.mark.parametrize("impl", ["dense", "blockwise"])
def test_full_forward_matches_the_reference(bench, tiny, impl):
    """T = 44 is more than five windows of 8."""
    reference, _, _ = bench
    model, pc, params, mcfg, weights = tiny
    model, _ = afmoe.make_model(dataclasses.replace(pc, attention_impl=impl))
    ids = np.random.default_rng(0).integers(0, pc.vocab_size, (2, 44))
    want = reference.Reference(mcfg).logits(weights, ids)
    got = model.apply({"params": params}, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.max(jnp.abs(got - want))) <= TOL


def test_the_reference_in_blocks_is_the_reference_whole(bench, tiny,
                                                        monkeypatch):
    """The blocks that let 33,792 positions fit the chip change no number:
    a block of queries reads every earlier key, rotated at its own row."""
    reference, _, _ = bench
    _, pc, _, mcfg, weights = tiny
    ids = np.random.default_rng(5).integers(0, pc.vocab_size, (1, 70))
    whole = reference.Reference(mcfg).logits(weights, ids)
    monkeypatch.setattr(reference, "ROW_BLOCK", 32)
    monkeypatch.setattr(reference, "SCORE_BYTES", 4 * 4 * 70 * 16)
    blocked = reference.Reference(mcfg).logits(weights, ids)
    assert float(jnp.max(jnp.abs(blocked - whole))) <= 1e-5


# window 8, page 4; contexts that cross a page's edge (11, 12, 13), the
# window's (7, 8, 9) and, with the chunk, a chunk's edge by -1 / 0 / +1, and
# one of five windows
@pytest.mark.parametrize("chunk", [4, 8, 16], ids=[
    "chunk_under_window", "chunk_is_window", "chunk_over_window"])
def test_prefill_in_chunks_then_decode_through_both_page_groups(
        bench, tiny, chunk):
    """The prefill's last row (whatever ran it: one program, or chunks over
    the pages the earlier chunks wrote in both groups) and every decoded
    token against the reference's ONE full forward over prompt + served
    tokens: the first as LOGITS, the rest as the gap by which a served
    token's reference logit lies under the reference's best."""
    reference, _, _ = bench
    _, pc, _, _, _ = tiny
    eng = _engine(tiny, prefill_chunk=chunk, debug_invariants=True)
    first_rows = {}
    inner = eng._first_token

    def keep(req, nxt, logit_row):
        first_rows[req.rid] = np.asarray(logit_row)
        return inner(req, nxt, logit_row)

    eng._first_token = keep
    lengths = sorted({7, 8, 9, 11, 12, 13, chunk - 1, chunk, chunk + 1,
                      2 * chunk + 1, 43})
    prompts = _prompts(pc, lengths, seed=chunk)
    reqs = [eng.submit(p, 10) for p in prompts]
    while not eng.idle:
        eng.step()
    for req in reqs:
        rows = _rows(reference, tiny, req.prompt + req.tokens)
        lo, n = len(req.prompt) - 1, len(req.tokens)
        assert np.max(np.abs(first_rows[req.rid] - rows[lo])) <= TOL, \
            len(req.prompt)
        served = rows[np.arange(lo, lo + n), req.tokens]
        assert np.max(rows[lo:lo + n].max(-1) - served) <= TOL, \
            len(req.prompt)
    # nothing is left in either group
    assert eng.pool.free == eng.pool.total
    assert eng._window.free == eng._window.total and not eng._window.held
    eng.close()


def test_what_each_layer_caches_is_stated_per_layer():
    pc = afmoe.PRESETS[CUT]
    assert pc.layer_caches == ("kv_window",) * 4 + ("kv",)
    assert pc.sliding_window == 2048 and pc.num_dense_layers == 1
    assert kv_pool.row_widths(pc) == (512, 512)
    assert kv_pool.has_window(pc) and not kv_pool.has_recurrent_state(pc)
    assert kv_pool.unheld_cache_reason(pc) is None
    assert pc.padded_vocab == 200192 and pc.experts_held == (0, 128)
    assert family_of(CUT) is afmoe and family_of("tiny-trinity") is afmoe
    whole = afmoe.PRESETS["trinity-mini"]
    assert [whole.layer_caches.count(k) for k in ("kv_window", "kv")] \
        == [24, 8]
    assert whole.layer_types[3] == whole.layer_types[31] == afmoe.FULL
    assert pc.is_buffer(("layer_1", "expert_bias"))
    assert not pc.is_buffer(("layer_1", "router"))
    for change, named in (({"n_group": 2}, "n_group"),
                          ({"topk_group": 2}, "topk_group"),
                          ({"mup_enabled": False}, "mup_enabled"),
                          ({"score_func": "softmax"}, "score_func"),
                          ({"num_shared_experts": 2}, "num_shared_experts"),
                          ({"experts_held": (100, 40)}, "experts_held"),
                          ({"layer_types": ("full_attention",)},
                           "layer_types"),
                          ({"attention_impl": "flash"}, "attention_impl")):
        with pytest.raises(ValueError, match=named):
            dataclasses.replace(pc, **change)


def test_parameter_count_of_the_cut_is_the_issues_table(bench):
    reference, driver, _ = bench
    with open(os.path.join(_BENCH, "configs", f"{CUT}.json")) as f:
        config = json.load(f)
    table = config["parameters"]
    model, _ = afmoe.make_model(CUT)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))

    def count(tree):
        return sum(math.prod(x.shape)
                   for x in jax.tree_util.tree_leaves(tree))

    def part(layer, *starts):
        return count({k: v for k, v in layer.items() if k.startswith(starts)})

    dense, expert = shapes["layer_0"], shapes["layer_1"]
    attention = ("q_", "k_", "v_", "o_proj", "g_proj")
    assert part(dense, *attention) == part(expert, *attention) \
        == table["attention_every_layer"] == 27_263_232
    assert part(dense, "input_", "post_", "pre_") \
        == table["four_block_norms"] == 8_192
    assert part(dense, "gate_proj", "up_proj", "down_proj") \
        == table["dense_ffn"] == 37_748_736
    parts = table["routed_ffn_parts"]
    assert part(expert, "router") == parts["router"] == 262_144
    assert part(expert, "expert_bias") == parts["expert_bias_buffer"] == 128
    assert part(expert, "shared_") == parts["shared_expert"] == 6_291_456
    assert part(expert, "experts_") == parts["experts_128"] == 805_306_368
    assert part(expert, "router", "expert", "shared_") \
        == table["routed_ffn"] == 811_860_096
    assert count(dense) == table["dense_layer"] == 65_020_160
    assert count(expert) == table["expert_layer"] == 839_131_520
    layers = sum(count(shapes[f"layer_{i}"]) for i in range(5))
    assert count(shapes) - layers == table["embedding_head_final_norm"] \
        == 819_988_480
    assert count(shapes) == table["held_here"] == 4_241_534_720
    assert (2 * table["dense_layer"] + 30 * table["expert_layer"]
            + table["embedding_head_final_norm"]) == table["published_26b"] \
        == 26_123_974_400
    # the reference's leaves are the same numbers
    counted = reference.count_parameters(reference.model_cfg(config))
    assert counted["total"] == table["held_here"]
    assert counted["layer_0"] == table["dense_layer"]
    assert counted["layer_4"] == table["expert_layer"]


def test_the_configuration_file_keeps_every_published_number(bench):
    """The catalog row's `config`, key for key, but for the keys listed
    under `reduced`; and the driver takes the file for the preset."""
    _, driver, _ = bench
    with open(os.path.join(_BENCH, "configs", f"{CUT}.json")) as f:
        config = json.load(f)
    row = {"global_attn_every_n_layers": 4, "head_dim": 128,
           "hidden_size": 2048, "intermediate_size": 6144,
           "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
           "moe_intermediate_size": 1024, "n_group": 1,
           "num_attention_heads": 32, "num_dense_layers": 2,
           "num_expert_groups": 1, "num_experts": 128,
           "num_experts_per_tok": 8, "num_hidden_layers": 32,
           "num_key_value_heads": 4, "num_limited_groups": 1,
           "num_shared_experts": 1, "rms_norm_eps": 1e-05,
           "rope_theta": 10000, "route_scale": 2.826,
           "sliding_window": 2048, "topk_group": 1, "vocab_size": 200192}
    for key, value in row.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["model_type"] == "afmoe" and config["hidden_act"] == "silu"
    assert config["score_func"] == "sigmoid" and config["rope_scaling"] is None
    assert config["tie_word_embeddings"] is False
    for key in ("mup_enabled", "route_norm", "use_grouped_mm"):
        assert config[key] is True
    assert sorted(config["reduced"]) == ["layer_types", "num_dense_layers",
                                         "num_hidden_layers"]
    assert len(config["published"]["layer_types"]) == 32
    assert config["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    driver.make_model(config)
    with pytest.raises(SystemExit, match="head_dim"):
        driver.make_model(dict(config, head_dim=64))


# -- the share ---------------------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer(bench):
    """The share test: two chips hold 4 of 8 SwiGLU experts each. Their
    partial routed sums, with the shared expert counted ONCE (it is added
    whole on every chip), add up to what the uncut reference gives for the
    whole expert layer; and no share is the whole."""
    reference, _, _ = bench
    E, F, G = 32, 24, 8
    mcfg = {"hidden_size": E, "moe_intermediate_size": F, "num_experts": G,
            "num_experts_per_tok": 2, "route_norm": True,
            "route_scale": 2.826, "experts_held": (0, G)}
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    w = {"router": jax.random.normal(key[0], (E, G)),
         "expert_bias": 0.1 * jax.random.normal(key[1], (G,)),
         "experts_gate_up": jax.random.normal(key[2], (G, E, 2 * F)),
         "experts_down": 0.3 * jax.random.normal(key[3], (G, F, E)),
         "shared_gate_proj": jax.random.normal(key[4], (E, F)),
         "shared_up_proj": jax.random.normal(key[5], (E, F)),
         "shared_down_proj": 0.3 * jax.random.normal(key[6], (F, E))}
    h = jax.random.normal(key[7], (48, E))
    whole, _ = reference.ffn(w, h, mcfg, "float32")
    shared = reference._swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                               w["shared_down_proj"], "float32")
    choice, weights = moe.route(h, w["router"], w["expert_bias"], 2, 2.826,
                                True)
    total = shared
    for first in (0, 4):
        part, _ = moe.routed_experts(
            h, choice, weights, w["experts_gate_up"][first:first + 4],
            w["experts_down"][first:first + 4], held=(first, 4))
        assert float(jnp.max(jnp.abs(part + shared - whole))) > 1e-2
        ref_part, _ = reference.ffn(
            dict(w, experts_gate_up=w["experts_gate_up"][first:first + 4],
                 experts_down=w["experts_down"][first:first + 4]),
            h, mcfg, "float32", held=(first, 4))
        assert float(jnp.max(jnp.abs(part + shared - ref_part))) <= 1e-4
        total = total + part
    scale = float(jnp.max(jnp.abs(whole)))
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * scale


# -- the mechanisms, each seen by the comparison ----------------------------

@pytest.mark.parametrize("fault", ["no_window", "window_off_by_one",
                                   "rope_everywhere", "no_qk_norm", "no_gate",
                                   "no_mup"])
def test_each_mechanism_left_out_is_seen_by_the_reference(bench, tiny,
                                                          fault):
    """What the tolerance must not hide. Broken by the benchmark tool's
    own patches, the full forward leaves the reference by far more than
    rounding."""
    reference, _, tool = bench
    model, pc, params, mcfg, weights = tiny
    ids = np.random.default_rng(0).integers(0, pc.vocab_size, (1, 44))
    want = reference.Reference(mcfg).logits(weights, ids)
    with tool.fault(fault):
        got = model.apply({"params": params}, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(got - want))) > 50 * TOL
    sound = model.apply({"params": params}, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(sound - want))) <= TOL


def test_a_stale_window_table_is_seen_by_the_reference(bench, tiny):
    """The tool's `stale_window_page`: pages go back and the table's first
    row stays behind. A context past the window then serves other tokens
    than the reference's."""
    reference, _, tool = bench
    _, pc, _, _, _ = tiny
    prompt, = _prompts(pc, (30,), seed=3)
    with tool.fault("stale_window_page"):
        eng = _engine(tiny, prefill_chunk=8)
        out, = eng.generate([prompt], 10)
        eng.close()
    rows = _rows(reference, tiny, prompt + out)
    lo, n = len(prompt) - 1, len(out)
    served = rows[np.arange(lo, lo + n), out]
    assert np.max(rows[lo:lo + n].max(-1) - served) > 50 * TOL


def test_the_selection_bias_is_the_references(bench, tiny):
    _, _, params, _, weights = tiny
    w = weights["layers"][2]
    assert w["expert_bias"].dtype == jnp.float32
    assert float(jnp.max(jnp.abs(w["expert_bias"]))) > 0
    assert (np.asarray(params["layer_2"]["expert_bias"])
            == np.asarray(w["expert_bias"])).all()
    assert "expert_bias" not in weights["layers"][0]


def test_scopes_are_in_the_lowered_serve_programs(tiny):
    eng = _engine(tiny)
    eng.generate(_prompts(tiny[1], (9,)), 2)
    (key, prog), = eng._decode_progs.items()
    k_pages, v_pages = eng._kv
    text = prog.lower(
        eng._params, k_pages, v_pages, np.zeros(key, np.int32),
        np.zeros(key[:1], np.int32), np.zeros(key[:1], np.int32),
        *eng._window.tail([], eng._window.decode_pages, key[0])
    ).as_text(debug_info=True)
    for scope in ("afmoe.attn.window", "afmoe.attn.full", "afmoe.mlp",
                  "afmoe.moe", "afmoe.embed", "afmoe.head", "moe.route",
                  "moe.experts", "moe.shared"):
        assert scope in text, scope
    eng.close()


# -- what cannot be done to a window group refuses --------------------------

@pytest.mark.parametrize("kwargs", [
    {"prefix_cache": True}, {"draft": object()}, {"kv_adopter": object()}],
    ids=["prefix_cache", "drafter", "kv_transfer"])
def test_engine_refuses_what_shares_pages_by_position(tiny, kwargs):
    with pytest.raises(ValueError, match="gives pages back behind the "
                                         "window") as err:
        _engine(tiny, **kwargs)
    assert str(err.value) == kv_pool.WINDOW_CACHE_REASON
    with pytest.raises(ValueError, match="gives pages back"):
        kv_pool.kv_head_geometry(tiny[1])
