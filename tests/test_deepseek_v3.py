"""The DeepSeek-V3 family on the serving path (models/deepseek_v3.py,
ops/moe.py, ops/mla_attention.py, the latent pool of engine/kv_pool.py),
at the `tiny-kanana` preset with float32 parameters and compute, so that
what separates program and reference is the ORDER of float32 sums (sorted
grouped products against a dense masked sum, absorbed against expanded
attention): every tolerance below is a few float32 roundings of numbers
of size <= 1, 1e-4 at the loosest.

The reference is the benchmark's own plain one
(benchmarks/reference/deepseek_v3.py), which imports nothing of the
program; its weights are the program's through the benchmark driver's
own conversion."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import kv_pool, serve, speculative
from distributedtraining_tpu.models import (
    deepseek_v3 as ds, family, family_of, gpt2)
from distributedtraining_tpu.ops import mla_attention as mla, moe

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's reference and driver modules, imported as the
    benchmark imports them."""
    sys.path.insert(0, _BENCH)
    try:
        from drivers import open_loop_mla_moe as driver
        from reference import deepseek_v3 as reference
        yield reference, driver
    finally:
        sys.path.remove(_BENCH)
        for name in [m for m in sys.modules
                     if m.split(".")[0] in ("drivers", "reference")]:
            del sys.modules[name]


@pytest.fixture(scope="module")
def tiny(bench):
    reference, driver = bench
    pc = ds.PRESETS["tiny-kanana"]
    config = dict({f.name: getattr(pc, f.name)
                   for f in dataclasses.fields(pc)},
                  assumed={"padded_vocab": pc.padded_vocab})
    mcfg = reference.model_cfg(config)
    model, _ = ds.make_model(pc)
    params = driver.program_params(mcfg, 7, jnp.float32)
    return model, pc, params, mcfg, reference.init_weights(mcfg, 7)


# -- program against reference ----------------------------------------------

def test_full_forward_matches_the_reference(bench, tiny):
    reference, _ = bench
    model, pc, params, mcfg, weights = tiny
    ids = np.random.default_rng(0).integers(0, pc.vocab_size, (2, 48))
    want = reference.Reference(mcfg).logits(weights, ids)
    got = model.apply({"params": params}, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4


def test_prefill_then_decode_through_the_latent_cache(bench, tiny):
    """Slots at different lengths, prefix cache on: every served token is
    the reference's own greedy pick, token by token, and the reference
    logit of each served token lies within 1e-4 of the reference's best
    at its position (it IS the best unless two logits tie to rounding)."""
    reference, _ = bench
    model, pc, params, mcfg, weights = tiny
    eng = serve.GenerationEngine(model, params, max_slots=4, page_size=8,
                                 max_seq_len=96, max_new_tokens=16,
                                 prefix_cache=True)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, pc.vocab_size, n).tolist()
               for n in (5, 23, 9, 40, 16)]
    outs = eng.generate(prompts, 16)
    eng.close()
    ref = reference.Reference(mcfg)
    for prompt, out in zip(prompts, outs):
        seq = np.asarray([prompt + out])
        rows = np.asarray(ref.logits(weights, seq))[0, :, :pc.vocab_size]
        lo = len(prompt) - 1
        served = rows[np.arange(lo, lo + 16), out]
        assert np.max(rows[lo:lo + 16].max(-1) - served) <= 1e-4
    # what the pool holds: the latent and the rotary key, in whole lane
    # tiles, nothing per head
    assert [x.shape[-1] for half in eng._kv for x in half[:1]] == [128, 128]


def test_absorbed_attention_is_the_expanded_attention(tiny):
    """One block, the same tokens: without a cache it attends in the
    expanded form; given an EMPTY paged cache and the same tokens as
    fresh rows it attends in the absorbed form (the twin's multi-token
    path). One function."""
    model, pc, params, _, _ = tiny
    B, T = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, pc.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    block = ds.DeepseekV3Block(pc, routed=True)
    p = {"params": params["layer_1"]}
    expanded = block.apply(p, x, family.Step(position_ids=pos))
    widths = kv_pool.row_widths(pc)
    pages = tuple(jnp.zeros((3, 8, w)) for w in widths)
    absorbed = block.apply(p, x, family.Step(
        position_ids=pos, kv_lens=jnp.zeros((B,), jnp.int32),
        kv_pages=pages, page_tables=jnp.zeros((B, 2), jnp.int32)))
    assert float(jnp.max(jnp.abs(expanded - absorbed))) <= 1e-5


# -- the expert layer --------------------------------------------------------

def _skewed(n=32, k=3, G=8, E=16, F=8, seed=0):
    """Half of all rows go to expert 5, experts 0, 2 and 7 get none."""
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((n, E)), jnp.float32)
    choice = np.zeros((n, k), np.int32)
    for i in range(n):
        rest = rng.permutation([1, 3, 4, 6])
        choice[i] = ([5] + list(rest[:k - 1])) if i % 2 == 0 \
            else rest[:k]
    # expert 5 in every second row: half of those rows' first column
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (n, k)), jnp.float32)
    w_gu = jnp.asarray(rng.standard_normal((G, E, 2 * F)) * 0.3, jnp.float32)
    w_d = jnp.asarray(rng.standard_normal((G, F, E)) * 0.3, jnp.float32)
    return h, jnp.asarray(choice), weights, w_gu, w_d


def _experts_loop(h, choice, weights, w_gate_up, w_down):
    """The literal spelling the layer is tested against: every row, every
    chosen expert, one at a time, in float32."""
    F = w_down.shape[1]
    highest = jax.lax.Precision.HIGHEST
    h32 = h.astype(jnp.float32)
    out = jnp.zeros(h32.shape, jnp.float32)
    for n in range(h.shape[0]):
        for j in range(choice.shape[1]):
            e = int(choice[n, j])
            gu = jnp.dot(h32[n], w_gate_up[e].astype(jnp.float32),
                         precision=highest)
            act = jax.nn.silu(gu[:F]) * gu[F:]
            out = out.at[n].add(weights[n, j] * jnp.dot(
                act, w_down[e].astype(jnp.float32), precision=highest))
    return out


def test_expert_layer_drops_nothing_under_a_skewed_router():
    h, choice, weights, w_gu, w_d = _skewed()
    counts = np.bincount(np.asarray(choice).reshape(-1), minlength=8)
    assert counts[5] == 16 and (counts[[0, 2, 7]] == 0).all()
    got, stats = moe.routed_experts(h, choice, weights, w_gu, w_d)
    want = _experts_loop(h, choice, weights, w_gu, w_d)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
    assert int(stats["moe_rows"]) == 32 * 3
    assert int(stats["moe_experts_touched"]) == 5
    # padding rows are computed and not counted
    live = jnp.arange(32) < 10
    _, stats = moe.routed_experts(h, choice, weights, w_gu, w_d, live=live)
    assert int(stats["moe_rows"]) == 30
    assert int(stats["moe_experts_touched"]) == len(
        set(np.asarray(choice)[:10].reshape(-1)))


def test_megablox_grouped_product_matches_ragged_dot():
    """The Pallas grouped matmul (interpreted) against jax.lax.ragged_dot
    at bfloat16 shapes its tiling takes, rows padded to its m-tile, some
    groups empty."""
    rng = np.random.default_rng(2)
    G, k, n, m = 4, 128, 256, 200
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((G, k, n)) * 0.1, jnp.bfloat16)
    sizes = jnp.asarray([120, 0, 79, 1], jnp.int32)
    assert moe.gmm_supports(k, n, lhs.dtype)
    assert not moe.gmm_supports(k, n, jnp.float32)
    got = moe.grouped_matmul(lhs, rhs, sizes, impl="gmm_interpret")
    want = moe.grouped_matmul(lhs, rhs, sizes, impl="ragged_dot")
    assert got.shape == want.shape == (m, n)
    # bfloat16 results of float32 sums: one rounding of numbers <= 4
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) <= 0.04


def test_selection_bias_moves_the_choice_and_not_the_weights():
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    w_r = jnp.asarray(rng.standard_normal((32, 8)) * 0.2, jnp.float32)
    zero = jnp.zeros((8,))
    c0, w0 = moe.route(h, w_r, zero, 3, 2.448)
    # a bias that lifts expert 6 over everything: it is always chosen
    bias = zero.at[6].set(10.0)
    c1, w1 = moe.route(h, w_r, bias, 3, 2.448)
    assert (np.asarray(c1) == 6).any(axis=1).all()
    assert not (np.asarray(c0) == 6).any(axis=1).all()
    # the weights are the SCORES of the chosen, normalised and scaled:
    # they sum to the scale and never hold the bias
    assert np.allclose(np.asarray(w1).sum(-1), 2.448, atol=1e-5)
    s = np.asarray(jax.nn.sigmoid(h @ w_r))
    picked = np.take_along_axis(s, np.asarray(c1), axis=-1)
    assert np.allclose(np.asarray(w1),
                       picked / picked.sum(-1, keepdims=True) * 2.448,
                       atol=1e-5)
    assert np.allclose(np.asarray(w0).sum(-1), 2.448, atol=1e-5)


# -- the latent pool ---------------------------------------------------------

def test_pool_widths_are_the_models_to_state():
    pc = ds.PRESETS["kanana-2-30b-a3b-l8"]
    assert pc.cache_row_widths == (512, 64)
    # stored in whole lane tiles: the rotary key's 64 beside 64 zero lanes
    assert kv_pool.row_widths(pc) == (512, 128)
    pool = kv_pool.make_pool(2, 5, 16, kv_pool.row_widths(pc), jnp.bfloat16)
    assert [[x.shape for x in half] for half in pool] == [
        [(5, 16, 512)] * 2, [(5, 16, 128)] * 2]
    # GPT-2 and Llama: one K/V pair of heads, exactly as before
    g = gpt2.PRESETS["gpt2-774m"]
    assert kv_pool.row_widths(g) == (1280, 1280)
    assert kv_pool.kv_head_geometry(g) == (20, 64)
    from distributedtraining_tpu.models import llama
    assert kv_pool.row_widths(llama.PRESETS["llama3-8b"]) == (1024, 1024)
    old = (jnp.zeros((5, 16, 1280), jnp.bfloat16),) * 2
    new = kv_pool.make_pool(2, 5, 16, kv_pool.row_widths(g), jnp.bfloat16)
    for half in new:
        assert [(x.shape, x.dtype) for x in half] == [
            (x.shape, x.dtype) for x in old]
        assert all(not x.any() for x in half)


def test_narrow_rows_are_written_beside_zero_lanes():
    pool = kv_pool.make_pool(1, 4, 8, (128, 128), jnp.float32)
    c = jnp.ones((1, 8, 32))
    k_r = 2 * jnp.ones((1, 8, 8))
    inter = {"layer_0": {"kv_cache": ((c, k_r),)}}
    k, v = kv_pool.write_pages(*pool, inter, ["layer_0"], jnp.asarray([2]))
    assert float(k[0][2, :, :32].min()) == 1 and not k[0][2, :, 32:].any()
    assert float(v[0][2, :, :8].min()) == 2 and not v[0][2, :, 8:].any()
    assert not k[0][jnp.asarray([0, 1, 3])].any()
    k, v = kv_pool.write_next_row(
        k, v, {"layer_0": {"kv_cache": ((c[:, :1], k_r[:, :1]),)}},
        ["layer_0"], jnp.asarray([[3, 1]]), jnp.asarray([9]))
    assert float(k[0][1, 1, :32].min()) == 1 and not k[0][1, 0].any()


# -- kernel against twin -----------------------------------------------------

def _mla_case(B, H, C, R, P, MP, lens, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    pool = 1 + B * MP

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    kr_pages = jnp.pad(arr(pool, P, R), ((0, 0), (0, 0), (0, 128 - R)))
    tables = jnp.asarray(1 + rng.permutation(B * MP).reshape(B, MP),
                         jnp.int32)
    return (arr(B, 1, H, C) * 0.3, arr(B, 1, H, R) * 0.3, arr(pool, P, C),
            kr_pages, tables, jnp.asarray(lens, jnp.int32), arr(B, 1, C),
            arr(B, 1, R))


@pytest.mark.parametrize("lens, mp", [([13, 64, 1], 4), ([0, 31], 2),
                                      ([250, 7, 129, 256], 16)])
def test_latent_kernel_matches_its_twin(lens, mp):
    """Interpret mode, float32: ragged lengths, page-boundary lengths, an
    empty context (the fresh row alone), several chunks."""
    args = _mla_case(len(lens), 8, 128, 16, 16, mp, lens)
    assert mla.kernel_supports(*args[:4])
    got = mla.mla_decode_attention(*args, 0.25, interpret=True)
    want = mla.mla_decode_reference(*args, 0.25)
    assert got.shape == want.shape == (len(lens), 1, 8, 128)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5


def test_latent_kernel_is_selected_by_shape():
    a = _mla_case(2, 8, 128, 16, 16, 2, [3, 4])
    assert mla.kernel_supports(*a[:4])
    two_tokens = jnp.concatenate([a[0], a[0]], axis=1)
    assert not mla.kernel_supports(two_tokens, a[1], a[2], a[3])
    assert not mla.kernel_supports(a[0], a[1], a[2], a[3][..., :64])
    assert not mla.kernel_supports(a[0], a[1], a[2].astype(jnp.bfloat16),
                                   a[3])
    with pytest.raises(ValueError, match="unsupported shapes"):
        mla.mla_decode_attention(two_tokens, *a[1:], 0.25, interpret=True)


# -- who refuses, who takes it ----------------------------------------------

def test_speculative_lane_and_kv_transfer_refuse_a_latent_cache(tiny):
    model, pc, params, _, _ = tiny
    gmodel, gcfg = gpt2.make_model("tiny")
    for draft, target in ((gmodel, pc), (model, gcfg), (model, pc)):
        assert speculative.compat_reason(draft, target) \
            == kv_pool.LATENT_CACHE_REASON
    assert speculative.compat_reason(gmodel, gcfg) is None
    with pytest.raises(ValueError, match="latent row"):
        kv_pool.kv_head_geometry(pc)
    with pytest.raises(ValueError, match="KV transfer plane"):
        serve.GenerationEngine(model, params, max_slots=2, page_size=8,
                               max_seq_len=32, phase="decode",
                               kv_adopter=object())


def test_common_build_takes_the_third_family(tmp_path):
    from distributedtraining_tpu.config import RunConfig
    from distributedtraining_tpu.utils import flight
    from neurons import common

    assert family_of("tiny-kanana") is ds
    assert family_of("kanana-2-30b-a3b-l8") is ds
    assert family_of("tiny") is gpt2 and family_of("no-such") is gpt2
    cfg = RunConfig.from_args("server", [
        "--backend", "local", "--work-dir", str(tmp_path), "--model",
        "tiny-kanana", "--dataset", "synthetic", "--hotkey", "hotkey_0",
        "--dp", "1"])
    try:
        comps = common.build(cfg)
        assert isinstance(comps.model, ds.DeepseekV3)
        assert comps.model_cfg is ds.PRESETS["tiny-kanana"]
    finally:
        flight.reset()


def test_moe_counters_ride_the_token_fetch_only_with_a_sink(tiny):
    from distributedtraining_tpu.utils import obs

    class Sink:
        def log(self, *_a, **_k):
            pass

        def close(self):
            pass

    model, pc, params, _, _ = tiny
    eng = serve.GenerationEngine(model, params, max_slots=2, page_size=8,
                                 max_seq_len=64, max_new_tokens=4)
    try:
        eng.generate([[1, 2, 3]], 4)
        assert obs.registry().peek("serve.moe.rows") is None
        obs.configure(Sink(), role="server")
        eng.generate([[4, 5, 6, 7, 8]], 4)
        reg = obs.registry()
        k, routed_layers = pc.num_experts_per_tok, 2
        # one prefill of 5 live tokens (its bucket's padding not counted)
        # and 3 decode steps of one live slot
        assert reg.peek("serve.moe.rows").value == (5 + 3) * k * routed_layers
        touched = reg.peek("serve.moe.experts_touched").value
        assert 4 * k * routed_layers <= touched <= (5 + 3) * k * routed_layers
        hist = reg.peek("serve.moe.rows_per_expert")
        assert hist.count == 3 and hist.percentiles((50.0,))["p50"] == 1.0
    finally:
        obs.reset()
        eng.close()
