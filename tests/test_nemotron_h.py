"""The Nemotron-H family on the serving path (models/nemotron_h.py,
ops/ssm.py, the held-expert layer of ops/moe.py, the per-slot state pool of
engine/kv_pool.py and engine/serve.py), at the `tiny-nemotron-h` preset
with float32 parameters and compute, so that what separates program and
reference is the ORDER of float32 sums (the chunked scan against the plain
one, sorted grouped products against a dense masked sum, paged against
dense attention). The weights are drawn at the signal sizes of the
published widths (matrix std 0.16 at hidden 64 = 0.02 at 4096), where the
state is a large part of a Mamba-2 layer's output and the residual stream
grows to ~15: tolerances are a few float32 roundings of numbers that size,
2e-4 at the loosest.

The reference is the benchmark's own plain one
(benchmarks/reference/nemotron_h.py), which imports nothing of the
program; its weights are the program's through the benchmark driver's own
conversion."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import (kv_pool, serve, serve_weights,
                                            speculative)
from distributedtraining_tpu.models import family_of, gpt2, nemotron_h as nh
from distributedtraining_tpu.ops import moe, ssm

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
TOL = 2e-4


@pytest.fixture(scope="module")
def bench():
    """The benchmark's reference and driver modules, imported as the
    benchmark imports them."""
    sys.path.insert(0, _BENCH)
    try:
        from drivers import open_loop_ssm_moe as driver
        from reference import nemotron_h as reference
        yield reference, driver
    finally:
        sys.path.remove(_BENCH)
        for name in [m for m in sys.modules
                     if m.split(".")[0] in ("drivers", "reference")]:
            del sys.modules[name]


@pytest.fixture(scope="module")
def tiny(bench):
    reference, driver = bench
    pc = nh.PRESETS["tiny-nemotron-h"]
    config = dict({f.name: getattr(pc, f.name)
                   for f in dataclasses.fields(pc)},
                  assumed={"padded_vocab": pc.padded_vocab,
                           "matrix_std": 0.16})
    mcfg = reference.model_cfg(config)
    model, _ = nh.make_model(pc)
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


# -- program against reference ----------------------------------------------

def test_full_forward_matches_the_reference(bench, tiny):
    reference, _ = bench
    model, pc, params, mcfg, weights = tiny
    ids = np.random.default_rng(0).integers(0, pc.vocab_size, (2, 150))
    want = reference.Reference(mcfg).logits(weights, ids)
    got = model.apply({"params": params}, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(got - want))) <= TOL


def test_prefill_of_a_padded_bucket_then_decode_through_both_caches(bench,
                                                                    tiny):
    """Slots at different lengths, none a whole bucket: every served token
    is within rounding of the reference's own greedy pick over a FULL pass
    of prompt + served tokens, 32 decode steps on."""
    reference, _ = bench
    model, pc, params, mcfg, weights = tiny
    eng = _engine(tiny, debug_invariants=True)
    prompts = _prompts(pc, (5, 23, 9, 40, 17))
    outs = eng.generate(prompts, 32)
    ref = reference.Reference(mcfg)
    for prompt, out in zip(prompts, outs):
        seq = np.asarray([prompt + out])
        rows = np.asarray(ref.logits(weights, seq))[0, :, :pc.vocab_size]
        lo = len(prompt) - 1
        served = rows[np.arange(lo, lo + 32), out]
        assert np.max(rows[lo:lo + 32].max(-1) - served) <= TOL
    # what the pools hold: pages for the one attention layer, a float32
    # state and a tail for each of the five Mamba-2 layers, nothing for
    # the expert layers
    k_pages, v_pages = eng._kv
    assert len(k_pages) == len(v_pages) == 1
    assert k_pages[0].shape[-1] == pc.n_kv_head * pc.head_dim
    states, tails = eng._ssm
    assert [s.shape for s in states] == [(4 + 1, *pc.ssm_state_shape)] * 5
    assert [t.shape for t in tails] == [(4 + 1, *pc.ssm_tail_shape)] * 5
    assert states[0].dtype == jnp.float32
    # every row was let go with its last request's state in it
    assert sorted(eng._state_free) == [0, 1, 2, 3] and not eng._state_of
    eng.close()


def test_what_each_layer_caches_is_stated_per_layer():
    pc = nh.PRESETS["nemotron-3-super-120b-a12b-l11-e128"]
    assert pc.layer_caches == ("ssm", None, "ssm", None, "ssm", None, "ssm",
                               "kv", None, "ssm", None)
    assert pc.ssm_state_shape == (128, 64, 128)
    assert pc.ssm_tail_shape == (3, 10240)
    assert kv_pool.row_widths(pc) == (256, 256)
    assert kv_pool.has_recurrent_state(pc)
    whole = nh.PRESETS["nemotron-3-super-120b-a12b"]
    assert [whole.layer_caches.count(k) for k in ("ssm", None, "kv")] \
        == [40, 40, 8]
    # a family that states nothing caches the paged pair in every layer
    g = gpt2.PRESETS["gpt2-774m"]
    assert kv_pool.layer_caches(g, 3) == ("kv", "kv", "kv")
    assert not kv_pool.has_recurrent_state(g)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        dataclasses.replace(pc, num_hidden_layers=12)
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(pc, experts_held=(500, 128))


# -- the slot's state over its life ------------------------------------------

def test_a_slot_reused_after_a_longer_request_serves_a_fresh_engines_tokens(
        tiny):
    """No row of the state pool is ever zeroed: the prefill writes over
    whatever the last request left. One slot, so every request after the
    first lands on a used row."""
    _, pc, _, _, _ = tiny
    long_one, short_one = _prompts(pc, (60, 7), seed=2)
    used = _engine(tiny, max_slots=1)
    used.generate([long_one], 32)
    assert float(jnp.max(jnp.abs(used._ssm[0][0][0]))) > 1e-3   # left there
    after = used.generate([short_one], 32)[0]
    used.close()
    fresh = _engine(tiny, max_slots=1)
    want = fresh.generate([short_one], 32)[0]
    fresh.close()
    assert after == want


def test_preemption_regenerates_the_same_tokens(tiny):
    """A pool too small for three long generations preempts the youngest:
    its state row goes back, and its re-prefill makes the same tokens."""
    _, pc, _, _, _ = tiny
    prompts = _prompts(pc, (30, 28, 26), seed=3)
    roomy = _engine(tiny, max_new_tokens=40)
    want = roomy.generate(prompts, 40)
    roomy.close()
    from distributedtraining_tpu.utils import obs

    class Sink:
        def log(self, *_a, **_k):
            pass

        def close(self):
            pass

    obs.configure(Sink(), role="server")
    try:
        tight = _engine(tiny, max_new_tokens=40, pool_pages=1 + 18,
                        debug_invariants=True)
        got = tight.generate(prompts, 40)
        tight.close()
        assert obs.registry().peek("serve.preempted").value >= 1
    finally:
        obs.reset()
    assert got == want


def test_sampled_lanes_ride_the_same_state(tiny):
    """The sampled decode program carries the per-slot state too: a
    temperature-0 lane inside it is the greedy lane."""
    _, pc, _, _, _ = tiny
    a, b = _prompts(pc, (12, 21), seed=4)
    eng = _engine(tiny)
    greedy = eng.generate([a], 16)[0]
    ra = eng.submit(a, 16)
    rb = eng.submit(b, 16, temperature=0.8, top_p=0.9, seed=5)
    while not (ra.done_evt.is_set() and rb.done_evt.is_set()):
        eng.step()
    eng.close()
    assert ra.tokens == greedy and len(rb.tokens) == 16


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
    for draft, target in ((gmodel, pc), (model, gcfg), (model, pc)):
        assert speculative.compat_reason(draft, target) \
            == kv_pool.RECURRENT_STATE_REASON
    with pytest.raises(ValueError, match="recurrent state per slot"):
        kv_pool.kv_head_geometry(pc)
    # no suffix-prefill program is ever built for it
    eng = _engine(tiny)
    eng.generate(_prompts(pc, (9, 9)), 4)
    assert not eng._prefill_ctx_progs and eng._cache is None
    eng.close()


def test_common_build_takes_the_fourth_family(tmp_path):
    from distributedtraining_tpu.config import RunConfig
    from distributedtraining_tpu.utils import flight
    from neurons import common

    assert family_of("tiny-nemotron-h") is nh
    assert family_of("nemotron-3-super-120b-a12b-l11-e128") is nh
    cfg = RunConfig.from_args("server", [
        "--backend", "local", "--work-dir", str(tmp_path), "--model",
        "tiny-nemotron-h", "--dataset", "synthetic", "--hotkey", "hotkey_0",
        "--dp", "1"])
    try:
        comps = common.build(cfg)
        assert isinstance(comps.model, nh.NemotronH)
        assert comps.model_cfg is nh.PRESETS["tiny-nemotron-h"]
    finally:
        flight.reset()


def test_serving_tree_keeps_the_float32_leaves_float32():
    """`rounds_first` at the cell's preset (bfloat16 parameters and
    compute): a float32 base rounds its matrices, and `A_log`, `D`,
    `dt_bias`, the convolution, every norm gain, the router and its
    selection bias stay float32."""
    pc = dataclasses.replace(nh.PRESETS["tiny-nemotron-h"],
                             dtype="bfloat16")
    model, _ = nh.make_model(pc)
    base = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    tree = serve_weights.abstract(pc, base)
    flat = {"/".join(str(k.key) for k in path): a.dtype for path, a
            in jax.tree_util.tree_flatten_with_path(tree)[0]}
    stay = [k for k, d in flat.items() if d == jnp.float32]
    assert {k.split("/")[-1] for k in stay} == {
        "A_log", "D", "dt_bias", "conv1d_weight", "conv1d_bias",
        "mixer_norm", "scale", "router", "e_score_correction_bias"}
    assert all(d == jnp.bfloat16 for k, d in flat.items() if k not in stay)
    assert flat["layer_1/experts_up"] == jnp.bfloat16
    assert flat["embed_tokens"] == flat["lm_head"] == jnp.bfloat16


# -- the expert layer told which experts it holds ---------------------------

def _share_case(n=40, total=8, k=3, L=32, F=48, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = jax.random.normal(key[0], (n, L))
    w_up = 0.3 * jax.random.normal(key[1], (total, L, F))
    w_down = 0.3 * jax.random.normal(key[2], (total, F, L))
    choice = jnp.argsort(jax.random.uniform(key[3], (n, total)),
                         axis=-1)[:, :k].astype(jnp.int32)
    weights = jax.random.uniform(key[4], (n, k))
    return h, choice, weights, w_up, w_down


def test_the_shares_partial_sums_add_up_to_the_layer_that_holds_all():
    h, choice, weights, w_up, w_down = _share_case()
    whole, stats = moe.routed_experts(h, choice, weights, w_up, w_down)
    assert int(stats["moe_rows"]) == 40 * 3 and "moe_rows_elsewhere" \
        not in stats
    total, rows, elsewhere = jnp.zeros_like(whole), 0, 0
    for first in (0, 2, 4, 6):
        part, st = moe.routed_experts(
            h, choice, weights, w_up[first:first + 2],
            w_down[first:first + 2], held=(first, 2))
        assert float(jnp.max(jnp.abs(part - whole))) > 1e-2   # a true cut
        total = total + part
        rows += int(st["moe_rows"])
        elsewhere += int(st["moe_rows_elsewhere"])
        assert int(st["moe_rows"]) + int(st["moe_rows_elsewhere"]) == 120
        assert int(st["moe_experts_touched"]) <= 2
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5
    assert rows == 120 and elsewhere == 3 * 120
    # a share that holds everything is the layer that is told nothing
    same, st = moe.routed_experts(h, choice, weights, w_up, w_down,
                                  held=(0, 8))
    assert float(jnp.max(jnp.abs(same - whole))) == 0.0
    assert int(st["moe_rows_elsewhere"]) == 0


def test_padding_rows_are_computed_and_not_counted_in_a_share():
    h, choice, weights, w_up, w_down = _share_case(seed=1)
    live = jnp.arange(40) < 25
    _, st = moe.routed_experts(h, choice, weights, w_up[:4], w_down[:4],
                               held=(0, 4), live=live)
    here = int(jnp.sum((choice[:25] < 4)))
    assert int(st["moe_rows"]) == here
    assert int(st["moe_rows_elsewhere"]) == 25 * 3 - here
    assert 1 <= int(st["moe_experts_touched"]) <= 4


def test_the_expert_body_follows_from_the_stacks():
    """One up stack as wide as the down stack is deep: squared ReLU; a
    first stack twice as wide: SwiGLU; anything else is refused."""
    h, choice, weights, w_up, w_down = _share_case(n=6, seed=2)
    got, _ = moe.routed_experts(h, choice, weights, w_up, w_down)
    want = np.zeros_like(np.asarray(got))
    for t in range(6):
        for e, w in zip(np.asarray(choice[t]), np.asarray(weights[t])):
            u = np.maximum(np.asarray(h[t]) @ np.asarray(w_up[e]), 0) ** 2
            want[t] += w * (u @ np.asarray(w_down[e]))
    assert np.max(np.abs(np.asarray(got) - want)) <= 1e-4
    with pytest.raises(ValueError, match="neither a fused SwiGLU"):
        moe.routed_experts(h, choice, weights, w_up[..., :40], w_down)
    with pytest.raises(ValueError, match="held"):
        moe.routed_experts(h, choice, weights, w_up, w_down, held=(0, 4))


# -- what has to stay float32, and why --------------------------------------

def test_a_state_carried_in_bfloat16_drifts_where_float32_does_not():
    """1,536 decode steps of one slot (the cell's longest request),
    bfloat16 activations, against the recurrence in float64. Carried in
    float32 the read-out stays within 1e-4 of its size; rounded to
    bfloat16 after every step it is off by more than 30 times that: the
    state is a running sum, and a rounding a step is 1,536 roundings."""
    H, P, G, N, T = 8, 8, 2, 128, 1536
    k = jax.random.split(jax.random.PRNGKey(0), 6)

    def bf(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    x, Bm, Cm = (bf(jax.random.normal(k[i], s)) for i, s in enumerate(
        [(T, H, P), (T, G, N), (T, G, N)]))
    dt = jax.nn.softplus(jax.random.normal(k[3], (T, H)) - 4.0)
    A = -jnp.exp(jax.random.uniform(k[4], (H,), minval=0.0, maxval=1.0))
    D = jnp.ones((H,))

    def run(round_state):
        def step(state, inp):
            y, state = ssm.ssm_decode_update(
                state, jnp.zeros((1,), jnp.int32), *(v[None] for v in inp[:2]),
                A, *(v[None] for v in inp[2:]), D, impl="xla")
            return (bf(state) if round_state else state), y[0]
        return jax.lax.scan(step, jnp.zeros((1, H, P, N)), (x, dt, Bm, Cm))[1]

    h = np.zeros((H, P, N))
    want = np.zeros((T, H, P))
    x64, dt64, b64, c64 = (np.asarray(v, np.float64)
                           for v in (x, dt, Bm, Cm))
    a64 = np.asarray(A, np.float64)
    for t in range(T):
        b = np.repeat(b64[t], H // G, axis=0)
        c = np.repeat(c64[t], H // G, axis=0)
        h = (np.exp(dt64[t] * a64)[:, None, None] * h
             + (dt64[t][:, None] * x64[t])[..., None] * b[:, None, :])
        want[t] = (h * c[:, None, :]).sum(-1) + x64[t]
    size = np.abs(want[-256:]).max()
    err32 = np.abs(np.asarray(run(False))[-256:] - want[-256:]).max() / size
    err16 = np.abs(np.asarray(run(True))[-256:] - want[-256:]).max() / size
    assert err32 < 1e-4 < 3e-3 < err16, (err32, err16)


def test_a_router_scored_in_bfloat16_chooses_other_experts():
    """22 of 512 sigmoid scores: the 22nd and the 23rd lie about 1e-3
    apart. Scored in float32 the choice is float64's on every row whose
    margin is over 1e-6; scored in bfloat16 (8 bits: steps of 2e-3 near
    0.5) about half the rows change at least one expert."""
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(key[0], (256, 64)).astype(jnp.bfloat16)
    w = (0.16 * jax.random.normal(key[1], (64, 512))).astype(
        jnp.bfloat16).astype(jnp.float32)
    b = (0.02 * jax.random.normal(key[2], (512,))).astype(
        jnp.bfloat16).astype(jnp.float32)
    choice, weights = moe.route(h, w, b, 22, 5.0)
    s = 1 / (1 + np.exp(-(np.asarray(h, np.float64) @ np.asarray(
        w, np.float64))))
    ranked = np.argsort(-(s + np.asarray(b, np.float64)), axis=-1)
    top = np.sort(s + np.asarray(b, np.float64), axis=-1)[:, ::-1]
    clear = (top[:, 21] - top[:, 22]) > 1e-6
    assert clear.mean() > 0.95
    same = (np.sort(np.asarray(choice), -1) == np.sort(ranked[:, :22], -1)
            ).all(-1)
    assert same[clear].all()
    picked = np.take_along_axis(s, np.asarray(choice), axis=-1)
    assert np.max(np.abs(np.asarray(weights) - picked / picked.sum(
        -1, keepdims=True) * 5.0)) < 1e-5
    low = jax.nn.sigmoid(jnp.dot(h, w.astype(jnp.bfloat16)))   # bfloat16
    _, low_choice = jax.lax.top_k(low + b.astype(jnp.bfloat16), 22)
    changed = (np.sort(np.asarray(low_choice), -1)
               != np.sort(ranked[:, :22], -1)).any(-1)
    assert changed.mean() > 0.25


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
        assert obs.registry().peek("serve.ssm.slot_steps") is None
        obs.configure(Sink(), role="server")
        eng.generate([[4, 5, 6, 7, 8]], 4)
        reg = obs.registry()
        # 3 decode steps of one live slot in each of the 5 Mamba-2 layers
        # (a bucket's empty slot is not counted; a prefill counts none)
        assert reg.peek("serve.ssm.slot_steps").value == 3 * 5
        k, expert_layers = pc.num_experts_per_tok, 5
        # all 8 experts are held at this size: nothing is left elsewhere
        assert reg.peek("serve.moe.rows").value \
            == (5 + 3) * k * expert_layers
        assert reg.peek("serve.moe.rows_elsewhere").value == 0
        assert reg.peek("serve.moe.rows_per_expert").count == 3
        assert reg.peek("serve.ssm.state_bytes").value == sum(
            x.nbytes for half in eng._ssm for x in half)
    finally:
        obs.reset()
        eng.close()
