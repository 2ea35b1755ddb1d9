"""The LFM2-MoE family on the TRAINING path (models/lfm2_moe.py, the
segment-aware convolution of ops/ssm.py, the held-expert layer of
ops/moe.py under `jax.grad`, the buffer rule and the step counters of
engine/train.py), at the `tiny-lfm2` preset with float32 parameters and
compute, so that what separates program and reference is the ORDER of
float32 sums (sorted grouped products against a dense masked sum, one
[T, T] softmax against per-head ones).

The reference is the benchmark's own plain one
(benchmarks/reference/lfm2_moe.py), which imports nothing of the program;
its weights are the program's through the benchmark driver's own
conversion."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import MinerLoop, TrainEngine
from distributedtraining_tpu.engine.train import (_default_lm_loss,
                                                  default_optimizer)
from distributedtraining_tpu.models import family_of, lfm2_moe as lf
from distributedtraining_tpu.ops import moe
from distributedtraining_tpu.transport import InMemoryTransport
from distributedtraining_tpu.utils import obs

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
TOL = 2e-5
LR, WD = 5e-4, 0.01


@pytest.fixture(scope="module")
def bench():
    """The benchmark's reference and driver modules, imported as the
    benchmark imports them."""
    sys.path.insert(0, _BENCH)
    try:
        from drivers import miner_steps_lfm2_moe as driver
        from reference import lfm2_moe as reference
        yield reference, driver
    finally:
        sys.path.remove(_BENCH)
        for name in [m for m in sys.modules
                     if m.split(".")[0] in ("drivers", "reference")]:
            del sys.modules[name]


def _config(pc, **over) -> dict:
    return dict({f.name: getattr(pc, f.name) for f in dataclasses.fields(pc)},
                assumed={"padded_vocab": pc.padded_vocab}, **over)


@pytest.fixture(scope="module")
def tiny(bench):
    reference, driver = bench
    pc = lf.PRESETS["tiny-lfm2"]
    mcfg = reference.model_cfg(_config(pc))
    weights = reference.init_weights(mcfg, 7)
    model, _ = lf.make_model(pc)
    return model, pc, driver.to_program_tree(weights), mcfg, weights


def _packed_batch(pc, lens_rows, seed=1):
    """Rows of packed documents, `gen.packed_batches`' conventions."""
    rng = np.random.default_rng(seed)
    T = sum(lens_rows[0])
    B = len(lens_rows)
    seg = np.stack([np.repeat(np.arange(len(r)), r) for r in lens_rows])
    pos = np.stack([np.concatenate([np.arange(n) for n in r])
                    for r in lens_rows])
    mask = np.ones((B, T), np.float32)
    for b, r in enumerate(lens_rows):
        mask[b, np.cumsum(r) - 1] = 0.0
    return {"input_ids": rng.integers(0, pc.vocab_size, (B, T)).astype(
                np.int32),
            "segment_ids": seg.astype(np.int32),
            "position_ids": pos.astype(np.int32), "loss_mask": mask}


def _reference_grads(reference, mcfg, weights, batch):
    got = {}
    loss = reference.Reference(mcfg).loss_and_grads(
        weights, batch, lambda where, g: got.update(
            reference._flat_where(where, g)))
    return float(loss), got


def _flat_program(driver, tree, mixers):
    return {driver.program_leaf_name(path, mixers): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(tree)}


# -- program against reference ----------------------------------------------

class _Sink:
    """A sink that keeps nothing: with one configured the registry counts."""

    def log(self, *_a, **_k):
        pass

    def close(self):
        pass


def _share(pc, params, count):
    """The model and the tree of a chip that holds the first `count`
    experts of every routed layer."""
    model, _ = lf.make_model(dataclasses.replace(pc,
                                                 experts_held=(0, count)))
    params = dict(params)
    for i in range(pc.num_dense_layers, pc.num_hidden_layers):
        layer = dict(params[f"layer_{i}"])
        layer["experts_in"] = layer["experts_in"][:count]
        layer["experts_down"] = layer["experts_down"][:count]
        params[f"layer_{i}"] = layer
    return model, params


def test_logits_match_the_reference(bench, tiny):
    reference, _ = bench
    model, pc, params, mcfg, weights = tiny
    batch = _packed_batch(pc, [[20, 1, 27], [48]])
    kw = dict(segment_ids=batch["segment_ids"],
              position_ids=batch["position_ids"])
    got = model.apply({"params": params}, batch["input_ids"], **kw)
    want = reference.Reference(mcfg).logits(weights, batch["input_ids"], **kw)
    assert got.shape == want.shape == (2, 48, pc.padded_vocab)
    assert float(jnp.max(jnp.abs(got - want))) <= TOL


def test_loss_and_every_leafs_gradient_match_the_reference(bench, tiny):
    reference, driver = bench
    model, pc, params, mcfg, weights = tiny
    batch = _packed_batch(pc, [[20, 1, 27], [48]], seed=2)
    (loss, _), grads = jax.value_and_grad(
        lambda p: _default_lm_loss(model, p, batch), has_aux=True)(params)
    ref_loss, ref_grads = _reference_grads(reference, mcfg, weights, batch)
    assert abs(float(loss) - ref_loss) <= TOL
    got = _flat_program(driver, grads, pc.layer_types)
    assert set(got) == set(ref_grads)
    seen = set()
    for name, g in got.items():
        seen.add(name.split(".", 2)[-1])
        want = ref_grads[name]
        scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
        assert float(jnp.max(jnp.abs(g - want))) <= 2e-4 * scale, name
        if name.endswith("expert_bias"):
            assert not np.asarray(g).any() and not np.asarray(want).any()
        else:
            assert np.asarray(g).any(), name
    # the router's, each expert stack's, the taps', the q / k gains'
    assert {"feed_forward.gate", "feed_forward.experts_in",
            "feed_forward.experts_down", "conv.conv",
            "self_attn.q_layernorm", "self_attn.k_layernorm"} <= seen


def test_three_engine_steps_follow_the_references_adamw(bench, tiny):
    reference, driver = bench
    model, pc, params, mcfg, weights = tiny
    batches = [_packed_batch(pc, [[20, 1, 27], [30, 18]], seed=s)
               for s in (3, 4, 5)]
    engine = TrainEngine(model, optimizer=default_optimizer(
        LR, weight_decay=WD, is_buffer=pc.is_buffer))
    state = engine.init_state(params=params)
    losses = []
    for batch in batches:
        state, m = engine.train_step(state, batch)
        losses.append(float(m["loss"]))
    ref = reference.train_reference(mcfg, 7, batches, lr=LR, weight_decay=WD,
                                    params=weights)
    assert max(abs(a - b) for a, b in zip(losses, ref["losses"])) <= TOL
    got = _flat_program(driver, state.params, pc.layer_types)
    want = reference.flat_names(ref["params"])
    start = reference.flat_names(weights)
    for name in want:
        moved = float(jnp.max(jnp.abs(want[name] - start[name])))
        gap = float(jnp.max(jnp.abs(got[name] - want[name])))
        if name.endswith("expert_bias"):
            # a buffer: bit-equal to the base's, in program and reference
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          np.asarray(start[name]))
            assert moved == 0.0
        else:
            # three Adam steps move a leaf by about 3 lr; the program's
            # leaf lies within a hundredth of that from the reference's
            assert moved > LR and gap <= 0.01 * moved, (name, gap, moved)
    # and the optimizer holds no moments for the buffer
    mu_names = {driver.program_leaf_name(path, pc.layer_types)
                for path, _ in jax.tree_util.tree_leaves_with_path(
                    state.opt_state) if driver._is_mu(path)}
    assert mu_names == {n for n in want if not n.endswith("expert_bias")}


def test_the_default_optimizer_would_decay_the_buffer(tiny):
    """What the rule is for: plain AdamW moves a leaf whose gradient is
    zero."""
    model, pc, params, _, _ = tiny
    batch = _packed_batch(pc, [[48]])
    before = np.asarray(params["layer_1"]["expert_bias"])
    assert before.any()
    for is_buffer, same in ((None, False), (pc.is_buffer, True)):
        engine = TrainEngine(model, optimizer=default_optimizer(
            LR, weight_decay=WD, is_buffer=is_buffer))
        state, _ = engine.train_step(engine.init_state(params=params), batch)
        after = np.asarray(state.params["layer_1"]["expert_bias"])
        assert (after == before).all() == same
    # an engine left to choose its optimizer reads the family's rule
    state, _ = TrainEngine(model).train_step(
        TrainEngine(model).init_state(params=params), batch)
    np.testing.assert_array_equal(
        np.asarray(state.params["layer_1"]["expert_bias"]), before)


def test_a_packed_row_gives_each_document_what_it_gives_alone(tiny):
    """Convolution and attention both: loss and gradient of a row of two
    documents are the token-weighted sums of the documents' own."""
    model, pc, params, _, _ = tiny
    lens = [21, 27]
    packed = _packed_batch(pc, [lens], seed=6)
    # the loss reads the mask at the LABEL's position, so "a label inside
    # its document" is zero on each document's FIRST token (the packer's
    # own mask marks the last: PERF.md section 7's open off-by-one, which
    # counts one label across each boundary, is not this test's)
    mask = np.ones((1, 48), np.float32)
    mask[0, [0, lens[0]]] = 0.0
    packed["loss_mask"] = mask

    def weighted(p, batch):
        loss, count = _default_lm_loss(model, p, batch)
        return loss * count, count

    (total, count), grad = jax.value_and_grad(
        lambda p: weighted(p, packed), has_aux=True)(params)
    parts, at = [], 0
    for n in lens:
        alone = {k: v[:, at:at + n] for k, v in packed.items()}
        alone["segment_ids"] = np.zeros_like(alone["segment_ids"])
        parts.append(jax.value_and_grad(
            lambda p: weighted(p, alone), has_aux=True)(params))
        at += n
    assert float(count) == sum(float(c) for (_, c), _ in parts) == 46.0
    assert abs(float(total) - sum(float(t) for (t, _), _ in parts)) <= 1e-3
    summed = jax.tree_util.tree_map(lambda *g: sum(g),
                                    *[g for _, g in parts])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grad),
                            jax.tree_util.tree_leaves(summed)):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * scale, path

    # a tap or a key that read across the boundary would show here
    def second_doc_loss(ids):
        batch = dict(packed, input_ids=ids)
        mask = np.array(packed["loss_mask"])
        mask[:, :lens[0]] = 0.0
        return _default_lm_loss(model, params, dict(batch, loss_mask=mask))[0]
    changed = np.array(packed["input_ids"])
    changed[0, :lens[0]] = (changed[0, :lens[0]] + 1) % pc.vocab_size
    assert float(second_doc_loss(packed["input_ids"])) == float(
        second_doc_loss(changed))


def test_remat_on_equals_remat_off(tiny):
    model, pc, params, _, _ = tiny
    batch = _packed_batch(pc, [[20, 28], [48]], seed=8)
    on, _ = lf.make_model(dataclasses.replace(pc, remat=True))

    def grads(m):
        return jax.value_and_grad(
            lambda p: _default_lm_loss(m, p, batch)[0])(params)

    (loss_off, g_off), (loss_on, g_on) = grads(model), grads(on)
    assert float(loss_off) == float(loss_on)
    for a, b in zip(jax.tree_util.tree_leaves(g_off),
                    jax.tree_util.tree_leaves(g_on)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-7)


@pytest.mark.parametrize("held", [
    pytest.param(8, id="all-held"), pytest.param(2, id="a-quarter-prefix")])
def test_the_train_step_moves_routed_rows_by_gathers_through_remat(tiny,
                                                                   held):
    """The engine's own step over the rematted blocks: `ops/moe.py`'s two
    rules survive `nn.remat` and the engine's `jax.grad`, so nothing inside
    a routed layer scatters into anything a row wide. What scatters there:
    the `bincount` over the held groups (forward, and remat's re-run) and
    the router's `[N, experts]` scores; outside, the lookup and the labels.
    A quarter of the experts held: the prefix is on (128 of the 192 sorted
    rows) and brings no scatter, in its path or in the overflow's branch."""
    from test_moe_grad import scatters

    _, pc, _, _, _ = tiny
    pc = dataclasses.replace(pc, experts_held=(0, held))
    assert moe.prefix_rows(2 * 48 * 2, held, pc.num_experts) == (
        192 if held == 8 else 128)
    model, _ = lf.make_model(dataclasses.replace(pc, remat=True))
    engine = TrainEngine(model)
    step = engine.train_step.__wrapped__.trace(
        engine.abstract_state(), _packed_batch(pc, [[20, 28], [48]])
    ).jaxpr.jaxpr
    found = list(scatters(step))
    routed = [(shape, stack) for _, shape, stack in found
              if "._experts" in stack]
    G, R, rows = pc.experts_held[1], pc.num_experts, 2 * 48
    assert {shape for shape, _ in routed} == {(G,), (rows, R)}
    assert any("rematted_computation" in stack for _, stack in routed)
    assert not [shape for shape, stack in routed if "moe.experts" in stack]
    # the lookup's transpose is still one: the walker does see a wide one
    assert (pc.padded_vocab, pc.hidden_size) in {s for _, s, _ in found}


# -- the share and the model --------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer_forward_and_backward(
        bench):
    """Guide section 4: the partial sums of the four shares (`held` = (0,
    8), (8, 8), (16, 8), (24, 8)) add up to what the uncut REFERENCE gives
    for the whole routed layer; each share's gradient of its own stacks is
    the uncut gradient's slice, and the gradients with respect to `h` sum
    likewise."""
    reference, _ = bench
    pc = dataclasses.replace(lf.PRESETS["tiny-lfm2"], num_experts=32,
                             num_experts_per_tok=4, experts_held=(0, 32))
    whole = reference.model_cfg(_config(pc))
    p = reference.init_layer(whole, 9, 2)
    rng = np.random.default_rng(10)
    h = jnp.asarray(rng.standard_normal((2, 24, pc.hidden_size)), jnp.float32)
    target = jnp.asarray(rng.standard_normal(h.shape), jnp.float32)

    def uncut(h, w_in, w_down):
        q = dict(p, **{"feed_forward.experts_in": w_in,
                       "feed_forward.experts_down": w_down})
        return reference._routed_ffn(
            q, h, held=(0, 32), top_k=4, norm=True, scale=1.0,
            norm_eps=1e-6, precision="float32")

    stacks = (p["feed_forward.experts_in"], p["feed_forward.experts_down"])
    want = uncut(h, *stacks)
    want_g = jax.grad(lambda *a: jnp.sum(uncut(*a) * target), (0, 1, 2))(
        h, *stacks)

    flat = h.reshape(-1, pc.hidden_size)
    total, total_dh, elsewhere = 0.0, 0.0, 0
    for first in (0, 8, 16, 24):
        held = (first, 8)
        # a share draws its own experts by their index among all 32
        mine = reference.init_layer(dict(whole, experts_held=held), 9, 2)
        for key, full in zip(("feed_forward.experts_in",
                              "feed_forward.experts_down"), stacks):
            np.testing.assert_array_equal(np.asarray(mine[key]),
                                          np.asarray(full[first:first + 8]))

        def share(flat, w_in, w_down, held=held):
            # the router is every chip's alike: its part of dh too
            choice, weights = moe.route(
                flat, p["feed_forward.gate"], p["feed_forward.expert_bias"],
                4, 1.0, True, 1e-6)
            return moe.routed_experts(flat, choice, weights, w_in, w_down,
                                      held=held)

        out, stats = share(flat, mine["feed_forward.experts_in"],
                           mine["feed_forward.experts_down"])
        dh, d_in, d_down = jax.grad(
            lambda *a: jnp.sum(share(*a)[0] * target.reshape(flat.shape)),
            (0, 1, 2))(flat, mine["feed_forward.experts_in"],
                       mine["feed_forward.experts_down"])
        total, total_dh = total + out, total_dh + dh
        elsewhere += int(stats["moe_rows_elsewhere"])
        for got, full in ((d_in, want_g[1]), (d_down, want_g[2])):
            piece = full[first:first + 8]
            assert float(jnp.max(jnp.abs(got - piece))) <= 2e-4 * float(
                jnp.max(jnp.abs(piece)))
    assert elsewhere == 3 * 48 * 4          # every row is elsewhere 3 times
    assert float(jnp.max(jnp.abs(total.reshape(h.shape) - want))) <= TOL
    assert float(jnp.max(jnp.abs(total_dh.reshape(h.shape)
                                 - want_g[0]))) <= TOL


def test_the_cut_preset_is_the_configuration_files(bench):
    """`jax.eval_shape` of the preset against the file's `parameters`, and
    every published size in the file against the preset (the driver's own
    check)."""
    import json
    _, driver = bench
    with open(os.path.join(_BENCH, "configs",
                           "lfm2-8b-a1b-l5-e8-v16k.json")) as f:
        config = json.load(f)
    pc = driver.check_config(config)
    assert pc is lf.PRESETS["lfm2-8b-a1b-l5-e8-v16k"]
    model, _ = lf.make_model(pc)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == config["parameters"] == 507_820_288
    assert all(x.dtype == jnp.float32
               for x in jax.tree_util.tree_leaves(shapes))
    with pytest.raises(SystemExit, match="num_experts_per_tok"):
        driver.check_config(dict(config, num_experts_per_tok=2))
    # the whole model, as published: 8.34B with the tied head
    whole, _ = lf.make_model("lfm2-8b-a1b")
    shapes = jax.eval_shape(lambda: whole.init_params(jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e9, 2) == 8.34


# -- through the roles --------------------------------------------------------

def test_common_build_takes_the_fifth_family_and_trains_it(tmp_path):
    from distributedtraining_tpu.config import RunConfig
    from distributedtraining_tpu.utils import flight
    from neurons import common

    assert family_of("tiny-lfm2") is lf
    assert family_of("lfm2-8b-a1b-l5-e8-v16k") is lf
    cfg = RunConfig.from_args("miner", [
        "--backend", "memory", "--chain", "local", "--work-dir",
        str(tmp_path), "--model", "tiny-lfm2", "--dataset", "synthetic",
        "--hotkey", "hotkey_0", "--dp", "1", "--remat"])
    try:
        comps = common.build(cfg)
        assert isinstance(comps.model, lf.Lfm2Moe)
        assert comps.model_cfg.remat is True
        state = comps.engine.init_state(jax.random.PRNGKey(0))
        before = np.asarray(state.params["layer_2"]["expert_bias"]) + 0.0
        batch = _packed_batch(comps.model_cfg, [[24, 24]])
        state, m = comps.engine.train_step(state, batch)
        assert np.isfinite(float(m["loss"]))
        np.testing.assert_array_equal(
            np.asarray(state.params["layer_2"]["expert_bias"]), before)
    finally:
        flight.reset()


def test_counters_leave_the_step_beside_the_loss_and_reach_the_registry(
        tiny):
    _, pc, params, _, _ = tiny
    model, params = _share(pc, params, 4)
    engine = TrainEngine(model)
    batch = _packed_batch(pc, [[48], [48]])
    _, m = engine.train_step(engine.init_state(params=params), batch)
    assert set(m) == {"loss", "tokens", *lf.TRAIN_COUNTERS.values()}
    rows, away, fullest, touched, past, layers_past = (
        int(m[k]) for k in lf.TRAIN_COUNTERS.values())
    # 4 routed layers x 96 tokens x 2 experts a token
    assert rows + away == 4 * 96 * 2 and 0 < rows < 4 * 96 * 2
    assert rows / 4 / 4 <= fullest / 4 <= rows / 4
    # each layer's rows fell to at least one and at most its 4 held experts
    assert 4 <= touched <= 4 * 4
    # half the experts held: the prefix is 128 of a layer's 192 sorted rows,
    # and a router this even stays inside it
    assert past == 0 == layers_past and rows / 4 < 128

    def run_loop():
        loop = MinerLoop(engine, InMemoryTransport(), "m0",
                         send_interval=1e9, check_update_interval=1e9)
        loop.bootstrap(params=params)
        loop.run(iter([batch, batch, batch]))
        return loop

    run_loop()                              # no sink: nothing is kept
    assert obs.registry().peek("train.moe.rows") is None
    obs.configure(_Sink(), role="miner")
    try:
        loop = run_loop()
        reg = obs.registry()
        total = sum(reg.peek(n).value for n in ("train.moe.rows",
                                                "train.moe.rows_elsewhere"))
        assert total == 3 * 4 * 96 * 2
        assert reg.peek("train.moe.rows_fullest_expert").value > 0
        # every routed layer of every step touched 1..8 of its experts
        assert 3 * 4 <= reg.peek("train.moe.experts_touched").value <= 96
        assert reg.peek("train.moe.rows_past_prefix").value == 0
        assert reg.peek("train.moe.layers_past_prefix").value == 0
        assert loop._counted_dev == []
    finally:
        obs.reset()
    # GPT-2's step returns what it returned
    from distributedtraining_tpu.models import gpt2
    g, gc = gpt2.make_model("tiny")
    ge = TrainEngine(g)
    ids = np.zeros((2, 16), np.int32)
    _, gm = ge.train_step(ge.init_state(jax.random.PRNGKey(0)),
                          {"input_ids": ids})
    assert set(gm) == {"loss", "tokens"}


def test_the_model_states_the_routers_expert_count(tiny, monkeypatch):
    """`routed_experts` takes the prefix's bound from what its caller
    states: the family passes `cfg.num_experts` beside `held`."""
    model, pc, params, _, _ = tiny
    seen = []
    real = moe.routed_experts

    def recorded(*args, **kwargs):
        seen.append((kwargs["held"], kwargs["router_experts"]))
        return real(*args, **kwargs)

    monkeypatch.setattr(moe, "routed_experts", recorded)
    model.apply({"params": params},
                _packed_batch(pc, [[48], [48]])["input_ids"])
    assert seen == [(pc.experts_held, pc.num_experts)] * 4


def test_a_step_past_the_prefix_counts_its_rows_and_loses_none(tiny,
                                                               monkeypatch):
    """A selection bias that sends EVERY row to the held half: 192 held
    rows a layer against a prefix of 128, so each routed layer of the
    engine's step runs its overflow, through remat. The step says so (4
    layers x 64 rows), the registry adds it up, and loss and gradients are
    those of the full-width layer (the bound at all the rows)."""
    _, pc, params, _, _ = tiny
    model, params = _share(dataclasses.replace(pc, remat=True), params, 4)
    for i in range(pc.num_dense_layers, pc.num_hidden_layers):
        params[f"layer_{i}"] = dict(
            params[f"layer_{i}"], expert_bias=jnp.where(
                jnp.arange(pc.num_experts) < 4, 10.0, 0.0))
    batch = _packed_batch(pc, [[48], [48]])

    def step():
        engine = TrainEngine(model)
        state, m = engine.train_step(engine.init_state(params=params), batch)
        grads = jax.grad(lambda p: _default_lm_loss(model, p, batch)[0])(
            params)
        return engine, m, grads

    engine, m, grads = step()
    assert int(m["train.moe.rows"]) == 4 * 192
    assert int(m["train.moe.rows_past_prefix"]) == 4 * (192 - 128)
    assert int(m["train.moe.layers_past_prefix"]) == 4
    monkeypatch.setattr(moe, "prefix_rows", lambda rows, *_: rows)
    _, wide, wide_grads = step()
    assert int(wide["train.moe.rows_past_prefix"]) == 0
    assert int(wide["train.moe.layers_past_prefix"]) == 0
    assert float(m["loss"]) == float(wide["loss"])
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(wide_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-6 * max(
                                       1.0, float(np.abs(b).max())))

    monkeypatch.undo()
    obs.configure(_Sink(), role="miner")
    try:
        loop = MinerLoop(engine, InMemoryTransport(), "m0",
                         send_interval=1e9, check_update_interval=1e9)
        loop.bootstrap(params=params)
        loop.run(iter([batch, batch]))
        # the first step's, at least: a step at 5e-4 against a bias of 10
        # moves no choice
        assert obs.registry().peek(
            "train.moe.rows_past_prefix").value == 2 * 4 * 64
        # and every layer-step of the two left the fast path
        assert obs.registry().peek(
            "train.moe.layers_past_prefix").value == 2 * 4
    finally:
        obs.reset()


def test_the_step_counts_the_block_pairs_its_attention_runs(tiny):
    """Where the flash kernels take their block tables from the rows'
    segment ids (two heads of 64 over one K/V head and three blocks of 128
    a row here, the kernels interpreted), the step returns how many block pairs ran and how
    many the causal mask alone would have run, and `MinerLoop` counts them
    with the routed layers' rows. Their ratio is the brute-force share of
    the batch's own [T, T] mask."""
    from distributedtraining_tpu.ops import flash_attention as fl
    _, pc, _, _, _ = tiny
    T, block = 128 * (fl.TABLE_MIN_BLOCKS + 1), 128
    two_heads = dataclasses.replace(pc, hidden_size=128,
                                    num_attention_heads=2,
                                    num_key_value_heads=1)
    model, _ = lf.make_model(two_heads)
    rng = np.random.default_rng(11)
    rows = []
    for _ in range(2):
        lens = (40 * (1.0 + rng.pareto(1.2, T // 40))).astype(np.int64)
        lens = lens[:np.searchsorted(np.cumsum(lens), T) + 1]
        lens[-1] -= lens.sum() - T
        rows.append([int(n) for n in lens])
    batch = _packed_batch(pc, rows)
    n = T // block
    want = 0
    for seg in batch["segment_ids"]:
        allowed = np.tril(seg[:, None] == seg[None, :])
        want += int(allowed.reshape(n, block, n, block).any((1, 3)).sum())
    engine = TrainEngine(model)
    params = model.init_params(jax.random.PRNGKey(0))
    run, causal = lf.ATTN_COUNTERS

    fl.use_interpret(True)
    obs.configure(_Sink(), role="miner")
    try:
        _, m = engine.train_step(engine.init_state(params=params), batch)
        loop = MinerLoop(engine, InMemoryTransport(), "m0",
                         send_interval=1e9, check_update_interval=1e9)
        loop.bootstrap(params=params)
        loop.run(iter([batch, batch]))
        counted = {k: obs.registry().peek(k).value for k in (run, causal)}
    finally:
        obs.reset()
        fl.use_interpret(False)
    assert counted == {run: 2 * want, causal: 2 * int(m[causal])}
    assert set(m) == {"loss", "tokens", *lf.TRAIN_COUNTERS.values(),
                      *lf.ATTN_COUNTERS}
    assert np.isfinite(float(m["loss"]))
    assert int(m[causal]) == 2 * n * (n + 1) // 2
    assert int(m[run]) == want and 0 < want < int(m[causal])
    # off the kernel's path (this lane's default) the step counts rows only
    plain = TrainEngine(model)
    _, m = plain.train_step(plain.init_state(params=params), batch)
    assert set(m) == {"loss", "tokens", *lf.TRAIN_COUNTERS.values()}


def test_a_delta_goes_through_the_memory_transport_and_applies(tiny):
    """What the fleet plane does with the family's tree today (ROADMAP M2):
    a push from `MinerLoop`, the fetch a validator makes, the delta applied
    to the base: the expert stacks travel as any leaf, the buffer's delta
    is empty."""
    from distributedtraining_tpu import delta as delta_lib
    model, pc, params, _, _ = tiny
    engine = TrainEngine(model)
    transport = InMemoryTransport()
    loop = MinerLoop(engine, transport, "m0", send_interval=1e9,
                     check_update_interval=1e9)
    loop.bootstrap(params=params)
    batch = _packed_batch(pc, [[20, 28], [48]], seed=12)
    loop.run(iter([batch, batch]))
    trained = jax.tree_util.tree_map(np.asarray, loop.state.params)
    loop.flush()
    assert loop.report.pushes == 1 and loop.report.pushes_failed == 0
    template = jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape, x.dtype), params)
    delta = transport.fetch_delta("m0", template)
    assert delta is not None
    assert delta_lib.shapes_match(delta, params)
    assert not delta_lib.has_nonfinite(delta)
    for i in range(1, 5):
        assert not np.asarray(delta[f"layer_{i}"]["expert_bias"]).any()
        assert np.asarray(delta[f"layer_{i}"]["experts_in"]).any()
    applied = delta_lib.apply_delta(params, delta)
    for a, b in zip(jax.tree_util.tree_leaves(applied),
                    jax.tree_util.tree_leaves(trained)):
        np.testing.assert_allclose(np.asarray(a), b, rtol=0, atol=1e-6)
    ok, why = delta_lib.screen_delta(delta, params)
    assert ok, why


def test_the_serve_engine_refuses_the_family_with_the_sentence(tiny):
    from distributedtraining_tpu.engine import kv_pool, serve
    model, pc, params, _, _ = tiny
    reason = kv_pool.unheld_cache_reason(pc)
    assert "'conv'" in reason and "no pool" in reason
    with pytest.raises(ValueError) as err:
        serve.GenerationEngine(model, params, max_slots=2, page_size=8,
                               max_seq_len=64, max_new_tokens=8)
    assert str(err.value) == reason
    from distributedtraining_tpu.models import gpt2, nemotron_h
    assert kv_pool.unheld_cache_reason(gpt2.PRESETS["tiny"]) is None
    assert kv_pool.unheld_cache_reason(
        nemotron_h.PRESETS["tiny-nemotron-h"]) is None
