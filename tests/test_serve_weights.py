"""The serving tree (engine/serve_weights.py): what the serve programs
take as their first argument is made once per revision, rounded exactly
where the forward rounds first, so every number is the one the float32
base gives, bit for bit, for every family the engine serves.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import kv_pool, serve_weights
from distributedtraining_tpu.engine.serve import (BaseRevisionWatcher,
                                                  GenerationEngine,
                                                  _layer_keys,
                                                  host_param_template,
                                                  reference_generate)
from distributedtraining_tpu.engine.speculative import DraftEngine
from distributedtraining_tpu.models import deepseek_v3, gpt2, llama
from distributedtraining_tpu.transport import InMemoryTransport
from distributedtraining_tpu.utils import obs

# float32 parameters, bfloat16 compute: what the GPT-2 cells state
CONFIGS = {
    "gpt2": (gpt2, gpt2.PRESETS["tiny"]),
    "llama": (llama, llama.PRESETS["tiny-llama"]),
    "deepseek_v3": (deepseek_v3, dataclasses.replace(
        deepseek_v3.PRESETS["tiny-kanana"], dtype="bfloat16")),
}
FAMILIES = sorted(CONFIGS)
P = 8          # page size
PROMPT = [3, 17, 200, 5, 9, 41, 77, 2, 130, 8, 19]


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    module, cfg = CONFIGS[request.param]
    model, cfg = module.make_model(cfg)
    base = model.init_params(jax.random.PRNGKey(1))
    return request.param, model, cfg, base


@pytest.fixture()
def sink():
    class _Sink:
        def log(self, rec, **kw):
            pass

    obs.configure(_Sink(), role="server")
    try:
        yield obs.registry()
    finally:
        obs.reset()


def _paths(tree):
    return {tuple(k.key for k in path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _assert_rounded_where_stated(cfg, tree):
    for path, leaf in _paths(tree).items():
        if cfg.rounds_first(path):
            assert leaf.dtype == cfg.compute_dtype(), path


def _prefill_and_decode_logits(model, cfg, tree):
    """A 16-token prefill's logits, its cache rows written to a pool as
    the engine writes them, then one decode step's logits over the
    paged cache."""
    layers = _layer_keys(tree)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 2 * P), 0,
                             cfg.vocab_size)
    pre, muts = model.apply(
        {"params": tree}, ids, attention_mask=jnp.ones_like(ids),
        sow_kv=True, mutable=["intermediates"])
    k_pages, v_pages = kv_pool.make_pool(
        len(layers), 4, P, kv_pool.row_widths(cfg), cfg.compute_dtype())
    k_pages, v_pages = kv_pool.write_pages(
        k_pages, v_pages, muts["intermediates"], layers,
        jnp.asarray([1, 2]))
    dec, _ = model.apply(
        {"params": tree}, jnp.asarray([[7]]),
        position_ids=jnp.asarray([[2 * P]]),
        kv_pages=tuple(zip(k_pages, v_pages)),
        page_tables=jnp.asarray([[1, 2, 3]]),
        kv_lens=jnp.asarray([2 * P]), sow_kv=True,
        mutable=["intermediates"])
    return np.asarray(pre), np.asarray(dec)


def test_rounds_what_the_family_states_and_nothing_else(family):
    name, model, cfg, base = family
    tree = serve_weights.make(cfg, base)
    before, after = _paths(base), _paths(tree)
    for path, leaf in before.items():
        want = cfg.compute_dtype() if cfg.rounds_first(path) else leaf.dtype
        assert after[path].dtype == want, path
        if want == leaf.dtype:
            assert after[path] is leaf, path      # the same array
    extra = set(after) - set(before)
    if name == "gpt2":
        # the tied head's operand: wte rounded, a leaf of its own; the
        # lookup's tables and every LayerNorm stay float32
        assert extra == {("lm_head",)}
        assert np.array_equal(
            np.asarray(tree["lm_head"]),
            np.asarray(base["wte"].astype(jnp.bfloat16)))
        assert tree["wte"] is base["wte"] and tree["wpe"] is base["wpe"]
        assert after[("h_0", "ln_1", "scale")].dtype == jnp.float32
        assert after[("h_0", "c_attn", "bias")].dtype == jnp.bfloat16
    else:
        assert not extra
    assert serve_weights.nbytes(tree) < 0.8 * serve_weights.nbytes(base)


def test_prefill_and_decode_logits_equal_the_float32_bases(family):
    _, model, cfg, base = family
    tree = serve_weights.make(cfg, base)
    for got, want in zip(_prefill_and_decode_logits(model, cfg, tree),
                         _prefill_and_decode_logits(model, cfg, base)):
        assert got.dtype == np.float32 and np.isfinite(want).all()
        assert np.array_equal(got, want)          # bit for bit


def test_engine_tokens_equal_the_reference_on_the_float32_base(family):
    _, model, cfg, base = family
    eng = GenerationEngine(model, base, max_slots=2, page_size=P,
                           max_seq_len=64)
    try:
        _assert_rounded_where_stated(cfg, eng._params)
        req = eng.submit(PROMPT, 10)
        while not req.done_evt.is_set():
            eng.step()
        assert req.tokens == reference_generate(model, base, PROMPT, 10)
    finally:
        eng.close()


def test_a_tree_in_the_compute_dtype_passes_through(family, sink):
    """``kanana-2-30b-a3b-l8`` holds bfloat16 parameters: the same
    arrays come back, nothing is counted as rounded, and the decode
    program is lowered to the text the base itself gives."""
    name, _, cfg, _ = family
    module, _ = CONFIGS[name]
    model, cfg = module.make_model(dataclasses.replace(
        cfg, param_dtype=cfg.dtype))
    base = jax.device_put(model.init_params(jax.random.PRNGKey(1)))
    tree = serve_weights.make(cfg, base)
    assert jax.tree_util.tree_structure(tree) == \
        jax.tree_util.tree_structure(base)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(tree),
                                      jax.tree_util.tree_leaves(base)))
    assert sink.counter("serve.weights.rounded_leaves").value == 0
    assert sink.gauge("serve.weights.bytes").value == \
        serve_weights.nbytes(base)
    eng = GenerationEngine(model, base, max_slots=2, page_size=P,
                           max_seq_len=64)
    try:
        k_pages, v_pages = eng._kv
        rest = (k_pages, v_pages, np.zeros((2, 4), np.int32),
                np.zeros((2,), np.int32), np.zeros((2,), np.int32))
        prog = eng._decode_prog(2, 4)
        assert prog.lower(eng._params, *rest).as_text() == \
            prog.lower(base, *rest).as_text()
    finally:
        eng.close()


def test_a_serving_tree_handed_in_again_is_itself(family):
    _, _, cfg, base = family
    tree = serve_weights.make(cfg, base)
    again = serve_weights.make(cfg, tree)
    assert set(again) == set(tree)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(again),
                                      jax.tree_util.tree_leaves(tree)))


def test_a_staged_revision_is_rounded_on_the_watchers_thread(family, sink):
    """The watcher the engine holds stages the serving tree itself, so
    the swap binds it as it is: one observation of the preparation per
    install and per staging, none at the swap, whose stall is the
    rebind and the prefix cache's flush, as before."""
    _, model, cfg, base = family
    base2 = model.init_params(jax.random.PRNGKey(7))
    tr = InMemoryTransport()
    watcher = BaseRevisionWatcher(tr, lambda: host_param_template(model),
                                  poll_s=999.0)
    eng = GenerationEngine(model, base, revision="r1", watcher=watcher,
                           max_slots=2, page_size=P, max_seq_len=64,
                           prefix_cache=True)
    try:
        first = eng.submit(PROMPT, 4)
        while not first.done_evt.is_set():
            eng.step()
        prepared = sink.histogram("serve.weights.prepare_ms")
        assert prepared.count == 1
        rev2 = tr.publish_base(base2)
        t = threading.Thread(target=watcher.poll_once, name="serve-watch")
        t.start()
        t.join()
        assert prepared.count == 2
        staged = watcher._pending[1]
        _assert_rounded_where_stated(cfg, staged)
        eng.step()                                # idle: the swap lands
        assert eng.revision == rev2 and eng._params is staged
        assert prepared.count == 2                # the swap made nothing
        assert sink.histogram("serve.swap_stall_ms").count == 1
        assert sink.counter("serve.prefix_flushes").value == 1
        req = eng.submit(PROMPT, 6)
        while not req.done_evt.is_set():
            eng.step()
        assert req.tokens == reference_generate(model, base2, PROMPT, 6)
    finally:
        eng.close()


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_the_drafter_takes_the_same_path(name, sink):
    """``DraftEngine`` binds its base's serving tree at install and, with
    a watcher, stages it on that watcher's thread; the latent cache of
    the third family is refused by the speculative lane as before."""
    module, cfg = CONFIGS[name]
    model, cfg = module.make_model(cfg)
    base = model.init_params(jax.random.PRNGKey(3))
    tr = InMemoryTransport()
    watcher = BaseRevisionWatcher(tr, lambda: host_param_template(model),
                                  poll_s=999.0)
    draft = DraftEngine(model, base, max_slots=2, page_size=P,
                        max_seq_len=64, watcher=watcher)
    try:
        want = _paths(serve_weights.make(cfg, base))
        got = _paths(draft._params)
        assert set(got) == set(want)
        assert all(got[p].dtype == want[p].dtype for p in want)
        tr.publish_base(model.init_params(jax.random.PRNGKey(4)))
        assert watcher.poll_once()
        staged = watcher.take_pending()[1]
        assert all(leaf.dtype == want[p].dtype
                   for p, leaf in _paths(staged).items())
        draft.install_params(staged)
        assert all(a is b for a, b in zip(
            jax.tree_util.tree_leaves(draft._params),
            jax.tree_util.tree_leaves(staged)))
    finally:
        draft.close()


def test_the_avals_for_an_ahead_of_time_compile_are_the_trees(family):
    _, model, cfg, base = family
    tree = serve_weights.make(cfg, base)
    avals = serve_weights.abstract(cfg, jax.eval_shape(lambda: base))
    got, want = _paths(avals), _paths(tree)
    assert set(got) == set(want)
    assert all((got[p].shape, got[p].dtype) == (want[p].shape, want[p].dtype)
               for p in want)
