"""The KV pool's layout (engine/kv_pool.py), held structurally.

Every serve program takes the pool as ``2L`` per-layer arrays in the
stored ``[pages, P, Hkv*D]`` shape, donated, and writes each layer in
place. What must never come back is a copy of the pool (or of a layer)
inside a program: on the chip that copy was 64% of the device's time.
So, for every program family and both attention paths, built with
donation ON: the lowered program aliases all ``2L`` pool arguments to
outputs, and its jaxpr makes nothing as large as the whole pool and
nothing as large as one layer other than the scatter / update into
that layer. The pool here has more pages than ``slots x pages a slot``
so that a gathered context stays smaller than a layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from distributedtraining_tpu.engine import kv_pool, kv_transfer
from distributedtraining_tpu.engine.serve import GenerationEngine
from distributedtraining_tpu.engine.speculative import DraftEngine
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.ops import paged_attention as pa

SLOTS, P, PAGES_PER_SLOT, POOL_PAGES, DRAFT_K = 2, 8, 4, 128, 3

# Hkv*D = 2*64 = 128, a whole lane row: the Pallas kernel's shape.
# Hkv*D = 5*40 = 200, like gpt2-xl's 1600: the XLA gather path.
SHAPES = {"kernel": dict(n_head=2, n_embd=128),
          "xl_like": dict(n_head=5, n_embd=200)}

FAMILIES = ["decode", "decode_sample", "prefill", "prefill_ctx", "verify",
            "page_copy", "kv_adopt", "draft_step", "draft_prefill"]
KERNEL_FAMILIES = {"decode", "decode_sample", "draft_step"}   # Tq == 1


@pytest.fixture(scope="module", params=sorted(SHAPES))
def engines(request):
    cfg = gpt2.GPT2Config(vocab_size=64, n_positions=P * PAGES_PER_SLOT,
                          n_layer=2, dtype="float32", vocab_multiple=64,
                          **SHAPES[request.param])
    model, cfg = gpt2.make_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    geometry = dict(max_slots=SLOTS, page_size=P, pool_pages=POOL_PAGES)
    eng = GenerationEngine(model, params, **geometry)
    draft = DraftEngine(model, params, **geometry)
    # the CPU backend ignores donation, so the engines leave it off
    # here; the programs below are only traced and lowered, never run
    eng._donate = draft._donate = True
    eng.draft_k = DRAFT_K
    try:
        yield request.param, cfg, eng, draft
    finally:
        eng.close()
        draft.close()


def _program(family, cfg, eng, draft):
    """(program, arguments, positions of k_pages and v_pages)."""
    def i32(*shape):
        return np.zeros(shape, np.int32)

    def f32(*shape):
        return np.zeros(shape, np.float32)

    B, MP, T, W = SLOTS, PAGES_PER_SLOT, P * PAGES_PER_SLOT, DRAFT_K + 1
    sample = (f32(B), f32(B), i32(B), i32(B))   # temps, top_ps, seeds, idx
    owner = draft if family.startswith("draft") else eng
    k, v = owner._kv
    params = owner._params
    if family == "decode":
        return eng._decode_prog(B, MP), (
            params, k, v, i32(B, MP), i32(B), i32(B)), 1
    if family == "decode_sample":
        return eng._decode_sample_prog(B, MP), (
            params, k, v, i32(B, MP), i32(B), i32(B), *sample), 1
    if family == "prefill":
        return eng._prefill_prog(T), (
            params, i32(1, T), np.int32(5), k, v, i32(MP)), 3
    if family == "prefill_ctx":
        return eng._prefill_ctx_prog(T // 2, MP), (
            params, i32(1, T // 2), np.int32(9), np.int32(5), k, v,
            i32(1, MP)), 4
    if family == "verify":
        return eng._verify_prog(B, MP), (
            params, k, v, i32(B, MP), i32(B), i32(B, W), i32(B),
            *sample), 1
    if family == "page_copy":
        return eng._page_copy_prog(), (k, v, np.int32(1), np.int32(2)), 0
    if family == "kv_adopt":
        page = np.zeros((cfg.n_layer, P, cfg.n_head, cfg.head_dim),
                        np.float32)
        return kv_transfer.make_adopt_prog(True), (
            k, v, page, page, np.int32(1)), 0
    if family == "draft_step":
        return draft._step_prog(B, MP), (
            params, k, v, i32(B, MP), i32(B), i32(B), *sample), 1
    assert family == "draft_prefill"
    return draft._prefill_prog(T), (
        params, i32(1, T), np.int32(5), k, v, i32(MP)), 3


def _equations(jaxpr, outer_of=None):
    """Every equation, those of nested jaxprs in place of the equation
    that carries them (its outputs are theirs). A ``pallas_call`` is a
    leaf: its body works on blocks in VMEM, not on arrays in HBM.
    ``outer_of``, where given, is filled with the variable of the
    enclosing jaxpr behind each input of a nested ``jit`` (the paged
    decode kernel's call is one, ops/paged_attention.py): an argument
    handed through a ``jit`` is the same array."""
    for eqn in jaxpr.eqns:
        subs = [] if eqn.primitive.name == "pallas_call" else [
            getattr(x, "jaxpr", x)
            for val in eqn.params.values()
            for x in (val if isinstance(val, (tuple, list)) else (val,))
            if isinstance(getattr(x, "jaxpr", x), jex_core.Jaxpr)]
        if subs:
            for sub in subs:
                if outer_of is not None and eqn.primitive.name in (
                        "jit", "pjit"):
                    for inner, outer in zip(sub.invars, eqn.invars):
                        if isinstance(outer, jex_core.Var):   # no literal
                            outer_of[inner] = outer_of.get(outer, outer)
                yield from _equations(sub, outer_of)
        else:
            yield eqn


@pytest.mark.parametrize("family", FAMILIES)
def test_program_writes_each_layer_in_place(engines, family, monkeypatch):
    shape, cfg, eng, draft = engines
    on_kernel = shape == "kernel" and family in KERNEL_FAMILIES
    if on_kernel:
        # kernel selection reads the backend; say TPU, as on the chip
        monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    prog, args, at = _program(family, cfg, eng, draft)
    L = cfg.n_layer
    layer = (POOL_PAGES, P, cfg.n_head * cfg.head_dim)
    k_pages, v_pages = args[at], args[at + 1]
    assert len(k_pages) == len(v_pages) == L
    assert {x.shape for x in k_pages + v_pages} == {layer}

    traced = prog.__wrapped__.trace(*args)
    lowered = traced.lower(
        lowering_platforms=("tpu",) if on_kernel else None)

    # all 2L pool arguments donated, nothing else, and each one aliased
    # to an output of the lowered module
    donated = [jax.tree_util.tree_leaves(a) for a in lowered.args_info[0]]
    for i, leaves in enumerate(donated):
        assert all(x.donated == (i in (at, at + 1)) for x in leaves), i
    assert len(donated[at]) == len(donated[at + 1]) == L
    assert lowered.as_text().count("tf.aliasing_output") == 2 * L

    layer_elems = int(np.prod(layer))
    updates, kernels = 0, 0
    inputs = set(traced.jaxpr.jaxpr.invars)
    outer_of = {}
    for eqn in _equations(traced.jaxpr.jaxpr, outer_of):
        if eqn.primitive.name == "pallas_call":
            kernels += 1
            # the kernel takes a layer as it lies: the program's own
            # argument, not something made from it
            stored = [outer_of.get(x, x) for x in eqn.invars
                      if x.aval.shape == layer]
            assert len(stored) == 2 and set(stored) <= inputs
        for out in eqn.outvars:
            if out.aval.size < layer_elems:
                continue
            assert out.aval.size < 2 * L * layer_elems, (
                f"{eqn.primitive.name} makes a whole pool: {out.aval}")
            assert eqn.primitive.name in ("scatter",
                                          "dynamic_update_slice") \
                and out.aval.shape == layer \
                and eqn.invars[0].aval.shape == layer, (
                    f"{eqn.primitive.name} makes {out.aval.str_short()}, "
                    f"as large as a layer {layer}, and is no update "
                    f"into one")
            updates += 1
    assert updates == 2 * L
    assert kernels == (L if on_kernel else 0)


def test_read_pages_and_adopt_page_round_trip_the_wire_format():
    """The transfer plane's page is ``[L, P, Hkv, D]``; the pool stores
    ``[pages, P, Hkv*D]`` per layer. What `adopt_page` writes,
    `read_pages` reads back, bit for bit, and other pages stay."""
    L, pages, hkv, d = 3, 6, 5, 40
    rng = np.random.default_rng(0)
    pool = kv_pool.make_pool(L, pages, P, (hkv * d, hkv * d), jnp.float32)
    assert len(pool[0]) == len(pool[1]) == L
    assert pool[0][0].shape == (pages, P, hkv * d)
    k_new = rng.standard_normal((L, P, hkv, d)).astype(np.float32)
    v_new = rng.standard_normal((L, P, hkv, d)).astype(np.float32)
    pool = kv_pool.adopt_page(*pool, jnp.asarray(k_new),
                              jnp.asarray(v_new), 4)
    pool = kv_pool.copy_page(*pool, 4, 2)
    k_host, v_host = kv_pool.read_pages(pool, [2, 4, 1], hkv)
    assert k_host.shape == v_host.shape == (L, 3, P, hkv, d)
    for got, want in ((k_host, k_new), (v_host, v_new)):
        np.testing.assert_array_equal(got[:, 0], want)
        np.testing.assert_array_equal(got[:, 1], want)
        assert not got[:, 2].any()
