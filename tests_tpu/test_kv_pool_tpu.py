"""The serving KV pool on the real chip's compiler, at `serve-large-chat`'s
geometry: 36 layers, 2,049 pages of 16, 20 heads of 64, bfloat16.

tests/test_kv_pool.py holds the programs' STRUCTURE on the CPU (jaxpr and
donation). What only XLA:TPU decides is whether it then updates the
pool in place or lays a layer out anew: the copies this pool replaced
were 64% of the device's time and a second pool of memory. Nothing is
allocated or run here: abstract arguments, compiled, read.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import serve, serve_weights
from distributedtraining_tpu.models import gpt2

SLOTS, P, SEQ = 32, 16, 1024


@pytest.fixture(scope="module")
def large():
    model, cfg = gpt2.make_model(gpt2.PRESETS["gpt2-774m"])
    # what the programs take: the float32 base's serving tree
    params = serve_weights.abstract(cfg, jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=8)))
    eng = serve.GenerationEngine(model, None, max_slots=SLOTS, page_size=P,
                                 max_seq_len=SEQ)
    assert eng._donate, "not a TPU backend"
    eng._layers = serve._layer_keys(params)
    layer = jax.ShapeDtypeStruct(
        (eng.pool_pages, P, cfg.n_head * cfg.head_dim), jnp.bfloat16)
    half = (layer,) * cfg.n_layer
    assert (eng.pool_pages, cfg.n_layer) == (2049, 36)
    yield eng, params, half, layer
    eng.close()


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _programs(eng, params, half):
    mp = SEQ // P
    return {
        "decode_32x64": (eng._decode_prog(SLOTS, mp), (
            params, half, half, _i32(SLOTS, mp), _i32(SLOTS),
            _i32(SLOTS))),
        "prefill_1024": (eng._prefill_prog(SEQ), (
            params, _i32(1, SEQ), _i32(), half, half, _i32(mp))),
        "page_copy": (eng._page_copy_prog(), (half, half, _i32(), _i32())),
    }


@pytest.mark.parametrize("name", ["decode_32x64", "prefill_1024",
                                  "page_copy"])
def test_compiled_program_updates_the_pool_in_place(large, name):
    eng, params, half, layer = large
    prog, args = _programs(eng, params, half)[name]
    compiled = prog.lower(*args).compile()
    text = compiled.as_text()
    layer_elems = int(np.prod(layer.shape))
    pool_bytes = 2 * len(half) * layer_elems * layer.dtype.itemsize

    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 4, (
        f"{name}: {mem.temp_size_in_bytes / 2**30:.2f} GiB of temporaries "
        f"beside a {pool_bytes / 2**30:.2f} GiB pool")

    # every pool parameter is aliased to an output
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert aliases and len(re.findall(r"\(\d+, \{\}", aliases.group(1))) \
        == 2 * len(half)

    # nothing as large as a layer is made other than by the update
    # into that layer (`scatter`, `dynamic-update-slice`, or the fusion
    # that holds one); least of all a `copy`
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if not m or not m.group(1):
            continue
        elems = int(np.prod([int(d) for d in m.group(1).split(",")]))
        op = m.group(2)
        if elems < layer_elems or op in ("parameter", "get-tuple-element",
                                         "tuple", "bitcast"):
            continue
        if elems == layer_elems:
            assert op in ("scatter", "dynamic-update-slice", "fusion"), line
        else:
            # the tied embedding's cast and the prefill's logits are
            # larger than a layer and are the model's, not the pool's
            assert op != "copy" or "2049" not in m.group(1), line
    if name == "decode_32x64":
        assert text.count("tpu_custom_call") == len(half)


# ---------------------------------------------------------------------------
# the latent pool (models/deepseek_v3.py), at `serve-kanana-chat`'s geometry:
# 8 layers, 16,385 pages of 16, rows of 512 (the latent) and 128 (the rotary
# key's 64 in one lane tile), bfloat16, beside 9.44 GiB of weights
# ---------------------------------------------------------------------------

K_SLOTS, K_SEQ, K_PREFILL = 64, 4096, 2048


@pytest.fixture(scope="module")
def kanana():
    from distributedtraining_tpu.engine import kv_pool
    from distributedtraining_tpu.models import deepseek_v3
    model, cfg = deepseek_v3.make_model("kanana-2-30b-a3b-l8")
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=8))
    eng = serve.GenerationEngine(model, None, max_slots=K_SLOTS, page_size=P,
                                 max_seq_len=K_SEQ)
    assert eng._donate, "not a TPU backend"
    eng._layers = serve._layer_keys(params)
    assert (eng.pool_pages, len(eng._layers)) == (16385, 8)
    halves = tuple(
        (jax.ShapeDtypeStruct((eng.pool_pages, P, w), jnp.bfloat16),) * 8
        for w in kv_pool.row_widths(cfg))
    assert [h[0].shape[-1] for h in halves] == [512, 128]
    yield eng, params, halves
    eng.close()


@pytest.mark.parametrize("name", ["decode_64x256", "prefill_2048"])
def test_latent_pool_is_updated_in_place(kanana, name):
    eng, params, (c_half, kr_half) = kanana
    mp = K_SEQ // P
    prog, args = {
        "decode_64x256": (eng._decode_prog(K_SLOTS, mp), (
            params, c_half, kr_half, _i32(K_SLOTS, mp), _i32(K_SLOTS),
            _i32(K_SLOTS))),
        "prefill_2048": (eng._prefill_prog(K_PREFILL), (
            params, _i32(1, K_PREFILL), _i32(), c_half, kr_half,
            _i32(K_PREFILL // P))),
    }[name]
    compiled = prog.lower(*args).compile()
    text = compiled.as_text()
    pool_bytes = sum(int(np.prod(h[0].shape)) * 2 * len(h)
                     for h in (c_half, kr_half))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # the prefill's 1.05 GB of float32 logits for all 2,048 positions is
    # the model's temporary, not the pool's (PERF.md section 7)
    assert mem.temp_size_in_bytes < pool_bytes / 2
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert aliases and len(re.findall(r"\(\d+, \{\}", aliases.group(1))) \
        == 16
    # nothing of a pool layer's shape is made but by the update into it
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[16385,16,(?:512|128)\]"
                     r"\S* ([\w\-]+)\(", line)
        if m:
            assert m.group(1) in ("scatter", "dynamic-update-slice", "fusion",
                                  "parameter", "get-tuple-element",
                                  "bitcast"), line
    if name == "decode_64x256":
        # the latent kernel in each of 8 layers, the grouped expert
        # product twice in each of 7
        assert text.count("tpu_custom_call") == 8 + 14
