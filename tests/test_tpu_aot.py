"""Compile for the chip without one: the DeepSeek-V3 family's two Pallas
kernels at kanana-2's published widths and the decode bucket's shapes, and
the train step's causal attention kernels at the train cell's shape,
through the TPU's own compiler against a DESCRIBED v5e (nothing runs, no
chip is needed). Interpret mode cannot show what this does: Mosaic refused
the first latent kernel here for a 64-lane page slice, which is why the
pool stores the rotary key in a whole lane tile (engine/kv_pool.py).

All such compiles live in this one file: only the worker that runs it
loads the TPU's library, inside a fixture, after collection."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """An AOT result cannot be read back from the persistent cache
    without a chip: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("slots, pages", [(64, 256), (8, 16)])
def test_latent_decode_kernel_compiles_at_published_widths(one_chip, slots,
                                                           pages):
    from distributedtraining_tpu.ops import mla_attention as mla

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = 1 + slots * pages
    args = (sds((slots, 1, 32, 512)), sds((slots, 1, 32, 64)),
            sds((pool, 16, 512)), sds((pool, 16, 128)),
            sds((slots, pages), jnp.int32), sds((slots,), jnp.int32),
            sds((slots, 1, 512)), sds((slots, 1, 64)))
    assert mla.kernel_supports(*args[:4])
    compiled = _compile(
        lambda *a: mla.mla_decode_attention(*a, 192 ** -0.5), *args)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "mla_decode_attention" in text
    # the pool is read as it lies: nothing pool-sized is made
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24


@pytest.mark.parametrize("rows", [48, 384, 12288])
def test_grouped_expert_product_compiles_at_published_widths(one_chip, rows,
                                                             monkeypatch):
    from distributedtraining_tpu.ops import moe
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda x, gu, d, s: moe._experts_sorted(x, gu, d, s, None),
        sds((rows, 2048)), sds((128, 2048, 1536)), sds((128, 768, 2048)),
        sds((128,), jnp.int32))
    text = compiled.as_text()
    # gate+up fused and down: two Mosaic calls, named for the trace reader
    assert text.count("tpu_custom_call") == 2
    assert "%gmm" in text


@pytest.mark.parametrize("packed", [True, False])
def test_causal_attention_gradient_compiles_at_the_train_cells_shape(
        one_chip, monkeypatch, packed):
    """`train-large-t1024`: [4, 1024, 20, 64] bfloat16, packed documents.
    The gradient holds two Mosaic calls (the forward that keeps its
    log-sum-exp, and ONE fused backward), and the softmax statistics never
    lie in HBM broadcast 128 or more lanes wide per (row, head)."""
    from distributedtraining_tpu.ops import flash_attention as fl
    monkeypatch.setattr(fl, "_on_tpu", lambda: True)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, w, seg):
        out = fl.flash_attention(q, k, v, segment_ids=seg)
        return jnp.sum(out.astype(jnp.float32) * w)

    qkv = sds((4, 1024, 20, 64))
    seg = sds((4, 1024), jnp.int32) if packed else None
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv,
                        sds((4, 1024, 20, 64), jnp.float32), seg)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    tag = "_segmented" if packed else ""
    # the names the device-trace readers find the kernels by
    assert f"%flash_mha_fwd{tag}_residuals" in text
    assert f"%flash_mha_dkv{tag}_no_residuals" in text
    wide = re.findall(r"= f32\[4,20,1024,(\d+)\]\S* broadcast\(", text)
    assert not [n for n in wide if int(n) >= 128], wide
    # forward alone (the validator's eval, remat's first pass): one call
    fwd = _compile(lambda q, k, v, seg: fl.flash_attention(
        q, k, v, segment_ids=seg), qkv, qkv, qkv, seg)
    assert fwd.as_text().count("tpu_custom_call") == 1
