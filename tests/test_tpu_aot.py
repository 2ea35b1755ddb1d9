"""Compile for the chip without one: the DeepSeek-V3 family's two Pallas
kernels at kanana-2's published widths and the decode bucket's shapes,
through the TPU's own compiler against a DESCRIBED v5e (nothing runs, no
chip is needed). Interpret mode cannot show what this does: Mosaic refused
the first latent kernel here for a 64-lane page slice, which is why the
pool stores the rotary key in a whole lane tile (engine/kv_pool.py).

All such compiles live in this one file: only the worker that runs it
loads the TPU's library, inside a fixture, after collection."""

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
