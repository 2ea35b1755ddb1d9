"""Paged-attention decode kernel numerics on the real chip.

The on-device half of tests/test_paged_attention.py (whose kernel cases
run interpreted under the CPU-forcing conftest): the REAL Mosaic
lowering — scalar-prefetched page tables driving per-page DMA, VMEM
scratch persistence across the streaming grid — against the XLA
reference at serving shapes, plus the engine-level greedy parity that
the serving plane's correctness contract rests on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import paged_attention as pa


def _case(B, Hq, Hkv, D, P, MP, lens, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    pool = 1 + B * MP
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), dtype)
    # one layer of the pool as the engine stores it: [pages, P, Hkv*D]
    kp = jnp.asarray(rng.standard_normal((pool, P, Hkv * D)), dtype)
    vp = jnp.asarray(rng.standard_normal((pool, P, Hkv * D)), dtype)
    kn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), dtype)
    vn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), dtype)
    pt = jnp.asarray(rng.integers(1, pool, (B, MP)), jnp.int32)
    sl = jnp.asarray(lens, jnp.int32)
    return q, kp, vp, pt, sl, kn, vn


def _reference(args):
    """The XLA twin with true-f32 matmuls: the TPU default runs an f32
    einsum as one bf16 pass (~1e-3), which is the reference's error, not
    the kernel's (its dots run at HIGHEST)."""
    with jax.default_matmul_precision("highest"):
        return pa.paged_decode_reference(*args)


@pytest.mark.parametrize("shape", [
    (4, 8, 2, 64, 16, 8, [13, 127, 64, 1]),     # llama GQA, ragged
    (2, 4, 4, 64, 16, 8, [0, 128]),             # MHA, boundary lengths
    (8, 8, 2, 128, 16, 16, [100] * 8),          # D=128, multi-chunk
    (8, 12, 12, 64, 16, 64, [5, 37, 200, 1000, 0, 16, 511, 1023]),  # gpt2
])
def test_kernel_matches_reference_on_chip(shape):
    B, Hq, Hkv, D, P, MP, lens = shape
    args = _case(B, Hq, Hkv, D, P, MP, lens)
    assert pa.kernel_supports(args[0], args[1])
    out = pa.paged_decode_attention(*args)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_reference(args), np.float32),
                               atol=2e-5)


def test_model_entry_selects_kernel_on_tpu():
    """``paged_attention`` (what the models call) lowers to the Mosaic
    kernel at a supported shape and to plain XLA at an unsupported one —
    selection by shape, visible in the lowered text."""
    args = _case(2, 4, 4, 64, 16, 8, [7, 90])
    assert "tpu_custom_call" in jax.jit(pa.paged_attention).lower(
        *args).as_text()
    small = _case(2, 4, 4, 16, 16, 8, [7, 90])      # Hkv*D = 64 lanes
    assert "tpu_custom_call" not in jax.jit(pa.paged_attention).lower(
        *small).as_text()


def test_kernel_bf16_pages():
    """Production serving dtype: bf16 pages, fp32 softmax inside the
    kernel (flash-kernel tolerance, not f32 parity)."""
    args = _case(4, 8, 2, 64, 16, 8, [50, 3, 120, 77], dtype=jnp.bfloat16)
    out = pa.paged_decode_attention(*args)
    ref = pa.paged_decode_reference(*args)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


# Hq, Hkv, D, window of the cells that run the kernel (gpt2-large, nemotron,
# solar, trinity's global and sliding layers)
SERVED = [(20, 20, 64, None), (32, 2, 128, None), (64, 8, 128, None),
          (32, 4, 128, None), (32, 4, 128, 300)]


@pytest.mark.parametrize("Hq, Hkv, D, window", SERVED)
def test_kernel_is_its_twin_at_the_served_head_shapes_bf16(Hq, Hkv, D,
                                                           window):
    """The four served head shapes on bfloat16 pools, as every cell holds
    them: operands in the pool's dtype, float32 sums; the twin rounds the
    normalised probabilities, the kernel the unnormalised ones. Lengths: a
    dead row, 1, a page's edge, a chunk's edge - 1 / at / + 1, two chunks
    and one, and a short context under a table of 136 pages."""
    chunk = 16 * pa._chunk_pages(136, 16, Hkv * D, jnp.bfloat16)
    lens = [0, 1, 16, chunk - 1, chunk, chunk + 1, 2 * chunk + 1, 40]
    args = _case(len(lens), Hq, Hkv, D, 16, 136, lens, seed=Hq,
                 dtype=jnp.bfloat16)
    kw = {} if window is None else {"window": window}
    out = jax.jit(lambda *a: pa.paged_decode_attention(*a, **kw))(*args)
    ref = jax.jit(lambda *a: pa.paged_decode_reference(*a, **kw))(*args)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=4e-2)


def test_engine_greedy_parity_on_chip():
    """The serving contract on real hardware: engine decode (kernel
    path — Hkv*D = 128 fills a lane tile) token-identical to the
    full-recompute oracle, true-f32 matmuls on both sides."""
    from distributedtraining_tpu.engine.serve import (GenerationEngine,
                                                      reference_generate)
    from distributedtraining_tpu.models import gpt2

    model, cfg = gpt2.make_model(gpt2.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=128, n_layer=2, n_head=2,
        dtype="float32", vocab_multiple=64))
    params = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=n))
               for n in (5, 11)]
    with jax.default_matmul_precision("highest"):
        eng = GenerationEngine(model, params, max_slots=2, page_size=16)
        try:
            got = eng.generate(prompts, 8)
            assert got == [reference_generate(model, params, p, 8)
                           for p in prompts]
            k_pages, v_pages = eng._kv
            for key, prog in eng._decode_progs.items():
                text = prog.lower(
                    eng._params, k_pages, v_pages,
                    np.zeros(key, np.int32), np.zeros(key[:1], np.int32),
                    np.zeros(key[:1], np.int32)).as_text()
                assert "tpu_custom_call" in text, \
                    f"decode bucket {key} ran the XLA twin"
        finally:
            eng.close()
