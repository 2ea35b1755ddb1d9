"""Blockwise (portable lax-flash) attention: parity vs dense, dead-row
semantics, fallback routing. See ops/attention.py::blockwise_attention —
the memory-honest fallback when the Pallas flash kernel declines, and the
spelling the AOT scale artifacts compile (scripts/scale_aot.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops.attention import (
    BLOCKWISE_FALLBACK_LEN, blockwise_attention, causal_attention,
    combine_masks, dot_product_attention, make_causal_mask)

B, T, H, D = 2, 200, 4, 16


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    am = jnp.asarray(rng.integers(0, 2, (B, T)).astype(np.float32))
    am = am.at[:, 0].set(1)
    seg = jnp.asarray(np.sort(rng.integers(0, 3, (B, T)), axis=1), jnp.int32)
    return q, k, v, am, seg


@pytest.mark.parametrize("masks", ["none", "pad", "seg", "pad+seg"])
def test_blockwise_matches_dense(qkv, masks):
    """Forward and gradient parity vs the dense reference on every mask
    combination, with non-divisible block sizes (T=200, bq=64, bkv=48
    exercises both padding paths). Rows with no visible key (possible
    under pad+seg) emit exact 0 — the flash-kernel convention — and are
    excluded from the parity comparison (dense emits uniform garbage
    there; the data pipeline excludes such tokens from the loss)."""
    q, k, v, am, seg = qkv
    kwargs = {}
    if "pad" in masks:
        kwargs["attention_mask"] = am
    if "seg" in masks:
        kwargs["segment_ids"] = seg
    full = combine_masks(make_causal_mask(T), kwargs.get("attention_mask"),
                         kwargs.get("segment_ids"))
    ref = dot_product_attention(q, k, v, full)
    out = blockwise_attention(q, k, v, block_q=64, block_kv=48, **kwargs)
    alive = np.asarray(full.any(axis=-1))            # [B, H, Tq]
    alive_bthd = np.broadcast_to(
        alive.transpose(0, 2, 1)[..., None], out.shape)
    assert np.abs(np.asarray(out) - np.asarray(ref))[alive_bthd].max() < 2e-5
    dead = np.abs(np.asarray(out))[~alive_bthd]
    assert dead.size == 0 or dead.max() == 0

    alive_f = jnp.asarray(alive_bthd, jnp.float32)
    g_ref = jax.grad(lambda q_: ((dot_product_attention(q_, k, v, full)
                                  * alive_f) ** 2).sum())(q)
    g_new = jax.grad(lambda q_: ((blockwise_attention(
        q_, k, v, block_q=64, block_kv=48, **kwargs) * alive_f) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g_new), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_fallback_routes_by_length(qkv, monkeypatch):
    """On backends where the Pallas kernel declines, impl='flash' falls
    back to blockwise at long T (dense [T, T] temps would explode) and
    dense at short T (faster, tiny temps)."""
    from distributedtraining_tpu.ops import attention as attn
    q, k, v, am, seg = qkv
    calls = []
    monkeypatch.setattr(attn, "blockwise_attention",
                        lambda *a, **kw: calls.append("block") or
                        blockwise_attention(*a, **kw))
    # force the kernel to decline regardless of backend
    import distributedtraining_tpu.ops.flash_attention as fa
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: None)

    short = causal_attention(q, k, v, impl="flash")
    assert calls == []  # T=200 < threshold: dense fallback
    tlong = BLOCKWISE_FALLBACK_LEN
    rng = np.random.default_rng(1)
    ql = jnp.asarray(rng.normal(size=(1, tlong, 2, 8)), jnp.float32)
    causal_attention(ql, ql, ql, impl="flash")
    assert calls == ["block"]
    # and the explicit impl works at any length
    causal_attention(q, k, v, impl="blockwise")
    assert calls == ["block", "block"]
    assert short.shape == (B, T, H, D)


# -- a window -----------------------------------------------------------------

def _windowed_by_definition(q, k, v, window, attention_mask=None):
    T, D = q.shape[1], q.shape[-1]
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = ((j <= i) & (j > i - window))[None, None]
    if attention_mask is not None:
        mask = mask & attention_mask[:, None, None, :].astype(bool)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("impl", ["dense", "blockwise"])
@pytest.mark.parametrize("window", [1, 7, 64, 65, 500])
def test_window_is_the_newest_keys_up_to_the_query(impl, window):
    """T = 200 in blocks of 512 -> one block; `blockwise_attention` with
    blocks of 64 is covered below. Position i sees (i - window, i]."""
    from distributedtraining_tpu.ops.attention import causal_attention
    rng = np.random.default_rng(window)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 200, 2, 16)), jnp.float32)
               for _ in range(3))
    amask = jnp.asarray(rng.random((2, 200)) > 0.1, jnp.int32
                        ).at[:, 0].set(1)
    want = _windowed_by_definition(q, k, v, window, amask)
    # a row none of whose window's keys is real has no defined output
    i, j = np.arange(200)[:, None], np.arange(200)[None, :]
    seen = ((j <= i) & (j > i - window))[None] & np.asarray(
        amask, bool)[:, None, :]
    live = seen.any(-1)[..., None, None]
    got = causal_attention(q, k, v, attention_mask=amask, impl=impl,
                           window=window)
    blocks = blockwise_attention(q, k, v, attention_mask=amask, block_q=64,
                                 block_kv=64, window=window)
    for out in (got, blocks):
        np.testing.assert_allclose(np.asarray(out) * live,
                                   np.asarray(want) * live, atol=2e-5)


@pytest.mark.parametrize("impl", ["flash", "ring"])
def test_kernels_with_no_window_refuse_one(impl):
    from distributedtraining_tpu.ops.attention import causal_attention
    x = jnp.zeros((1, 8, 1, 8))
    with pytest.raises(ValueError, match="has no window"):
        causal_attention(x, x, x, impl=impl, window=4)


@pytest.mark.parametrize("impl", ["dense", "blockwise"])
def test_window_none_lowers_to_what_stood(impl):
    from distributedtraining_tpu.ops.attention import causal_attention
    x = jnp.zeros((1, 64, 2, 8))
    plain = jax.jit(lambda a: causal_attention(a, a, a, impl=impl))
    none = jax.jit(lambda a: causal_attention(a, a, a, impl=impl,
                                              window=None))
    assert plain.lower(x).as_text() == none.lower(x).as_text()
