"""Flash-attention numerics on the real chip: forward AND grad parity vs the
dense oracle at T in {256, 1024}, packed segments included, and at the
train cell's own shape with document boundaries off every block grid.

This is the on-device half of tests/test_flash_attention.py (which pins
the selection rule and the block schedule on CPU). The schedule
(ops/flash_attention.py) rests on these numerics.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops.attention import causal_attention
from distributedtraining_tpu.ops.flash_attention import flash_attention


def _qkv(B=2, T=512, H=4, D=64, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
                 for _ in range(3))


def _segments(B, T, seed=1):
    """Block-constant packing ids, 128-aligned like data/packing.py output."""
    rng = np.random.default_rng(seed)
    seg = np.repeat(rng.integers(0, 3, (B, T // 128)), 128, axis=1)
    return jnp.asarray(np.sort(seg, axis=1), jnp.int32)  # monotone per row


def _documents(B, T, seed=1):
    """Packing ids of documents with Pareto lengths 64..T (shape 1.2) laid
    end to end, as the train cell's traffic packs them: the boundaries
    fall anywhere, not on a 128 grid."""
    rng = np.random.default_rng(seed)
    # T // 64 documents of at least 64 tokens always cover the row
    lens = (64 * (1.0 + rng.pareto(1.2, (B, T // 64)))).astype(np.int64)
    seg = np.stack([np.repeat(np.arange(row.size), row)[:T] for row in lens])
    assert ((np.flatnonzero(np.diff(seg[0])) + 1) % 128 != 0).any()
    return jnp.asarray(seg, jnp.int32)


@pytest.mark.parametrize("T", [256, 1024])
def test_forward_matches_dense(T):
    q, k, v = _qkv(T=T)
    out = flash_attention(q, k, v)
    assert out is not None, "kernel declined on TPU at a supported shape"
    ref = causal_attention(q, k, v, impl="dense")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("T", [256, 1024])
def test_forward_matches_dense_packed(T):
    q, k, v = _qkv(T=T)
    seg = _segments(*q.shape[:2])
    out = flash_attention(q, k, v, segment_ids=seg)
    assert out is not None
    ref = causal_attention(q, k, v, segment_ids=seg, impl="dense")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("T", [256, 1024])
@pytest.mark.parametrize("packed", [False, True])
def test_grads_match_dense(T, packed):
    q, k, v = _qkv(T=T)
    seg = _segments(*q.shape[:2]) if packed else None

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, segment_ids=seg)
                       .astype(jnp.float32) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, segment_ids=seg,
                                        impl="dense")
                       .astype(jnp.float32) ** 2)

    gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gd):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-1, err_msg=f"d{name} mismatch (T={T}, packed={packed})")


@pytest.mark.parametrize("seed", [1, 2])
def test_train_cell_shape_unaligned_documents(seed):
    """B=4, T=1024, H=20, D=64 (`train-large-t1024`), forward and the
    three gradients against the dense oracle, with document boundaries
    that cut through blocks: a block the causal mask leaves whole may
    still be cut by a segment edge."""
    q, k, v = _qkv(B=4, T=1024, H=20, D=64, seed=seed)
    seg = _documents(4, 1024, seed=seed)
    w = _qkv(B=4, T=1024, H=20, D=64, seed=seed + 10)[0]

    def loss(impl):
        def f(q, k, v):
            out = (flash_attention(q, k, v, segment_ids=seg)
                   if impl == "flash" else
                   causal_attention(q, k, v, segment_ids=seg, impl="dense"))
            return jnp.sum(out.astype(jnp.float32)
                           * w.astype(jnp.float32)), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, out_f), gf = jax.jit(loss("flash"))(q, k, v)
    (_, out_d), gd = jax.jit(loss("dense"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f, np.float32),
                               np.asarray(out_d, np.float32), atol=3e-2)
    for name, a, b in zip("qkv", gf, gd):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-1, err_msg=f"d{name} mismatch at the cell's shape")


def test_train_step_flash_vs_dense_loss():
    """One GPT-2 train step each way: the flash path's loss must track the
    dense path's (same init, same batch) — catches wiring bugs where the
    kernel silently drops masks."""
    from distributedtraining_tpu.engine import TrainEngine
    from distributedtraining_tpu.models import gpt2

    losses = {}
    for impl in ("flash", "dense"):
        # head_dim 64 + T 256: shapes the kernel accepts (a tinier config
        # would silently decline to dense and compare dense vs dense)
        cfg = gpt2.GPT2Config(vocab_size=512, n_positions=256, n_embd=256,
                              n_layer=2, n_head=4, vocab_multiple=128,
                              attention_impl=impl)
        model, cfg = gpt2.make_model(cfg)
        engine = TrainEngine(model, seq_len=256)
        state = engine.init_state(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (2, 256)), jnp.int32)}
        _, m = engine.train_step(state, batch)
        losses[impl] = float(m["loss"])
    assert np.isfinite(losses["flash"])
    np.testing.assert_allclose(losses["flash"], losses["dense"], rtol=2e-2)


def test_fused_loss_matches_standard_on_chip():
    """Fused (tiled-head) CE vs the materialized-logits path on real
    hardware: loss parity through a full jitted train step at a kernel-
    relevant shape (head_dim 64, T 512)."""
    from distributedtraining_tpu.engine import TrainEngine
    from distributedtraining_tpu.models import gpt2

    cfg = gpt2.GPT2Config(vocab_size=50257, n_positions=512, n_embd=256,
                          n_layer=2, n_head=4, vocab_multiple=128)
    model, cfg = gpt2.make_model(cfg)
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, 512)), jnp.int32)}
    losses = {}
    for fused in (False, True):
        engine = TrainEngine(model, seq_len=512, fused_loss=fused)
        state = engine.init_state(jax.random.PRNGKey(0))
        _, m = engine.train_step(state, batch)
        losses[fused] = float(m["loss"])
    assert np.isfinite(losses[True])
    np.testing.assert_allclose(losses[True], losses[False], rtol=2e-3)


@pytest.mark.parametrize("param_dtype", ["float32", "bfloat16"])
def test_remat_policy_grads_are_bare_remats_to_bfloat16_rounding(
        param_dtype, monkeypatch):
    """Two GPT-2 blocks over packed documents, three ways: no remat, a bare
    `nn.remat` (which calls the forward kernel again in its re-run) and
    the blocks' policy (`attention.remat_policy`: the forward kernel's
    output and log-sum-exp stay, one forward call a layer). The loss is
    one number; every leaf's gradient agrees to bfloat16's rounding, and
    NOT to the bit: XLA fuses the re-run differently from the forward
    (and each of the three programs differently), so bfloat16
    intermediates round at other points and a bare remat is already off
    the unwrapped blocks' gradient by as much (PERF.md section 6, PR 37:
    widest element 4.7e-3 of a leaf's largest here, 7.7e-3 at 36 layers).
    What is held: the policy is no farther from either than that."""
    from distributedtraining_tpu.models import gpt2

    cfg = gpt2.GPT2Config(vocab_size=512, n_positions=1024, n_embd=256,
                          n_layer=2, n_head=4, vocab_multiple=128,
                          remat=True, param_dtype=param_dtype)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 1024)), jnp.int32)
    seg = _documents(2, 1024)
    params = gpt2.make_model(cfg)[0].init(jax.random.PRNGKey(0), ids)

    def forward_calls(jaxpr):
        return sum(
            ("flash_mha_fwd" in eqn.params["name"]
             if eqn.primitive.name == "pallas_call" else
             sum(map(forward_calls, jax.core.jaxprs_in_params(eqn.params))))
            for eqn in jaxpr.eqns)

    def forward_calls_and_grads(cfg):
        model, _ = gpt2.make_model(cfg)

        def loss(p):
            logits = model.apply(p, ids, segment_ids=seg)
            return jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), -1))

        fn = jax.value_and_grad(loss)
        return (forward_calls(jax.make_jaxpr(fn)(params).jaxpr),
                jax.jit(fn)(params))

    none_calls, none = forward_calls_and_grads(
        dataclasses.replace(cfg, remat=False))
    kept_calls, kept = forward_calls_and_grads(cfg)
    monkeypatch.setattr(gpt2, "remat_policy", lambda: None)
    bare_calls, bare = forward_calls_and_grads(cfg)
    assert (none_calls, kept_calls, bare_calls) == (
        cfg.n_layer, cfg.n_layer, 2 * cfg.n_layer)
    np.testing.assert_allclose(
        [float(kept[0]), float(bare[0])], float(none[0]), rtol=1e-5)
    trees = [jax.tree_util.tree_leaves_with_path(t[1])
             for t in (kept, bare, none)]
    assert {str(g.dtype) for _, g in trees[0]} == {param_dtype}
    for (path, a), (_, b), (_, c) in zip(*trees):
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        assert np.any(a), path
        for got, want in ((a, b), (a, c), (b, c)):
            np.testing.assert_allclose(
                got, want, rtol=0, atol=4e-2 * np.abs(want).max(),
                err_msg=jax.tree_util.keystr(path))
