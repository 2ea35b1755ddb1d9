"""Flash-attention numerics on the real chip: forward AND grad parity vs the
dense oracle at T in {256, 1024}, packed segments included, at the train
cell's own shape with document boundaries off every block grid, and at the
LFM2 cell's (B=2, H=32, T=8,192); at both the kernels are this module's own
over the pair list of the rows' segment ids, rows-major (two heads of 64 a
128-lane block), and GPT-2's block hands them c_attn's fused array.

This is the on-device half of tests/test_flash_attention.py (which pins
the selection rule and the block schedule on CPU). The schedule
(ops/flash_attention.py) rests on these numerics.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import flash_attention as fl
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


@pytest.mark.parametrize("seed", [1, 2])
def test_train_cell_shape_the_fused_entry(seed, monkeypatch):
    """`train-large-t1024` as GPT-2's block calls it: c_attn's fused
    `[4, 1024, 3 x 1280]` through `flash_attention_qkv` (this module's
    rows-major kernels, two heads of 64 a lane block, at two blocks a row)
    against `flash_attention` on the three parts, bit for bit in output and
    gradients, and against the library's kernels heads first: `out` bit
    for bit, the gradients to a rounding (dQ adds up in float32 here, `di`
    is summed as a product with the heads' 0 / 1 matrix)."""
    B, T, H, D = 4, 1024, 20, 64
    E = H * D
    qkv = jnp.concatenate([x.reshape(B, T, E) for x in
                           _qkv(B=B, T=T, H=H, D=D, seed=seed)], axis=-1)
    w = _qkv(B=B, T=T, H=H, D=D, seed=seed + 10)[0].reshape(B, T, E)
    seg = _documents(B, T, seed=seed)
    assert fl._table_engages(T, H, D, seg)
    run, causal = fl.block_pairs(jax.ShapeDtypeStruct((B, T, H, D), w.dtype),
                                 None, seg)
    assert 2 * B <= int(run) <= int(causal) == 3 * B

    def fused(qkv):
        return fl.flash_attention_qkv(qkv, H, segment_ids=seg)

    def split(qkv):
        q, k, v = (x.reshape(B, T, H, D) for x in jnp.split(qkv, 3, -1))
        return flash_attention(q, k, v, segment_ids=seg).reshape(B, T, E)

    def with_gradient(fn):
        def f(qkv):
            out = fn(qkv)
            return jnp.sum(out.astype(jnp.float32)
                           * w.astype(jnp.float32)), out
        (_, out), grad = jax.jit(jax.value_and_grad(f, has_aux=True))(qkv)
        return np.asarray(out, np.float32), np.asarray(grad, np.float32)

    got = with_gradient(fused)
    for a, b in zip(got, with_gradient(split)):
        np.testing.assert_array_equal(a, b)
    monkeypatch.setattr(fl, "TABLE_MIN_BLOCKS", T)       # the library's
    assert not fl._table_engages(T, H, D, seg)
    out_l, grad_l = with_gradient(lambda x: split(x))
    np.testing.assert_array_equal(got[0], out_l)
    np.testing.assert_allclose(got[1], grad_l, rtol=2 ** -6,
                               atol=2 ** -7 * np.abs(grad_l).max())


@pytest.mark.parametrize("seed", [1, 2])
def test_lfm2_cell_shape_pair_list_from_the_segment_ids(seed, monkeypatch):
    """B=2, H=32, T=8,192, D=64 packed (`train-lfm2-t8192`): the kernels
    visit the block pairs the rows' segment ids need. The output equals the
    library's kernel under the causal constants BIT FOR BIT. dK and dV are
    the same float32 sums over the same pairs in the same order, but Mosaic
    folds an accumulator into a product's passes its own way in each
    kernel, so a few elements in 100,000 round to the neighbouring bfloat16
    (PR 40's first chip run: 1,133 and 338 of 33,554,432 in dK, at blocks of
    128, one pass a product, none). dQ adds up in float32 here where the
    library sums bfloat16 shares, so it is the library's to a rounding of
    its largest share. All four agree with the dense oracle on the heads it
    can hold (heads are independent; the [B, H, T, T] scores of all 32
    would be 17 GB)."""
    B, T, H, D = 2, 8192, 32, 64
    q, k, v = _qkv(B=B, T=T, H=H, D=D, seed=seed)
    w = _qkv(B=B, T=T, H=H, D=D, seed=seed + 10)[0]
    seg = _documents(B, T, seed=seed)
    assert fl._table_engages(T, H, D, seg)
    run, causal = fl.block_pairs(q, None, seg)
    assert 0 < int(run) < int(causal) == B * 16 * 17 // 2

    def loss(attend, w):
        def f(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out.astype(jnp.float32)
                           * w.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    def flash(q, k, v):
        return flash_attention(q, k, v, segment_ids=seg)

    (_, out), grads = loss(flash, w)(q, k, v)
    monkeypatch.setattr(fl, "TABLE_MIN_BLOCKS", T)       # the constants
    assert not fl._table_engages(T, H, D, seg)
    (_, out_s), grads_s = loss(lambda *a: flash(*a), w)(q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), (out, *grads),
                          (out_s, *grads_s)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if name == "out":
            np.testing.assert_array_equal(
                a, b, err_msg="out: pair list against causal constants")
            continue
        np.testing.assert_allclose(a, b, rtol=2 ** -6, err_msg=name,
                                   atol=2 ** -7 * np.abs(b).max())
        if name != "dq":
            assert (a != b).mean() < 1e-3, (name, (a != b).mean())
    heads = np.array([0, H - 1])
    cut = lambda x: x[:, :, heads]
    (_, out_d), grads_d = loss(
        lambda q, k, v: causal_attention(q, k, v, segment_ids=seg,
                                         impl="dense"),
        cut(w))(cut(q), cut(k), cut(v))
    # bfloat16: a rounding of a value near 4 is 2 ** -5
    np.testing.assert_allclose(np.asarray(cut(out), np.float32),
                               np.asarray(out_d, np.float32), atol=3e-2,
                               rtol=2 ** -7)
    for name, a, b in zip("qkv", grads, grads_d):
        np.testing.assert_allclose(
            np.asarray(cut(a), np.float32), np.asarray(b, np.float32),
            atol=1e-1, err_msg=f"d{name} mismatch at the LFM2 cell's shape")


def test_remat_policy_keeps_the_residuals_under_the_pair_list():
    """One attention layer of the LFM2 cell's widths over packed rows of
    8,192, under `nn.remat` with the blocks' policy: the gradient's
    program calls the forward kernel once, not twice (its `out` and
    log-sum-exp carry `RESIDUAL_NAME` and stay), and the result is the
    unwrapped layer's to bfloat16 rounding."""
    import flax.linen as nn

    from distributedtraining_tpu.ops.attention import remat_policy
    B, T, H, D = 2, 8192, 32, 64
    q, k, v = _qkv(B=B, T=T, H=H, D=D, seed=3)
    seg = _documents(B, T, seed=3)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, q, k, v):
            return causal_attention(q, k, v, segment_ids=seg, impl="flash")

    def forward_calls(jaxpr):
        return sum(
            ("flash_mha_fwd" in eqn.params["name"]
             if eqn.primitive.name == "pallas_call" else
             sum(map(forward_calls, jax.core.jaxprs_in_params(eqn.params))))
            for eqn in jaxpr.eqns)

    def run(layer):
        def loss(q, k, v):
            return jnp.sum(layer.apply({}, q, k, v).astype(jnp.float32) ** 2)
        fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
        return (forward_calls(jax.make_jaxpr(fn)(q, k, v).jaxpr),
                jax.jit(fn)(q, k, v))

    plain_calls, plain = run(Layer())
    kept_calls, kept = run(nn.remat(Layer, policy=remat_policy())())
    bare_calls, _ = run(nn.remat(Layer)())
    assert (plain_calls, kept_calls, bare_calls) == (1, 1, 2)
    np.testing.assert_allclose(float(kept[0]), float(plain[0]), rtol=1e-5)
    for a, b in zip(kept[1], plain[1]):
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b, rtol=0,
                                   atol=4e-2 * np.abs(b).max())


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
