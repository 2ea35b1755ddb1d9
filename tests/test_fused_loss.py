"""Fused linear cross-entropy: numerics vs the materialized-logits oracle.

The fused path (ops.losses.fused_linear_cross_entropy) computes the tied
LM head tile-by-tile with an online softmax, never materializing the
[B, T, V] logits. These tests pin its forward value AND parameter gradients
to the standard causal_lm_loss path at tolerances tight enough to catch any
online-softmax or label-gather slip, including non-dividing vocab/chunk
shapes and masked tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import TrainEngine
from distributedtraining_tpu.engine.train import (_default_lm_loss,
                                                  _fused_lm_loss)
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.ops.losses import (causal_lm_loss,
                                                fused_linear_cross_entropy)


def _case(V=300, E=16, N=24, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.standard_normal((N, E)), dtype)
    wte = jnp.asarray(rng.standard_normal((V, E)) * 0.3, dtype)
    labels = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32)
    return hidden, wte, labels


@pytest.mark.parametrize("chunk", [64, 100, 300, 512])
def test_fused_matches_dense_value(chunk):
    """chunk < V, chunk not dividing V, chunk == V, chunk > V."""
    hidden, wte, labels = _case()
    logits = (hidden @ wte.T).astype(jnp.float32)[None]
    want, want_n = causal_lm_loss(
        jnp.concatenate([logits, logits[:, -1:]], axis=1),  # unshift helper
        jnp.concatenate([jnp.zeros((1, 1), jnp.int32), labels[None]], axis=1))
    got, got_n = fused_linear_cross_entropy(hidden[None], wte, labels[None],
                                            chunk=chunk)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert float(got_n) == float(want_n)


def test_fused_grads_match_dense():
    hidden, wte, labels = _case(V=257, E=8, N=12)

    def dense(h, w):
        logits = jnp.einsum("ne,ve->nv", h, w).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[..., 0]
        return jnp.mean(logz - ll)

    def fused(h, w):
        loss, _ = fused_linear_cross_entropy(h[None], w, labels[None],
                                             chunk=100)
        return loss

    gd = jax.grad(dense, argnums=(0, 1))(hidden, wte)
    gf = jax.grad(fused, argnums=(0, 1))(hidden, wte)
    for name, a, b in zip(("dhidden", "dwte"), gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6, err_msg=name)


def test_fused_respects_loss_mask():
    hidden, wte, labels = _case(N=10)
    mask = jnp.asarray([1, 1, 0, 1, 0, 1, 1, 1, 0, 1], jnp.float32)
    got, n = fused_linear_cross_entropy(hidden[None], wte, labels[None],
                                        mask[None], chunk=64)
    # oracle: per-token CE, masked mean
    logits = (hidden @ wte.T).astype(jnp.float32)
    per = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[:, None], -1)[..., 0]
    want = float(jnp.sum(per * mask) / jnp.sum(mask))
    np.testing.assert_allclose(float(got), want, rtol=1e-5)
    assert float(n) == 7.0


def test_fused_engine_matches_standard_engine():
    """Full model: _fused_lm_loss == _default_lm_loss in value and in the
    training trajectory (same init, same batches, losses track)."""
    model, cfg = gpt2.make_model("tiny")
    params = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)}

    l0, n0 = _default_lm_loss(model, params, batch)
    l1, n1 = _fused_lm_loss(model, params, batch)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-4)
    assert float(n0) == float(n1)

    std = TrainEngine(model, seq_len=16)
    fus = TrainEngine(model, seq_len=16, fused_loss=True)
    s_std = std.init_state(params=params)
    s_fus = fus.init_state(params=params)
    for i in range(4):
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)}
        s_std, m_std = std.train_step(s_std, batch)
        s_fus, m_fus = fus.train_step(s_fus, batch)
        np.testing.assert_allclose(float(m_fus["loss"]), float(m_std["loss"]),
                                   rtol=5e-4)


def test_fused_engine_on_mesh():
    """fused_loss composes with mesh sharding (same LM task, so the guard
    that rejects custom loss_fn + mesh does not apply)."""
    from distributedtraining_tpu.parallel import MeshConfig, make_mesh

    model, cfg = gpt2.make_model("tiny")
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2))
    engine = TrainEngine(model, mesh=mesh, seq_len=16, fused_loss=True)
    state = engine.init_state(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = engine.place_batch({"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)})
    state, m = engine.train_step(state, batch)
    assert np.isfinite(float(m["loss"]))

    with pytest.raises(ValueError):
        TrainEngine(model, seq_len=16, fused_loss=True,
                    loss_fn=lambda *a: None)


def test_fused_engine_llama():
    """The fused path picks up Llama's untied lm_head automatically — at
    Llama vocab widths the avoided logits tensor is the whole point."""
    from distributedtraining_tpu.models import llama

    model, cfg = llama.make_model("tiny-llama")
    params = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)}
    l0, n0 = _default_lm_loss(model, params, batch)
    l1, n1 = _fused_lm_loss(model, params, batch)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-4)
    assert float(n0) == float(n1)

    fus = TrainEngine(model, seq_len=16, fused_loss=True)
    state = fus.init_state(params=params)
    state, m = fus.train_step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_fused_engine_on_sp_mesh():
    """Fused CE composes with sequence parallelism: the hidden states enter
    the loss sharded over sp, and the off-by-one label shift forces a
    reshard GSPMD must handle."""
    from distributedtraining_tpu.ops import ring_attention as ring
    from distributedtraining_tpu.parallel import MeshConfig, make_mesh

    cfg = gpt2.GPT2Config(vocab_size=512, n_positions=32, n_embd=64,
                          n_layer=2, n_head=4, vocab_multiple=128,
                          attention_impl="ring")
    model, cfg = gpt2.make_model(cfg)
    mesh = make_mesh(MeshConfig(dp=2, sp=4))
    try:
        engine = TrainEngine(model, mesh=mesh, seq_len=32, fused_loss=True)
        state = engine.init_state(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = engine.place_batch({"input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)})
        state, m = engine.train_step(state, batch)
        assert np.isfinite(float(m["loss"]))
    finally:
        ring.set_ring_mesh(None)


def test_fused_loss_on_lora_engine():
    """config-4 combination: adapter-only training with the tiled-head CE.
    Values match the dense-logits LoRA step (same init, same batch)."""
    from distributedtraining_tpu.engine import LoRAEngine
    from distributedtraining_tpu.models.lora import LoRAConfig

    model, cfg = gpt2.make_model("tiny")
    base = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    lcfg = LoRAConfig(rank=2)
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)}

    dense = LoRAEngine(model, lcfg, seq_len=16)
    fused = LoRAEngine(model, lcfg, seq_len=16, fused_loss=True)
    b = dense.place_params(base)
    sd = dense.init_state(jax.random.PRNGKey(1), b)
    sf = fused.init_state(jax.random.PRNGKey(1), b)
    for _ in range(3):
        sd, md = dense.train_step(sd, b, batch)
        sf, mf = fused.train_step(sf, b, batch)
    np.testing.assert_allclose(float(mf["loss"]), float(md["loss"]),
                               rtol=1e-3)
    for a, c in zip(jax.tree_util.tree_leaves(sd.params),
                    jax.tree_util.tree_leaves(sf.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=5e-3, atol=5e-5)


# ---------------------------------------------------------------------------
# Pallas spelling (ops/pallas_ce.py) — runs in interpret mode off-TPU, so
# the same numerics pins apply here; the on-chip execution record lives in
# tests_tpu/test_step_variants_tpu.py.
# ---------------------------------------------------------------------------

def test_pallas_ce_matches_dense_value_and_grads():
    """Forward value and BOTH grads against the materialized-logits oracle,
    with a non-dividing vocab (padding path) and a loss mask."""
    hidden, wte, labels = _case(V=300, E=64, N=24)
    mask = jnp.asarray((np.random.default_rng(1).random(24) > 0.3)
                       .astype(np.float32))

    def dense(h, w):
        logits = jnp.einsum("ne,ve->nv", h, w).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[..., 0]
        per = logz - ll
        return jnp.sum(per * mask) / jnp.sum(mask)

    def pallas(h, w):
        loss, _ = fused_linear_cross_entropy(h[None], w, labels[None],
                                             mask[None], impl="pallas",
                                             interpret=True)
        return loss

    v0 = dense(hidden, wte)
    v1 = pallas(hidden, wte)
    np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
    gd = jax.grad(dense, argnums=(0, 1))(hidden, wte)
    gp = jax.grad(pallas, argnums=(0, 1))(hidden, wte)
    for name, a, b in zip(("dhidden", "dwte"), gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6, err_msg=name)


def test_pallas_ce_bf16_hidden_f32_head():
    """The production dtype mix: bf16 activations against the f32 head
    param — dW must come back f32 (accumulated in f32 inside the kernel),
    dh in bf16."""
    hidden, wte, labels = _case(V=256, E=64, N=32, dtype=jnp.bfloat16)
    wte = wte.astype(jnp.float32)

    def pallas(h, w):
        loss, _ = fused_linear_cross_entropy(h[None], w, labels[None],
                                             impl="pallas", interpret=True)
        return loss

    def dense(h, w):
        logits = jnp.einsum("ne,ve->nv", h, w.astype(h.dtype),
                            preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[..., 0]
        return jnp.mean(logz - ll)

    gp = jax.grad(pallas, argnums=(0, 1))(hidden, wte)
    gd = jax.grad(dense, argnums=(0, 1))(hidden, wte)
    assert gp[0].dtype == jnp.bfloat16
    assert gp[1].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gd[1]),
                               rtol=2e-2, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(gp[0], np.float32), np.asarray(gd[0], np.float32),
        rtol=5e-2, atol=5e-4)


@pytest.fixture
def pallas_interpret():
    """Engine-level callers pass no ``interpret=``; the CPU lane asks for
    the interpreter explicitly through the module hook."""
    from distributedtraining_tpu.ops import pallas_ce
    pallas_ce.use_interpret(True)
    yield
    pallas_ce.use_interpret(False)


def test_pallas_engine_step_matches_standard(pallas_interpret):
    """Full train step with fused_loss='pallas' (interpreted here) tracks
    the standard engine's loss trajectory."""
    model, cfg = gpt2.make_model("tiny")
    params = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    rng = np.random.default_rng(0)
    std = TrainEngine(model, seq_len=16)
    pal = TrainEngine(model, seq_len=16, fused_loss="pallas")
    s_std = std.init_state(params=params)
    s_pal = pal.init_state(params=params)
    for _ in range(3):
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)}
        s_std, m_std = std.train_step(s_std, batch)
        s_pal, m_pal = pal.train_step(s_pal, batch)
        np.testing.assert_allclose(float(m_pal["loss"]),
                                   float(m_std["loss"]), rtol=5e-4)


def test_pallas_engine_on_mesh_matches_scan(devices, pallas_interpret):
    """fused_loss='pallas' on a dp x fsdp x tp mesh (the shard_map
    spelling, interpret mode here): full jitted train step tracks the
    GSPMD-partitioned scan spelling on the same mesh (flagship kernel x
    flagship parallelism)."""
    import dataclasses

    import optax

    from distributedtraining_tpu.parallel import MeshConfig, make_mesh

    cfg = dataclasses.replace(gpt2.PRESETS["tiny"], n_embd=128, n_head=4,
                              dtype="float32")
    model, _ = gpt2.make_model(cfg)
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    p = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    # sgd: params diff == grad diff (no Adam sign-amplification on
    # near-zero grads; see tests_tpu/test_step_variants_tpu.py)
    pal = TrainEngine(model, mesh=mesh, seq_len=16, fused_loss="pallas",
                      optimizer=optax.sgd(1.0))
    scn = TrainEngine(model, mesh=mesh, seq_len=16, fused_loss="scan",
                      optimizer=optax.sgd(1.0))
    s_pal = pal.init_state(params=p)
    s_scn = scn.init_state(params=p)
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)}
    s_pal, m_pal = pal.train_step(s_pal, pal.place_batch(batch))
    s_scn, m_scn = scn.train_step(s_scn, scn.place_batch(batch))
    np.testing.assert_allclose(float(m_pal["loss"]), float(m_scn["loss"]),
                               rtol=1e-5)
    assert float(m_pal["tokens"]) == float(m_scn["tokens"])
    for a, b in zip(jax.tree_util.tree_leaves(s_pal.params),
                    jax.tree_util.tree_leaves(s_scn.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_fused_on_unknown_mesh_axis_falls_back(devices, caplog):
    """fused_loss on a mesh with an axis outside dp/fsdp/tp/sp falls back
    to the unfused loss with a warning instead of refusing to construct:
    the fused path is a perf lever, and a role wired onto a research mesh
    should run correct-but-unfused rather than fail to boot. Nothing
    psums over the wrong axis set because the fused spelling never
    engages at all."""
    import logging as _logging

    import numpy as _np
    from jax.sharding import Mesh

    model, _ = gpt2.make_model("tiny")
    # the standard axes must exist (the logical sharding rules reference
    # them); the size->1 exotic 'ep' axis is what trips the fused check
    mesh = Mesh(_np.array(jax.devices()[:4]).reshape(2, 1, 1, 1, 2),
                ("dp", "fsdp", "sp", "tp", "ep"))
    with caplog.at_level(_logging.WARNING,
                         logger="distributedtraining_tpu.engine.train"):
        engine = TrainEngine(model, mesh=mesh, seq_len=16,
                             fused_loss="pallas")
    assert any("falling back to the unfused" in r.getMessage()
               for r in caplog.records)
    # the resolved loss is the plain (materialized-logits) spelling
    assert engine._task_loss is not None


def test_pallas_engine_on_sp_mesh_matches_scan(devices, pallas_interpret):
    """fused_loss='pallas' on a dp x sp (ring attention) mesh: the mesh
    spelling shifts the LABELS instead of slicing the hidden states, so
    sequence shards carry no cross-shard dependency and the flagship
    kernel composes with the long-context path too."""
    import dataclasses

    import optax

    from distributedtraining_tpu.ops import ring_attention as ring
    from distributedtraining_tpu.parallel import MeshConfig, make_mesh

    cfg = dataclasses.replace(gpt2.PRESETS["tiny"], n_embd=128, n_head=4,
                              dtype="float32", attention_impl="ring",
                              n_positions=32)
    model, _ = gpt2.make_model(cfg)
    mesh = make_mesh(MeshConfig(dp=2, sp=4))
    try:
        p = model.init_params(jax.random.PRNGKey(0), seq_len=32)
        pal = TrainEngine(model, mesh=mesh, seq_len=32,
                          fused_loss="pallas", optimizer=optax.sgd(1.0))
        scn = TrainEngine(model, mesh=mesh, seq_len=32,
                          fused_loss="scan", optimizer=optax.sgd(1.0))
        s_pal = pal.init_state(params=p)
        s_scn = scn.init_state(params=p)
        rng = np.random.default_rng(0)
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)}
        s_pal, m_pal = pal.train_step(s_pal, pal.place_batch(batch))
        s_scn, m_scn = scn.train_step(s_scn, scn.place_batch(batch))
        np.testing.assert_allclose(float(m_pal["loss"]),
                                   float(m_scn["loss"]), rtol=1e-5)
        assert float(m_pal["tokens"]) == float(m_scn["tokens"])
        for a, b in zip(jax.tree_util.tree_leaves(s_pal.params),
                        jax.tree_util.tree_leaves(s_scn.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)
    finally:
        ring.set_ring_mesh(None)


def test_fused_auto_selects_scan_off_tpu():
    """impl='auto' must not route through the Pallas kernels on a CPU
    backend (interpret mode is for tests; production fallback is scan)."""
    from distributedtraining_tpu.ops.pallas_ce import pallas_ce_available
    hidden, wte, _ = _case(V=256, E=128, N=16)
    assert pallas_ce_available(hidden, wte) is False


def test_pallas_explicit_off_tpu_raises():
    """Explicit impl='pallas' off-TPU without an interpret request must
    fail loudly: nothing selects the interpreter on its own (it is orders
    of magnitude slower than the scan spelling, and a silent switch hides
    which path ran)."""
    hidden, wte, labels = _case(V=256, E=64, N=16)
    with pytest.raises(ValueError, match="interpret mode"):
        fused_linear_cross_entropy(hidden[None], wte, labels[None],
                                   impl="pallas")
    fused_linear_cross_entropy(hidden[None], wte, labels[None],
                               impl="pallas", interpret=True)
