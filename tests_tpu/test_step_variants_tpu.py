"""On-chip checks for the step/merge variants added in round 2.

Small configs (compile time): each case pins on-device agreement between
a variant and its reference spelling, not throughput.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from distributedtraining_tpu import delta as delta_lib
from distributedtraining_tpu.engine import TrainEngine
from distributedtraining_tpu.models import gpt2

SEQ = 128


def _batch(cfg, b=2, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (b, SEQ)), jnp.int32)}


def test_scan_blocks_loss_matches_unrolled_on_chip():
    cfg = dataclasses.replace(gpt2.PRESETS["tiny"], n_positions=SEQ)
    m1, _ = gpt2.make_model(cfg)
    m2, _ = gpt2.make_model(dataclasses.replace(cfg, scan_blocks=True))
    p1 = m1.init_params(jax.random.PRNGKey(0))
    e1 = TrainEngine(m1, seq_len=SEQ)
    e2 = TrainEngine(m2, seq_len=SEQ)
    s1 = e1.init_state(params=p1)
    s2 = e2.init_state(params=gpt2.stack_blocks(p1, cfg.n_layer))
    batch = _batch(cfg)
    _, l1 = e1.train_step(s1, batch)
    _, l2 = e2.train_step(s2, batch)
    np.testing.assert_allclose(float(l1["loss"]), float(l2["loss"]),
                               rtol=5e-3)  # bf16 compute


def test_accumulated_step_matches_full_batch_on_chip():
    """accum_steps=2 vs the full batch through the REAL jitted step.

    Params are compared under sgd(1.0), where params_before - params_after
    IS the gradient — comparing after an Adam step instead would amplify
    reduction-order rounding on any near-zero gradient into a full
    lr-sized difference (one bias-corrected Adam step is ~lr*sign(g)
    however small |g| is), which is what this test tripped over the first
    time it ever ran on hardware."""
    import optax

    cfg = dataclasses.replace(gpt2.PRESETS["tiny"], n_positions=SEQ,
                              dtype="float32")
    model, _ = gpt2.make_model(cfg)
    p = model.init_params(jax.random.PRNGKey(0))
    # 'highest' forces true-f32 matmuls (bf16x6 passes): the TPU default
    # runs f32 matmuls as single-pass bf16 multiplies, which puts
    # reduction-order differences at bf16 scale (~4e-4 observed) and
    # drowns the summation-order property this test pins
    with jax.default_matmul_precision("highest"):
        e1 = TrainEngine(model, seq_len=SEQ, optimizer=optax.sgd(1.0))
        e2 = TrainEngine(model, seq_len=SEQ, optimizer=optax.sgd(1.0),
                         accum_steps=2)
        s1 = e1.init_state(params=p)
        s2 = e2.init_state(params=p)
        batch = _batch(cfg, b=4)
        s1, m1 = e1.train_step(s1, batch)
        s2, m2 = e2.train_step(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4)
    # identical math up to summation order: measured on-chip agreement is
    # ~3e-8 abs / ~8e-4 rel (near-zero grads); tolerances give ~3x margin
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=1e-6)


def test_bf16_logits_loss_close_on_chip():
    """logits_dtype='bfloat16' on the real chip: same train-step loss to
    bf16 rounding (the MXU accumulation stays f32 either way)."""
    cfg = dataclasses.replace(gpt2.PRESETS["tiny"], n_positions=SEQ)
    m32, _ = gpt2.make_model(cfg)
    m16, _ = gpt2.make_model(
        dataclasses.replace(cfg, logits_dtype="bfloat16"))
    p = m32.init_params(jax.random.PRNGKey(0))
    e32 = TrainEngine(m32, seq_len=SEQ)
    e16 = TrainEngine(m16, seq_len=SEQ)
    batch = _batch(cfg)
    _, l32 = e32.train_step(e32.init_state(params=p), batch)
    _, l16 = e16.train_step(e16.init_state(params=p), batch)
    np.testing.assert_allclose(float(l16["loss"]), float(l32["loss"]),
                               rtol=1e-2)


def test_flat_merge_matches_leafwise_on_chip():
    model, cfg = gpt2.make_model("tiny")
    base = model.init_params(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    leaves, treedef = jax.tree_util.tree_flatten(base)
    deltas = []
    for _ in range(4):
        key, k = jax.random.split(key)
        ks = jax.random.split(k, len(leaves))
        deltas.append(jax.tree_util.tree_unflatten(
            treedef, [0.01 * jax.random.normal(kk, l.shape, l.dtype)
                      for kk, l in zip(ks, leaves)]))
    stacked = delta_lib.stack_deltas(deltas)
    w = jnp.asarray([0.4, 0.3, 0.2, 0.1])
    a = jax.jit(delta_lib.weighted_merge)(base, stacked, w)
    b = jax.jit(delta_lib.weighted_merge_flat)(base, stacked, w)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


def test_pallas_fused_ce_matches_standard_on_chip():
    """The Pallas fused-CE kernels (ops/pallas_ce.py) on real hardware —
    the first Mosaic-lowered execution record for this kernel (interpret
    mode off-TPU cannot catch lowering bugs).

    Loss is pinned per-step; GRADIENTS are pinned through the full jitted
    step under sgd(1.0) (param diff == grad diff). Comparing params after
    an Adam step amplifies bf16 rounding on near-zero grads into lr-sized
    sign-flip differences (~lr*sign(g) per step) — the original spelling
    of this test, which failed on its first real-hardware run for exactly
    that reason while the kernel itself was numerically fine."""
    import optax

    cfg = dataclasses.replace(gpt2.PRESETS["tiny"], n_positions=SEQ,
                              n_embd=128, n_head=4)
    model, _ = gpt2.make_model(cfg)
    p = model.init_params(jax.random.PRNGKey(0))
    std = TrainEngine(model, seq_len=SEQ, optimizer=optax.sgd(1.0))
    pal = TrainEngine(model, seq_len=SEQ, optimizer=optax.sgd(1.0),
                      fused_loss="pallas")
    s_std = std.init_state(params=p)
    s_pal = pal.init_state(params=p)
    first = True
    for seed in range(2):
        batch = _batch(cfg, seed=seed)
        s_std, m_std = std.train_step(s_std, batch)
        s_pal, m_pal = pal.train_step(s_pal, batch)
        np.testing.assert_allclose(float(m_pal["loss"]),
                                   float(m_std["loss"]), rtol=5e-3)
        if first:
            # two correct-but-different bf16 computations of the same
            # gradients (kernel recompute vs materialized logits): one
            # bf16 ulp of the largest params (~1e-3 abs measured on-chip);
            # checked after the FIRST step only — later steps legitimately
            # diverge as the parameter trajectories separate
            for a, b in zip(jax.tree_util.tree_leaves(s_std.params),
                            jax.tree_util.tree_leaves(s_pal.params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-2, atol=2e-3)
            first = False


def test_pallas_sharded_ce_matches_unsharded_on_chip():
    """fused_ce_loss_sharded on a 1-device mesh vs the plain kernel: the
    shard_map spelling (all-gathers, row split, psum) must lower through
    Mosaic and agree with the unsharded path on real hardware. Multi-chip
    behavior is CPU-mesh-tested (tests/test_fused_loss.py); this pins the
    on-chip lowering of the same program."""
    import numpy as np
    from jax.sharding import Mesh

    from distributedtraining_tpu.ops.pallas_ce import (fused_ce_loss,
                                                       fused_ce_loss_sharded)

    rng = np.random.default_rng(0)
    B, T, E, V = 2, 64, 128, 384
    hidden = jnp.asarray(rng.normal(size=(B, T, E)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(V, E)) * 0.05, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "fsdp", "tp"))

    def plain(h, w):
        return fused_ce_loss(h, w, labels)[0]

    def sharded(h, w):
        return fused_ce_loss_sharded(h, w, labels, mesh=mesh)[0]

    v0 = float(jax.jit(plain)(hidden, head))
    v1 = float(jax.jit(sharded)(hidden, head))
    np.testing.assert_allclose(v1, v0, rtol=1e-5)
    g0 = jax.jit(jax.grad(plain, argnums=(0, 1)))(hidden, head)
    g1 = jax.jit(jax.grad(sharded, argnums=(0, 1)))(hidden, head)
    for name, a, b in zip(("dh", "dw"), g1, g0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6, err_msg=name)
