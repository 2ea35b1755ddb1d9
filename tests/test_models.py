"""Model zoo: shapes, loss sanity, packing masks, LoRA zero-init property."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.models import GPT2, GPT2Config, Llama, LlamaConfig, lora
from distributedtraining_tpu.models import gpt2 as gpt2_mod
from distributedtraining_tpu.models import llama as llama_mod
from distributedtraining_tpu.ops import causal_lm_loss


@pytest.fixture(scope="module")
def tiny_gpt2():
    model, cfg = gpt2_mod.make_model("tiny")
    params = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    return model, cfg, params


def test_gpt2_forward_shape(tiny_gpt2):
    model, cfg, params = tiny_gpt2
    ids = jnp.zeros((2, 16), jnp.int32)
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert logits.dtype == jnp.float32


def test_bf16_logits_storage(tiny_gpt2):
    """logits_dtype='bfloat16' halves the logit buffer while the loss stays
    within bf16 rounding of the f32-logits loss (accumulation is f32 either
    way — only storage precision changes)."""
    import dataclasses

    model, cfg, params = tiny_gpt2
    bf_model, bf_cfg = gpt2_mod.make_model(
        dataclasses.replace(cfg, logits_dtype="bfloat16"))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)), jnp.int32)
    lf = model.apply({"params": params}, ids)
    lb = bf_model.apply({"params": params}, ids)
    assert lb.dtype == jnp.bfloat16 and lf.dtype == jnp.float32
    loss_f, _ = causal_lm_loss(lf, ids)
    loss_b, _ = causal_lm_loss(lb, ids)
    np.testing.assert_allclose(float(loss_b), float(loss_f),
                               rtol=1e-2)  # bf16 has ~3 significant digits


def test_gpt2_causality(tiny_gpt2):
    """Changing a future token must not change past logits."""
    model, cfg, params = tiny_gpt2
    k = jax.random.PRNGKey(1)
    ids = jax.random.randint(k, (1, 16), 0, cfg.vocab_size)
    logits1 = model.apply({"params": params}, ids)
    ids2 = ids.at[0, 10].set((ids[0, 10] + 1) % cfg.vocab_size)
    logits2 = model.apply({"params": params}, ids2)
    np.testing.assert_allclose(np.asarray(logits1[0, :10]),
                               np.asarray(logits2[0, :10]), atol=2e-2)
    assert not np.allclose(np.asarray(logits1[0, 10:]),
                           np.asarray(logits2[0, 10:]), atol=1e-3)


def test_segment_ids_isolate_packed_sequences(tiny_gpt2):
    """With packing, tokens must not attend across segment boundaries."""
    model, cfg, params = tiny_gpt2
    k = jax.random.PRNGKey(2)
    a = jax.random.randint(k, (1, 8), 0, cfg.vocab_size)
    b = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, cfg.vocab_size)
    packed = jnp.concatenate([a, b], axis=1)
    seg = jnp.concatenate([jnp.zeros((1, 8), jnp.int32),
                           jnp.ones((1, 8), jnp.int32)], axis=1)
    pos = jnp.concatenate([jnp.arange(8), jnp.arange(8)])[None, :]
    packed_logits = model.apply({"params": params}, packed,
                                segment_ids=seg, position_ids=pos)
    solo_logits = model.apply({"params": params}, b)
    np.testing.assert_allclose(np.asarray(packed_logits[0, 8:]),
                               np.asarray(solo_logits[0]), atol=2e-2)


def test_loss_decreases_under_sgd(tiny_gpt2):
    model, cfg, params = tiny_gpt2
    ids = jax.random.randint(jax.random.PRNGKey(4), (4, 16), 0, cfg.vocab_size)

    def loss_fn(p):
        logits = model.apply({"params": p}, ids)
        loss, _ = causal_lm_loss(logits, ids)
        return loss

    l0 = loss_fn(params)
    g = jax.grad(loss_fn)(params)
    params2 = jax.tree_util.tree_map(lambda p, gr: p - 0.1 * gr, params, g)
    l1 = loss_fn(params2)
    assert float(l1) < float(l0)


def test_llama_forward_and_gqa():
    model, cfg = llama_mod.make_model("tiny-llama")
    params = model.init_params(jax.random.PRNGKey(0), seq_len=16)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    # causality holds with RoPE + GQA
    logits2 = model.apply({"params": params},
                          ids.at[0, 12].set((ids[0, 12] + 1) % cfg.vocab_size))
    np.testing.assert_allclose(np.asarray(logits[0, :12]),
                               np.asarray(logits2[0, :12]), atol=2e-2)


def test_lora_zero_init_is_identity():
    model, cfg = llama_mod.make_model("tiny-llama")
    params = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    lcfg = lora.LoRAConfig(rank=4)
    lp = lora.init_lora(jax.random.PRNGKey(5), params, lcfg)
    eff = lora.apply_lora(params, lp, lcfg)
    for a, b in zip(jax.tree_util.tree_leaves(eff),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_lora_delta_matches_apply():
    model, cfg = llama_mod.make_model("tiny-llama")
    params = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    lcfg = lora.LoRAConfig(rank=4)
    lp = lora.init_lora(jax.random.PRNGKey(5), params, lcfg)
    # give B nonzero values so the delta is nontrivial
    lp = jax.tree_util.tree_map(lambda x: x + 0.01, lp)
    from distributedtraining_tpu import delta as d
    full = d.apply_delta(params, lora.lora_to_full_delta(params, lp, lcfg))
    eff = lora.apply_lora(params, lp, lcfg)
    for a, b in zip(jax.tree_util.tree_leaves(full),
                    jax.tree_util.tree_leaves(eff)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_lora_adapts_expected_kernels():
    model, cfg = llama_mod.make_model("tiny-llama")
    params = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    lp = lora.init_lora(jax.random.PRNGKey(5), params, lora.LoRAConfig(rank=2))
    # 2 layers x (wq, wk, wv, wo) = 8 adapted kernels
    assert len(lora.adapted_pairs(lp)) == 8


def test_llama2_7b_shapes_on_v4_32_mesh():
    """Shape-validate the llama2-7b preset (full-param AND LoRA engines) on
    a 32-device virtual mesh — subprocess because it needs its own
    XLA_FLAGS device count (presets never shape-validated at
    scale break on first contact, e.g. GQA kv-heads vs tp divisibility)."""
    import os
    import subprocess
    import sys

    worker = os.path.join(os.path.dirname(__file__), "validate_7b_worker.py")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=32")
    proc = subprocess.run([sys.executable, worker], env=env,
                          capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.startswith("OK "), proc.stdout
