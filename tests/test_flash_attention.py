"""Flash-attention selection rule on the CPU lane.

The suite's conftest forces the CPU platform (virtual 8-device mesh), where
the rule never selects the Pallas kernel; its numerics against the dense
oracle run on the chip (tests_tpu/test_flash_attention_tpu.py). What is
pinned here is the rule itself and the XLA path the caller takes when it
says no.
"""

import jax.numpy as jnp
import numpy as np

from distributedtraining_tpu.ops import flash_attention as fl
from distributedtraining_tpu.ops.attention import causal_attention


def _qkv(B=2, T=512, H=4, D=64, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
                 for _ in range(3))


def test_rule_is_backend_and_shape():
    q, k, v = _qkv(T=512)
    assert fl.supports(q, None)
    assert fl.flash_attention(q, k, v) is None           # CPU backend
    assert not fl.supports(_qkv(T=128)[0], None)         # short
    assert not fl.supports(_qkv(T=320)[0], None)         # unaligned
    assert not fl.supports(_qkv(D=32)[0], None)          # lane tiling
    assert not fl.supports(q, jnp.ones(q.shape[:2], jnp.int32))  # padding


def test_flash_impl_takes_xla_path_off_tpu():
    q, k, v = _qkv(T=256)
    out = causal_attention(q, k, v, impl="flash")
    ref = causal_attention(q, k, v, impl="dense")
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))
