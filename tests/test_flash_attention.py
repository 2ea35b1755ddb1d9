"""Flash-attention selection rule on the CPU lane.

The suite's conftest forces the CPU platform (virtual 8-device mesh), where
the rule never selects the Pallas kernel; its numerics against the dense
oracle run on the chip (tests_tpu/test_flash_attention_tpu.py). What is
pinned here is the rule itself, the XLA path the caller takes when it
says no, and the kernel's schedule as a pure function of T: which blocks,
and which (q block, kv block) pairs a causal mask leaves to run.
"""

import jax.numpy as jnp
import numpy as np
import pytest

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


def _pairs_share(info):
    """Of a kernel's (q block, kv block) pairs, the share its block table
    leaves to run (0 = the mask covers the pair whole)."""
    table = np.asarray(info.block_mask)
    return np.count_nonzero(table) / table.size


@pytest.mark.parametrize("T", [256, 384, 512, 640, 768, 1024, 1536, 2048,
                               4096])
def test_schedule_blocks_divide_T(T):
    """For every T the rule accepts, every block of the forward and of the
    backward divides T, is a whole number of 128-lane tiles, and the
    kernel's tables can be built (the library refuses blocks that do not
    divide)."""
    assert fl.supports(_qkv(B=1, T=T, H=1)[0], None)
    b = fl._block_sizes(T)
    assert b.use_fused_bwd_kernel and b.has_backward_blocks
    for name in ("block_q", "block_kv", "block_kv_compute", "block_q_dkv",
                 "block_kv_dkv", "block_kv_dkv_compute"):
        size = getattr(b, name)
        assert T % size == 0 and size % 128 == 0, (name, size)
    fl._causal_kernel(T, 2)


@pytest.mark.parametrize("T, most", [(256, 1.0), (512, 1.0), (1024, 0.75),
                                     (2048, 0.625), (4096, 0.5625)])
def test_schedule_runs_only_the_causal_pairs(T, most):
    """The share of block pairs a causal mask leaves to run is a constant
    of (T, blocks): at T = 1024 at most 0.75 in the forward and in the
    backward, and no pair above the diagonal in either table. One q block
    of T rows (what stood here before) would read 1.0."""
    kernel = fl._causal_kernel(T, 2)
    assert kernel.dq_mask_info is None          # the fused backward
    fwd, dkv = kernel.fwd_mask_info, kernel.dkv_mask_info
    assert _pairs_share(fwd) <= most and _pairs_share(dkv) <= most
    # [heads, q blocks, kv blocks]: nothing above the diagonal runs, every
    # pair strictly below it runs unmasked (2), the diagonal masks (1)
    for table in (np.asarray(fwd.block_mask)[0],
                  np.asarray(dkv.block_mask)[0]):
        assert not np.triu(table, 1).any()
        assert (np.diag(table) == 1).all()
        assert (table[np.tril_indices_from(table, -1)] == 2).all()
