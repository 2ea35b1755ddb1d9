"""Dequant->scatter-add kernel numerics on the real chip.

The on-device half of tests/test_dequant_scatter.py: the real Mosaic
lowering of the in-place RMW scatter loop (VMEM-resident accumulator,
SMEM indices and values, ``input_output_aliases``) against the XLA
scatter-add, and the kernel-routed ``accumulate_delta`` against the
densify reference.
"""

import jax
import jax.numpy as jnp
import numpy as np

from distributedtraining_tpu import delta as dl
from distributedtraining_tpu.ops import dequant_scatter as dsc


def test_kernel_matches_xla_on_chip():
    assert dsc.enabled(), "accumulate paths must select the kernel on TPU"
    rng = np.random.default_rng(0)
    for n, k in ((1 << 16, 1024), (768 * 768, 9216),
                 (dsc.MAX_ACC_ELEMS, dsc.MAX_ENTRIES)):
        flat = jnp.asarray(rng.standard_normal(n), jnp.float32)
        idx = jnp.asarray(rng.integers(0, n, k), jnp.int32)
        for q in (jnp.asarray(rng.integers(-127, 128, k), jnp.int8),
                  jnp.asarray(rng.standard_normal(k), jnp.float32)):
            out = dsc.dequant_scatter_add(flat, idx, q, 0.37)
            ref = flat.at[idx].add(q.astype(jnp.float32) * 0.37)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)


def test_accumulate_delta_kernel_route_on_chip():
    rng = np.random.default_rng(1)
    d = {"w": jnp.asarray(rng.standard_normal((512, 256)), jnp.float32),
         "b": jnp.asarray(rng.standard_normal((64,)), jnp.float32)}
    template = jax.tree_util.tree_map(
        lambda x: np.zeros(np.shape(x), np.float32), d)
    packed, _ = dl.pack_delta_v2(d, density=1.0 / 32.0)

    def acc0():   # fresh each time: the kernel route donates it on TPU
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros(np.shape(x), jnp.float32), template)

    got = dl.accumulate_delta(acc0(), packed, 0.7)   # kernel route on TPU
    dense = dl.densify_packed_v2(packed, template)
    ref = dl.accumulate_delta(acc0(), dense, 0.7)
    for k_ in d:
        np.testing.assert_allclose(np.asarray(got[k_]),
                                   np.asarray(ref[k_]), atol=1e-6)
