"""Flash (blockwise-softmax) attention for TPU.

Online-softmax attention that never materializes the [T, T] score matrix in
HBM. The implementation rides JAX's Pallas TPU ops library
(``jax.experimental.pallas.ops.tpu.splash_attention``), which provides the
forward *and* backward kernels behind a ``custom_vjp`` — differentiability
is what makes this usable in the train step, where a forward-only kernel
would silently fall back to dense under ``jax.grad`` (Pallas has no
autodiff).

The schedule is the causal half only. The library reads the causal mask
at trace time: a (q block, kv block) pair the mask covers whole does no
kernel work, a pair it leaves whole runs unmasked, and only the pairs the
diagonal crosses compute the mask. The backward is ONE kernel of five
matrix products (dK, dV and dQ from one pass over the scores), and the
softmax statistics reach it as a ``[H, T]`` log-sum-exp. A remat-wrapped
block keeps those two arrays of the forward kernel, its output and the
log-sum-exp (``B x T x E x 2 + B x H x T x 4`` bytes an attention layer by
shape; heads of 64 are stored in 128-lane tiles on the chip, which doubles
the first term), so remat's re-run does not call the forward kernel again
(``RESIDUAL_NAME`` below, ``ops.attention.remat_policy``). Measured on the
v5e at the train cell's shape (B=4, H=20, T=1024, D=64, bfloat16, packed
documents; ``scripts/ab_flash.py``, PERF.md section 6, PR 28) against the
library's older ``flash_attention`` kernel that stood here before: forward
0.376 ms for 0.568, forward + backward 1.16 ms for 2.31. That kernel with
one 1,024-row q block never skipped a block (its test of the block's
bottom-left corner was true for all of them), ran seven products in two
backward kernels and first wrote its statistics to HBM 128 lanes wide;
re-blocked at its best (512 everywhere) it reached 0.346 / 1.96 ms.

Supports causal masking and packed-sequence ``segment_ids`` (block-diagonal
attention), which is the data pipeline's hot path. Selection is a rule over
what the code can observe (:func:`supports`: a TPU backend, no padding
mask, block-aligned T, a head dim the kernel's lane tiling holds);
``flash_attention`` returns None when the rule says no and the caller takes
the dense or blockwise XLA path (ops/attention.py) — identical numerics,
different memory profile. Once the rule says yes, the kernel's build and
compile errors propagate.

Layouts: this framework uses [B, T, H, D]; the kernel wants [H, T, D] and
is mapped over B. The transposes are free at trace level (XLA fuses them
into the kernel's block loads). The kernel takes no scale: q is scaled by
``D ** -0.5`` before it, in q's dtype (exact in bfloat16 at D = 64, a power
of two; one more rounding of q elsewhere).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as splash_mask)
from jax.sharding import PartitionSpec as P

from .embed import ambient_mesh


# The library names its kernels ``splash_mha_<phase>...``; they run here as
# ``flash_mha_<phase>...``, as the kernels that stood here were called:
# the device-trace readers tell the attention kernels by ``flash`` in an
# instruction's own name (benchmarks/layer_metrics/flash_attn_roofline.json),
# and the name is the library's to give (an enclosing ``jax.named_scope``
# does not reach it). Set once, at import: the library's own ``jax.jit``
# caches a trace under the name it was made with.
_library_kernel_name = splash.get_kernel_name


def _kernel_name(*args, **kwargs) -> str:
    return _library_kernel_name(*args, **kwargs).replace(
        "splash_", "flash_", 1)


splash.get_kernel_name = _kernel_name


# The ``checkpoint_name`` the library gives the forward kernel's ``out`` and
# log-sum-exp inside its forward rule, and the ``jax.checkpoint`` policy that
# saves what carries it (``ops.attention.remat_policy``). Where the rule
# below says no, nothing carries the name and the policy keeps nothing.
RESIDUAL_NAME = "flash_attn_residuals"
KEEP_RESIDUALS = jax.checkpoint_policies.save_only_these_names(RESIDUAL_NAME)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def supports(q: jax.Array, attention_mask: Optional[jax.Array]) -> bool:
    """The shape/mask rule, backend aside. Padding masks ride the dense
    path (the kernel takes segment ids, not key masks); short sequences
    gain nothing from blocking; head dims outside the kernel's lane
    tiling fail in Mosaic."""
    _, T, _, D = q.shape
    return (attention_mask is None and T >= 256 and T % 128 == 0
            and (D % 128 == 0 or D == 64))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *,
                    attention_mask: Optional[jax.Array] = None,
                    segment_ids: Optional[jax.Array] = None,
                    kv_segment_ids: Optional[jax.Array] = None
                    ) -> Optional[jax.Array]:
    """[B, T, H, D] causal flash attention, or None when the selection
    rule (TPU backend and :func:`supports`) says no.

    segment_ids: [B, T] packing ids (block-diagonal attention), as produced
    by data/packing.py.

    Under an ambient mesh the kernel runs per device inside a
    ``shard_map`` (GSPMD refuses to partition a Mosaic call): batch rows
    over dp x fsdp and heads over tp, which needs no collective because
    attention is independent per sequence and per head. A
    sequence-sharded mesh (sp > 1) is ring attention's, and the rule says
    no."""
    if not (_on_tpu() and supports(q, attention_mask)):
        return None
    mesh = ambient_mesh()
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        return None
    B, T, H, D = q.shape
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids

    def kernel(q, k, v, seg_q, seg_kv):
        seg = None
        if seg_q is not None:
            seg = splash.SegmentIds(q=seg_q.astype(jnp.int32),
                                    kv=seg_kv.astype(jnp.int32))
        # q.shape[2]: the heads this device holds under the shard_map
        out = jax.vmap(_causal_kernel(T, q.shape[2]))(
            (q * D ** -0.5).transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), seg)
        return out.transpose(0, 2, 1, 3)

    if mesh is None:
        return kernel(q, k, v, segment_ids, kv_segment_ids)
    # an axis that does not divide its dim stays replicated (duplicate
    # work on that axis, same answer)
    rows = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    if B % math.prod(mesh.shape[a] for a in rows):
        rows = ()
    heads = "tp" if H % mesh.shape.get("tp", 1) == 0 else None
    qkv = P(rows or None, None, heads, None)
    seg = None if segment_ids is None else P(rows or None, None)
    return shard_map(kernel, mesh=mesh, in_specs=(qkv, qkv, qkv, seg, seg),
                     out_specs=qkv, check_vma=False)(
        q, k, v, segment_ids, kv_segment_ids)


def _block_sizes(T: int) -> splash.BlockSizes:
    """The largest of 512, 256, 128 that divides T, for q and kv blocks of
    the forward and of the fused backward alike, each kv block computed in
    one piece. Of the sweep on the v5e (``scripts/ab_flash.py``, PR 28;
    blocks 128-1,024 x compute chunks 128-1,024 at B=4, H=20, T=1,024,
    D=64) 512 was the fastest forward (0.376 ms; 256: 0.658, 128: 1.754)
    and within 3% of the fastest backward (one 1,024 block, which skips
    nothing); at B=1 and T of 256, 512, 1,024 it is never slower than the
    kernel that stood here. The rule reads T alone: a 512 x 512 float32
    score block is 1 MiB of VMEM at any head dim."""
    b = next(b for b in (512, 256, 128) if T % b == 0)
    return splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=True)


def _causal_kernel(T: int, H: int) -> splash.SplashAttentionKernel:
    """The library's kernel over H heads of one [T, T] causal mask, for one
    sequence: ``kernel(q, k, v, segment_ids)`` on [H, T, D]. Built while
    tracing (a few ms): its block tables, which say for every block pair
    whether it runs and whether it needs the mask, become constants of the
    program."""
    return splash.make_splash_mha(
        splash_mask.MultiHeadMask([splash_mask.CausalMask((T, T))] * H),
        block_sizes=_block_sizes(T), head_shards=1, q_seq_shards=1,
        residual_checkpoint_name=RESIDUAL_NAME)
