"""Flash (blockwise-softmax) attention for TPU.

Online-softmax attention that never materializes the [T, T] score matrix in
HBM. The implementation rides JAX's Pallas TPU ops library
(``jax.experimental.pallas.ops.tpu.flash_attention``), which provides the
forward *and* backward kernels behind a ``custom_vjp`` — differentiability
is what makes this usable in the train step, where a forward-only kernel
would silently fall back to dense under ``jax.grad`` (Pallas has no
autodiff).

Supports causal masking and packed-sequence ``segment_ids`` (block-diagonal
attention), which is the data pipeline's hot path. Selection is a rule over
what the code can observe (:func:`supports`: a TPU backend, no padding
mask, block-aligned T, a head dim the kernel's lane tiling holds);
``flash_attention`` returns None when the rule says no and the caller takes
the dense or blockwise XLA path (ops/attention.py) — identical numerics,
different memory profile. Once the rule says yes, the kernel's build and
compile errors propagate.

Layouts: this framework uses [B, T, H, D]; the kernel wants [B, H, T, D].
The transposes are free at trace level (XLA fuses them into the kernel's
block loads).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental.pallas.ops.tpu import flash_attention as fa
from jax.sharding import PartitionSpec as P

from .embed import ambient_mesh


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
            seg = fa.SegmentIds(q=seg_q.astype(jnp.int32),
                                kv=seg_kv.astype(jnp.int32))
        out = fa.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), segment_ids=seg, causal=True,
            sm_scale=D ** -0.5, block_sizes=_block_sizes(T))
        return out.transpose(0, 2, 1, 3).astype(q.dtype)

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


def _block_sizes(T: int):
    """A whole-row query block (capped at 1024) with 256-wide kv blocks,
    in place of the library default of 128-wide blocks. Chosen on an
    earlier machine and JAX; compiles on the v5e under jax 0.9.0, its
    speed against the default is not measured."""
    bq = next(b for b in (1024, 512, 256, 128) if T % b == 0)
    bk = 256 if T % 256 == 0 else 128
    return fa.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk,
        block_k_dkv=bk, block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)
