"""Flash (blockwise-softmax) attention for TPU.

Online-softmax attention that never materializes the [T, T] score matrix in
HBM. The implementation rides JAX's Pallas TPU ops library
(``jax.experimental.pallas.ops.tpu.splash_attention``), which provides the
forward *and* backward kernels behind a ``custom_vjp`` — differentiability
is what makes this usable in the train step, where a forward-only kernel
would silently fall back to dense under ``jax.grad`` (Pallas has no
autodiff).

The schedule is the causal half at most. The library reads the causal
mask at trace time: a (q block, kv block) pair the mask covers whole does
no kernel work, a pair it leaves whole runs unmasked, and only the pairs
the diagonal crosses compute the mask. Those block tables are constants of
the program, and its grid still visits every pair: one that does no work
costs its step (0.3 us forward, 0.75 backward on the v5e). Packed rows of
at least ``TABLE_MIN_BLOCKS`` blocks go another way (:func:`_table_engages`
is the whole rule): the pairs their documents need are computed from their
OWN segment ids, on the device inside the step (:func:`needed_pairs`: a
pair runs unless the two blocks' id ranges are disjoint, so on the packer's
non-decreasing ids q block ``i`` runs the kv blocks from its first token's
document to ``i`` and nothing else; on any other ids it runs too much,
never too little), listed (:func:`_pair_list`), and two kernels of this
module walk the list and nothing else: their grid is ``(heads, pairs)``,
its second bound the COUNT of pairs, an operand of the call. They are the
library's kernels step for step (``_fwd_kernel``, ``_dkv_kernel``: the same
products in the same precisions, the causal and the segment mask in every
pair that runs), under the library's names, so a pair that runs computes
what it computed and a pair left out is one whose scores the segment mask
set to nothing: the output, dK and dV are the library's bit for bit; dQ
adds up in float32 in VMEM where the library writes every kv block's share
to HBM in q's dtype and sums those, so in bfloat16 it is the library's to a
rounding (:func:`block_pairs` counts the pairs that run and the causal
half's). Either way the backward is ONE kernel of five
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

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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


# Rows of at least this many blocks run the pairs their segment ids need
# (at two blocks nothing can be left out), and rows of at most TABLE_MAX_T
# tokens: the backward holds a head's whole dQ row in VMEM (1 KiB a token)
# and the kernels the pair list in scalar memory (``scripts/ab_flash.py
# --packed``, PERF.md section 6, PR 40: faster than the library's kernels
# at 4, 8 and 16 blocks a row).
TABLE_MIN_BLOCKS = 4
TABLE_MAX_T = 16384

# test hook: off the chip the rule selects the kernel all the same and it
# runs in the Pallas interpreter, so that the CPU lane reads the KERNEL's
# numbers (set via use_interpret)
_FORCE_INTERPRET = False


def use_interpret(on: bool) -> None:
    """Route :func:`flash_attention` through the interpreter (CPU test
    lanes). Production never sets this."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = bool(on)


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
    if not _selected(q, attention_mask):
        return None
    mesh = ambient_mesh()
    B, T, H, D = q.shape
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids

    def kernel(q, k, v, seg_q, seg_kv):
        if seg_q is not None:
            seg_q, seg_kv = seg_q.astype(jnp.int32), seg_kv.astype(jnp.int32)
        heads_first = ((q * D ** -0.5).transpose(0, 2, 1, 3),
                       k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
        if _table_engages(T, seg_q):
            out = _packed_attention(*heads_first, seg_q, seg_kv,
                                    _block_sizes(T).block_q)
        else:
            seg = (None if seg_q is None
                   else splash.SegmentIds(q=seg_q, kv=seg_kv))
            # q.shape[2]: the heads this device holds under the shard_map
            out = jax.vmap(_causal_kernel(T, q.shape[2]))(*heads_first, seg)
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


def _selected(q: jax.Array, attention_mask: Optional[jax.Array]) -> bool:
    """The whole selection rule: the chip (or the test hook), the shapes
    and mask :func:`supports` takes, and no sequence-sharded mesh."""
    if not ((_on_tpu() or _FORCE_INTERPRET) and supports(q, attention_mask)):
        return False
    mesh = ambient_mesh()
    return mesh is None or mesh.shape.get("sp", 1) == 1


def _table_engages(T: int, segment_ids: Optional[jax.Array]) -> bool:
    """Whether the kernels walk the pairs the rows' segment ids need: only
    where there are ids, and only for a row long enough for it to pay.
    Elsewhere the library's kernels over :func:`_causal_kernel`'s constants
    stand, and the program is the one it was."""
    return (segment_ids is not None and T <= TABLE_MAX_T
            and T // _block_sizes(T).block_q >= TABLE_MIN_BLOCKS)


def needed_pairs(seg_q: jax.Array, seg_kv: jax.Array,
                 block: int) -> jax.Array:
    """[..., T] segment ids -> [..., T / block, T / block] booleans: may
    (q block i, kv block j) hold a pair that is causal and in one segment?
    Yes on the diagonal (a row always sees itself; the kernel's softmax
    needs one block that runs), never above it, and below it unless the
    two blocks' id ranges are disjoint. For ids that do not decrease along
    the row (data/packing.py) that is exact; for any others it says yes
    too often, never no wrongly. No ``[T, T]`` array is built."""
    def ends(seg):
        blocks = seg.reshape(*seg.shape[:-1], -1, block)
        return blocks.min(-1), blocks.max(-1)
    q_lo, q_hi = ends(seg_q)
    kv_lo, kv_hi = ends(seg_kv)
    meet = ((q_lo[..., :, None] <= kv_hi[..., None, :])
            & (kv_lo[..., None, :] <= q_hi[..., :, None]))
    i = jnp.arange(q_lo.shape[-1])
    return (meet & (i[None, :] < i[:, None])) | (i[None, :] == i[:, None])


def _pair_list(needed: jax.Array):
    """[B, n, n] booleans -> the grid of a kernel that visits the True
    pairs and no others: ``(row, major, minor, edges, count)``, the first
    four int32 ``[B n (n + 1) / 2]`` (what a triangle can hold) in the
    array's own order (row, then axis 1, then axis 2), ``count`` how many
    of them are pairs. Past ``count`` the last pair is named again: the
    grid ends at ``count``, and what the pipeline looks up one step ahead
    is a block it already holds. ``edges`` bit 0 / 1: first / last pair of
    its (row, major) group, whose accumulators the kernel zeroes and
    writes there; bit 2 / 3: first / last pair of its row."""
    B, n, _ = needed.shape
    size = B * n * (n + 1) // 2
    flat = needed.reshape(-1)
    count = flat.sum(dtype=jnp.int32)
    at, = jnp.nonzero(flat, size=size, fill_value=0)
    step = jnp.arange(size, dtype=jnp.int32)
    at = jnp.where(step < count, at, at[count - 1]).astype(jnp.int32)

    def edges(key):
        first = jnp.concatenate([jnp.ones(1, bool), key[1:] != key[:-1]])
        last = jnp.concatenate([key[1:] != key[:-1], jnp.ones(1, bool)])
        return first, last | (step == count - 1)
    group_first, group_last = edges(at // n)
    row_first, row_last = edges(at // (n * n))
    bits = (group_first + 2 * group_last + 4 * row_first + 8 * row_last)
    return (at // (n * n), at // n % n, at % n, bits.astype(jnp.int32),
            count)


_LANES, _SUBLANES = 128, 8
_MASK_VALUE = splash.DEFAULT_MASK_VALUE
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _allowed(q_block, kv_block, q_ids, kv_ids, block: int, kv_axis: int):
    """[block, block] booleans of one block pair, keys along ``kv_axis``:
    causal (positions from the two block numbers) and of one segment."""
    shape = (block, block)
    kv_pos = kv_block * block + jax.lax.broadcasted_iota(
        jnp.int32, shape, kv_axis)
    q_pos = q_block * block + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 - kv_axis)
    return (q_pos >= kv_pos) & (q_ids == kv_ids)


def _fwd_kernel(_row, q_block, kv_block, edges, q_ref, k_ref, v_ref,
                q_ids_ref, kv_ids_ref, out_ref, *rest, block: int):
    """One (q block, kv block) pair of the forward: the library's
    ``flash_attention_kernel`` step for step (the same products in the same
    precisions, the running maximum and sum 128 lanes wide), on a grid
    whose second axis is the pair list of :func:`_pair_list`, q-major."""
    *lse_ref, m_ref, l_ref, acc_ref = rest
    p = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(edges[p] & 1 != 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)

    qk = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                             preferred_element_type=f32)
    wide = block // _LANES
    allowed = _allowed(q_block[p], kv_block[p],
                       jnp.tile(q_ids_ref[...], (1, wide)),
                       kv_ids_ref[:1, :], block, kv_axis=1)
    qk = jnp.where(allowed, qk, _MASK_VALUE)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_next = jnp.maximum(m_prev, qk.max(axis=-1)[:, None])
    s = jnp.exp(qk - jnp.tile(m_next, (1, wide)))
    alpha = jnp.exp(m_prev - m_next)
    l_ref[...] = jax.lax.broadcast_in_dim(
        s.sum(axis=-1), l_prev.shape, (0,)) + alpha * l_prev
    m_ref[...] = m_next
    D = acc_ref.shape[-1]
    lanes = lambda x: jnp.tile(x, (1, pl.cdiv(D, _LANES)))[..., :D]
    acc_ref[...] = lanes(alpha) * acc_ref[...] + jax.lax.dot_general(
        s, v_ref[...].astype(f32), _NN)

    @pl.when(edges[p] & 2 != 0)
    def _():
        l = l_ref[...]
        out_ref[...] = (acc_ref[...] * lanes(1.0 / l)).astype(out_ref.dtype)
        for ref in lse_ref:
            ref[...] = jnp.log(l) + m_ref[...]


def _dkv_kernel(_row, kv_block, q_block, edges, q_ref, k_ref, v_ref,
                q_ids_ref, kv_ids_ref, lse_ref, do_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                block: int):
    """One pair of the fused backward, kv-major: the library's
    ``_flash_attention_dkv_kernel`` product for product (five of them, from
    one pass over the scores). dK and dV add up over a kv block's pairs,
    which follow each other; dQ adds up in float32 over the whole row of
    one head, ``[T, D]`` of VMEM, and is written once a row (the library
    writes a ``[T / block, H, T, D]`` array of shares and sums it after)."""
    p = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(edges[p] & 4 != 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(edges[p] & 1 != 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    qk = jax.lax.dot_general(k, q, _NT, preferred_element_type=f32)
    allowed = _allowed(q_block[p], kv_block[p], q_ids_ref[:1, :],
                       jnp.tile(kv_ids_ref[...], (1, block // _LANES)),
                       block, kv_axis=0)
    qk = jnp.where(allowed, qk, _MASK_VALUE)
    prob = jnp.exp(qk - lse_ref[:1, :])
    dv_acc[...] += jax.lax.dot(prob.astype(do.dtype), do,
                               preferred_element_type=f32)
    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=f32)
    ds = (dp - di_ref[:1, :]) * prob
    dk_acc[...] += jax.lax.dot_general(ds.astype(do.dtype), q, _NN,
                                       preferred_element_type=f32)
    rows = pl.ds(pl.multiple_of(q_block[p] * block, block), block)
    dq_acc[rows, :] += jax.lax.dot_general(ds.T.astype(k.dtype), k, _NN,
                                           preferred_element_type=f32)

    @pl.when(edges[p] & 2 != 0)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(edges[p] & 8 != 0)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _ids_operands(seg_q, seg_kv, block: int, kv_axis: int):
    """The two id operands with their block specs: the ids of the axis that
    runs along lanes once a sublane tile, the other's once a lane tile (the
    library's expansion, for Mosaic's tilings)."""
    def along_lanes(seg, which):
        return (jnp.broadcast_to(seg[:, None, :],
                                 (seg.shape[0], _SUBLANES, seg.shape[1])),
                pl.BlockSpec((None, _SUBLANES, block),
                             lambda h, p, row, *at: (row[p], 0, at[which][p])))

    def along_sublanes(seg, which):
        return (jnp.broadcast_to(seg[:, :, None], (*seg.shape, _LANES)),
                pl.BlockSpec((None, block, _LANES),
                             lambda h, p, row, *at: (row[p], at[which][p], 0)))
    # `at` is (major, minor, edges): the q block is the forward's major and
    # the backward's minor
    if kv_axis == 1:
        return along_sublanes(seg_q, 0), along_lanes(seg_kv, 1)
    return along_lanes(seg_q, 1), along_sublanes(seg_kv, 0)


def _head_blocks(block: int, D: int, which: int) -> pl.BlockSpec:
    """One ``[block, D]`` block of a ``[B, H, T, D]`` array: the pair's row,
    the grid's head, the pair's major (0) or minor (1) block."""
    return pl.BlockSpec((None, None, block, D),
                        lambda h, p, row, *at: (row[p], h, at[which][p], 0))


def _vmem_limit(T: int) -> int:
    # the backward's whole-row dQ (float32 beside the doubly buffered
    # output, a head of 64 stored 128 lanes wide) on top of what the pairs'
    # own blocks take
    return 32 * 2 ** 20 + T * _LANES * (4 + 2 * 2)


def _fwd_call(q, k, v, seg_q, seg_kv, *, block: int, save_residuals: bool):
    B, H, T, D = q.shape
    *table, count = _pair_list(needed_pairs(seg_q, seg_kv, block))
    (q_ids, q_ids_spec), (kv_ids, kv_ids_spec) = _ids_operands(
        seg_q, seg_kv, block, kv_axis=1)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [_head_blocks(block, D, 0)]
    if save_residuals:
        out_shape.append(jax.ShapeDtypeStruct((B, H, T, _LANES), jnp.float32))
        out_specs.append(_head_blocks(block, _LANES, 0))
    name = _kernel_name(is_mqa=False, save_residuals=save_residuals,
                        is_segmented=True, phase="fwd")
    with jax.named_scope(name):
        out, *lse = pl.pallas_call(
            functools.partial(_fwd_kernel, block=block),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(H, count),
                in_specs=[_head_blocks(block, D, 0), _head_blocks(block, D, 1),
                          _head_blocks(block, D, 1), q_ids_spec, kv_ids_spec],
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                                pltpu.VMEM((block, _LANES), jnp.float32),
                                pltpu.VMEM((block, D), jnp.float32)]),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            name=name, interpret=_FORCE_INTERPRET,
        )(*table, q, k, v, q_ids, kv_ids)
    return (out, lse[0][..., 0]) if save_residuals else out


def _dkv_call(q, k, v, seg_q, seg_kv, lse, do, di, *, block: int):
    B, H, T, D = q.shape
    *table, count = _pair_list(
        needed_pairs(seg_q, seg_kv, block).swapaxes(1, 2))
    (q_ids, q_ids_spec), (kv_ids, kv_ids_spec) = _ids_operands(
        seg_q, seg_kv, block, kv_axis=0)
    # a statistic a q position, along lanes, once a sublane tile
    stat_spec = pl.BlockSpec(
        (None, None, _SUBLANES, block),
        lambda h, p, row, kv, q_block, _: (row[p], h, 0, q_block[p]))
    wide = lambda x: jnp.broadcast_to(x[:, :, None, :], (B, H, _SUBLANES, T))
    name = _kernel_name(is_mqa=False, save_residuals=False,
                        is_segmented=True, phase="dkv")
    with jax.named_scope(name):
        return pl.pallas_call(
            functools.partial(_dkv_kernel, block=block),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(H, count),
                in_specs=[_head_blocks(block, D, 1), _head_blocks(block, D, 0),
                          _head_blocks(block, D, 0), q_ids_spec, kv_ids_spec,
                          stat_spec, _head_blocks(block, D, 1), stat_spec],
                out_specs=[
                    pl.BlockSpec((None, None, T, D),
                                 lambda h, p, row, *_: (row[p], h, 0, 0)),
                    _head_blocks(block, D, 0), _head_blocks(block, D, 0)],
                scratch_shapes=[pltpu.VMEM((T, D), jnp.float32),
                                pltpu.VMEM((block, D), jnp.float32),
                                pltpu.VMEM((block, D), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in (q, k, v)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(T)),
            name=name, interpret=_FORCE_INTERPRET,
        )(*table, q, k, v, q_ids, kv_ids, wide(lse), do, wide(di))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _packed_attention(q, k, v, seg_q, seg_kv, block: int):
    """Causal attention inside the segments of ``[B, H, T, D]`` rows, over
    the block pairs the rows' ids need and no others."""
    return _fwd_call(q, k, v, seg_q, seg_kv, block=block,
                     save_residuals=False)


def _packed_attention_fwd(q, k, v, seg_q, seg_kv, block):
    out, lse = _fwd_call(q, k, v, seg_q, seg_kv, block=block,
                         save_residuals=True)
    # what a remat-wrapped block keeps (KEEP_RESIDUALS), as the library
    # names its own
    out = checkpoint_name(out, RESIDUAL_NAME)
    lse = checkpoint_name(lse, RESIDUAL_NAME)
    return out, (q, k, v, seg_q, seg_kv, out, lse)


def _packed_attention_bwd(block, residuals, do):
    q, k, v, seg_q, seg_kv, out, lse = residuals
    di = jnp.einsum("bhsd,bhsd->bhs", out.astype(jnp.float32),
                    do.astype(jnp.float32))
    dq, dk, dv = _dkv_call(q, k, v, seg_q, seg_kv, lse, do, di, block=block)
    return dq, dk, dv, None, None


_packed_attention.defvjp(_packed_attention_fwd, _packed_attention_bwd)


def block_pairs(q: jax.Array, attention_mask: Optional[jax.Array],
                segment_ids: Optional[jax.Array]
                ) -> Optional[tuple[jax.Array, jax.Array]]:
    """``(pairs the kernels run, pairs the causal mask alone would run)``
    of one :func:`flash_attention` call on these arguments, summed over the
    rows (the forward's list; the backward's is its transpose), or None
    where the library's kernels run over the constants. Their ratio is the share of the causal
    half the packing leaves: 1.0 for rows of one document each."""
    T = q.shape[1]
    if not (_selected(q, attention_mask) and _table_engages(T, segment_ids)):
        return None
    seg = segment_ids.astype(jnp.int32)
    needed = needed_pairs(seg, seg, _block_sizes(T).block_q)
    n = needed.shape[-1]
    return (needed.sum().astype(jnp.int32),
            jnp.int32(needed.shape[0] * n * (n + 1) // 2))


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
    program (long packed rows run :func:`_packed_attention` instead)."""
    return splash.make_splash_mha(
        splash_mask.MultiHeadMask([splash_mask.CausalMask((T, T))] * H),
        block_sizes=_block_sizes(T), head_shards=1, q_seq_shards=1,
        residual_checkpoint_name=RESIDUAL_NAME, interpret=_FORCE_INTERPRET)
