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
costs its step (0.3 us forward, 0.75 backward on the v5e). Packed rows go
another way wherever their heads fill whole 128-lane blocks
(:func:`_table_engages` is the whole rule): the pairs their documents need
are computed from their OWN segment ids, on the device inside the step
(:func:`needed_pairs`: a pair runs unless the two blocks' id ranges are
disjoint, so on the packer's non-decreasing ids q block ``i`` runs the kv
blocks from its first token's document to ``i`` and nothing else; on any
other ids it runs too much, never too little), listed (:func:`_pair_list`),
and two kernels of this module walk the list and nothing else: their grid
is ``(lane blocks, pairs)``, its second bound the COUNT of pairs, an operand
of the call. They are the library's kernels step for step a head
(``_fwd_kernel``, ``_dkv_kernel``: the same products in the same
precisions, the causal and the segment mask in every pair that runs),
under the library's names, so a pair that runs computes what it computed
and a pair left out is one whose scores the segment mask set to nothing:
the output and dV are the library's bit for bit; dQ adds up in float32 in
VMEM where the library writes every kv block's share to HBM in q's dtype
and sums those, so in bfloat16 it is the library's to a rounding, as is dK
in a few elements (:func:`block_pairs` counts the pairs that run and the
causal half's). Either way the backward is ONE kernel of five matrix
products (dK, dV and dQ from one pass over the scores), and the softmax
statistics reach it as a ``[H, T]`` log-sum-exp. A remat-wrapped block
keeps those two arrays of the forward kernel, its output and the
log-sum-exp (``B x T x E x 2 + B x H x T x 4`` bytes an attention layer;
the library's heads-first output of heads of 64 is stored in 128-lane
tiles on the chip, twice that first term; this module's is stored as its
shape says), so remat's re-run does not call the forward kernel again
(``RESIDUAL_NAME`` below, ``ops.attention.remat_policy``). Measured on the
v5e at the train cell's shape (B=4, H=20, T=1024, D=64, bfloat16, packed
documents; ``scripts/ab_flash.py``, PERF.md section 6, PRs 28, 40, 42).

Supports causal masking and packed-sequence ``segment_ids`` (block-diagonal
attention), which is the data pipeline's hot path. Selection is a rule over
what the code can observe (:func:`supports`: a TPU backend, no padding
mask, block-aligned T, a head dim the kernel's lane tiling holds);
``flash_attention`` returns None when the rule says no and the caller takes
the dense or blockwise XLA path (ops/attention.py) — identical numerics,
different memory profile. Once the rule says yes, the kernel's build and
compile errors propagate.

Layouts: this framework holds [B, T, H, D], which is ``[B, T, H D]`` as it
lies, and that is what this module's kernels take and give: a
``[block, 128]`` block of it holds ``128 / D`` heads (two heads of 64; one
head of 128), each head's products run on its own lanes, and q's scale
(``D ** -0.5``, in q's dtype: exact in bfloat16 at D = 64, a power of two;
one more rounding of q elsewhere) is applied inside. A fused projection's
``[B, T, 3 H D]`` goes in as ONE array (:func:`flash_attention_qkv`: the
index maps of k and v start ``H D / 128`` and ``2 H D / 128`` lane blocks
in), and the output is the ``[B, T, H D]`` the output projection reads: no
split, scaling, transpose or copy lies between the projections and the
kernels. The library's kernels want [H, T, D], mapped over B: where they
run (no ids, a row of one block, an odd number of heads of 64 a device) q
is scaled and all three are transposed heads first and back, as before.
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
# ... and the backward kernel's pair list where this module's kernels run: a
# few dozen integers a layer, the same in every layer of a step. Kept, the
# lists of all layers are ONE computation of the forward pass; left to the
# re-run, every layer sorts its own behind remat's barrier.
PAIRS_NAME = "flash_attn_pairs"
KEEP_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    RESIDUAL_NAME, PAIRS_NAME)


# Rows of at least this many blocks run the pairs their segment ids need
# (a row of two blocks can leave out one of three: the pair below the
# diagonal, when no document crosses the middle), and rows of at most
# TABLE_MAX_T tokens: the backward holds a lane block's whole dQ row in VMEM
# (1 KiB a token) and the kernels the pair list in scalar memory
# (``scripts/ab_flash.py --packed`` and ``--layout``, PERF.md section 6,
# PRs 40 and 42: faster than the library's kernels at 2, 4, 8 and 16 blocks
# a row).
TABLE_MIN_BLOCKS = 2
TABLE_MAX_T = 16384

# the registry's names for what :func:`block_pairs` counts, under which a
# model's train step hands them out (docs/observability.md)
BLOCK_PAIR_COUNTERS = ("train.attn.block_pairs_run",
                       "train.attn.block_pairs_causal")

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
    if not _selected(q.shape, attention_mask):
        return None
    mesh = ambient_mesh()
    B, T, H, D = q.shape
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids

    def kernel(q, k, v, seg_q, seg_kv):
        if seg_q is not None:
            seg_q, seg_kv = seg_q.astype(jnp.int32), seg_kv.astype(jnp.int32)
        b, _, h, _ = q.shape    # the rows and heads this device holds
        if _table_engages(T, h, D, seg_q):
            # [B, T, H, D] is [B, T, H D] as it lies: no copy either way
            rows_major = tuple(x.reshape(b, T, h * D) for x in (q, k, v))
            return _packed_attention(rows_major, seg_q, seg_kv, h,
                                     _block_sizes(T).block_q).reshape(q.shape)
        seg = (None if seg_q is None
               else splash.SegmentIds(q=seg_q, kv=seg_kv))
        out = jax.vmap(_causal_kernel(T, h))(
            (q * D ** -0.5).transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), seg)
        return out.transpose(0, 2, 1, 3)

    if mesh is None:
        return kernel(q, k, v, segment_ids, kv_segment_ids)
    rows = _row_axes(mesh, B)
    heads = None if _heads_here(H) == H else "tp"
    qkv = P(rows, None, heads, None)
    seg = None if segment_ids is None else P(rows, None)
    return shard_map(kernel, mesh=mesh, in_specs=(qkv, qkv, qkv, seg, seg),
                     out_specs=qkv, check_vma=False)(
        q, k, v, segment_ids, kv_segment_ids)


def flash_attention_qkv(qkv: jax.Array, n_head: int,
                        *,
                        attention_mask: Optional[jax.Array] = None,
                        segment_ids: Optional[jax.Array] = None
                        ) -> Optional[jax.Array]:
    """:func:`flash_attention` on a fused projection's ``[B, T, 3 E]``
    (q, k and v side by side, heads inside each) -> ``[B, T, E]``, or None
    where the rule says no. Where this module's kernels run
    (:func:`_table_engages`) they read q, k and v out of the ONE array and
    write the heads side by side again: nothing is split, scaled,
    transposed or copied between the two projections and the kernels.
    Elsewhere (the library's kernels; heads split over ``tp``) it is
    :func:`flash_attention` on the three parts."""
    B, T, E = qkv.shape[0], qkv.shape[1], qkv.shape[2] // 3
    shape = (B, T, n_head, E // n_head)
    if not _selected(shape, attention_mask):
        return None
    if (_heads_here(n_head) != n_head
            or not _table_engages(T, *shape[2:], segment_ids)):
        q, k, v = (x.reshape(shape) for x in jnp.split(qkv, 3, axis=-1))
        return flash_attention(q, k, v, attention_mask=attention_mask,
                               segment_ids=segment_ids).reshape(B, T, E)

    def kernel(qkv, seg):
        seg = seg.astype(jnp.int32)
        return _packed_attention((qkv,), seg, seg, n_head,
                                 _block_sizes(T).block_q)

    mesh = ambient_mesh()
    if mesh is None:
        return kernel(qkv, segment_ids)
    rows = _row_axes(mesh, B)
    return shard_map(kernel, mesh=mesh,
                     in_specs=(P(rows, None, None), P(rows, None)),
                     out_specs=P(rows, None, None), check_vma=False)(
        qkv, segment_ids)


def _row_axes(mesh, B: int):
    """The mesh axes the batch rows are split over inside the
    ``shard_map``: an axis that does not divide its dim stays replicated
    (duplicate work on that axis, same answer)."""
    rows = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    if B % math.prod(mesh.shape[a] for a in rows):
        rows = ()
    return rows or None


def _heads_here(H: int) -> int:
    """The heads of H a device holds inside the ``shard_map``."""
    mesh = ambient_mesh()
    tp = 1 if mesh is None else mesh.shape.get("tp", 1)
    return H // tp if H % tp == 0 else H


def _selected(shape, attention_mask: Optional[jax.Array]) -> bool:
    """The whole selection rule: the chip (or the test hook), the
    ``[B, T, H, D]`` shape and mask :func:`supports` takes, and no
    sequence-sharded mesh."""
    if not ((_on_tpu() or _FORCE_INTERPRET)
            and supports(jax.ShapeDtypeStruct(shape, jnp.float32),
                         attention_mask)):
        return False
    mesh = ambient_mesh()
    return mesh is None or mesh.shape.get("sp", 1) == 1


def _table_engages(T: int, H: int, D: int,
                   segment_ids: Optional[jax.Array]) -> bool:
    """Whether this module's kernels run, over the pairs the rows' segment
    ids need: where there are ids, the row is at least ``TABLE_MIN_BLOCKS``
    blocks and at most ``TABLE_MAX_T`` tokens long, and the H heads a
    device holds fill whole 128-lane blocks of ``[B, T, H D]`` (heads of 64
    in pairs; a head of 128 or more by itself). Elsewhere the library's
    kernels over :func:`_causal_kernel`'s constants stand, heads first, and
    the program is the one it was."""
    return (segment_ids is not None and T <= TABLE_MAX_T
            and T // _block_sizes(T).block_q >= TABLE_MIN_BLOCKS
            and (H * D) % max(D, _LANES) == 0)


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


def _mine(g: int, D: int, width: int):
    """[1, width] booleans: head ``g``'s ``D`` lanes of a lane block."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return (lane >= g * D) & (lane < (g + 1) * D)


def _head(ref, g: int, D: int):
    """Head ``g`` of a ``[block, lanes]`` block, for a product over ALL
    lanes: the block whole with the other heads' lanes exact zeros, so the
    product adds zeros to a float32 sum and nothing else (on a 128 x 128
    MXU a contraction of 64 costs the full pass anyway). Static 64-lane
    slices, the other spelling, compile too and run slower: Mosaic re-lays
    the upper half (``scripts/ab_flash.py --layout``, PERF.md section 6,
    PR 42)."""
    x = ref[...]
    if D >= x.shape[-1]:
        return x
    return jnp.where(_mine(g, D, x.shape[-1]), x, jnp.zeros_like(x))


def _accumulate(ref, g: int, D: int, share=None, *, scale=None,
                rows=slice(None)):
    """Head ``g``'s lanes of an accumulator: times ``scale``, a number a
    row (128 lanes wide, as the statistics are kept), plus the head's
    ``share``, which :func:`_head`'s operands left zero on the other
    heads' lanes (their scale is 1)."""
    x = ref[rows, :]
    width = x.shape[-1]
    if scale is not None:
        scale = jnp.tile(scale, (1, pl.cdiv(width, _LANES)))[:, :width]
        if D < width:
            scale = jnp.where(_mine(g, D, width), scale, 1.0)
        x = scale * x
    ref[rows, :] = x if share is None else x + share


def _fwd_kernel(_row, q_block, kv_block, edges, q_ref, k_ref, v_ref,
                q_ids_ref, kv_ids_ref, out_ref, *rest, block: int, D: int):
    """One (q block, kv block) pair of the forward for the heads of one
    lane block: the library's ``flash_attention_kernel`` step for step a
    head (the same products in the same precisions, the running maximum
    and sum 128 lanes wide), on a grid whose second axis is the pair list
    of :func:`_pair_list`, q-major. q is scaled here, in its own dtype."""
    *lse_ref, m_ref, l_ref, acc_ref = rest
    p = pl.program_id(1)
    f32 = jnp.float32
    heads = range(acc_ref.shape[-1] // D)

    @pl.when(edges[p] & 1 != 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)

    wide = block // _LANES
    allowed = _allowed(q_block[p], kv_block[p],
                       jnp.tile(q_ids_ref[...], (1, wide)),
                       kv_ids_ref[:1, :], block, kv_axis=1)
    for g in heads:
        qk = jax.lax.dot_general(_head(q_ref, g, D) * D ** -0.5,
                                 _head(k_ref, g, D), _NT,
                                 preferred_element_type=f32)
        qk = jnp.where(allowed, qk, _MASK_VALUE)
        m_prev, l_prev = m_ref[g], l_ref[g]
        m_next = jnp.maximum(m_prev, qk.max(axis=-1)[:, None])
        s = jnp.exp(qk - jnp.tile(m_next, (1, wide)))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[g] = jax.lax.broadcast_in_dim(
            s.sum(axis=-1), l_prev.shape, (0,)) + alpha * l_prev
        m_ref[g] = m_next
        _accumulate(acc_ref, g, D, jax.lax.dot_general(
            s, _head(v_ref, g, D).astype(f32), _NN), scale=alpha)

    @pl.when(edges[p] & 2 != 0)
    def _():
        for g in heads:
            l = l_ref[g]
            _accumulate(acc_ref, g, D, scale=1.0 / l)
            for ref in lse_ref:
                # a number a q position, along lanes
                ref[g] = (jnp.log(l) + m_ref[g]).T[:1]
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _dkv_kernel(_row, kv_block, q_block, edges, q_ref, k_ref, v_ref,
                q_ids_ref, kv_ids_ref, lse_ref, do_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                block: int, D: int):
    """One pair of the fused backward for the heads of one lane block,
    kv-major: the library's ``_flash_attention_dkv_kernel`` product for
    product a head (five of them, from one pass over the scores). dK and dV
    add up over a kv block's pairs, which follow each other; dQ adds up in
    float32 over the whole row, ``[T, lanes]`` of VMEM, and is written once
    a row with q's scale on it (the library writes a
    ``[T / block, H, T, D]`` array of shares and sums it after)."""
    p = pl.program_id(1)
    f32 = jnp.float32
    scale = D ** -0.5

    @pl.when(edges[p] & 4 != 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(edges[p] & 1 != 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    allowed = _allowed(q_block[p], kv_block[p], q_ids_ref[:1, :],
                       jnp.tile(kv_ids_ref[...], (1, block // _LANES)),
                       block, kv_axis=0)
    rows = pl.ds(pl.multiple_of(q_block[p] * block, block), block)
    for g in range(dq_acc.shape[-1] // D):
        q, k, v, do = (_head(q_ref, g, D) * scale, _head(k_ref, g, D),
                       _head(v_ref, g, D), _head(do_ref, g, D))
        qk = jax.lax.dot_general(k, q, _NT, preferred_element_type=f32)
        qk = jnp.where(allowed, qk, _MASK_VALUE)
        prob = jnp.exp(qk - lse_ref[g])
        _accumulate(dv_acc, g, D, jax.lax.dot(
            prob.astype(do.dtype), do, preferred_element_type=f32))
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=f32)
        ds = (dp - di_ref[g]) * prob
        _accumulate(dk_acc, g, D, jax.lax.dot_general(
            ds.astype(do.dtype), q, _NN, preferred_element_type=f32))
        _accumulate(dq_acc, g, D, jax.lax.dot_general(
            ds.T.astype(k.dtype), k, _NN, preferred_element_type=f32),
            rows=rows)

    @pl.when(edges[p] & 2 != 0)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(edges[p] & 8 != 0)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _ids_operands(seg_q, seg_kv, block: int, kv_axis: int):
    """The two id operands with their block specs: the ids of the axis that
    runs along lanes once a sublane tile, the other's once a lane tile (the
    library's expansion, for Mosaic's tilings)."""
    def along_lanes(seg, which):
        return (jnp.broadcast_to(seg[:, None, :],
                                 (seg.shape[0], _SUBLANES, seg.shape[1])),
                pl.BlockSpec((None, _SUBLANES, block),
                             lambda c, p, row, *at: (row[p], 0, at[which][p])))

    def along_sublanes(seg, which):
        return (jnp.broadcast_to(seg[:, :, None], (*seg.shape, _LANES)),
                pl.BlockSpec((None, block, _LANES),
                             lambda c, p, row, *at: (row[p], at[which][p], 0)))
    # `at` is (major, minor, edges): the q block is the forward's major and
    # the backward's minor
    if kv_axis == 1:
        return along_sublanes(seg_q, 0), along_lanes(seg_kv, 1)
    return along_lanes(seg_q, 1), along_sublanes(seg_kv, 0)


def _row_blocks(block: int, width: int, which: int,
                first: int = 0) -> pl.BlockSpec:
    """One ``[block, width]`` block of a ``[B, T, lanes]`` array: the
    pair's row, its major (0) or minor (1) block, the grid's lane block
    counted from ``first`` (where q, k or v starts in a fused array)."""
    return pl.BlockSpec(
        (None, block, width),
        lambda c, p, row, *at: (row[p], at[which][p], first + c))


def _stat_blocks(block: int, heads: int, n: int, which: int) -> pl.BlockSpec:
    """The statistics of a lane block's ``heads`` heads over one q block,
    out of a ``[B H, 1, T]`` array: a number a q position, along lanes.
    ``n``: the lane blocks of a row."""
    return pl.BlockSpec(
        (heads, 1, block),
        lambda c, p, row, *at: (row[p] * n + c, 0, at[which][p]))


def _vmem_limit(T: int, width: int, backward: bool) -> int:
    """What a kernel may take of VMEM, and no more: XLA keeps the step's
    activations in what the limit leaves, and under the 33 MiB this module
    asked before it evicted a 42 MB operand of the MLP's weight gradient to
    HBM in every layer of gpt2-large (PERF.md section 6, PR 42). At blocks
    of 512 the pairs' blocks, accumulators and ``[block, block]`` float32
    temporaries take 4.4 MiB forward and 5.4 backward, twice that 256 lanes
    wide (AOT for v5e, ``tests/test_tpu_aot.py``); the backward adds its
    whole-row dQ, float32 beside the doubly buffered output."""
    blocks = 8 * 2 ** 20 * width // _LANES
    return blocks + (T * width * (4 + 2 * 2) if backward else 0)


def _layout(arrays, H: int):
    """What the kernels are handed: ``(q, k, v)`` of ``[B, T, H D]`` each,
    or ONE fused ``[B, T, 3 H D]`` array three times over (no copy of
    either) -> ``((q, k, v), the lane block each starts at, D, the lane
    blocks of H heads, their width)``."""
    fused = len(arrays) == 1
    D = arrays[0].shape[-1] // (3 * H if fused else H)
    width = max(D, _LANES)
    n = H * D // width
    return (arrays * 3, (0, n, 2 * n), D, n, width) if fused else (
        arrays, (0, 0, 0), D, n, width)


# Both calls are jitted, as the library's kernel is: a model of 36 layers
# traces and lowers each kernel ONCE and calls it 36 times (unjitted, a
# warm set-up of gpt2-large spent 18 s more lowering 72 kernel bodies:
# PERF.md section 6, PR 42). ``interpret`` is an argument because a cached
# trace keeps the mode it was made in.
@functools.partial(jax.jit, static_argnames=("H", "block", "save_residuals",
                                             "interpret"))
def _fwd_call(arrays, seg_q, seg_kv, *, H: int, block: int,
              save_residuals: bool, interpret: bool):
    (q, k, v), first, D, n, width = _layout(arrays, H)
    B, T = seg_q.shape
    *table, count = _pair_list(needed_pairs(seg_q, seg_kv, block))
    (q_ids, q_ids_spec), (kv_ids, kv_ids_spec) = _ids_operands(
        seg_q, seg_kv, block, kv_axis=1)
    out_shape = [jax.ShapeDtypeStruct((B, T, H * D), q.dtype)]
    out_specs = [_row_blocks(block, width, 0)]
    if save_residuals:
        out_shape.append(jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32))
        out_specs.append(_stat_blocks(block, width // D, n, 0))
    name = _kernel_name(is_mqa=False, save_residuals=save_residuals,
                        is_segmented=True, phase="fwd")
    stat = pltpu.VMEM((width // D, block, _LANES), jnp.float32)
    with jax.named_scope(name):
        out, *lse = pl.pallas_call(
            functools.partial(_fwd_kernel, block=block, D=D),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(n, count),
                in_specs=[_row_blocks(block, width, 0, first[0]),
                          _row_blocks(block, width, 1, first[1]),
                          _row_blocks(block, width, 1, first[2]),
                          q_ids_spec, kv_ids_spec],
                out_specs=out_specs,
                scratch_shapes=[stat, stat,
                                pltpu.VMEM((block, width), jnp.float32)]),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(T, width, False)),
            name=name, interpret=interpret,
        )(*table, q, k, v, q_ids, kv_ids)
    return (out, lse[0].reshape(B, H, T)) if save_residuals else out


@functools.partial(jax.jit, static_argnames=("H", "block", "interpret"))
def _dkv_call(arrays, seg_q, seg_kv, pairs, lse, do, di, *, H: int,
              block: int, interpret: bool):
    (q, k, v), first, D, n, width = _layout(arrays, H)
    B, T = seg_q.shape
    *table, count = pairs
    (q_ids, q_ids_spec), (kv_ids, kv_ids_spec) = _ids_operands(
        seg_q, seg_kv, block, kv_axis=0)
    stat_spec = _stat_blocks(block, width // D, n, 1)
    stat = lambda x: x.reshape(B * H, 1, T)
    name = _kernel_name(is_mqa=False, save_residuals=False,
                        is_segmented=True, phase="dkv")
    with jax.named_scope(name):
        return pl.pallas_call(
            functools.partial(_dkv_kernel, block=block, D=D),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(n, count),
                in_specs=[_row_blocks(block, width, 1, first[0]),
                          _row_blocks(block, width, 0, first[1]),
                          _row_blocks(block, width, 0, first[2]),
                          q_ids_spec, kv_ids_spec, stat_spec,
                          _row_blocks(block, width, 1), stat_spec],
                out_specs=[
                    pl.BlockSpec((None, T, width),
                                 lambda c, p, row, *_: (row[p], 0, c)),
                    _row_blocks(block, width, 0),
                    _row_blocks(block, width, 0)],
                scratch_shapes=[pltpu.VMEM((T, width), jnp.float32),
                                pltpu.VMEM((block, width), jnp.float32),
                                pltpu.VMEM((block, width), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(do.shape, do.dtype)] * 3,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(T, width, True)),
            name=name, interpret=interpret,
        )(*table, q, k, v, q_ids, kv_ids, stat(lse), do, stat(di))


@functools.partial(jax.jit, static_argnames="block")
def _kv_major_pairs(seg_q, seg_kv, *, block: int):
    """The fused backward's pair list (jitted for the reason above)."""
    return _pair_list(needed_pairs(seg_q, seg_kv, block).swapaxes(1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _packed_attention(arrays, seg_q, seg_kv, H: int, block: int):
    """Causal attention inside the segments of ``[B, T]`` rows of H heads,
    rows-major (``arrays``: :func:`_layout`) -> ``[B, T, H D]``, over the
    block pairs the rows' ids need and no others."""
    return _fwd_call(arrays, seg_q, seg_kv, H=H, block=block,
                     save_residuals=False, interpret=_FORCE_INTERPRET)


def _packed_attention_fwd(arrays, seg_q, seg_kv, H, block):
    out, lse = _fwd_call(arrays, seg_q, seg_kv, H=H, block=block,
                         save_residuals=True, interpret=_FORCE_INTERPRET)
    # what a remat-wrapped block keeps (KEEP_RESIDUALS), as the library
    # names its own: ``out`` is the array the output projection reads
    out = checkpoint_name(out, RESIDUAL_NAME)
    lse = checkpoint_name(lse, RESIDUAL_NAME)
    # the backward's list, kv-major
    pairs = checkpoint_name(_kv_major_pairs(seg_q, seg_kv, block=block),
                            PAIRS_NAME)
    return out, (arrays, seg_q, seg_kv, pairs, out, lse)


def _packed_attention_bwd(H, block, residuals, do):
    arrays, seg_q, seg_kv, pairs, out, lse = residuals
    # a head's sum of out * do over its own lanes, as a product with the
    # 0 / 1 matrix that says which lanes are whose: a sum over PART of the
    # lanes would first re-lay the whole [B, T, E] float32 array. `HIGH`
    # (the operand in two bfloat16 pieces) is exact on the product of two
    # bfloat16 numbers, so this is the float32 sum the library makes.
    f32 = jnp.float32
    mine = jnp.repeat(jnp.eye(H, dtype=f32), out.shape[-1] // H, axis=0)
    di = jnp.einsum("bte,eh->bht", out.astype(f32) * do.astype(f32), mine,
                    precision=jax.lax.Precision.HIGH)
    grads = tuple(_dkv_call(arrays, seg_q, seg_kv, pairs, lse, do, di, H=H,
                            block=block, interpret=_FORCE_INTERPRET))
    if len(arrays) == 1:
        grads = (jnp.concatenate(grads, axis=-1),)
    return grads, None, None


_packed_attention.defvjp(_packed_attention_fwd, _packed_attention_bwd)


def block_pairs(q, attention_mask: Optional[jax.Array],
                segment_ids: Optional[jax.Array]
                ) -> Optional[tuple[jax.Array, jax.Array]]:
    """``(pairs the kernels run, pairs the causal mask alone would run)``
    of one :func:`flash_attention` call on these arguments (``q``: the
    ``[B, T, H, D]`` array or its shape alone), summed over the rows (the
    forward's list; the backward's is its transpose), or None where the
    library's kernels run over the constants. Their ratio is the share of
    the causal half the packing leaves: 1.0 for rows of one document each."""
    _, T, H, D = q.shape
    if not (_selected(q.shape, attention_mask)
            and _table_engages(T, _heads_here(H), D, segment_ids)):
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
