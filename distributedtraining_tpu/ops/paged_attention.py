"""Fused paged-attention decode kernel for TPU.

The serving plane's per-token cost: every decode step attends one fresh
query per sequence over that sequence's paged KV context. The XLA
spelling (engine/serve.py before this kernel) gathered every slot's full
padded context out of the page pool into a dense ``[B, S, Hkv, D]``
tensor per layer per token — O(B*S) HBM bytes moved to compute an
output whose useful work is O(sum(seq_lens)) — and then materialized a
``[B, Tq, S+Tq]`` boolean mask on top. This module deletes both: one
Pallas kernel walks each slot's page table, DMAs exactly the pages the
table names from HBM into VMEM, runs the fp32 online-softmax attend
in-kernel (GQA-aware: pages hold ``Hkv`` heads, queries ``Hq``; no
``jnp.repeat`` broadcast ever materializes), folds the step's OWN fresh
(k, v) in as the final context column (they are not in the pool yet —
the engine scatters them after the forward), and masks dead page slots
with ``seq_lens``.

Why not ``jax.experimental.pallas.ops.tpu.paged_attention``: the
library kernel downcasts every loaded K/V block to bfloat16 before the
QK/PV matmuls (``MultiPageAsyncCopyDescriptor._maybe_dequantize``),
which breaks this repo's greedy-parity contract (engine outputs pinned
token-identical to the full-recompute oracle at f32 — docs/serving.md).
This kernel keeps the pool dtype through the loads and accumulates in
fp32, so parity vs ``ops.attention.cached_attention`` holds to 1e-6.

Selection, not probing: :func:`paged_attention` (the model-facing
entry) picks the kernel from what it can observe — a TPU backend and a
shape the kernel's layout supports (:func:`kernel_supports`) — and the
XLA spelling (:func:`paged_decode_reference`, which IS the pre-kernel
math) otherwise. Once the kernel is selected, build and compile errors
propagate. ``interpret=True`` is an explicit argument of
:func:`paged_decode_attention` for the CPU tests; nothing chooses it on
its own.

Kernel layout: Mosaic tiles the two minor dims of every buffer to
(8|16, 128), so a ``[P, Hkv, D]`` page with D=64 cannot be DMA'd or
sliced as stored, and re-laying a ``[pages, P, Hkv, D]`` array as
lane-dense rows is a copy of the whole array under that tiling, not a
view. The pool is therefore STORED the way the kernel DMAs it — one
array per layer of lane-dense ``[pages, P, Hkv*D]`` rows
(engine/kv_pool.py) — and passed to the ``pallas_call`` as it lies. The
kernel never splits the ``Hkv*D`` lane axis: per-head reductions and
broadcasts go through a 0/1 segment matrix ``seg[Hkv*D, 128]`` on the
MXU (``(q*k) @ seg`` sums each head's D lanes into one score lane;
``p @ seg.T`` spreads a probability back over its head's lanes) at
HIGHEST precision, so the f32 parity contract survives. The XLA twin
gathers the table's pages from the same stored shape and splits
``Hkv*D`` into heads on the gathered context only.

Layouts: q / k_new / v_new are ``[B, Tq, H(kv), D]`` (decode is one
token per slot per step); the page pool is one layer's stored
``[pages, P, Hkv*D]`` array; ``page_tables`` is ``[B, MP]`` int32 into
the pool (padded rows point at trash page 0); ``seq_lens`` ``[B]`` is
each slot's REAL context length (the fresh token sits at position
``seq_lens[b]``, always visible to itself).

A WINDOW layer (``window=`` on every entry here) sees only the ``window``
newest positions up to the query itself: keys at positions ``<= newest -
window`` are masked, and context chunks (kernel) or blocks (XLA) wholly
behind that bound are never fetched. Nothing in this module reads an
absolute position (a rotation is applied before a row is cached), so the
mask is translation invariant: the engine hands a window layer a NARROW
table whose entry 0 is the first page the window still reaches and
``seq_lens`` counted from that page's first row (engine/kv_pool.py, the
shifted table). The windowed kernel is the same body under a Mosaic name
of its own, ``paged_window_decode_attention``; the float32 parity contract
above holds for it. ``window=None`` lowers every function to the text it
lowered to before the argument existed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, cached_attention

# one decode chunk = this many pages DMA'd + attended per grid step;
# the (slot, page) buckets ride a power-of-two ladder (engine/serve.py
# BucketLadder), so any larger MP is divisible and smaller MPs run as
# a single chunk
PAGES_PER_CHUNK = 8

_LANES = 128     # score lanes: one per kv head, zero-padded
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _chunk_pages(mp: int) -> int:
    """Largest power-of-two divisor of ``mp`` capped at PAGES_PER_CHUNK."""
    c = 1
    while c < PAGES_PER_CHUNK and mp % (c * 2) == 0:
        c *= 2
    return c


def kernel_supports(q: jax.Array, k_pages: jax.Array) -> bool:
    """The shapes the kernel's lane-dense layout handles: one query
    token, whole query groups per kv head, at most one score lane per
    kv head, ``Hkv*D`` a whole number of 128-lane tiles, and pages that
    are whole sublane tiles of the pool dtype (8 rows f32, 16 bf16)."""
    B, Tq, Hq, D = q.shape
    _, P, HD = k_pages.shape
    Hkv = HD // D
    sublanes = 8 * 4 // jnp.dtype(k_pages.dtype).itemsize
    return (Tq == 1 and Hkv * D == HD and 0 < Hkv <= _LANES
            and Hq % Hkv == 0 and HD % _LANES == 0 and P % sublanes == 0)


def _seg(hd: int, d: int, *, transpose: bool = False) -> jax.Array:
    """``seg[x, h] = 1.0`` where lane ``x`` of the ``Hkv*D`` axis
    belongs to kv head ``h`` (``x // d == h``, spelled without the
    integer divide); ``[hd, 128]``, or its ``[128, hd]`` transpose."""
    shape = (_LANES, hd) if transpose else (hd, _LANES)
    x = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transpose else 0)
    h = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if transpose else 1)
    return jnp.logical_and(x >= h * d, x < (h + 1) * d).astype(jnp.float32)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _rows8(x):
    """Broadcast a ``[1, n]`` row to the 8-sublane tile the MXU wants."""
    return jnp.broadcast_to(x, (8, x.shape[1]))


def _online_update(g, s, v, valid, seg_t, acc_ref, m_ref, l_ref):
    """Streaming-softmax accumulate for query-group row ``g``: ``s``
    ``[T, 128]`` scores (one lane per kv head), ``v`` ``[T, Hkv*D]``.
    ``acc`` holds the UNNORMALIZED weighted sum (the division by ``l``
    happens once, at finalize), ``m``/``l`` the running max / normalizer
    per head lane. ``p`` is re-zeroed under the mask so a fully-dead
    chunk contributes exact zeros — the blockwise_attention
    convention."""
    m_prev = m_ref[g:g + 1, :]                           # [1, 128]
    l_prev = l_ref[g:g + 1, :]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)        # [T, 128]
    alpha = jnp.exp(m_prev - m_new)
    m_ref[g:g + 1, :] = m_new
    l_ref[g:g + 1, :] = l_prev * alpha + jnp.sum(p, axis=0, keepdims=True)
    t = p.shape[0]
    if t % 8:                                            # the fresh column
        p = _rows8(p)
        pv = (_dot(p, seg_t) * v)[:1]
    else:
        pv = jnp.sum(_dot(p, seg_t) * v, axis=0, keepdims=True)
    alpha_x = _dot(_rows8(alpha), seg_t)[:1]             # [1, Hkv*D]
    acc_ref[g:g + 1, :] = acc_ref[g:g + 1, :] * alpha_x + pv


def _decode_kernel(page_tables_ref, seq_lens_ref,   # scalar prefetch
                   q_ref, k_pages_ref, v_pages_ref, k_new_ref, v_new_ref,
                   o_ref,
                   k_buf, v_buf, acc_ref, m_ref, l_ref, sem,
                   *, pages_per_chunk: int, page_size: int,
                   n_chunks: int, group: int, head_dim: int,
                   scale: float, window: int | None = None):
    """One (batch row, context chunk) grid step of the fused decode.

    Grid is ``(B, n_chunks + 1)``: the first ``n_chunks`` steps DMA
    ``pages_per_chunk`` pages of this row's table and fold them into
    the running online softmax (f32 ``m``/``l``/unnormalized ``acc``
    persist in VMEM scratch across the sequential grid); the FINAL step
    appends the fresh (k_new, v_new) column — the token being decoded,
    not yet in the pool — and writes ``acc / l``. Chunks wholly past
    ``seq_lens[b]`` skip both the DMA and the math (the bucket-padded
    tail of a short sequence costs nothing but the grid iteration). With
    ``window`` the fresh token sees positions ``> seq_len - window`` only:
    chunks wholly at or behind that bound skip likewise.
    """
    b = pl.program_id(0)
    i = pl.program_id(1)
    seq_len = seq_lens_ref[b]
    chunk = pages_per_chunk * page_size
    hd = q_ref.shape[-1]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale             # [G, Hkv*D]

    runs = jnp.logical_and(i < n_chunks, i * chunk < seq_len)
    if window is not None:
        # the chunk's last position is still inside the window
        runs = jnp.logical_and(runs, (i + 1) * chunk - 1 > seq_len - window)

    @pl.when(runs)
    def _context_chunk():
        # gather exactly the pages the table names for this chunk
        for j in range(pages_per_chunk):
            page = page_tables_ref[b, i * pages_per_chunk + j]
            rows = pl.ds(j * page_size, page_size)
            pltpu.make_async_copy(
                k_pages_ref.at[page], k_buf.at[rows], sem.at[0]).start()
            pltpu.make_async_copy(
                v_pages_ref.at[page], v_buf.at[rows], sem.at[1]).start()
        for j in range(pages_per_chunk):
            rows = pl.ds(j * page_size, page_size)
            pltpu.make_async_copy(
                k_pages_ref.at[0], k_buf.at[rows], sem.at[0]).wait()
            pltpu.make_async_copy(
                v_pages_ref.at[0], v_buf.at[rows], sem.at[1]).wait()
        k = k_buf[...].astype(jnp.float32)               # [T, Hkv*D]
        v = v_buf[...].astype(jnp.float32)
        seg, seg_t = _seg(hd, head_dim), _seg(hd, head_dim, transpose=True)
        pos = i * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, _LANES), 0)
        valid = pos < seq_len                            # dead pages masked
        if window is not None:
            valid = jnp.logical_and(valid, pos > seq_len - window)
        for g in range(group):
            # s[t, h] = q[g, head h lanes] . k[t, head h lanes]
            s = _dot(k * q[g:g + 1, :], seg)             # [T, 128]
            s = jnp.where(valid, s, NEG_INF)
            _online_update(g, s, v, valid, seg_t, acc_ref, m_ref, l_ref)

    @pl.when(i == n_chunks)
    def _append_fresh_and_finalize():
        kn = k_new_ref[0].astype(jnp.float32)            # [1, Hkv*D]
        vn = v_new_ref[0].astype(jnp.float32)
        seg, seg_t = _seg(hd, head_dim), _seg(hd, head_dim, transpose=True)
        valid = jnp.ones((1, _LANES), dtype=jnp.bool_)
        for g in range(group):
            s = _dot(_rows8(kn * q[g:g + 1, :]), seg)[:1]
            _online_update(g, s, vn, valid, seg_t, acc_ref, m_ref, l_ref)
        # l >= exp(0) > 0 on real head lanes; padded lanes never spread
        # (their seg_t rows are zero), so clamp only guards 0 * inf
        l_x = _dot(jnp.maximum(l_ref[...], 1e-30), seg_t)   # [G8, Hkv*D]
        o_ref[0] = (acc_ref[...] / l_x)[:group].astype(o_ref.dtype)


def _build_call(B, G, HD, D, P, MP, q_dtype, page_dtype, interpret: bool,
                window: int | None = None):
    """Construct the pallas_call for one shape signature (lane-dense
    operands: q/out ``[B, G, HD]``, pages ``[pool, P, HD]``, fresh
    k/v ``[B, 1, HD]``)."""
    ppc = _chunk_pages(MP)
    n_chunks = MP // ppc
    g8 = -(-G // 8) * 8
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # page_tables, seq_lens
        grid=(B, n_chunks + 1),
        in_specs=[
            pl.BlockSpec((1, G, HD), lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # k_pages (HBM)
            pl.BlockSpec(memory_space=pl.ANY),        # v_pages (HBM)
            pl.BlockSpec((1, 1, HD), lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, HD), lambda b, i, *_: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, HD), lambda b, i, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((ppc * P, HD), page_dtype),        # k chunk
            pltpu.VMEM((ppc * P, HD), page_dtype),        # v chunk
            pltpu.VMEM((g8, HD), jnp.float32),            # acc
            pltpu.VMEM((g8, _LANES), jnp.float32),        # running max
            pltpu.VMEM((g8, _LANES), jnp.float32),        # running sum
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, pages_per_chunk=ppc, page_size=P,
        n_chunks=n_chunks, group=G, head_dim=D, scale=D ** -0.5,
        **({} if window is None else {"window": window}))
    return pl.pallas_call(  # devprof: exempt (attributed under serve.decode in-step)
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, HD), q_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=("paged_decode_attention" if window is None
              else "paged_window_decode_attention"),
    )


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_tables: jax.Array,
                           seq_lens: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, *,
                           interpret: bool = False,
                           window: int | None = None) -> jax.Array:
    """The fused kernel. Raises ``ValueError`` on a shape
    :func:`kernel_supports` rejects; build and compile errors propagate.

    q/k_new/v_new: ``[B, 1, Hq/Hkv/Hkv, D]``; k_pages/v_pages: one
    layer's stored ``[pages, P, Hkv*D]`` pool, handed to the kernel as
    it lies; page_tables ``[B, MP]`` int32; seq_lens ``[B]`` int32.
    Returns ``[B, 1, Hq, D]``.

    ``interpret=True`` runs the Pallas interpreter so the KERNEL math is
    pinned on CPU (tier-1 tests); only a caller passes it.
    """
    if not kernel_supports(q, k_pages):
        raise ValueError(
            f"paged_decode_attention: unsupported shapes q={q.shape} "
            f"pages={k_pages.shape} {k_pages.dtype}")
    B, _, Hq, D = q.shape
    _, P, HD = k_pages.shape
    Hkv = HD // D
    G = Hq // Hkv
    MP = page_tables.shape[1]
    # query head hq = h * G + g  ->  row g, head-h lanes
    qg = q.reshape(B, Hkv, G, D).transpose(0, 2, 1, 3).reshape(B, G, HD)
    call = _build_call(B, G, HD, D, P, MP, q.dtype, k_pages.dtype,
                       interpret, window)
    out = call(page_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
               qg, k_pages, v_pages,
               k_new.reshape(B, 1, HD), v_new.reshape(B, 1, HD))
    out = out.reshape(B, G, Hkv, D).transpose(0, 2, 1, 3)
    return out.reshape(B, 1, Hq, D)


def paged_decode_reference(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_tables: jax.Array,
                           seq_lens: jax.Array, k_new: jax.Array,
                           v_new: jax.Array,
                           window: int | None = None) -> jax.Array:
    """The XLA spelling the kernel replaces — gather the table's pages
    from the stored ``[pages, P, Hkv*D]`` pool into a padded context
    (only the GATHERED rows are split into heads; the pool never is),
    append the fresh column, broadcast GQA heads,
    and run :func:`ops.attention.cached_attention` (whose context-length
    mask is an iota compare fused into the scores, not a materialized
    boolean buffer). This is the production CPU path AND the parity
    oracle the kernel is pinned against."""
    B, Tq, Hq, D = q.shape
    _, P, HD = k_pages.shape
    Hkv = HD // D
    MP = page_tables.shape[1]
    k_ctx = k_pages[page_tables].reshape(B, MP * P, Hkv, D)
    v_ctx = v_pages[page_tables].reshape(B, MP * P, Hkv, D)
    k_full = jnp.concatenate([k_ctx, k_new], axis=1)
    v_full = jnp.concatenate([v_ctx, v_new], axis=1)
    if Hkv != Hq:
        rep = Hq // Hkv
        k_full = jnp.repeat(k_full, rep, axis=2)
        v_full = jnp.repeat(v_full, rep, axis=2)
    if window is not None:
        return cached_attention(q, k_full, v_full, seq_lens, window)
    return cached_attention(q, k_full, v_full, seq_lens)


# a suffix (Tq > 1) over a paged context of at least this many positions
# attends it a block at a time (:func:`paged_suffix_attention`): the
# gathered spelling builds ``[B, Hq, Tq, S]`` float32 scores, 1 GiB at 64
# heads x 512 rows x 8,192 positions. Every cell served before the blocked
# spelling existed stays under it (their contexts are <= 4,096)
BLOCKED_MIN_CONTEXT = 8192
CONTEXT_BLOCK = 512      # positions gathered and attended a loop step


def paged_suffix_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_tables: jax.Array,
                           seq_lens: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, *,
                           block: int = CONTEXT_BLOCK,
                           window: int | None = None) -> jax.Array:
    """``Tq`` fresh tokens a row over a LONG paged context, in XLA: the
    mask semantics of :func:`paged_decode_reference` (context positions
    below ``seq_lens[b]``, the fresh rows causal among themselves), with
    no ``[Tq, context]`` tensor: a loop gathers ``block`` positions' pages
    at a time, scores them a K/V head's query group at once (``[B, Hkv,
    G, Tq, block]`` float32, GQA never broadcast) and folds them into a
    running float32 online softmax; the fresh rows are the last block.
    The loop runs only as far as the longest row's context, so a page
    table padded to the top of the ladder costs nothing for a short one.
    With ``window`` fresh row ``t`` sees positions ``> seq_lens[b] + t -
    window`` only, and the loop STARTS at the first block the earliest
    row's window still reaches: a window layer's blocks alone."""
    B, Tq, Hq, D = q.shape
    _, P, HD = k_pages.shape
    Hkv = HD // D
    G, MP = Hq // Hkv, page_tables.shape[1]
    bp = max(1, min(block // P, MP))                  # pages a loop step
    f32 = jnp.float32
    qg = (q.reshape(B, Tq, Hkv, G, D).astype(f32) * D ** -0.5)

    def fold(carry, k_blk, v_blk, valid):
        """k_blk, v_blk [B, S, Hkv, D]; valid [B, Tq, S]."""
        m, l, acc = carry                             # [B,Hkv,G,Tq](,D)
        s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k_blk.astype(f32),
                       preferred_element_type=f32)
        ok = valid[:, None, None]
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bhgqs,bshd->bhgqd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=f32)
        return (m_new, l * alpha + jnp.sum(p, axis=-1),
                acc * alpha[..., None] + pv)

    def in_context(pos):
        """pos [S] the positions of a context block -> valid [B, Tq, S]."""
        ok = jnp.broadcast_to(
            (pos[None, :] < seq_lens[:, None])[:, None, :],
            (B, Tq, pos.shape[0]))
        if window is None:
            return ok
        return ok & (pos[None, None, :] > seq_lens[:, None, None]
                     + jnp.arange(Tq)[None, :, None] - window)

    def context_block(i, carry):
        pages = jax.lax.dynamic_slice_in_dim(page_tables, i * bp, bp, axis=1)
        k_blk = k_pages[pages].reshape(B, bp * P, Hkv, D)
        v_blk = v_pages[pages].reshape(B, bp * P, Hkv, D)
        return fold(carry, k_blk, v_blk,
                    in_context(i * bp * P + jnp.arange(bp * P)))

    carry = (jnp.full((B, Hkv, G, Tq), NEG_INF, f32),
             jnp.zeros((B, Hkv, G, Tq), f32),
             jnp.zeros((B, Hkv, G, Tq, D), f32))
    n_blocks = jnp.minimum(-(-jnp.max(seq_lens) // (bp * P)), MP // bp)
    first = 0 if window is None else jnp.minimum(
        jnp.maximum(jnp.min(seq_lens) - window + 1, 0) // (bp * P), n_blocks)
    carry = jax.lax.fori_loop(first, n_blocks, context_block, carry)
    if MP % bp:
        # the table's last pages, where it is no whole number of blocks
        tail = page_tables[:, MP - MP % bp:]
        carry = fold(
            carry, k_pages[tail].reshape(B, -1, Hkv, D),
            v_pages[tail].reshape(B, -1, Hkv, D),
            in_context((MP - MP % bp) * P + jnp.arange((MP % bp) * P)))
    causal = jnp.tril(jnp.ones((Tq, Tq), bool))
    if window is not None:
        causal = causal & ~jnp.tril(jnp.ones((Tq, Tq), bool), -window)
    causal = jnp.broadcast_to(causal[None], (B, Tq, Tq))
    m, l, acc = fold(carry, k_new, v_new, causal)
    out = (acc / l[..., None]).astype(v_new.dtype)    # [B,Hkv,G,Tq,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, Hq, D)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_tables: jax.Array, seq_lens: jax.Array,
                    k_new: jax.Array, v_new: jax.Array,
                    window: int | None = None) -> jax.Array:
    """Model-facing entry (gpt2/llama decode blocks): the kernel on TPU
    at a shape its layout supports, the XLA reference otherwise —
    identical numerics either way (parity pinned in
    tests/test_paged_attention.py and
    tests_tpu/test_paged_attention_tpu.py). A suffix (``Tq > 1``) over a
    table of :data:`BLOCKED_MIN_CONTEXT` positions or more attends it in
    blocks (:func:`paged_suffix_attention`): a rule over the shape, so a
    short-context engine runs what it ran. A ``window`` layer (the table
    and lengths its group's: the module's docstring) runs the windowed
    kernel, and ANY suffix of it in blocks: its table is narrow, and the
    gathered spelling would score every row against all of it."""
    if window is not None:
        if _on_tpu() and kernel_supports(q, k_pages):
            return paged_decode_attention(q, k_pages, v_pages, page_tables,
                                          seq_lens, k_new, v_new,
                                          window=window)
        if q.shape[1] > 1:
            return paged_suffix_attention(q, k_pages, v_pages, page_tables,
                                          seq_lens, k_new, v_new,
                                          window=window)
        return paged_decode_reference(q, k_pages, v_pages, page_tables,
                                      seq_lens, k_new, v_new, window)
    if _on_tpu() and kernel_supports(q, k_pages):
        return paged_decode_attention(q, k_pages, v_pages, page_tables,
                                      seq_lens, k_new, v_new)
    if (q.shape[1] > 1 and page_tables.shape[1] * k_pages.shape[1]
            >= BLOCKED_MIN_CONTEXT):
        return paged_suffix_attention(q, k_pages, v_pages, page_tables,
                                      seq_lens, k_new, v_new)
    return paged_decode_reference(q, k_pages, v_pages, page_tables,
                                  seq_lens, k_new, v_new)
