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
QK/PV matmuls (``MultiPageAsyncCopyDescriptor._maybe_dequantize``)
whatever the pool holds, which breaks this repo's greedy-parity contract
(engine outputs pinned token-identical to the full-recompute oracle at
f32 — docs/serving.md). This kernel multiplies in the POOL's dtype and
sums in float32: a float32 pool at ``Precision.HIGHEST`` (parity vs
``ops.attention.cached_attention`` to 1e-6), a bfloat16 pool with
bfloat16 K, V and probabilities into float32 sums, which is what the twin
(``cached_attention``: ``preferred_element_type=float32``,
``probs.astype(v.dtype)``), :func:`paged_suffix_attention` and
``mla_decode_attention`` compute for the same layers; bfloat16 products
are exact in float32, so the scores lose nothing. The softmax statistics,
the running max and sum and the accumulator are float32 for every pool,
and the scale multiplies the float32 scores, never a rounded ``q``.

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
kernel never splits the ``Hkv*D`` lane axis. Of the two spellings that
put the products on the MXU (ISSUE 47), it takes (b): a row's queries
become ONE block ``qb`` whose row ``(g, h)`` holds query row ``g``'s
head-``h`` lanes and zeros elsewhere, so a chunk costs two products for
all query rows of all heads: ``qb k^T`` ``[rows, T]`` (the zeros add
nothing: the same sums as a product a head) and ``p v`` kept ``[rows,
Hkv*D]`` float32, of which head ``h``'s lanes of row ``(g, h)`` are
selected ONCE, at the row's end. The rows' order is a rule over ``G`` and
``Hkv`` (:func:`_query_block`: the order with the fewer rows). Spelling
(a), a pair of products a K/V head's lane tile (two heads to a tile at
D = 64), was built on this body, measured and taken out: slower at all
four served shapes, by 9% (nemotron's) to 60% (gpt2-large's), 34% at solar's
(`scripts/ab_paged_decode.py`; PERF.md §6, PR 47: the MXU's time is the K
and V tiles it latches, the same in both, and a product a tile pays its
fixed cost eight or ten times a chunk). The XLA twin gathers the table's
pages from the same stored shape and splits ``Hkv*D`` into heads on the
gathered context only.

What a call costs follows the live context (PR 47): the grid is the
bucket's rows; a row walks ``ceil(seq_len / chunk)`` chunks in a loop (a
window layer from the first page its window reaches), chunk ``c + 1``'s
page DMAs started before chunk ``c``'s products (two K and two V
buffers); a chunk is up to :data:`CHUNK_ROWS` positions and
:data:`CHUNK_BYTES` a buffer, from ``Hkv*D`` and the pool dtype
(:func:`_chunk_pages`); pages past the context are never read; a row
with ``seq_len == 0`` (a bucket's padding) writes its fresh V to every
query head, which is its attention, and runs no product. q, the fresh
rows and the output lie whole in VMEM for the call, so a grid step moves
nothing but pages.

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

# the unit the engine rounds a table's width to (engine/kv_pool.py): every
# table this kernel is handed is a multiple of it, or a smaller power of
# two. The chunk the kernel walks is chosen in :func:`_chunk_pages`
PAGES_PER_CHUNK = 8

CHUNK_BYTES = 1 << 20    # one K (or V) buffer of a chunk, at most
CHUNK_ROWS = 1024        # positions a chunk, at most

_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _chunk_pages(mp: int, page_size: int, hd: int, dtype) -> int:
    """Pages DMA'd and attended a loop step: the largest power of two
    whose K (or V) rows fill at most :data:`CHUNK_BYTES` and
    :data:`CHUNK_ROWS` positions, from the row's width and the pool's
    dtype alone (bfloat16 pages of 16: 64 at 256 or 512 lanes, 32 at
    1,024, 16 at 1,280), and no more than the table holds. Placed on the
    chip (`scripts/ab_paged_decode.py --chunk-rows`, PERF.md §6, PR 47):
    1,024 positions against 512 read 8% faster at 512 lanes and the same
    at 1,024; a 2.6 MB buffer at 1,280 lanes read 12-22% slower than 640 KiB
    with one or two short rows live, where the first chunk's DMA is
    exposed."""
    rows = min(CHUNK_ROWS, CHUNK_BYTES // (hd * jnp.dtype(dtype).itemsize))
    ppc = 1
    while ppc * 2 * page_size <= rows:
        ppc *= 2
    return min(ppc, mp)


def kernel_supports(q: jax.Array, k_pages: jax.Array) -> bool:
    """The shapes the kernel's lane-dense layout handles: one query
    token, whole query groups per kv head, at most 128 kv heads (a head
    took a score lane before PR 47; kept, so that selection is what it
    was), ``Hkv*D`` a whole number of 128-lane tiles, and pages that
    are whole sublane tiles of the pool dtype (8 rows f32, 16 bf16)."""
    B, Tq, Hq, D = q.shape
    _, P, HD = k_pages.shape
    Hkv = HD // D
    sublanes = 8 * 4 // jnp.dtype(k_pages.dtype).itemsize
    return (Tq == 1 and Hkv * D == HD and 0 < Hkv <= _LANES
            and Hq % Hkv == 0 and HD % _LANES == 0 and P % sublanes == 0)


def _precision(dtype):
    return _HIGHEST if jnp.dtype(dtype) == jnp.float32 else None


def _query_block(G: int, Hkv: int) -> tuple[bool, int, int]:
    """How a row's queries lie in the query block ``qb``: ``(heads_major,
    blocks, rows a block)``. Row ``(g, h)`` holds query row ``g``'s
    head-``h`` lanes; a block is a sublane tile or more, so either every
    query row brings a block of its K/V heads (``G`` blocks of ``Hkv``
    rounded up to 8: gpt2-large's 1 x 24) or every K/V head a block of its
    query rows (``Hkv`` blocks of ``G`` rounded up to 8: nemotron's 2 x 16,
    trinity's 4 x 8). The fewer rows win: they are what the MXU streams
    past every latched K and V tile, and what the softmax runs over."""
    by_query, by_head = G * (-(-Hkv // 8) * 8), Hkv * (-(-G // 8) * 8)
    if by_head < by_query:
        return True, Hkv, -(-G // 8) * 8
    return False, G, -(-Hkv // 8) * 8


def _head_lanes(shape: tuple[int, int], d: int, head=None) -> jax.Array:
    """``shape`` bool: lane ``x`` belongs to K/V head ``head`` (``x // d ==
    head``, spelled without the integer divide); ``head=None``: to the
    head of the ROW's own number, and rows past the last head own no
    lane."""
    x = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    h = jax.lax.broadcasted_iota(jnp.int32, shape, 0) if head is None \
        else head
    return jnp.logical_and(x >= h * d, x < (h + 1) * d)


def _decode_kernel(page_tables_ref, seq_lens_ref,   # scalar prefetch
                   q_ref, k_pages_ref, v_pages_ref, k_new_ref, v_new_ref,
                   o_ref,
                   k_buf, v_buf, qb_ref, acc_ref, m_ref, l_ref, sem,
                   *, pages_per_chunk: int, page_size: int, table_pages: int,
                   group: int, head_dim: int, scale: float,
                   window: int | None = None):
    """One batch row of the fused decode (grid ``(B,)``; q, the fresh
    rows and the output lie whole in VMEM, so a step moves nothing but
    the pages it attends).

    A row with no context writes its fresh V to every query head and is
    done. A live row builds its query block ``qb`` (row ``(g, h)``: query
    row ``g``'s head-``h`` lanes, zeros elsewhere; the rows' order is
    :func:`_query_block`'s), starts from the fresh token (``m`` its score,
    ``l`` 1, ``acc`` its V: the token being decoded is not in the pool yet),
    then
    walks the chunks its context fills, from the first page a window
    still reaches to the last live page: chunk ``c + 1``'s page DMAs are
    started before chunk ``c``'s two products (``qb k^T`` and ``p v`` on
    the MXU in the operands' dtype, float32 sums; ``m`` / ``l`` / ``acc``
    float32 in VMEM scratch). Pages past the context are never read: a
    last chunk's dead pages are zeroed in the V buffer (a masked score's
    ``p`` is exactly 0, and 0 x stale VMEM must stay 0) and masked in the
    scores. The finalize divides by ``l`` and takes head ``h``'s lanes of
    row ``(g, h)``, once.
    """
    b = pl.program_id(0)
    seq_len = seq_lens_ref[b]
    P, ppc, G, D = page_size, pages_per_chunk, group, head_dim
    chunk = ppc * P
    rows, hd = qb_ref.shape
    heads_major, n_blocks, block = _query_block(G, hd // D)
    f32 = jnp.float32
    prec = _precision(qb_ref.dtype)
    vn = v_new_ref[pl.ds(b, 1), :]         # [1, Hkv*D] f32

    @pl.when(seq_len == 0)
    def _fresh_token_alone():
        o_ref[b] = jnp.broadcast_to(vn, (G, hd)).astype(o_ref.dtype)

    @pl.when(seq_len > 0)
    def _live_row():
        # a block's lanes, built once a row and not once a chunk: the K/V
        # head of each row's number (a query row's block), or head i's
        lanes = ([_head_lanes((block, hd), D, i) for i in range(n_blocks)]
                 if heads_major
                 else [_head_lanes((block, hd), D)] * n_blocks)
        # q_ref[b]: the row's queries, zero rows up to a sublane tile
        blocks = [jnp.where(lanes[i], q_ref[b] if heads_major
                            else q_ref[b, i:i + 1, :], 0.0)
                  for i in range(n_blocks)]
        if rows > n_blocks * block:
            blocks.append(jnp.zeros((rows - n_blocks * block, hd), f32))
        qblk = jnp.concatenate(blocks, axis=0)           # [rows, Hkv*D] f32
        qb_ref[...] = qblk.astype(qb_ref.dtype)
        s_new = jnp.sum(qblk * k_new_ref[pl.ds(b, 1), :], axis=1,
                        keepdims=True) * scale           # [rows, 1]
        m_ref[...] = jnp.broadcast_to(s_new, m_ref.shape)
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(vn, acc_ref.shape)

        n_pages = jnp.minimum((seq_len + P - 1) // P, table_pages)
        first = (0 if window is None
                 else jnp.maximum(seq_len - window + 1, 0) // P)
        n_chunks = (n_pages - first + ppc - 1) // ppc

        def pages_of(c):
            """Chunk ``c``'s first table entry and its count of live pages."""
            base = first + c * ppc
            return base, jnp.minimum(ppc, n_pages - base)

        def rows_of(j):
            return pl.ds(pl.multiple_of(j * P, P), P)

        def each_live_page(c, slot, act):
            """``act`` on the K and the V copy of every live page of chunk
            ``c`` into buffer ``slot``; -> the count of them."""
            base, live = pages_of(c)

            def one(j, _):
                page = page_tables_ref[b, base + j]
                act(pltpu.make_async_copy(k_pages_ref.at[page],
                                          k_buf.at[slot, rows_of(j)],
                                          sem.at[0, slot]))
                act(pltpu.make_async_copy(v_pages_ref.at[page],
                                          v_buf.at[slot, rows_of(j)],
                                          sem.at[1, slot]))
            jax.lax.fori_loop(0, live, one, None)
            return live

        def start(c, slot):
            each_live_page(c, slot, lambda copy: copy.start())

        def wait(c, slot):
            live = each_live_page(c, slot, lambda copy: copy.wait())

            def dead(j, _):
                v_buf[slot, rows_of(j), :] = jnp.zeros((P, hd), v_buf.dtype)
            jax.lax.fori_loop(live, ppc, dead, None)

        @pl.when(n_chunks > 0)
        def _first_chunk():
            start(0, 0)

        def attend(c, _):
            slot = c % 2

            @pl.when(c + 1 < n_chunks)
            def _next_chunk_in_flight():
                start(c + 1, 1 - slot)

            wait(c, slot)
            k = k_buf[slot].astype(qb_ref.dtype)         # [T, Hkv*D]
            v = v_buf[slot].astype(qb_ref.dtype)
            s = jax.lax.dot_general(
                qb_ref[...], k, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=f32) * scale      # [rows, T]
            pos = pages_of(c)[0] * P + jax.lax.broadcasted_iota(
                jnp.int32, (1, chunk), 1)
            valid = pos < seq_len                        # dead pages masked
            if window is not None:
                valid = jnp.logical_and(valid, pos > seq_len - window)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[...]                          # [rows, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # m >= the fresh token's score, finite: a masked column's p is
            # exp(NEG_INF - m) = exactly 0
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m_prev - m_new)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=f32)
            acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

        jax.lax.fori_loop(0, n_chunks, attend, None)

        out = acc_ref[...] / l_ref[...][:, :1]           # [rows, Hkv*D]
        g8 = q_ref.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (g8, hd), 0)
        res = jnp.zeros((g8, hd), f32)
        for i in range(n_blocks):
            mine = jnp.where(lanes[i], out[i * block:(i + 1) * block], 0.0)
            if heads_major:          # head i's lanes of its G query rows
                res = res + mine
            else:                    # query row i: every head's own lanes
                res = jnp.where(row == i, jnp.sum(mine, axis=0,
                                                  keepdims=True), res)
        o_ref[b] = res[:G].astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _build_call(B, G, HD, D, P, MP, q_dtype, page_dtype, interpret: bool,
                window: int | None = None):
    """The jitted pallas_call for one shape signature (lane-dense
    operands: q ``[B, G8, HD]`` (``G`` rows and zero rows up to a sublane
    tile) and fresh k/v ``[B, HD]`` float32, pages ``[pool, P, HD]``, out
    ``[B, G, HD]`` in ``q_dtype``). ONE object a signature, under a
    ``jit`` of the kernel's own name: a decode program calls the kernel
    once a layer, and every call but the first finds the kernel traced
    and, inside one program, lowered (a private function called a layer);
    without it a program's set-up traces and lowers the body 36 times
    (PERF.md §6, PR 47: 85 s of a warm `serve-large-chat` set-up)."""
    ppc = _chunk_pages(MP, P, HD, page_dtype)
    operand = jnp.promote_types(q_dtype, page_dtype)
    _, n_blocks, block = _query_block(G, HD // D)
    rows = -(-n_blocks * block // 16) * 16
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # page_tables, seq_lens
        grid=(B,),
        in_specs=[
            whole,                                    # q
            pl.BlockSpec(memory_space=pl.ANY),        # k_pages (HBM)
            pl.BlockSpec(memory_space=pl.ANY),        # v_pages (HBM)
            whole, whole,                             # fresh k, v
        ],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((2, ppc * P, HD), page_dtype),     # k chunks
            pltpu.VMEM((2, ppc * P, HD), page_dtype),     # v chunks
            pltpu.VMEM((rows, HD), operand),              # query block
            pltpu.VMEM((rows, HD), jnp.float32),          # acc
            pltpu.VMEM((rows, _LANES), jnp.float32),      # running max
            pltpu.VMEM((rows, _LANES), jnp.float32),      # running sum
            pltpu.SemaphoreType.DMA((2, 2)),              # (k | v, slot)
        ],
    )
    kernel = functools.partial(
        _decode_kernel, pages_per_chunk=ppc, page_size=P, table_pages=MP,
        group=G, head_dim=D, scale=D ** -0.5,
        **({} if window is None else {"window": window}))
    name = ("paged_decode_attention" if window is None
            else "paged_window_decode_attention")
    call = pl.pallas_call(  # devprof: exempt (attributed under serve.decode in-step)
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, HD), q_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )

    def run(*operands):
        return call(*operands)

    run.__name__ = run.__qualname__ = name      # what the jit is called
    return jax.jit(run)  # devprof: exempt (attributed under serve.decode in-step)


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
    f32 = jnp.float32
    # query head hq = h * G + g  ->  row g, head-h lanes
    qg = q.reshape(B, Hkv, G, D).transpose(0, 2, 1, 3).reshape(B, G, HD)
    qg = jnp.pad(qg.astype(f32), ((0, 0), (0, -G % 8), (0, 0)))
    call = _build_call(B, G, HD, D, P, MP, q.dtype, k_pages.dtype,
                       interpret, window)
    out = call(page_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
               qg, k_pages, v_pages,
               k_new.reshape(B, HD).astype(f32),
               v_new.reshape(B, HD).astype(f32))
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
