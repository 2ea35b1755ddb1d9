"""Latent (MLA) attention over the paged latent cache, in the absorbed form.

A latent-attention layer (models/deepseek_v3.py) caches, per token, the
normed latent ``c`` (``kv_lora_rank`` wide) and ONE rotary key ``k_r``
shared by all heads. With the up-projection's key half absorbed into the
query (``q' = q_nope W_uk^T``), a decode step is multi-query attention in
the latent space: every head's ``[q' | q_rope]`` against the same
``[c | k_r]`` rows, and the values are the same ``c`` rows again, so each
cached row is read ONCE and used for the score and for the output; the
caller maps the latent output back through ``W_uv``.

Two spellings of one function, selected by a rule over backend and shape
(:func:`kernel_supports`), never a probe:

* :func:`mla_decode_reference` — XLA: gather the table's pages into a
  padded context, append the fresh rows, masked softmax. The CPU path,
  the multi-token path (suffix prefill over cached context), and what
  the kernel is tested against.
* :func:`mla_decode_attention` — the Pallas kernel on
  ``paged_decode_attention``'s scalar-prefetch structure
  (ops/paged_attention.py): grid ``(slot, chunk)``, the chunk's pages
  DMA'd from the pool as it lies by page-table lookup, an online softmax
  in float32 scratch, the step's fresh row folded in as the last column,
  chunks wholly past ``seq_lens[b]`` skipped.

Both take operands in the pool's dtype and accumulate in float32; the
probabilities are rounded to the pool's dtype for the value product, as
flash attention does.

Layouts: ``q_abs`` [B, Tq, H, C], ``q_rope`` [B, Tq, H, R]; ``c_pages``
[pages, P, C] and ``kr_pages`` [pages, P, R'], one layer's halves of the
pool (engine/kv_pool.py), where R' >= R is R stored in whole lane tiles
with zero pad lanes (the queries' rotary part is zero-padded to match, so
the pad adds exact zeros to every score); ``page_tables`` [B, MP];
``seq_lens`` [B] the cached context lengths; ``c_new`` [B, Tq, C],
``kr_new`` [B, Tq, R] the fresh rows (not in the pool yet). Returns the
latent output [B, Tq, H, C].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, causal_attention

# pages DMA'd and attended per grid step: 512 rows of 16-token pages
# (0.6 MB of latent rows in VMEM), so that a step's DMA latency is paid
# over enough bytes
PAGES_PER_CHUNK = 32
_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _precision(dtype):
    return _HIGHEST if jnp.dtype(dtype) == jnp.float32 else None


def _chunk_pages(mp: int) -> int:
    c = 1
    while c < PAGES_PER_CHUNK and mp % (c * 2) == 0:
        c *= 2
    return c


def mla_decode_reference(q_abs, q_rope, c_pages, kr_pages, page_tables,
                         seq_lens, c_new, kr_new, scale: float):
    """The XLA spelling. Context positions are valid below
    ``seq_lens[b]``; the trailing Tq fresh positions are causal among
    themselves and always visible to themselves
    (ops/attention.cached_attention's mask)."""
    B, Tq = q_abs.shape[:2]
    P = c_pages.shape[1]
    S = page_tables.shape[1] * P
    prec = _precision(c_pages.dtype)
    # a pool row may be wider than what it holds (zero pad lanes)
    c_full = jnp.concatenate(
        [c_pages[page_tables].reshape(B, S, -1)[..., :c_new.shape[-1]],
         c_new], axis=1)
    kr_full = jnp.concatenate(
        [kr_pages[page_tables].reshape(B, S, -1)[..., :kr_new.shape[-1]],
         kr_new], axis=1)
    scores = (jnp.einsum("bthc,bsc->bhts", q_abs, c_full, precision=prec,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthr,bsr->bhts", q_rope, kr_full,
                           precision=prec,
                           preferred_element_type=jnp.float32)) * scale
    kv_pos = jnp.arange(S + Tq)[None, None, :]
    q_pos = jnp.arange(Tq)[None, :, None]
    valid = (kv_pos < seq_lens[:, None, None]) | (
        (kv_pos >= S) & (kv_pos - S <= q_pos))
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bsc->bthc", probs.astype(c_full.dtype), c_full,
                     precision=prec, preferred_element_type=jnp.float32)
    return out.astype(q_abs.dtype)


def kernel_supports(q_abs, q_rope, c_pages, kr_pages) -> bool:
    """One query token; the latent width whole lane tiles; pages whole
    sublane tiles of the pool's dtype; head rows whole sublane tiles."""
    B, Tq, H, C = q_abs.shape
    P = c_pages.shape[1]
    sublanes = 8 * 4 // jnp.dtype(c_pages.dtype).itemsize
    return (Tq == 1 and C % _LANES == 0 and c_pages.shape[2] == C
            and kr_pages.shape[2] % _LANES == 0
            and kr_pages.shape[2] >= q_rope.shape[-1]
            and P % sublanes == 0 and H % 8 == 0
            and q_abs.dtype == c_pages.dtype)


def _dot_nt(a, b, prec):
    """a [M, K] . b [N, K]^T -> [M, N] float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=prec,
                               preferred_element_type=jnp.float32)


def _dot_nn(a, b, prec):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=prec,
                               preferred_element_type=jnp.float32)


def _kernel(page_tables_ref, seq_lens_ref,          # scalar prefetch
            qa_ref, qr_ref, c_pages_ref, kr_pages_ref, c_new_ref,
            kr_new_ref, o_ref,
            c_buf, kr_buf, acc_ref, m_ref, l_ref, sem,
            *, pages_per_chunk: int, page_size: int, n_chunks: int,
            scale: float, prec):
    b = pl.program_id(0)
    i = pl.program_id(1)
    seq_len = seq_lens_ref[b]
    chunk = pages_per_chunk * page_size

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qa = qa_ref[0]                                      # [H, C]
    qr = qr_ref[0]                                      # [H, R]

    def update(s, valid, values):
        """Fold scores ``s`` [H, T] (already masked where ``valid`` is
        given) and their value rows [T, C] into the running softmax."""
        m_prev = m_ref[:, :1]                           # [H, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + values(p)

    @pl.when(jnp.logical_and(i < n_chunks, i * chunk < seq_len))
    def _context_chunk():
        for j in range(pages_per_chunk):
            page = page_tables_ref[b, i * pages_per_chunk + j]
            rows = pl.ds(j * page_size, page_size)
            pltpu.make_async_copy(
                c_pages_ref.at[page], c_buf.at[rows], sem.at[0]).start()
            pltpu.make_async_copy(
                kr_pages_ref.at[page], kr_buf.at[rows], sem.at[1]).start()
        for j in range(pages_per_chunk):
            rows = pl.ds(j * page_size, page_size)
            pltpu.make_async_copy(
                c_pages_ref.at[0], c_buf.at[rows], sem.at[0]).wait()
            pltpu.make_async_copy(
                kr_pages_ref.at[0], kr_buf.at[rows], sem.at[1]).wait()
        c = c_buf[...]                                  # [T, C]
        kr = kr_buf[...]                                # [T, R]
        s = (_dot_nt(qa, c, prec) + _dot_nt(qr, kr, prec)) * scale
        pos = i * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < seq_len
        s = jnp.where(valid, s, NEG_INF)
        update(s, valid, lambda p: _dot_nn(p.astype(c.dtype), c, prec))

    @pl.when(i == n_chunks)
    def _append_fresh_and_finalize():
        cn = c_new_ref[0].astype(jnp.float32)           # [1, C]
        krn = kr_new_ref[0].astype(jnp.float32)         # [1, R]
        s = (jnp.sum(qa.astype(jnp.float32) * cn, axis=1, keepdims=True)
             + jnp.sum(qr.astype(jnp.float32) * krn, axis=1,
                       keepdims=True)) * scale          # [H, 1]
        # the fresh row's probability is rounded like every other
        update(s, None,
               lambda p: p.astype(c_new_ref.dtype).astype(jnp.float32) * cn)
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _build_call(B, H, C, R, P, MP, dtype, scale, interpret):
    ppc = _chunk_pages(MP)
    n_chunks = MP // ppc
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_chunks + 1),
        in_specs=[
            pl.BlockSpec((1, H, C), lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, H, R), lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),          # c_pages (HBM)
            pl.BlockSpec(memory_space=pl.ANY),          # kr_pages (HBM)
            pl.BlockSpec((1, 1, C), lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda b, i, *_: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, C), lambda b, i, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((ppc * P, C), dtype),
            pltpu.VMEM((ppc * P, R), dtype),
            pltpu.VMEM((H, C), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _kernel, pages_per_chunk=ppc, page_size=P, n_chunks=n_chunks,
        scale=scale, prec=_precision(dtype))
    return pl.pallas_call(  # devprof: exempt (attributed under serve.decode in-step)
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="mla_decode_attention",
    )


def mla_decode_attention(q_abs, q_rope, c_pages, kr_pages, page_tables,
                         seq_lens, c_new, kr_new, scale: float, *,
                         interpret: bool = False):
    """The fused kernel. Raises ``ValueError`` on a shape
    :func:`kernel_supports` rejects; build and compile errors propagate.
    ``interpret=True`` is for the CPU tests; only a caller passes it."""
    if not kernel_supports(q_abs, q_rope, c_pages, kr_pages):
        raise ValueError(
            f"mla_decode_attention: unsupported shapes q_abs={q_abs.shape} "
            f"q_rope={q_rope.shape} c_pages={c_pages.shape} "
            f"kr_pages={kr_pages.shape} {c_pages.dtype}")
    B, _, H, C = q_abs.shape
    P = c_pages.shape[1]
    R = kr_pages.shape[2]
    pad = ((0, 0),) * 2 + ((0, R - q_rope.shape[-1]),)
    q_rope = jnp.pad(q_rope.reshape(B, H, -1), pad)
    kr_new = jnp.pad(kr_new.reshape(B, 1, -1), pad)
    call = _build_call(B, H, C, R, P, page_tables.shape[1],
                       jnp.dtype(c_pages.dtype), float(scale), interpret)
    out = call(page_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
               q_abs.reshape(B, H, C), q_rope, c_pages, kr_pages,
               c_new.reshape(B, 1, C), kr_new)
    return out.reshape(B, 1, H, C)


def mla_paged_attention(q_abs, q_rope, c_pages, kr_pages, page_tables,
                        seq_lens, c_new, kr_new, scale: float):
    """Model-facing entry: the kernel on a TPU at a shape it supports,
    the XLA spelling otherwise."""
    with jax.named_scope("mla.decode"):
        if _on_tpu() and kernel_supports(q_abs, q_rope, c_pages, kr_pages):
            return mla_decode_attention(q_abs, q_rope, c_pages, kr_pages,
                                        page_tables, seq_lens, c_new,
                                        kr_new, scale)
        return mla_decode_reference(q_abs, q_rope, c_pages, kr_pages,
                                    page_tables, seq_lens, c_new, kr_new,
                                    scale)


def latent_attention(q_nope, q_rope, c, k_r, w_kv_b, scale: float, *,
                     kv_pages=None, page_tables=None, kv_lens=None,
                     attention_mask=None, segment_ids=None,
                     impl: str = "dense"):
    """A latent-attention layer's two forms, for every family that caches
    ``(c, k_r)`` a token (models/deepseek_v3.py, models/gigachat3_5.py).
    ``q_nope`` [B, T, H, Dn], ``q_rope`` [B, T, H, Dr] (after its rotary);
    ``c`` [B, T, C] the normed latent and ``k_r`` [B, T, Dr] the one
    shared rotary key of the FRESH rows; ``w_kv_b`` [C, H, Dn + Dv] the
    up-projection, key half first; ``scale`` the softmax scale (``(Dn +
    Dr) ** -0.5``, times what the family's position scaling states) ->
    the heads' values [B, T, H, Dv].

    Over the paged cache (``kv_pages``: one layer's pair) it attends in
    the ABSORBED form: ``q' = q_nope W_uk^T``, scores ``q' . c + q_rope .
    k_r``, ``o = (P c) W_uv``. Without a cache it attends in the EXPANDED
    form, ``[k_nope | v] = c W_kv_b`` a head, through
    ``ops.attention.causal_attention`` (q/k of ``Dn + Dr``, v of ``Dv``:
    the dense product takes the two widths as they are); that function
    scales by the query's width alone, so any more rides on the query."""
    B, T, H, Dn = q_nope.shape
    Dr = q_rope.shape[-1]
    if kv_pages is not None:
        q_abs = jnp.einsum("bthn,chn->bthc", q_nope, w_kv_b[..., :Dn])
        o_lat = mla_paged_attention(
            q_abs, q_rope, kv_pages[0], kv_pages[1], page_tables, kv_lens,
            c, k_r, scale)
        return jnp.einsum("bthc,chv->bthv", o_lat, w_kv_b[..., Dn:])
    with jax.named_scope("mla.prefill"):
        kv = jnp.einsum("btc,chd->bthd", c, w_kv_b)
        k = jnp.concatenate(
            [kv[..., :Dn],
             jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, Dr))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        plain = (Dn + Dr) ** -0.5
        if scale != plain:
            q = (q.astype(jnp.float32) * (scale / plain)).astype(q.dtype)
        return causal_attention(q, k, kv[..., Dn:],
                                attention_mask=attention_mask,
                                segment_ids=segment_ids, impl=impl)
