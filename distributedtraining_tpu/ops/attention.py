"""Causal self-attention for TPU.

Implementations:
- "dense": einsum QK^T -> fp32 softmax -> PV. XLA fuses this well on TPU for
  the reference's sequence lengths (64-512 tokens); it is the default and the
  correctness oracle for the fancier paths.
- "flash": Pallas blockwise-softmax kernel (ops/flash_attention.py), used for
  long sequences where the [T, T] score matrix stops fitting in VMEM.
- ring attention for sequence-parallel meshes lives in ops/ring_attention.py
  (it calls back into these per-block primitives).

Supports padding masks and packed-sequence segment ids (block-diagonal
attention), which the data pipeline uses to avoid the reference's pad-to-64
token waste (neurons/miner.py:70).

Shapes: q, k, v are [batch, seq, heads, head_dim].
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e9  # large-negative in bf16-safe range (bf16 max ~3.4e38, fine)


def make_causal_mask(q_len: int, kv_len: int | None = None,
                     *, q_offset: int = 0,
                     window: int | None = None) -> jax.Array:
    """Boolean [q_len, kv_len] mask, True = may attend.

    ``q_offset`` shifts query positions — used by ring attention where the
    local query block sits at a global offset relative to the key block.
    ``window``: position ``i`` sees ``j`` with ``i - window < j <= i``
    (``window`` keys, the token itself among them); None: every ``j <= i``.
    """
    kv_len = q_len if kv_len is None else kv_len
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    if window is None:
        return q_pos >= kv_pos
    return (q_pos >= kv_pos) & (kv_pos > q_pos - window)


def combine_masks(causal: jax.Array,
                  attention_mask: Optional[jax.Array],
                  segment_ids: Optional[jax.Array],
                  kv_segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Fold padding + packing masks into the causal mask.

    attention_mask: [B, kv_len] with 1 = real token.
    segment_ids:    [B, q_len] packing ids; tokens attend only within their
                    own segment (block-diagonal).
    Returns [B, 1, q_len, kv_len] boolean.
    """
    mask = causal[None, None, :, :]
    if attention_mask is not None:
        mask = mask & attention_mask[:, None, None, :].astype(bool)
    if segment_ids is not None:
        kv_seg = segment_ids if kv_segment_ids is None else kv_segment_ids
        same = segment_ids[:, :, None] == kv_seg[:, None, :]
        mask = mask & same[:, None, :, :]
    return mask


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array]) -> jax.Array:
    """Masked attention with fp32 softmax accumulation.

    q/k/v: [B, T, H, D] (any float dtype; scores accumulate in fp32).
    mask: broadcastable to [B, H, Tq, Tkv], True = attend.
    """
    depth = q.shape[-1]
    scale = depth ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


# dense materializes [B, H, T, T] scores; above this length a declined
# flash kernel falls back to the blockwise spelling instead, whose temp
# memory is O(B*H*bq*bk) — the same profile as the Pallas kernel
BLOCKWISE_FALLBACK_LEN = 1024


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        *,
                        attention_mask: Optional[jax.Array] = None,
                        segment_ids: Optional[jax.Array] = None,
                        block_q: int = 512,
                        block_kv: int = 512,
                        window: Optional[int] = None) -> jax.Array:
    """Causal attention as a double lax.scan over query/key blocks with an
    online softmax — the FlashAttention algorithm in portable lax (same
    streaming math as ring_attention._ring_body, but blocks come from a
    local reshape instead of an ICI ring).

    No [T, T] score matrix ever exists: peak temp is one [B, H, bq, bkv]
    tile, and ``jax.checkpoint`` on the inner step keeps the backward at
    the same profile (tiles recompute instead of being stashed per
    block). This is the memory-honest fallback when the Pallas flash
    kernel declines (CPU backends, odd shapes) and the spelling the AOT
    scale artifacts compile so their XLA memory analysis reflects the
    flash-kernel profile rather than a dense [T, T] blowup the TPU never
    pays. Masking matches combine_masks: causal + optional key padding
    mask + optional segment equality (packed sequences). ``window``: a
    query sees the ``window`` newest keys up to itself
    (:func:`make_causal_mask`); key blocks wholly behind a query block's
    window are skipped like the causally dead ones.
    """
    B, T, H, D = q.shape
    bq, bkv = min(block_q, T), min(block_kv, T)
    pad_q = (-T) % bq
    pad_kv = (-T) % bkv
    nq, nkv = (T + pad_q) // bq, (T + pad_kv) // bkv
    scale = D ** -0.5

    qf = (q.astype(jnp.float32) * scale)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # key validity: padding-mask AND in-bounds (scan blocks are static)
    kvalid = jnp.ones((B, T), bool) if attention_mask is None \
        else attention_mask.astype(bool)
    if pad_kv:
        kf = jnp.pad(kf, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        kvalid = jnp.pad(kvalid, ((0, 0), (0, pad_kv)))
    seg = segment_ids
    if seg is not None:
        qseg = jnp.pad(seg, ((0, 0), (0, pad_q)), constant_values=-1)
        kseg = jnp.pad(seg, ((0, 0), (0, pad_kv)), constant_values=-2)
        qseg = qseg.reshape(B, nq, bq).transpose(1, 0, 2)    # [nq, B, bq]
        kseg = kseg.reshape(B, nkv, bkv).transpose(1, 0, 2)  # [nkv, B, bkv]
    qb = qf.reshape(B, nq, bq, H, D).transpose(1, 0, 2, 3, 4)
    kb = kf.reshape(B, nkv, bkv, H, D).transpose(1, 0, 2, 3, 4)
    vb = vf.reshape(B, nkv, bkv, H, D).transpose(1, 0, 2, 3, 4)
    kvalid_b = kvalid.reshape(B, nkv, bkv).transpose(1, 0, 2)  # [nkv, B, bkv]

    def kv_tile_update(qi, q_tile, q_seg_tile, carry, kv):
        acc, m_prev, l_prev = carry
        ki, k_tile, v_tile, kv_ok, k_seg_tile = kv
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_tile, k_tile)
        q_pos = qi * bq + jnp.arange(bq)
        k_pos = ki * bkv + jnp.arange(bkv)
        mask = q_pos[:, None] >= k_pos[None, :]          # causal
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        mask = mask[None, :, :] & kv_ok[:, None, :]      # key padding
        if q_seg_tile is not None:
            mask = mask & (q_seg_tile[:, :, None] == k_seg_tile[:, None, :])
        scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new[..., None])
        # exp(NEG_INF - m) underflows to 0 for any real m, but a FULLY
        # masked running max (m_new == NEG_INF) would turn masked entries
        # into exp(0) = 1 — zero them explicitly so dead rows (no visible
        # key after causal+padding+segment masking) emit exact 0, the
        # flash-kernel convention
        p = p * mask[:, None, :, :]
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_tile)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return acc, m_new, l_new

    def kv_step(qi, q_tile, q_seg_tile, carry, kv):
        # skip causally-dead blocks (every key strictly in the future of
        # every query of this tile): about half the tiles at long T. The
        # predicate is a per-iteration scalar, so lax.cond executes only
        # one branch instead of lowering to a select
        ki = kv[0]
        dead = ki * bkv > qi * bq + (bq - 1)
        if window is not None:
            # the block's last key is behind the first query's window
            dead = dead | (ki * bkv + (bkv - 1) <= qi * bq - window)
        new_carry = jax.lax.cond(
            dead, lambda c, _kv: c,
            lambda c, kv_: kv_tile_update(qi, q_tile, q_seg_tile, c, kv_),
            carry, kv)
        return new_carry, None

    kv_step = jax.checkpoint(kv_step, static_argnums=())

    def q_step(_, q_in):
        qi, q_tile, q_seg_tile = q_in
        acc0 = jnp.zeros((B, bq, H, D), jnp.float32)
        m0 = jnp.full((B, H, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        kvs = (jnp.arange(nkv), kb, vb, kvalid_b,
               kseg if seg is not None else jnp.zeros((nkv,)))
        (acc, m, l), _ = jax.lax.scan(
            lambda c, kv: kv_step(qi, q_tile, q_seg_tile, c, kv),
            (acc0, m0, l0), kvs)
        l = jnp.maximum(l, 1e-30)
        return None, acc / l.transpose(0, 2, 1)[..., None]

    q_in = (jnp.arange(nq), qb, qseg if seg is not None else jnp.zeros((nq,)))

    def q_step_wrap(c, q_in_):
        qi, q_tile, q_seg_tile = q_in_
        return q_step(c, (qi, q_tile,
                          q_seg_tile if seg is not None else None))

    # checkpoint the WHOLE q block: without it the outer scan stashes
    # every inner-scan carry for every q block (nq x nkv x [B,bq,H,D]);
    # with it the backward recomputes one q block's inner scan at a time
    _, out = jax.lax.scan(jax.checkpoint(q_step_wrap), None, q_in)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, T + pad_q, H, D)
    return out[:, :T].astype(q.dtype)


def cached_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     ctx_lens: jax.Array,
                     window: Optional[int] = None) -> jax.Array:
    """Decode-step attention for KV-cache generation (engine/serve.py).

    ``q`` is the current step's queries [B, Tq, H, D]; ``k``/``v`` are the
    PADDED cached context concatenated with the current step's keys/values
    [B, S + Tq, H, D], where S is the (bucket-padded) context capacity.
    ``ctx_lens`` [B] gives each row's REAL context length: context
    positions >= ctx_lens[b] are padding (dead pages of the paged KV
    pool) and masked out; the trailing Tq positions are the new tokens,
    causally masked among themselves and always visible to themselves.

    Same fp32-softmax math as ``dot_product_attention`` — padded keys hit
    the NEG_INF branch, whose exp underflows to exact 0, so garbage in
    dead cache slots cannot leak into the output.

    The mask is an iota compare folded into the score computation, not a
    materialized buffer: the old spelling concatenated two broadcast
    ``[B, Tq, S]``/``[B, Tq, Tq]`` boolean arrays into a ``[B, Tq, S+Tq]``
    mask per decode step — O(B·S) bytes written every token for a
    predicate XLA can fuse into the ``where`` on the scores for free.
    Identical mask semantics (pinned in tests/test_paged_attention.py):
    context positions valid below ``ctx_lens``, the trailing Tq fresh
    positions causal among themselves and always visible to themselves.
    ``window``: fresh token ``t`` (position ``ctx_lens[b] + t``) sees only
    the ``window`` newest positions up to itself, of the context and of
    the fresh rows alike.
    """
    B, Tq, _, depth = q.shape
    S = k.shape[1] - Tq
    scale = depth ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(S + Tq)[None, None, :]                # [1, 1, S+Tq]
    q_pos = jnp.arange(Tq)[None, :, None]                     # [1, Tq, 1]
    valid = (kv_pos < ctx_lens[:, None, None]) | (
        (kv_pos >= S) & (kv_pos - S <= q_pos))                # [B, Tq, S+Tq]
    if window is not None:
        # a key's distance behind the query, over the context's dead tail
        key_pos = jnp.where(kv_pos >= S,
                            kv_pos - S + ctx_lens[:, None, None], kv_pos)
        valid = valid & (key_pos > ctx_lens[:, None, None] + q_pos - window)
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     *,
                     attention_mask: Optional[jax.Array] = None,
                     segment_ids: Optional[jax.Array] = None,
                     impl: str = "dense",
                     window: Optional[int] = None) -> jax.Array:
    """Causal self-attention entry point used by the models.

    impl: "dense" (XLA), "flash" (the Pallas kernel where
    flash_attention's rule selects it — TPU, no padding mask, aligned T —
    else blockwise at long T / dense at short T),
    "blockwise" (portable lax flash — O(block^2) temps everywhere), "ring"
    (sequence-parallel over the sp mesh axis; needs set_ring_mesh and
    unmasked/unpacked inputs). ``window`` (``"dense"`` and ``"blockwise"``
    only): a query sees the ``window`` newest keys up to itself.
    """
    B, T, H, D = q.shape
    if window is not None and impl in ("flash", "ring"):
        raise ValueError(
            f"causal_attention(impl={impl!r}) has no window: the flash "
            "kernels' pair list and the ring's block schedule are causal "
            "only; a window layer runs impl='dense' or 'blockwise'")
    if impl == "ring" and attention_mask is None and segment_ids is None:
        from . import ring_attention as ring
        mesh, _ = ring.get_ring_mesh()
        if mesh is not None:
            return ring.ring_attention(q, k, v)
        # no mesh installed -> dense fallback below
    if impl == "blockwise":
        return blockwise_attention(
            q, k, v, attention_mask=attention_mask, segment_ids=segment_ids,
            **({} if window is None else {"window": window}))
    if impl == "flash":
        from . import flash_attention
        # None = the selection rule said no (off-TPU, padding mask,
        # short or unaligned T); a selected kernel that fails raises
        out = flash_attention.flash_attention(
            q, k, v, attention_mask=attention_mask, segment_ids=segment_ids)
        if out is not None:
            return out
        if T >= BLOCKWISE_FALLBACK_LEN:
            # at long T the dense [T, T] scores would blow temp memory
            # the kernel path never pays — stream blocks instead
            return blockwise_attention(
                q, k, v, attention_mask=attention_mask,
                segment_ids=segment_ids)
        # short T: dense is faster and the temps are tiny
    mask = combine_masks(make_causal_mask(T, window=window), attention_mask,
                         segment_ids)
    return dot_product_attention(q, k, v, mask)


def causal_attention_qkv(qkv: jax.Array, n_head: int,
                         *,
                         attention_mask: Optional[jax.Array] = None,
                         segment_ids: Optional[jax.Array] = None,
                         impl: str = "dense") -> jax.Array:
    """:func:`causal_attention` on a fused projection's ``[B, T, 3E]``
    (q, k, v side by side) -> ``[B, T, E]``. Where the flash kernels take
    the fused array as it lies (``flash_attention.flash_attention_qkv``)
    nothing is split or copied; everywhere else this is the split, the
    heads' reshape and :func:`causal_attention`."""
    if impl == "flash":
        from . import flash_attention
        out = flash_attention.flash_attention_qkv(
            qkv, n_head, attention_mask=attention_mask,
            segment_ids=segment_ids)
        if out is not None:
            return out
    B, T, E3 = qkv.shape
    q, k, v = (x.reshape(B, T, n_head, -1)
               for x in jnp.split(qkv, 3, axis=-1))
    return causal_attention(q, k, v, attention_mask=attention_mask,
                            segment_ids=segment_ids,
                            impl=impl).reshape(B, T, E3 // 3)


def remat_policy():
    """The ``policy`` of every ``nn.remat`` around a block that calls
    :func:`causal_attention`: beside the block's input, keep the flash
    forward kernel's output and ``[H, T]`` log-sum-exp
    (``B x T x E x 2 + B x H x T x 4`` bytes an attention layer; where this
    module's own kernels run the output is the ``[B, T, E]`` array the
    output projection reads, stored as its shape says), so that
    the re-run hands them to the backward kernel and does not call the
    forward kernel a second time. Where the kernel does not run nothing
    carries the name, and remat keeps what a bare one keeps."""
    from . import flash_attention
    return flash_attention.KEEP_RESIDUALS
