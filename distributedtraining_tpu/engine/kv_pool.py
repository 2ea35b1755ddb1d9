"""The serving KV pool's device layout, and every way it is written.

A pool is a pair ``(k_pages, v_pages)``; each half is a tuple of one
array PER LAYER, stored in the shape the paged-attention kernel DMAs
from: lane-dense ``[pool_pages, P, Hkv*D]`` rows
(ops/paged_attention.py). Why per layer and not one ``[L, ...]`` array:
a Pallas custom call takes a buffer of its own, so a layer sliced out of
a stacked array is copied once per layer per step; a separate array is
passed as it lies. Why ``Hkv*D`` and not ``[..., Hkv, D]``: under TPU
tiling a head of 64 is half a lane row, and re-laying the pool for the
kernel is a copy of the pool.

Every serve program (engine/serve.py, engine/speculative.py,
engine/kv_transfer.py) takes all ``2L`` arrays donated, hands layer *i*
its own pair, writes layer *i*'s fresh rows into layer *i*'s own arrays
through the helpers here, and returns all ``2L``. Nothing stacks across
layers on the device: the fresh ``[B, T, Hkv, D]`` tensors are reshaped
to ``Hkv*D`` (small); the pool never is. The transfer plane's wire
format stays ``[L, P, Hkv, D]`` per page — :func:`read_pages` and
:func:`adopt_page` convert a few pages at the edge.

What one layer caches a token is the MODEL's to state
(:func:`row_widths`): a K/V pair of heads gives ``(Hkv*D, Hkv*D)``; a
latent-attention layer (models/deepseek_v3.py, models/gigachat3_5.py)
gives ``(kv_lora_rank, qk_rope_head_dim)`` — the normed latent ``c`` in
the first half of the pair, the one shared rotary key ``k_r`` in the
second. The pair, the per-layer arrays, donation and every write below
are the same for both:
"k" and "v" name the halves, not what is in them.

A second KIND of cache lives beside the pages, for a family whose layers
do not all cache per token (models/nemotron_h.py: Mamba-2 states beside
K/V heads; models/gigachat3_5.py: delta-rule states beside a LATENT pair):
the family states per layer what it keeps (``cfg.layer_caches``: ``"kv"``
the paged pair above, with the row widths it states; ``"ssm"`` a recurrent
state, None nothing), the paged pool holds arrays for the ``"kv"`` layers
only, and each ``"ssm"`` layer has one float32 state ``[slots + 1,
*cfg.ssm_state_shape]`` and one convolution tail ``[slots + 1,
*cfg.ssm_tail_shape]`` addressed by SLOT, not by position
(:func:`make_state_pool`: the shapes are the config's to state, Mamba-2's
``[H, P, N]`` or a delta rule's ``[Hv, dk, dv]``; what the state is CALLED
in the registry too, :func:`state_name`).
They are donated and returned like the pages. A slot's row is written
whole by its prefill (:func:`write_slot_state`) and moved on in place by
every decode step (the model's layer does that itself and sows the pools
back: :func:`sown_state`); the last row belongs to no request and takes a
decode bucket's padding rows, as page 0 takes their page writes. A state
exists only where a prefill ended, so the prefix cache keeps SNAPSHOTS of
it: rows of a second pool of the same layout (:func:`make_snapshot_pool`),
copied from a slot's row when its prompt is registered and back into a
slot's row when a later prompt extends it (:func:`copy_state_row`); the
suffix's prefill then continues from the row (:func:`slot_state_rows`).
Nothing of it can be rolled back after a refused draft or shipped page by
page: :data:`RECURRENT_STATE_REASON`.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

Pool = tuple[tuple[jax.Array, ...], tuple[jax.Array, ...]]
LANES = 128
LATENT_CACHE_REASON = (
    "this model caches a latent row and one shared rotary key per token "
    "(its config states cache_row_widths), not a K/V pair of heads: the "
    "speculative lane and the KV transfer plane mirror the K/V geometry "
    "and are not ported to it")
RECURRENT_STATE_REASON = (
    "this model keeps a recurrent state per slot (its config states 'ssm' "
    "in layer_caches), which is not addressed by position: a refused draft "
    "cannot be rolled back (the state has moved past it) and a state is no "
    "page to ship, so the speculative lane and the KV transfer plane are "
    "not ported to it")
StatePool = tuple[tuple[jax.Array, ...], tuple[jax.Array, ...]]


def unheld_cache_reason(cfg) -> str | None:
    """Why the serve engine cannot hold what this model's layers keep for
    a sequence, in one sentence; None for a model it can serve."""
    unheld = sorted({c for c in (cfg.layer_caches or ())
                     if c not in ("kv", "ssm", None)})
    if not unheld:
        return None
    return (f"this model's layers keep {', '.join(map(repr, unheld))} for a "
            "sequence (cfg.layer_caches), for which engine/kv_pool.py has no "
            "pool: it holds a K/V pair of heads a token ('kv') and a "
            "recurrent state with its convolution tail a slot ('ssm'), so "
            "the model trains and is not served")


def layer_caches(cfg, n_layers: int) -> tuple[str | None, ...]:
    """What each layer keeps for a sequence: what the model's config
    states (``layer_caches``), else the paged pair in every layer."""
    stated = cfg.layer_caches
    return ("kv",) * n_layers if stated is None else tuple(stated)


def has_recurrent_state(cfg) -> bool:
    return "ssm" in (cfg.layer_caches or ())


def state_name(cfg) -> str:
    """What the per-slot state is called in the registry
    (``serve.<name>.state_bytes``): what the config states
    (``models/family.FamilyConfig``: ``"ssm"`` where it states nothing)."""
    return cfg.state_name


def row_widths(cfg) -> tuple[int, int]:
    """The widths of the two rows one layer caches a token: what the
    model's config states (``cache_row_widths``), else one K/V pair of
    ``n_kv_head`` (or ``n_head``) heads of ``head_dim``."""
    stated = cfg.cache_row_widths
    if stated is not None:
        # stored in whole 128-lane tiles, the pad lanes zero for ever: a
        # 64-wide row alone in its array makes XLA:TPU lay the PAGES axis
        # minor-most and re-lay the array around every gather, and Mosaic
        # cannot slice a page out of it (AOT for v5e, PERF.md PR 27)
        return tuple(-(-w // LANES) * LANES for w in stated)
    width = (cfg.n_kv_head or cfg.n_head) * cfg.head_dim
    return width, width


def kv_head_geometry(cfg) -> tuple[int, int]:
    """``(kv_heads, head_dim)`` for the planes that mirror a K/V pair of
    heads (the transfer wire's ``[L, P, Hkv, D]`` pages, the drafter's
    pool). A model that caches anything else is refused with the
    reason."""
    if cfg.cache_row_widths is not None:
        raise ValueError(LATENT_CACHE_REASON)
    if has_recurrent_state(cfg):
        raise ValueError(RECURRENT_STATE_REASON)
    return cfg.n_kv_head or cfg.n_head, cfg.head_dim


def make_pool(n_layers: int, pool_pages: int, page_size: int,
              widths: tuple[int, int], dtype) -> Pool:
    return tuple(
        tuple(jnp.zeros((pool_pages, page_size, width), dtype)
              for _ in range(n_layers))
        for width in widths)


def make_state_pool(cfg, n_layers: int, slots: int) -> StatePool:
    """One float32 state and one tail (in the compute dtype) for each
    ``"ssm"`` layer, ``slots`` rows and one spare."""
    return (tuple(jnp.zeros((slots + 1, *cfg.ssm_state_shape), jnp.float32)
                  for _ in range(n_layers)),
            tuple(jnp.zeros((slots + 1, *cfg.ssm_tail_shape),
                            cfg.compute_dtype()) for _ in range(n_layers)))


def make_snapshot_pool(cfg, n_layers: int, rows: int) -> StatePool:
    """The prefix cache's copies of per-slot state: the layout of
    :func:`make_state_pool`, ``rows`` rows and no spare."""
    return make_state_pool(cfg, n_layers, rows - 1)


def copy_state_row(dst: StatePool, src: StatePool, src_row, dst_row
                   ) -> StatePool:
    """Row ``src_row`` of every layer of ``src`` over row ``dst_row`` of
    ``dst``: slot -> snapshot when a prompt is registered, snapshot ->
    slot when a later one extends it."""
    return tuple(tuple(d.at[dst_row].set(x[src_row].astype(d.dtype))
                       for d, x in zip(d_half, s_half))
                 for d_half, s_half in zip(dst, src))


def slot_state_rows(states, tails, slot) -> tuple:
    """What a prefill that CONTINUES starts from: row ``slot`` of every
    layer's pools as one ``(state [1, ..], tail [1, ..])`` pair a layer
    (the models' ``ssm_init``)."""
    return tuple((s[slot][None], t[slot][None])
                 for s, t in zip(states, tails))


def sown_state(inter, layers: Sequence[str]) -> StatePool:
    """What the ``"ssm"`` layers of a forward sowed under ``ssm_cache``,
    layer by layer: a decode step's moved pools, or a prefill's one state
    and tail."""
    return tuple(zip(*(inter[name]["ssm_cache"][0] for name in layers)))


def write_slot_state(states, tails, inter, layers, slot) -> StatePool:
    """A prefill's write: the state after the prompt's last token and the
    tail before it, of batch row 0, over row ``slot`` of every layer's
    pool. Whatever the row held is gone: a slot is never zeroed."""
    new_states, new_tails = sown_state(inter, layers)
    return (tuple(p.at[slot].set(x[0]) for p, x in zip(states, new_states)),
            tuple(p.at[slot].set(x[0].astype(p.dtype))
                  for p, x in zip(tails, new_tails)))


def _sown(inter, layers: Sequence[str]) -> tuple[list, list]:
    """Every layer's fresh k and v out of a ``sow_kv=True`` forward's
    intermediates, as lane-dense ``[B, T, Hkv*D]`` rows."""
    fresh = [inter[name]["kv_cache"][0] for name in layers]
    return tuple([x.reshape(*x.shape[:2], -1) for x in half]   # [B,T,Hkv,D]
                 for half in zip(*fresh))


def _to_width(x, pages):
    """A fresh row narrower than its pool's rows (a stated width stored
    in whole lane tiles) is zero-padded to them."""
    pad = pages.shape[-1] - x.shape[-1]
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad))) if pad else x


def write_rows(k_pages, v_pages, inter, layers, page_idx, off) -> Pool:
    """Scatter a forward's fresh token rows: layer *i*'s sown
    ``[B, T, Hkv*D]`` row ``[b, t]`` lands at ``(page_idx[b, t],
    off[b, t])`` of layer *i*'s own arrays (verify, suffix prefill)."""
    def put(pages, rows):
        return tuple(p.at[page_idx, off].set(_to_width(x, p))
                     for p, x in zip(pages, rows))

    k_new, v_new = _sown(inter, layers)
    return put(k_pages, k_new), put(v_pages, v_new)


def write_next_row(k_pages, v_pages, inter, layers, page_tables,
                   seq_lens) -> Pool:
    """A decode step's write: slot *b*'s one fresh row goes to position
    ``seq_lens[b]`` of the sequence its ``page_tables`` row names."""
    P = k_pages[0].shape[1]
    page_idx = jnp.take_along_axis(
        page_tables, (seq_lens // P)[:, None], axis=1)
    return write_rows(k_pages, v_pages, inter, layers, page_idx,
                      (seq_lens % P)[:, None])


def write_pages(k_pages, v_pages, inter, layers, page_row) -> Pool:
    """Scatter a full prefill's whole pages: layer *i*'s sown
    ``[1, len(page_row) * P, Hkv*D]`` rows, page by page."""
    def put(pages, rows):
        return tuple(
            p.at[page_row].set(_to_width(x, p).reshape(
                page_row.shape[0], -1, p.shape[-1]))
            for p, x in zip(pages, rows))

    k_new, v_new = _sown(inter, layers)
    return put(k_pages, k_new), put(v_pages, v_new)


def copy_page(k_pages, v_pages, src, dst) -> Pool:
    """Copy page ``src`` onto page ``dst`` in every layer (the
    copy-on-write primitive)."""
    return tuple(tuple(p.at[dst].set(p[src]) for p in half)
                 for half in (k_pages, v_pages))


def adopt_page(k_pages, v_pages, k_new, v_new, dst) -> Pool:
    """Write one wire-format ``[L, P, Hkv, D]`` K/V page into page
    ``dst``, layer by layer."""
    def put(pages, new):
        return tuple(p.at[dst].set(new[i].reshape(new.shape[1], -1))
                     for i, p in enumerate(pages))

    return put(k_pages, k_new), put(v_pages, v_new)


def read_pages(pool: Pool, idx, kv_heads: int) -> tuple[np.ndarray,
                                                        np.ndarray]:
    """Pages ``idx`` of every layer on the host, in the wire format's
    ``[L, n, P, Hkv, D]``: gathered per layer, so only those few pages
    are stacked and moved."""
    idx = jnp.asarray(idx, jnp.int32)
    k_host, v_host = jax.device_get(
        tuple(jnp.stack([x[idx] for x in half]) for half in pool))

    def heads(x):
        return np.asarray(x).reshape(*x.shape[:3], kv_heads, -1)

    return heads(k_host), heads(v_host)
