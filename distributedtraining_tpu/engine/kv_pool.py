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

A third kind, ``"kv_window"`` (models/afmoe.py: sliding-window layers
beside global ones), is the paged pair again, kept only while a token is
one of the ``cfg.sliding_window`` newest: those layers' arrays are a pool of
their OWN (:func:`make_pool` once more, ``window_pool_pages`` pages), with
their own table a slot and their own host accounting
(:class:`WindowPages`, a :class:`PagePool` that also gives pages back).
The table is SHIFTED, not a ring: a request's :class:`Held` is the list of
its pages from logical page ``first`` on; entry 0 of the table a program is
handed is page ``first``, and the program gets ``first * P`` beside it
(``window_starts``), so a window layer attends and writes at ``position -
start``. A page wholly behind ``newest - window`` is popped off the front
and ``first`` moves on (:meth:`WindowPages.release_behind`: at every decode
step's growth, at every prefill chunk's end). Why shifted: the host builds
every table anew each step anyway, the attention reads no absolute
position, so the kernel needs the relative length and one lower bound, and
the chunks it may skip are a prefix of the table; a ring would keep the
table still and pay a modulus in every mask. What shares pages by position
for all layers alike cannot hold for this group:
:data:`WINDOW_CACHE_REASON`.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import PAGES_PER_CHUNK

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
WINDOW_CACHE_REASON = (
    "this model keeps a sliding layer's K/V only while a token is inside "
    "the window (its config states 'kv_window' in layer_caches), in a page "
    "group that gives pages back behind the window: a shared prefix's "
    "window pages are mostly released, a refused draft cannot be rolled "
    "back over a page already given back, and the transfer wire ships one "
    "table for all layers, so the prefix cache, the speculative lane and "
    "the KV transfer plane are not ported to it")
StatePool = tuple[tuple[jax.Array, ...], tuple[jax.Array, ...]]
KINDS = ("kv", "kv_window", "ssm")


class PagePool:
    """Refcounted page accounting over pool indices ``1..pool_pages-1``
    (page 0 is the trash page and is never allocated). Every owner of a
    page — an active slot's page table, or a ``PrefixCache``
    entry — holds exactly one reference; a page returns to the free
    list only when its refcount reaches 0, so shared prompt pages
    survive the slots that mapped them. ``check`` is the debug-flag
    invariant the accounting contract rests on: free pages + referenced
    pages == total, and the refcounts exactly match the owners the
    engine can enumerate. This is the ``"kv"`` kind's accounting: a page
    is a request's until the request ends."""

    def __init__(self, pool_pages: int):
        self.total = pool_pages - 1          # trash page excluded
        self._free: list[int] = list(range(1, pool_pages))
        self._refs: dict[int, int] = {}

    @property
    def free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        if len(self._free) < n:
            return None
        out = self._free[:n]
        del self._free[:n]
        for p in out:
            self._refs[p] = 1
        return out

    def incref(self, page: int) -> None:
        self._refs[page] += 1

    def decref(self, page: int) -> None:
        left = self._refs[page] - 1
        if left:
            self._refs[page] = left
        else:
            del self._refs[page]
            self._free.append(page)

    def refs(self, page: int) -> int:
        return self._refs.get(page, 0)

    def check(self, expected: dict[int, int] | None = None) -> None:
        """The conservation invariant (engine ``debug_invariants``
        flag): every allocatable page is either free or referenced,
        never both, never neither — and when the engine passes the
        refcounts it can derive from its slots + cache, they must
        match the pool's exactly."""
        assert len(self._free) + len(self._refs) == self.total, (
            f"page leak: {len(self._free)} free + {len(self._refs)} "
            f"referenced != {self.total} total")
        assert all(r >= 1 for r in self._refs.values()), \
            f"non-positive refcount in {self._refs}"
        assert not set(self._free) & set(self._refs), \
            "page simultaneously free and referenced"
        if expected is not None:
            assert expected == self._refs, (
                f"refcount drift: engine expects {expected}, "
                f"pool holds {self._refs}")


def window_table_pages(window: int, chunk: int, page_size: int) -> int:
    """The most pages a request ever holds of the window group: the
    window, one prefill chunk and two page edges."""
    return -(-(window + chunk) // page_size) + 2


@dataclasses.dataclass
class Held:
    """What one request holds of the window group: ``pages[i]`` is its
    logical page ``first + i``; everything before ``first`` went back."""
    pages: list = dataclasses.field(default_factory=list)
    first: int = 0


class WindowPages(PagePool):
    """The ``"kv_window"`` kind: a :class:`PagePool` (nothing is shared, so
    every refcount is 1) whose requests hold pages only for positions a
    later query can still see, with the group's device arrays (``pools``)
    and what each request holds of it (:class:`Held`, by request id). The
    four verbs a kind has: :meth:`admit` (may the group take this prompt),
    :meth:`extend` (pages up to a position about to be written),
    :meth:`release_behind` (pages wholly behind ``newest - window`` go back)
    and :meth:`release` (all of them). The engine drives a family's window
    group through them and never asks whether it has one: a family without
    the statement gets :class:`NoWindow`, which answers every verb as a
    group that holds nothing and is never short. ``table_pages`` bounds
    what a request ever holds: the window, one prefill chunk and two page
    edges; ``decode_pages`` is the decode programs' table width (a decoding
    request holds at most ``window / P + 1`` pages), in whole kernel
    chunks. ``arity``: the arguments a serve program takes for the group
    (the two halves of its pool, which it donates and returns, the tables
    and the position of each table's first row)."""

    arity, donated = 4, 2

    def __init__(self, pool_pages: int, page_size: int, window: int,
                 chunk: int):
        super().__init__(pool_pages)
        self.page_size, self.window = page_size, window
        self.table_pages = window_table_pages(window, chunk, page_size)
        # the decode kernel attends PAGES_PER_CHUNK pages a grid step
        self.decode_pages = -(-(-(-window // page_size) + 1)
                              // PAGES_PER_CHUNK) * PAGES_PER_CHUNK
        self.held: dict[int, Held] = {}
        self.pools: tuple = ()      # (k_pages, v_pages), made with the kv pool

    def admit(self, rid: int, prompt_len: int) -> bool:
        """An empty holding for request ``rid`` if the free pages cover the
        most this prompt will hold at once (its chunks then never find the
        group short)."""
        peak = min(prompt_len // self.page_size + 1, self.table_pages)
        if self.free < peak:
            return False
        self.held[rid] = Held()
        return True

    def short(self, rid: int, pos: int) -> int:
        """Pages still to allocate before position ``pos`` is writable."""
        held = self.held[rid]
        return max(0, pos // self.page_size + 1 - held.first
                   - len(held.pages))

    def extend(self, rid: int, pos: int) -> bool:
        got = self.alloc(self.short(rid, pos))
        if got is None:
            return False
        self.held[rid].pages.extend(got)
        return True

    def release_behind(self, rid: int, newest: int) -> int:
        """Give back the pages no query at position ``newest`` or later
        sees: those whose last row is at or before ``newest - window``."""
        held = self.held[rid]
        keep_from = max(0, newest - self.window + 1) // self.page_size
        n = min(max(0, keep_from - held.first), len(held.pages))
        for p in held.pages[:n]:
            self.decref(p)
        del held.pages[:n]
        held.first += n
        return n

    def release(self, rid: int) -> None:
        """Everything request ``rid`` holds, and the holding (nothing for
        a request that holds none: a slot released twice)."""
        held = self.held.pop(rid, None)
        for p in held.pages if held else ():
            self.decref(p)

    def tail(self, rids: Sequence[int], width: int = 0, rows: int = 0
             ) -> tuple:
        """A serve program's arguments for the group: its pools, one table
        row a request (``width`` wide, 0: the widest a request ever holds;
        ``rows`` rows, the rest the trash page) and the position of each
        table's first row."""
        width = width or self.table_pages
        tables = np.zeros((max(rows, len(rids)), width), np.int32)
        starts = np.zeros((tables.shape[0],), np.int32)
        for i, rid in enumerate(rids):
            held = self.held[rid]
            row = held.pages[:width]
            tables[i, :len(row)] = row
            starts[i] = held.first * self.page_size
        return (*self.pools, tables, starts)

    def keep(self, moved: list) -> list:
        """Bind the pools a program returned; what else it returned."""
        self.pools, *rest = moved
        return rest

    def holdings(self, rids: Sequence[int], contexts: Sequence[int]
                 ) -> tuple[int, int]:
        """(pages these requests hold, tokens a window layer's decode step
        reads for them: ``min(context, window)`` each)."""
        return (sum(len(self.held[r].pages) for r in rids),
                sum(min(c, self.window) for c in contexts))

    def check_held(self, rids: Sequence[int]) -> None:
        """:meth:`PagePool.check` for the group: the holdings are those of
        ``rids`` and no other, none past the bound, and the refcounts are
        theirs."""
        assert set(self.held) == set(rids), \
            "a window holding without its slot, or a slot without one"
        expected: dict[int, int] = {}
        for held in self.held.values():
            assert len(held.pages) <= self.table_pages, (
                f"a request holds {len(held.pages)} window pages, past the "
                f"bound of {self.table_pages}")
            for p in held.pages:
                expected[p] = expected.get(p, 0) + 1
        self.check(expected)


class NoWindow:
    """The window group of a family that states no ``"kv_window"`` layer:
    :class:`WindowPages`'s verbs over nothing. Never short, holds nothing,
    takes no argument of a serve program."""

    arity = donated = free = total = 0
    held: dict = {}
    pools: tuple = ()

    def admit(self, rid, prompt_len) -> bool:
        return True

    def short(self, rid, pos) -> int:
        return 0

    def extend(self, rid, pos) -> bool:
        return True

    def release_behind(self, rid, newest) -> int:
        return 0

    def release(self, rid) -> None:
        pass

    def tail(self, rids, width=0, rows=0) -> tuple:
        return ()

    def keep(self, moved: list) -> list:
        return moved

    def holdings(self, rids, contexts) -> tuple[int, int]:
        return 0, 0

    def check_held(self, rids) -> None:
        pass


def window_group(cfg, pool_pages: int, slots: int, page_size: int,
                 chunk: int) -> WindowPages | NoWindow:
    """The window group for a family: :class:`WindowPages` where it states
    ``"kv_window"`` (``pool_pages`` 0: every slot may hold the group's
    bound at once), else :class:`NoWindow`."""
    if not has_window(cfg):
        return NoWindow()
    window = cfg.sliding_window
    return WindowPages(
        pool_pages or 1 + slots * window_table_pages(window, chunk,
                                                     page_size),
        page_size, window, chunk)


def unheld_cache_reason(cfg) -> str | None:
    """Why the serve engine cannot hold what this model's layers keep for
    a sequence, in one sentence; None for a model it can serve."""
    unheld = sorted({c for c in (cfg.layer_caches or ())
                     if c not in (*KINDS, None)})
    if not unheld:
        return None
    return (f"this model's layers keep {', '.join(map(repr, unheld))} for a "
            "sequence (cfg.layer_caches), for which engine/kv_pool.py has no "
            "pool: it holds a K/V pair of heads a token for ever ('kv'), "
            "the same pair while the token is inside a sliding window "
            "('kv_window') and a recurrent state with its convolution tail "
            "a slot ('ssm'), so the model trains and is not served")


def layer_caches(cfg, n_layers: int) -> tuple[str | None, ...]:
    """What each layer keeps for a sequence: what the model's config
    states (``layer_caches``), else the paged pair in every layer."""
    stated = cfg.layer_caches
    return ("kv",) * n_layers if stated is None else tuple(stated)


def has_recurrent_state(cfg) -> bool:
    return "ssm" in (cfg.layer_caches or ())


def has_window(cfg) -> bool:
    return "kv_window" in (cfg.layer_caches or ())


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
    if has_window(cfg):
        raise ValueError(WINDOW_CACHE_REASON)
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


def window_kwargs(win: tuple) -> dict:
    """A serve program's window group ``(k_pages, v_pages, tables,
    starts)`` as the model's keywords (models/family.ServedDecoder);
    nothing for a family without the group."""
    if not win:
        return {}
    k_pages, v_pages, tables, starts = win
    return dict(window_pages=tuple(zip(k_pages, v_pages)),
                window_tables=tables, window_starts=starts)


def write_window_rows(win: tuple, inter, layers, pos, valid) -> tuple:
    """A decode step's or a continued prefill's write into the window
    group: the fresh row at position ``pos[b, t]`` lands in the page its
    table names ``pos - start`` rows on (``valid`` [B, T] false: the trash
    page). -> ``((k_pages, v_pages),)`` moved, or ``()``."""
    if not win:
        return ()
    k_pages, v_pages, tables, starts = win
    P = k_pages[0].shape[1]
    entry = jnp.clip((pos - starts[:, None]) // P, 0, tables.shape[1] - 1)
    page_idx = jnp.where(valid, jnp.take_along_axis(tables, entry, axis=1),
                         0)
    return (write_rows(k_pages, v_pages, inter, layers, page_idx, pos % P),)


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
