"""Serving plane: continuous-batching generation over the live base model.

The north star says *serve heavy traffic from millions of users*; until
this module nothing in the repo served. The federated loop's payoff —
the averager's continuously-improving base — is deployed continuously
here: a :class:`GenerationEngine` decodes a rolling batch of requests
and **hot-swaps** base-model revisions between decode steps, turning the
fleet into "train in public, deploy continuously" (ROADMAP item 3; the
TPU serving recipe — batched decode, static-shaped cache, compiled-once
step — follows the Gemma-on-TPU paper in PAPERS.md, 2605.25645).

Design, in the order it matters on TPU:

- **Compiled-once decode.** One jitted prefill program per prompt-length
  bucket and one jitted decode-step program per (batch-slot bucket,
  KV-page bucket) — the PR-8 bucket-ladder discipline
  (engine/batched_eval.py): shapes ride a power-of-two ladder,
  ``prefer_compiled`` pads a miss up to an already-compiled bucket, and
  steady-state decode runs ZERO fresh compiles (pinned via the shared
  ``compile.ms`` histogram; ``serve.decode_bucket_compiles`` counts
  occurrences).
- **Paged KV cache.** One fixed page pool per process —
  ``[layers, pages, page_size, kv_heads, head_dim]`` — with per-slot
  page tables. A sequence owns exactly the pages its length needs, so
  admitting a short prompt next to a long generation never pads the
  whole batch to the longest sequence: decode recomputes ONE token per
  sequence per step and attention reads each slot's own pages straight
  through the table (ops/paged_attention.py — the fused gather+attend
  Pallas kernel on TPU, its XLA twin elsewhere; dead page slots are
  masked by real lengths, and the dense gathered context the
  pre-round-20 spelling materialized per token no longer exists).
  Long prompts prefill through the standard model forward with the
  bucket's padding mask, which ops/flash_attention.py's rule declines:
  prefill attention is the XLA path.
  Page exhaustion preempts the youngest sequence back to the queue
  (deterministic under greedy decode) instead of OOMing the pool.
- **Continuous batching.** The scheduler admits queued requests into
  free slots every step, evicts finished sequences immediately, and
  keeps the decode program full; per-token latency is one decode step,
  not one full-batch generation.
- **One program ahead.** ``step()`` dispatches its decode program and
  returns; that program's tokens surface at the next ``step()``. While
  the batch does not change, the next program takes the last one's picks
  where they lie on the device, so the device runs step n+1 while the
  host emits step n's tokens; a change of composition is collected
  first (``_chainable``; docs/serving.md has the contract).
- **Hot swap.** A :class:`BaseRevisionWatcher` subscribes to the
  averager's base revisions through the existing Transport on a
  background thread, stages the fetched tree on device, and the engine
  installs it BETWEEN decode steps (double-buffered: params are plain
  jit arguments and are never donated, so an in-flight program keeps its
  buffer while the next step picks up the new one — the swap itself is a
  pointer rebind, measured as ``serve.swap_stall_ms``). Policy "drain":
  in-flight sequences finish on the revision they started on (admission
  pauses until they do); policy "restart": swap immediately and requeue
  in-flight prompts on the new revision. A torn or failed revision fetch
  degrades to the current base — the batch never stalls on the Hub.

Round 16 adds the under-load story on top (docs/serving.md):

- **Sampled decode.** Per-request ``temperature`` / ``top_p`` / ``seed``
  ride the SAME paged-KV programs and (slot, page) bucket ladder as
  greedy decode: an all-greedy batch dispatches the original
  ``serve.decode`` program (the parity-pinned path, byte-identical to
  before), any sampled lane switches the whole batch to
  ``serve.decode_sample`` — greedy lanes inside it still argmax. PRNG
  keys are derived IN-JIT as ``fold_in(PRNGKey(seed), token_index)``,
  so a request's stream depends only on (seed, position), never on
  batch layout — bit-identical across runs and across greedy/sampled
  mixes.
- **Prefix-cache page sharing.** Prompt pages are content-hashed at
  page granularity into a refcounted index (:class:`PrefixCache` over
  :class:`PagePool`): a repeated system prompt costs ONE prefill
  fleet-wide; later requests map the cached pages read-only, suffix-
  prefill only their divergent tail (``serve.prefill_ctx``), and
  copy-on-write the first diverging page before any scatter lands in
  shared memory. Pages free only at refcount 0; eviction is LRU over
  cache-only pages, tried before preemption.
- **Admission control.** ``max_queue`` bounds the queue; the HTTP
  frontend sheds with 429 + ``Retry-After`` at the bound and 503 while
  a drain-policy swap is in flight — open-loop overload is refused
  BEFORE the queueing knee instead of manufacturing ttft collapse
  (engine/router.py spreads and sheds across N such servers).

Round 17 adds **speculative decoding** (engine/speculative.py + the
``draft=``/``draft_k=`` engine knobs): a small fleet-trained drafter
proposes K tokens per slot per step, ONE batched ``serve.verify`` pass
scores all K+1 positions per slot (the multi-token twin of
``serve.decode`` — same model ``kv_pages`` hook, same paged-attention
path ``serve.prefill_ctx`` rides, same (slot, page) bucket keys), and
each slot commits the longest proposal prefix matching the target's own
per-position picks. Because the sampler is a counter PRNG
(``fold_in(seed, token_index)``), those picks ARE the tokens the plain
path would emit — speculative output is provably lossless and
bit-identical to spec-off streams, for greedy and sampled lanes alike.
Rollback is length bookkeeping, the drafter has its own hot-swap lane,
and a missing/stale/broken drafter degrades to plain decode.

A family whose layers do not all cache per token (models/nemotron_h.py:
state-space layers beside attention; models/gigachat3_5.py: delta-rule
layers beside LATENT attention) states per layer what it keeps
(``cfg.layer_caches``): the page pool then holds the attention layers
only, with the row widths the config states, and beside it lives one
fixed-size recurrent state per SLOT for each other layer, in the shapes
the config states (engine/kv_pool.py), written whole by a slot's
prefill, moved on in place by every decode step, handed back at
``_release``. The pools ride behind the programs' other arguments; a
family without them passes nothing there. The prefix cache serves such a
family from SNAPSHOTS: the state after a registered prompt's last token,
kept in a pool beside the slot pool, restored into the admitted slot's row
when a later prompt extends that one, the suffix's prefill continuing from
it (:class:`PrefixCache`). The speculative lane and KV transfer cannot
roll a state back or ship it and refuse such a family
(``kv_pool.RECURRENT_STATE_REASON``; docs/serving.md).

A family with sliding-window layers (models/afmoe.py) states
``"kv_window"`` for them: their pages are a SECOND GROUP with its own pool,
its own narrow table a slot and its own accounting
(``kv_pool.WindowPages``), which gives a page back as soon as it lies
wholly behind the window, at a decode step's growth or a prefill chunk's
end, while the global layers' group keeps every token. Admission, growth
and preemption account both groups; the programs take the window group's
pools, table and the table's first position behind their other arguments,
ahead of a per-slot state's. The prefix cache, the speculative lane and KV
transfer refuse such a family (``kv_pool.WINDOW_CACHE_REASON``).

Everything is exposed through the PR-3 obs registry as ``serve.*`` and
scraped by the PR-5 exporter as ``dt_serve_*`` gauges.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import itertools
import json
import logging
import os
import re
import threading
import time
import weakref
from collections import OrderedDict, deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import devprof, flight, obs, reqtrace
from . import kv_pool, serve_weights
from .batched_eval import _timed_compile

logger = logging.getLogger(__name__)

Params = Any

DEFAULT_PAGE_SIZE = 16

_LIVE_FRONTENDS: "weakref.WeakSet[ServeHTTPFrontend]" = weakref.WeakSet()


def live_frontends() -> list["ServeHTTPFrontend"]:
    """Frontends with a listening socket — the tests/conftest.py hygiene
    guard fails any module that leaves one serving."""
    return list(_LIVE_FRONTENDS)


# ---------------------------------------------------------------------------
# Requests and slots
# ---------------------------------------------------------------------------

_RID = itertools.count()


@dataclasses.dataclass
class ServeRequest:
    """One generation request's lifecycle. ``tokens`` accumulates the
    GENERATED ids (the prompt is not echoed); ``revision`` is the base
    revision the finished output was decoded on (the whole output, under
    the drain policy; the post-restart revision under restart)."""
    prompt: list
    max_new_tokens: int
    temperature: float = 0.0    # 0 = greedy (the parity-pinned path)
    top_p: float = 1.0          # nucleus mass; 1.0 = full distribution
    seed: int = 0               # per-request PRNG stream root
    rid: int = dataclasses.field(default_factory=lambda: next(_RID))
    tokens: list = dataclasses.field(default_factory=list)
    status: str = "queued"      # queued | active | done | truncated
    #                             | prefilled (prefill-phase worker)
    revision: str | None = None
    # content-addressable identity (utils/reqtrace.py): minted at the
    # frontend (router or server) or by submit() itself; propagated via
    # the X-DT-Request-Id header and stamped on every trace stage
    request_id: str | None = None
    # disaggregated serving (engine/kv_transfer.py): on a DECODE worker,
    # the manifest ref of a prefill worker's exported KV to adopt; on a
    # PREFILL worker, filled at finish with the published ref. None on
    # the unified path. ``first_token`` rides alongside: the prefill
    # worker's first-token decision (greedy argmax or the counter-PRNG
    # sample at index 0), re-emitted verbatim by the decode worker —
    # the bit-identity anchor of the cross-worker contract.
    kv_ref: str | None = None
    first_token: int | None = None
    # wall clock, for what is printed (reqtrace, the HTTP layer); every
    # latency is computed on the monotonic pair below
    submitted_t: float = dataclasses.field(default_factory=time.time)
    submitted_pc: float = dataclasses.field(
        default_factory=time.perf_counter)
    # perf_counter at each token's emit, one per entry of ``tokens``
    emit_t: list = dataclasses.field(default_factory=list)
    done_evt: threading.Event = dataclasses.field(
        default_factory=threading.Event)

    def wait(self, timeout: float | None = None) -> bool:
        return self.done_evt.wait(timeout)


@dataclasses.dataclass
class _Slot:
    req: ServeRequest
    pages: list          # page-pool indices this sequence owns
    seq_len: int         # tokens currently in the KV cache
    last_tok: int        # next input token (already emitted to req.tokens)
    order: int           # admission order (preemption picks the youngest)
    spec_window: int = 0  # drafts allowed THIS step (set by _grow: the
    #                       pages for seq_len..seq_len+spec_window are
    #                       owned exclusively; 0 = plain-decode lane)
    released: bool = False  # pages and state row handed back (_release):
    #                         a row a dispatched program still runs for
    #                         this slot is dropped when it is collected
    # lazy trace accumulators (utils/reqtrace.py): the per-token hot
    # path only bumps these slot-local scalars; _trace_flush folds them
    # into the request's timeline as ONE coalesced span whenever the
    # story moves on (another stage, preempt, finish)
    tr_decode_n: int = 0
    tr_decode_t0: float = 0.0
    tr_decode_t1: float = 0.0
    tr_tpot_sum: float = 0.0
    tr_tpot_n: int = 0


@dataclasses.dataclass
class _Flight:
    """A plain decode program dispatched and not yet collected. ``out``
    is what the program returned first, as it lies on the device: the
    picks ``[sb]`` (the next program's ``tokens`` when it is chained on
    this one) and, for a family that sows them, the layers' counts."""
    slots: list          # the _Slot of each live row, in row order
    sb: int              # the slot bucket the picks are padded to
    out: Any


# ---------------------------------------------------------------------------
# Bucket ladder (the PR-8 compiled-bucket discipline, per dimension)
# ---------------------------------------------------------------------------

class BucketLadder:
    """Power-of-two ladder up to ``top`` (then multiples of ``top``),
    with the ``prefer_compiled`` pad-up rule from
    BatchedCohortEvaluator.bucket_for: when the exact-fit bucket is not
    yet compiled but a larger one is, reuse the compiled one (padding
    waste) instead of walking the ladder through fresh compiles."""

    def __init__(self, top: int, *, prefer_compiled: bool = True):
        if top < 1:
            raise ValueError(f"ladder top must be >= 1, got {top}")
        buckets = []
        b = 1
        while b < top:
            buckets.append(b)
            b *= 2
        buckets.append(top)
        self.buckets = tuple(buckets)
        self.prefer_compiled = prefer_compiled
        self.seen: set[int] = set()

    def bucket_for(self, n: int) -> int:
        if n < 1:
            raise ValueError(f"need >= 1, got {n}")
        for b in self.buckets:
            if n <= b:
                target = b
                break
        else:
            top = self.buckets[-1]
            target = ((n + top - 1) // top) * top
        if self.prefer_compiled and target not in self.seen:
            bigger = sorted(b for b in self.seen if b >= target)
            if bigger:
                target = bigger[0]
        return target

    def mark(self, b: int) -> bool:
        """Record a dispatch at bucket ``b``; True when it is fresh
        (= a compile happened)."""
        fresh = b not in self.seen
        self.seen.add(b)
        return fresh


# ---------------------------------------------------------------------------
# Refcounted page pool + content-addressed prefix cache
# ---------------------------------------------------------------------------

# the "kv" kind's accounting lives beside the pool's layout; the name stays
# importable from here
PagePool = kv_pool.PagePool


class PrefixCache:
    """Content-addressed prompt-prefix index over the page pool.

    Pages are keyed by CHAIN digest: page *i* of a prompt is stored
    under ``(digest(pages[:i]), tokens(page i))`` where the parent
    digest folds every earlier page's tokens — a page is reusable only
    when everything before it matched too. Entries come in two flavors
    sharing one table: FULL pages (``page_size`` tokens — the chain
    walks through them) and PARTIAL tail pages (fewer tokens —
    terminal; a later prompt may reuse the overlapping head rows, the
    stale tail rows stay masked behind ``kv_lens`` until copy-on-write
    makes the page private). Each entry holds ONE pool reference, so
    cached pages survive the slots that wrote them; eviction (LRU, on
    allocation pressure) only ever frees a page whose cache reference
    is the LAST one — refcount-0 discipline, never a live slot's page.

    Matching is capped one token short of the prompt on purpose: at
    least one suffix token must run through prefill to produce the
    request's first next-token logits.

    For a family that keeps a recurrent state a slot the pages are half
    of what a prefix is: the state after its last token is the other, and
    it exists only where a prefill ENDED. With ``snapshot_rows`` the cache
    also indexes SNAPSHOTS: rows of a pool beside the slot pool
    (engine/kv_pool.make_snapshot_pool), one state and tail set for every
    ``"ssm"`` layer, keyed like a partial page by ``(digest of the full
    pages, the tail's tokens)`` of the registered prompt, so a key says
    exactly which tokens the state has seen. :meth:`match_state` answers
    all or nothing: the longest registered prompt that the new one
    extends, with its pages AND its row, or a miss; a shorter overlap has
    pages and no state. Rows are LRU like pages, with one refinement: a
    snapshot that ONE registered prompt has extended is a session's
    previous turn, which no one asks for again, and goes to the cold end
    (a second extension marks a prefix that many share, and it stays).
    Taking such a row back is a RETIREMENT (``snapshots_retired``); only
    the loss of a snapshot nothing has superseded counts as an EVICTION
    (``snapshots_evicted``): the number a deployment sizes its pool by."""

    ROOT = b"pfx-root"

    def __init__(self, pool: PagePool, page_size: int,
                 snapshot_rows: int = 0):
        self.pool = pool
        self.P = page_size
        # key = (parent_digest, token_tuple) -> page id; dict order IS
        # the LRU order (hits re-insert at the back)
        self._entries: dict[tuple, int] = {}
        self._kids: dict[bytes, list[tuple]] = {}
        # snapshots: the same keys -> a row of the snapshot pool, LRU in
        # dict order; how many registered prompts extended each
        self.snapshot_rows = snapshot_rows
        self._snaps: OrderedDict[tuple, int] = OrderedDict()
        self._snap_kids: dict[bytes, list[tuple]] = {}
        self._snap_free: list[int] = list(range(snapshot_rows))
        self._snap_extended: dict[tuple, int] = {}
        self.hits = 0
        self.misses = 0
        self.tokens_saved = 0
        self.pages_shared = 0
        self.snapshots_evicted = 0

    @staticmethod
    def _digest(parent: bytes, tokens: tuple) -> bytes:
        h = hashlib.blake2b(parent, digest_size=16)
        h.update(np.asarray(tokens, np.int64).tobytes())
        return h.digest()

    def __len__(self) -> int:
        return len(self._entries)

    def pages(self) -> list[int]:
        return list(self._entries.values())

    def _touch(self, key: tuple) -> None:
        self._entries[key] = self._entries.pop(key)

    def match(self, prompt: list) -> tuple[list[int], int]:
        """Longest reusable page run for ``prompt``: ``(pages, matched
        tokens)`` with ``matched`` capped at ``len(prompt) - 1``. The
        LAST page of the run may be partially matched (``matched %
        page_size != 0`` — its remaining rows hold some other
        continuation's kv, masked by ``kv_lens`` and copy-on-written
        before any write). Takes NO references — the caller increfs
        exactly what it admits."""
        P = self.P
        limit = len(prompt) - 1
        pages: list[int] = []
        matched = 0
        h = self.ROOT
        while matched < limit:
            want = prompt[matched:matched + min(P, limit - matched)]
            best_key, best_overlap = None, 0
            for key in self._kids.get(h, ()):
                if key not in self._entries:
                    continue
                n = 0
                for a, b in zip(want, key[1]):
                    if a != b:
                        break
                    n += 1
                if n > best_overlap:
                    best_key, best_overlap = key, n
            if best_key is None:
                break
            pages.append(self._entries[best_key])
            self._touch(best_key)
            matched += best_overlap
            if best_overlap == P == len(best_key[1]):
                h = self._digest(h, best_key[1])
                continue
            break   # partial page use is terminal
        return pages, matched

    def match_state(self, prompt: list
                    ) -> tuple[list[int], int, tuple | None]:
        """For a family with per-slot state: ``(pages, matched tokens,
        snapshot key)`` of the LONGEST registered prompt that ``prompt``
        extends by at least one token, whose every page and whose
        snapshot are still cached; ``([], 0, None)`` otherwise. The last
        page is partial where the registered prompt ended inside it (the
        caller copies it before it writes). Takes no references."""
        P = self.P
        limit = len(prompt) - 1
        walked: list[int] = []
        best: tuple[list[int], int, tuple | None] = ([], 0, None)
        h = self.ROOT
        at = 0
        while True:
            for key in self._snap_kids.get(h, ()):
                tail = key[1]
                end = at + len(tail)
                if (end <= best[1] or end > limit or key not in self._snaps
                        or tuple(prompt[at:end]) != tail):
                    continue
                if tail and key not in self._entries:
                    continue        # the tail's page was evicted: no hit
                best = (walked + ([self._entries[key]] if tail else []),
                        end, key)
            key = (h, tuple(prompt[at:at + P]))
            if at + P > limit or key not in self._entries:
                break
            walked.append(self._entries[key])
            self._touch(key)
            h = self._digest(h, key[1])
            at += P
        if best[2] is not None:
            if best[2][1]:
                self._touch(best[2])
            self._snaps.move_to_end(best[2])
        return best

    def snapshot_row(self, key: tuple) -> int:
        return self._snaps[key]

    def take_snapshot_row(self) -> int | None:
        """A row of the snapshot pool for a prompt about to be
        registered: a free one, else the least recently used snapshot's
        (None for a pool of no rows)."""
        if self._snap_free:
            return self._snap_free.pop()
        if not self._snaps:
            return None
        key = next(iter(self._snaps))
        # a snapshot that one registered prompt extended is that prompt's
        # past (the session moved on and took a new one): RETIRED, no
        # loss; any other is a prefix someone may still ask for: EVICTED
        if self._snap_extended.get(key) == 1:
            obs.count("serve.prefix.snapshots_retired")
        else:
            self.snapshots_evicted += 1
            obs.count("serve.prefix.snapshots_evicted")
        return self._drop_snapshot(key)

    def _drop_snapshot(self, key: tuple) -> int:
        row = self._snaps.pop(key)
        self._snap_extended.pop(key, None)
        kids = self._snap_kids[key[0]]
        kids.remove(key)
        if not kids:
            del self._snap_kids[key[0]]
        return row

    def register(self, prompt: list, slot_pages: list,
                 snapshot_row: int | None = None,
                 extends: tuple | None = None) -> None:
        """Index a freshly prefilled prompt's pages (full pages by
        chain digest, the partial tail by its token tuple). Each NEW
        entry takes one pool reference; a page already cached under the
        same key is skipped — the identical-prompt case keeps finding
        the original entry, not the admitting slot's CoW copy.

        ``snapshot_row`` (a row from :meth:`take_snapshot_row`, already
        holding the state after ``prompt``'s last token) is indexed under
        the prompt's end; ``extends`` is the key of the snapshot this
        prompt was restored from, if any."""
        P = self.P
        h = self.ROOT
        toks: tuple = ()
        for i in range(0, len(prompt), P):
            toks = tuple(prompt[i:i + P])
            key = (h, toks)
            if key in self._entries:
                self._touch(key)
            else:
                page = slot_pages[i // P]
                self._entries[key] = page
                self._kids.setdefault(h, []).append(key)
                self.pool.incref(page)
            if len(toks) < P:
                break
            h = self._digest(h, toks)
            toks = ()
        if snapshot_row is None:
            return
        key = (h, toks)
        if key in self._snaps:
            # the same prompt again: the row it holds is this state too
            self._snap_free.append(self._snaps.pop(key))
        else:
            self._snap_kids.setdefault(h, []).append(key)
        self._snaps[key] = snapshot_row
        if extends in self._snaps and extends != key:
            n = self._snap_extended[extends] = (
                self._snap_extended.get(extends, 0) + 1)
            if n == 1:
                # a session's previous turn: the first to go
                self._snaps.move_to_end(extends, last=False)

    def check(self) -> None:
        """Every row of the snapshot pool is free or indexed, once."""
        rows = self._snap_free + list(self._snaps.values())
        assert sorted(rows) == list(range(self.snapshot_rows)), (
            f"snapshot rows {sorted(rows)} of {self.snapshot_rows}")

    def evict_one(self) -> bool:
        """Drop the least-recently-used entry whose cache reference is
        the LAST reference — a page still mapped by any slot (or
        reachable only through it) is never touched. Descendants of an
        evicted chain link become unreachable and age out the same
        way."""
        for key, page in self._entries.items():
            if self.pool.refs(page) == 1:
                del self._entries[key]
                kids = self._kids[key[0]]
                kids.remove(key)
                if not kids:
                    del self._kids[key[0]]
                self.pool.decref(page)
                obs.count("serve.prefix_evictions")
                return True
        return False

    def flush(self) -> None:
        """Drop every entry and release its pool reference. Cached KV
        is a pure function of (params, tokens) — a base-revision swap
        invalidates all of it at once; pages still mapped by live slots
        survive on their slot references and free when those release."""
        for page in self._entries.values():
            self.pool.decref(page)
        self._entries.clear()
        self._kids.clear()
        self._snap_free += list(self._snaps.values())
        self._snaps.clear()
        self._snap_kids.clear()
        self._snap_extended.clear()
        obs.count("serve.prefix_flushes")


def _sample_from_logits(logits, temps, top_ps, seeds, tok_idx):
    """Seeded temperature / top-p sampling over a ``[B, V]`` logits
    block — the one sampling spelling shared by ``serve.decode_sample``
    and ``serve.sample_tok``. The PRNG key for lane *b* is
    ``fold_in(PRNGKey(seeds[b]), tok_idx[b])``: token *t* of a request
    depends ONLY on (seed, t), never on batch composition or slot
    index, which is what makes sampled streams bit-reproducible across
    runs and across greedy/sampled mixed batches. ``temps[b] == 0``
    lanes take the argmax (greedy) branch."""
    greedy = jnp.argmax(logits, axis=-1)
    keys = jax.vmap(lambda s, t: jax.random.fold_in(
        jax.random.PRNGKey(s), t))(seeds, tok_idx)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    order = jnp.argsort(-scaled, axis=-1)
    ranked = jnp.take_along_axis(scaled, order, axis=-1)
    probs = jax.nn.softmax(ranked, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps[:, None]   # mass BEFORE each token;
    #                                          the top token always stays
    ranked = jnp.where(keep, ranked, -jnp.inf)
    pick = jax.vmap(jax.random.categorical)(keys, ranked)
    sampled = jnp.take_along_axis(order, pick[:, None], axis=-1)[:, 0]
    return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Reference oracle
# ---------------------------------------------------------------------------

# one jitted full-forward per (model, padded length) for the reference
# loop below — the ORACLE math is unchanged (full recompute of the whole
# sequence per token, no KV reuse, no paging; right-padding is masked to
# exact zeros), jit just stops every call from re-tracing eagerly
_REF_PROGS: dict[tuple, Callable] = {}


def reference_generate(model, params, prompt: Sequence[int],
                       max_new_tokens: int, *, eos_id: int | None = None
                       ) -> list[int]:
    """The O(T^2) correctness oracle: greedy argmax over a FULL model
    forward of the growing sequence per token — no cache, nothing shared
    with the engine's decode path. The engine's output is pinned
    token-identical to this loop (tests/test_serve.py)."""
    cfg = model.cfg
    toks = [int(t) for t in prompt]
    total = len(toks) + max_new_tokens
    t_pad = ((total + 15) // 16) * 16
    key = (id(model), t_pad)
    prog = _REF_PROGS.get(key)
    if prog is None:
        def fwd(p, ids, cur):
            amask = (jnp.arange(t_pad)[None, :] < cur).astype(jnp.int32)
            logits = model.apply({"params": p}, ids, attention_mask=amask)
            return jnp.argmax(
                logits[0, cur - 1, :cfg.vocab_size]).astype(jnp.int32)

        prog = _REF_PROGS[key] = jax.jit(fwd)  # devprof: exempt (the tests' reference path, not a production program)
    buf = np.zeros((1, t_pad), np.int32)
    buf[0, :len(toks)] = toks
    cur = len(toks)
    out: list[int] = []
    for _ in range(max_new_tokens):
        nxt = int(prog(params, buf, np.int32(cur)))
        buf[0, cur] = nxt
        out.append(nxt)
        cur += 1
        if eos_id is not None and nxt == eos_id:
            break
    return out


def host_param_template(model) -> Params:
    """Host zeros tree in the model's param structure — what
    ``Transport.fetch_base`` wants as its template."""
    abstract = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, a.dtype), abstract)


def _with_stats(pick, inter, layers):
    """A serve program's token pick, and beside it, in the same fetch,
    the counts the model's layers sowed under ``serve_stats`` (a
    routed-expert layer's rows and touched experts: ops/moe.py), summed
    over layers. A model that sows none returns the pick alone: its
    programs are what they were."""
    stats: dict = {}
    for name in layers:
        for sown in inter.get(name, {}).get("serve_stats", ()):
            for key, val in sown.items():
                stats[key] = stats[key] + val if key in stats else val
    return (pick, stats) if stats else pick


def _state_kwargs(slot_state: tuple) -> dict:
    """The model arguments of a decode program's per-slot state tail
    ``(states, tails, slots)``; none for a family that keeps none."""
    if not slot_state:
        return {}
    states, tails, slots = slot_state
    return {"ssm_pools": tuple(zip(states, tails)), "slots": slots}


def _split_pick(out) -> tuple:
    """(pick, sown counts or None) of what :func:`_with_stats` made."""
    return out if isinstance(out, tuple) else (out, None)


_SOWN_COUNTERS = {
    "moe_rows": "serve.moe.rows",
    "moe_experts_touched": "serve.moe.experts_touched",
    "moe_rows_elsewhere": "serve.moe.rows_elsewhere",
    "ssm_slot_steps": "serve.ssm.slot_steps",
    "gdn_slot_steps": "serve.gdn.slot_steps",
    "kda_slot_steps": "serve.kda.slot_steps",
}


def _count_sown(stats: dict, per_step: bool = False) -> None:
    """Feed what one program run's layers counted (summed over layers) to
    the registry: a routed-expert layer's rows computed here, rows left
    to other chips and touched experts, a recurrent layer's live slots;
    off, or for a model that sows none, one branch."""
    if not stats or not obs.enabled():
        return
    for key, val in stats.items():
        obs.count(_SOWN_COUNTERS[key], int(val))
    touched = int(stats.get("moe_experts_touched", 0))
    if per_step and touched:
        obs.observe("serve.moe.rows_per_expert",
                    int(stats["moe_rows"]) / touched)


def _count_chunk(out) -> None:
    """The counts of a prefill chunk that is not a prompt's last: its pick
    is no token of the request's, what its layers sowed is counted all
    the same."""
    stats = _split_pick(out)[1]
    if stats is not None and obs.enabled():     # no fetch for nothing
        _count_sown(jax.device_get(stats))


def _layer_keys(params) -> list[str]:
    """Transformer block keys of an UNROLLED param tree, in layer order
    (``h_0..`` for GPT-2, ``layer_0..`` for Llama and DeepSeek-V3) — the
    same keys the ``intermediates`` collection uses for sown cache rows."""
    found = []
    for k in params:
        m = re.fullmatch(r"(h_|layer_)(\d+)", k)
        if m:
            found.append((int(m.group(2)), k))
    if not found:
        raise ValueError(
            "no transformer block keys (h_*/layer_*) in the param tree; "
            "is this an unrolled GPT-2/Llama base?")
    return [k for _, k in sorted(found)]


# ---------------------------------------------------------------------------
# Base-revision watcher (the transport subscription)
# ---------------------------------------------------------------------------

class BaseRevisionWatcher:
    """Polls ``transport.base_revision()`` on a daemon thread (named
    ``serve-watch``); on change, fetches the base and STAGES it on device
    off the decode thread, so the engine's swap is a pointer rebind. Any
    failure — revision probe, torn fetch, decode error — counts
    ``serve.swap_fetch_failures`` and leaves the current base serving
    (the ChaosTransport round in tests/test_serve.py pins this)."""

    def __init__(self, transport, template_fn: Callable[[], Params], *,
                 poll_s: float = 10.0, start_revision: str | None = None,
                 fetcher=None):
        self._transport = transport
        self._template_fn = template_fn
        # content-addressed base fetches (engine/basedist.BaseFetcher):
        # the swap pull diffs the published manifest against the local
        # shard store and fetches only changed-hash layers, racing any
        # mirror that has the hash; ALL its failure paths — hostile or
        # torn manifest included — degrade to the monolithic pull and
        # then to "no new base", so serving stays on the current base
        # (the same contract the ChaosTransport round pins for the
        # monolithic path). None = monolithic pulls.
        self.fetcher = fetcher
        # what places a fetched base on the device. The engine that takes
        # this watcher sets its serving-tree maker (serve_weights.make)
        # here, so a revision is rounded on THIS thread and the swap is
        # a rebind; what was staged before that is a placed base, and
        # ``install_params`` takes either.
        self.prepare: Callable[[Params], Params] = jax.device_put
        self.poll_s = poll_s
        self._last_seen = start_revision
        self._pending: tuple[str | None, Params] | None = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "BaseRevisionWatcher":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="serve-watch", daemon=True)
            self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            try:
                self.poll_once()
            except Exception:  # a watcher crash must never kill serving
                logger.exception("base watcher poll failed")

    def poll_once(self) -> bool:
        """One synchronous probe+stage attempt (tests drive this
        directly). True when a new revision was staged."""
        try:
            rev = self._transport.base_revision()
        except Exception:
            obs.count("serve.swap_fetch_failures")
            return False
        if rev is None or rev == self._last_seen:
            return False
        try:
            if self.fetcher is not None:
                got = self.fetcher.fetch(self._template_fn(), revision=rev)
            else:
                got = self._transport.fetch_base(self._template_fn())
        except Exception:
            obs.count("serve.swap_fetch_failures")
            flight.record("swap", outcome="fetch_failed",
                          revision=rev or "")
            logger.warning("base fetch for revision %s failed; serving "
                           "stays on the current base", rev, exc_info=True)
            return False
        if got is None:
            obs.count("serve.swap_fetch_failures")
            flight.record("swap", outcome="torn_fetch", revision=rev or "")
            return False
        base, fetched_rev = got
        placed = self.prepare(base)
        jax.block_until_ready(placed)   # stage fully OFF the decode thread
        with self._lock:
            self._pending = (fetched_rev, placed)
            self._last_seen = fetched_rev
        obs.count("serve.swaps_staged")
        logger.info("staged base revision %s for hot swap", fetched_rev)
        return True

    def take_pending(self) -> tuple[str | None, Params] | None:
        with self._lock:
            p, self._pending = self._pending, None
            return p

    def close(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class GenerationEngine:
    """Continuous-batching greedy decoder over a paged KV cache.

    ``model`` is a GPT-2/Llama flax module; the engine rebuilds it with
    ``remat=False, scan_blocks=False`` (generation never differentiates,
    and wire bases are unrolled already) — pass TRAINING params freely,
    the trees are identical. Thread contract: ``submit`` is thread-safe
    (HTTP handler threads call it); ``step`` must be driven from ONE
    thread (``ServeLoop`` or the role main)."""

    def __init__(self, model, params: Params | None = None, *,
                 revision: str | None = None,
                 max_slots: int = 8,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 pool_pages: int = 0,
                 window_pool_pages: int = 0,
                 max_seq_len: int = 0,
                 max_new_tokens: int = 64,
                 eos_id: int | None = None,
                 prefer_compiled: bool = True,
                 swap_policy: str = "drain",
                 watcher: BaseRevisionWatcher | None = None,
                 max_queue: int = 0,
                 prefix_cache: bool = False,
                 snapshot_rows: int = 0,
                 prefill_chunk: int = 0,
                 debug_invariants: bool = False,
                 draft=None,
                 draft_k: int = 4,
                 trace: bool = True,
                 trace_exemplars: int = 4,
                 trace_window_s: float = 30.0,
                 burn=None,
                 phase: str = "unified",
                 kv_exporter=None,
                 kv_adopter=None):
        if swap_policy not in ("drain", "restart"):
            raise ValueError(f"swap_policy must be drain|restart, "
                             f"got {swap_policy!r}")
        if phase not in ("unified", "prefill", "decode"):
            raise ValueError(f"phase must be unified|prefill|decode, "
                             f"got {phase!r}")
        if phase == "prefill" and kv_exporter is None:
            raise ValueError("phase='prefill' needs a kv_exporter "
                             "(engine/kv_transfer.KVExporter) — a "
                             "prefill worker that cannot export KV "
                             "serves nothing")
        if max_slots < 1 or page_size < 1:
            raise ValueError("max_slots and page_size must be >= 1")
        cfg = model.cfg
        cfg = dataclasses.replace(cfg, remat=False, scan_blocks=False)
        # a recurrent state cannot be rolled back or shipped: what needs
        # either refuses the family, with the reason
        if kv_pool.has_recurrent_state(cfg) and (
                draft is not None or kv_exporter is not None
                or kv_adopter is not None):
            raise ValueError(kv_pool.RECURRENT_STATE_REASON)
        # nor can what shares or ships pages by position for all layers
        # alike hold for a group that gives pages back behind a window
        if kv_pool.has_window(cfg) and (
                prefix_cache or draft is not None or kv_exporter is not None
                or kv_adopter is not None):
            raise ValueError(kv_pool.WINDOW_CACHE_REASON)
        self.model = type(model)(cfg)
        self.cfg = cfg
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.swap_policy = swap_policy
        self.watcher = watcher
        if watcher is not None:
            watcher.prepare = functools.partial(serve_weights.make, cfg)
        cap = getattr(cfg, "n_positions", None) or getattr(
            cfg, "max_seq_len", 0)
        # page-align DOWN so no prefill bucket can exceed the model's
        # position capacity (a padded prefill never indexes wpe/rope
        # beyond it)
        self.max_seq_len = (min(max_seq_len or cap, cap)
                            // page_size) * page_size
        if self.max_seq_len < page_size:
            raise ValueError(f"max_seq_len {self.max_seq_len} < page_size "
                             f"{page_size}")
        self.pages_per_slot = self.max_seq_len // page_size
        # the most tokens one prefill program takes: a longer prompt (or
        # suffix) is prefilled as that many and then as continuations over
        # its own pages and state, inside its admission. 0: no bound, one
        # program whatever the length (what every engine did before a
        # 40k-token prompt met a dense [T, T] attention)
        if prefill_chunk % page_size:
            raise ValueError(f"prefill_chunk {prefill_chunk} is no whole "
                             f"number of pages of {page_size}")
        self._chunk_pages = min(self.pages_per_slot,
                                prefill_chunk // page_size
                                or self.pages_per_slot)
        # page 0 is the TRASH page: padded batch slots and padded
        # page-table entries all point at it, so scatter writes from
        # dead lanes land somewhere harmless
        self.pool_pages = pool_pages or (
            1 + self.max_slots * self.pages_per_slot)
        if self.pool_pages < 1 + self.pages_per_slot:
            raise ValueError(
                f"pool_pages {self.pool_pages} cannot hold even one "
                f"max-length sequence ({self.pages_per_slot} pages) + "
                "the trash page")

        self._slot_ladder = BucketLadder(max_slots,
                                         prefer_compiled=prefer_compiled)
        self._page_ladder = BucketLadder(self.pages_per_slot,
                                         prefer_compiled=prefer_compiled)
        self._prefill_ladder = BucketLadder(self._chunk_pages,
                                            prefer_compiled=prefer_compiled)
        self.prefer_compiled = prefer_compiled

        # speculative decoding (engine/speculative.py): a drafter
        # proposes up to draft_k tokens per slot per step and ONE
        # serve.verify pass scores all K+1 positions; W = draft_k + 1
        # is baked static into the verify program family so mixed
        # drafting/non-drafting batches ride the same (slot, page) keys
        if draft is not None:
            if draft_k < 1:
                raise ValueError(f"draft_k must be >= 1, got {draft_k}")
            if hasattr(draft, "model"):
                from . import speculative as _spec
                reason = _spec.compat_reason(draft.model, cfg)
                if reason:
                    raise ValueError(f"incompatible draft model: {reason}")
        self._draft = draft
        self.draft_k = int(draft_k)
        self._verify_progs: dict[tuple[int, int], Callable] = {}
        self._verify_seen: set[tuple[int, int]] = set()
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_rounds = 0

        self._decode_progs: dict[tuple[int, int], Callable] = {}
        self._prefill_progs: dict[int, Callable] = {}
        # sampled-decode twins of the decode program family, plus the
        # suffix-prefill family the prefix cache dispatches (both ride
        # their own (bucket, bucket) keys so greedy steady-state compile
        # pins never see them)
        self._decode_sample_progs: dict[tuple[int, int], Callable] = {}
        self._prefill_ctx_progs: dict[tuple[int, int], Callable] = {}
        self._pctx_t_ladder = BucketLadder(self._chunk_pages,
                                           prefer_compiled=prefer_compiled)
        self._pctx_p_ladder = BucketLadder(self.pages_per_slot,
                                           prefer_compiled=prefer_compiled)
        self._sample_tok_prog_: Callable | None = None
        self._sample_tok_warm = False
        self._page_copy_prog_: Callable | None = None
        self._page_copy_warm = False
        # disaggregated serving (engine/kv_transfer.py): worker class +
        # the transfer plane. "prefill" finishes every request after
        # prefill + KV export; "decode" adopts exported pages at
        # admission (degrading to local prefill on any transfer
        # defect); "unified" is the classic engine — and the fallback
        # class the router keeps routing to in mixed fleets.
        self.phase = phase
        self._kv_exporter = kv_exporter
        self._kv_adopter = kv_adopter
        self._kv_adopt_prog_: Callable | None = None
        self._kv_adopt_warm = False
        self.kv_exported = 0     # requests whose KV export published
        self.kv_adopted = 0      # requests admitted on adopted pages
        self.kv_reprefills = 0   # adoption degrades -> local prefill
        self.kv_rev_mismatch = 0  # transfers refused on revision skew
        # donation lets XLA update the page pool in place (it is the
        # dominant buffer); CPU ignores donation with a warning, so skip
        self._donate = jax.default_backend() not in ("cpu",)

        self._params: Params | None = None
        self.revision: str | None = None
        self._layers: list[str] | None = None
        self._kv_arrays: kv_pool.Pool | None = None
        # whether some layer keeps a state per slot (cfg.layer_caches),
        # those layers' pools (states, tails), and which of their rows
        # each admitted request owns; all stay empty for a family that
        # caches per token only
        refusal = kv_pool.unheld_cache_reason(cfg)
        if refusal:
            raise ValueError(refusal)
        self._recurrent = kv_pool.has_recurrent_state(cfg)
        self._ssm: kv_pool.StatePool = ((), ())
        self._ssm_bytes = 0.0
        self._state_gauge = f"serve.{kv_pool.state_name(cfg)}.state_bytes"
        self._state_free: list[int] = []
        self._state_of: dict[int, int] = {}
        # the prefix cache's copies of it (kv_pool.make_snapshot_pool): as
        # many rows as slots unless told; none without the cache
        self._snapshot_rows = ((snapshot_rows or max_slots)
                               if self._recurrent and prefix_cache else 0)
        self._snap: kv_pool.StatePool = ((), ())
        self._state_copy_progs: dict[str, Callable] = {}
        # the window group (cfg.layer_caches states "kv_window"): its
        # accounting, its arrays and what each request holds of it; for a
        # family without the statement the same verbs over nothing
        self._window = kv_pool.window_group(
            cfg, window_pool_pages, max_slots, page_size,
            self._chunk_pages * page_size)
        # the transfer plane's wire is a K/V pair of heads: a model that
        # caches anything else is refused here, with the reason
        self._kv_geom = (kv_pool.kv_head_geometry(cfg)
                         if kv_exporter is not None or kv_adopter is not None
                         else None)
        self.pool: PagePool | None = None
        self._prefix_cache = prefix_cache
        self._cache: PrefixCache | None = None
        self.max_queue = max_queue
        self.debug_invariants = debug_invariants or bool(
            os.environ.get("DT_SERVE_DEBUG"))
        self.shed_count = 0          # frontend-counted 429 rejections
        self.cow_copies = 0
        self._active: list[_Slot] = []
        # the plain decode program dispatched and not yet collected: the
        # engine stays at most this ONE program ahead of the host
        self._flight: _Flight | None = None
        self._queue: deque[ServeRequest] = deque()
        self._qlock = threading.Lock()
        self._work_evt = threading.Event()
        self._pending_swap: tuple[str | None, Params] | None = None
        self._decode_seen: set[tuple[int, int]] = set()
        self._decode_sample_seen: set[tuple[int, int]] = set()
        self._pctx_seen: set[tuple[int, int]] = set()
        # set on preemption, cleared when a slot finishes: admission
        # would otherwise immediately re-take the pages growth just
        # freed and the pool would livelock at 100% churn
        self._admit_hold = False
        self._order = itertools.count()
        self._tok_rate_ema: float | None = None
        self.steps = 0
        self.tokens_emitted = 0
        self._decoded = 0    # tokens the decode programs emitted, ever
        # cumulative prefill dispatches (full + suffix): the load
        # harness's prefill cost model reads the delta per step to
        # charge compute-bound prefill work against a worker's clock
        self.prefills_done = 0
        # request-scoped lifecycle traces (utils/reqtrace.py): host-side
        # stage timelines + the tail-exemplar reservoir. Every
        # instrumentation site below is a single-branch no-op when
        # trace=False; ``burn`` (a health.BurnRateMonitor) receives each
        # finished/shed outcome as the SLO trace stream.
        self.trace = reqtrace.TraceBook(
            exemplar_k=trace_exemplars, window_s=trace_window_s,
            burn=burn) if trace else None
        if draft is not None and self.trace is not None:
            # the drafter records its cold catch-up prefills
            # ("spec_draft") against the same per-request timelines
            draft.trace = self.trace
        if params is not None:
            self.install_params(params, revision=revision)

    # -- weights ------------------------------------------------------------
    def install_params(self, params: Params, *,
                       revision: str | None = None) -> None:
        """Bind a base revision as the serving weights: ``params`` is a
        base as published (host or device) or a serving tree the watcher
        staged; what is bound, and what every serve program takes, is
        its serving tree (engine/serve_weights.py). Params are jit
        ARGUMENTS (never donated), so a swap cannot invalidate an
        in-flight program's buffers — the old tree simply drops its last
        reference."""
        placed = serve_weights.make(self.cfg, params)
        if self._layers is None:
            self._layers = _layer_keys(placed)
            self._init_kv()
        self._params = placed
        self.revision = revision

    def _layers_keeping(self, kind: str) -> list[str]:
        """The layers that keep ``kind`` for a sequence, in layer order:
        ``"kv"`` pages (every layer, unless the family states otherwise),
        ``"ssm"`` a per-slot state."""
        caches = kv_pool.layer_caches(self.cfg, len(self._layers))
        return [n for n, c in zip(self._layers, caches) if c == kind]

    @property
    def _kv_layers(self) -> list[str]:
        return self._layers_keeping("kv")

    @property
    def _ssm_layers(self) -> list[str]:
        return self._layers_keeping("ssm")

    def _init_kv(self) -> None:
        self.pool = PagePool(self.pool_pages)
        if self._prefix_cache:
            self._cache = PrefixCache(self.pool, self.page_size,
                                      self._snapshot_rows)
        if self._recurrent:
            self._state_free = list(range(self.max_slots))

    @property
    def _window_layers(self) -> list[str]:
        return self._layers_keeping("kv_window")

    @property
    def _kv(self) -> kv_pool.Pool:
        """The pool's device arrays, made when a program first takes
        them: a caller that handed ``install_params`` a float32 base
        lying on the device has let go of it by then, so that base, the
        serving tree and the pool never stand there together."""
        if self._kv_arrays is None:
            cfg = self.cfg
            self._kv_arrays = kv_pool.make_pool(
                len(self._kv_layers), self.pool_pages, self.page_size,
                kv_pool.row_widths(cfg), cfg.compute_dtype())
            if self._recurrent:
                self._ssm = kv_pool.make_state_pool(
                    cfg, len(self._ssm_layers), self.max_slots)
                self._ssm_bytes = float(sum(
                    x.nbytes for half in self._ssm for x in half))
                obs.gauge(self._state_gauge, self._ssm_bytes)
            self._window.pools = kv_pool.make_pool(
                len(self._window_layers), self._window.total + 1,
                self.page_size, kv_pool.row_widths(cfg),
                cfg.compute_dtype())
            if self._snapshot_rows:
                self._snap = kv_pool.make_snapshot_pool(
                    cfg, len(self._ssm_layers), self._snapshot_rows)
                obs.gauge("serve.prefix.snapshot_bytes", float(sum(
                    x.nbytes for half in self._snap for x in half)))
        return self._kv_arrays

    @_kv.setter
    def _kv(self, pool: kv_pool.Pool) -> None:
        self._kv_arrays = pool

    # -- submission ---------------------------------------------------------
    def submit(self, prompt: Sequence[int],
               max_new_tokens: int | None = None, *,
               temperature: float = 0.0, top_p: float = 1.0,
               seed: int = 0,
               request_id: str | None = None,
               kv_ref: str | None = None,
               first_token: int | None = None) -> ServeRequest:
        """Queue one generation request (thread-safe). Prompts longer
        than the cache capacity are rejected up front.
        ``temperature=0`` (the default) is greedy argmax — the
        parity-pinned path; ``temperature>0`` samples the scaled
        distribution truncated to ``top_p`` nucleus mass under the
        request's seeded PRNG stream."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        n_new = max_new_tokens if max_new_tokens is not None \
            else self.max_new_tokens
        if len(prompt) + n_new > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({n_new}) "
                f"exceeds max_seq_len {self.max_seq_len}")
        if kv_ref is not None and first_token is None:
            raise ValueError("kv_ref without first_token: the prefill "
                             "worker's first-token decision must ride "
                             "along for output identity")
        req = ServeRequest(prompt=prompt, max_new_tokens=n_new,
                           temperature=float(temperature),
                           top_p=float(top_p), seed=int(seed),
                           kv_ref=kv_ref,
                           first_token=(None if first_token is None
                                        else int(first_token)))
        if self.trace is not None:
            req.request_id = request_id or reqtrace.mint_request_id(
                prompt, max_new_tokens=n_new, temperature=req.temperature,
                top_p=req.top_p, seed=req.seed)
        else:
            req.request_id = request_id
        with self._qlock:
            self._queue.append(req)
            depth = len(self._queue)
        if self.trace is not None:
            self.trace.start(req, depth=depth)
        obs.count("serve.requests")
        self._work_evt.set()
        return req

    def _pop_queued(self) -> ServeRequest | None:
        with self._qlock:
            return self._queue.popleft() if self._queue else None

    def _requeue_front(self, req: ServeRequest) -> None:
        req.tokens.clear()
        req.emit_t.clear()
        req.status = "queued"
        with self._qlock:
            self._queue.appendleft(req)

    @property
    def queue_depth(self) -> int:
        with self._qlock:
            return len(self._queue)

    @property
    def active_count(self) -> int:
        return len(self._active)

    @property
    def idle(self) -> bool:
        """Nothing active, queued, or dispatched and not yet collected."""
        return (not self._active and self._flight is None
                and self.queue_depth == 0)

    @property
    def tokens_per_sec(self) -> float:
        return self._tok_rate_ema or 0.0

    @property
    def prefix_hits(self) -> int:
        return self._cache.hits if self._cache is not None else 0

    @property
    def prefix_misses(self) -> int:
        return self._cache.misses if self._cache is not None else 0

    @property
    def prefix_hit_rate(self) -> float:
        total = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / total if total else 0.0

    @property
    def prefix_tokens_saved(self) -> int:
        return self._cache.tokens_saved if self._cache is not None else 0

    @property
    def prefix_snapshots_evicted(self) -> int:
        return (self._cache.snapshots_evicted if self._cache is not None
                else 0)

    def flush_prefix_cache(self) -> None:
        """Forget every cached prefix (pages and snapshots): what a swap
        of the base does, for an operator who wants it without one."""
        if self._cache is not None:
            self._cache.flush()

    def declare_buckets(self, *, prefill_pages: Sequence[int] = (),
                        suffix_pages: Sequence[int] = (),
                        table_pages: Sequence[int] = (),
                        decode_pages: Sequence[int] = ()) -> None:
        """Name the rungs of the bucket ladders this deployment will
        compile: the prefill's and the suffix prefill's token buckets (in
        pages), the suffix prefill's and the decode programs' page-table
        widths. A need under a declared rung pads up to it as it does to
        a compiled one (``prefer_compiled``), so the programs that exist
        are the declared ones, whatever order the requests come in; each
        is compiled when first met."""
        for ladder, rungs in ((self._prefill_ladder, prefill_pages),
                              (self._pctx_t_ladder, suffix_pages),
                              (self._pctx_p_ladder, table_pages),
                              (self._page_ladder, decode_pages)):
            ladder.seen.update(int(r) for r in rungs)

    @property
    def speculative(self) -> bool:
        return self._draft is not None

    @property
    def spec_accept_rate(self) -> float:
        """Cumulative fraction of drafted tokens the verify pass
        accepted — the single number that decides whether speculation
        pays (tokens per verify ≈ 1 + rate·K)."""
        return (self._spec_accepted / self._spec_proposed
                if self._spec_proposed else 0.0)

    @property
    def spec_rounds(self) -> int:
        return self._spec_rounds

    def _spec_ready(self) -> bool:
        return self._draft is not None and getattr(self._draft, "ready",
                                                   False)

    # -- admission control --------------------------------------------------
    def admission_state(self) -> tuple[str, float]:
        """Admission-control verdict for frontends, decided BEFORE a
        request queues: ``("ok", 0)`` admits; ``("drain", s)`` — a
        staged drain-policy swap is finishing in-flight sequences, so
        new work would stall behind the drain (503); ``("shed", s)`` —
        the queue sits at ``max_queue`` and further open-loop arrivals
        would only manufacture ttft collapse past the queueing knee
        (429). The second element is the Retry-After estimate in
        seconds."""
        if self.swap_policy == "drain" and self._pending_swap is not None \
                and self._active:
            return "drain", self._retry_after()
        if self.max_queue and self.queue_depth >= self.max_queue:
            return "shed", self._retry_after()
        return "ok", 0.0

    def _retry_after(self) -> float:
        """Seconds until the queue plausibly has room: queued token
        work over the observed throughput, clamped to a range a client
        backoff can actually use."""
        depth = max(self.queue_depth, 1)
        tps = self.tokens_per_sec
        est = depth * self.max_new_tokens / tps if tps > 0 else 1.0
        return min(max(est, 1.0), 30.0)

    def wait_for_work(self, timeout: float) -> bool:
        """Block until a request arrives (ServeLoop's idle parking)."""
        got = self._work_evt.wait(timeout)
        if got:
            self._work_evt.clear()
        return got

    # -- programs -----------------------------------------------------------
    def _donated(self, pages_at: int, state_at: int) -> tuple:
        """The argument numbers a serve program updates in place: the two
        halves of the page pool and, for a family that keeps them, the
        two halves of the window group's pool and of the per-slot state
        behind the other arguments (the window group's four first)."""
        if not self._donate:
            return ()
        window = tuple(range(state_at, state_at + self._window.donated))
        state_at += self._window.arity
        state = (state_at, state_at + 1) if self._recurrent else ()
        return (pages_at, pages_at + 1, *window, *state)

    def _split_behind(self, behind: tuple) -> tuple[tuple, tuple]:
        """A program's trailing arguments -> (the window group's four, the
        per-slot state's three), each empty for a family without it."""
        n = self._window.arity
        return behind[:n], behind[n:]

    def _keep(self, k_pages, v_pages, moved: list) -> None:
        """Bind what a program returned behind its picks: the page pool,
        then the window group's pool and the per-slot state where the
        family has them."""
        self._kv = (k_pages, v_pages)
        moved = self._window.keep(moved)
        if moved:
            self._ssm = moved[0]

    def _window_chunk(self, req: ServeRequest, upto: int, width: int = 0
                      ) -> tuple:
        """The window group's tail for a prefill program that writes this
        request's rows up to position ``upto``: its pages grown that far
        (admission saw to it that the group has them), its table ``width``
        wide (0: the group's widest) and the position of the table's first
        row."""
        if not self._window.extend(req.rid, upto):
            raise RuntimeError("the window group ran short inside an "
                               "admission it had room for")
        return self._window.tail([req.rid], width)

    def _window_behind(self, rid: int, newest: int) -> None:
        """Give back this request's window pages that no query at
        ``newest`` or later sees (a chunk's end, a decode step's growth)."""
        n = self._window.release_behind(rid, newest)
        if n:
            obs.count("serve.kv.window.pages_released", n)

    def kv_holdings(self) -> tuple[int, int, int]:
        """(pages the active slots hold of the ``"kv"`` group, pages they
        hold of the window group, the tokens a window layer's decode step
        reads: ``min(context, window)`` a slot), summed over the slots."""
        return (sum(len(s.pages) for s in self._active),
                *self._window.holdings([s.req.rid for s in self._active],
                                       [s.seq_len for s in self._active]))

    def _slot_state(self, rows) -> tuple:
        """A serve program's per-slot state tail: the pools and the
        row(s) it writes; nothing for a family that keeps none. Read
        after ``self._kv``, which makes the pools."""
        return (*self._ssm, rows) if self._recurrent else ()

    def _prefill_prog(self, t_bucket: int) -> Callable:
        prog = self._prefill_progs.get(t_bucket)
        if prog is not None:
            return prog
        model, vocab = self.model, self.cfg.vocab_size
        layers, kv_layers, ssm_layers = (self._layers, self._kv_layers,
                                         self._ssm_layers)
        window_layers, split = self._window_layers, self._split_behind

        def serve_prefill(params, tokens, prompt_len, k_pages, v_pages,
                          page_row, *behind):
            # behind: a family with a window group passes (k_pages,
            # v_pages, page_row [1, pages], start) of it, one with per-slot
            # state
            # (states, tails, slot) after that; each gets its written
            # pools back behind, in that order
            win, slot_state = split(behind)
            amask = (jnp.arange(t_bucket)[None, :]
                     < prompt_len).astype(jnp.int32)
            logits, muts = model.apply(
                {"params": params}, tokens, attention_mask=amask,
                sow_kv=True, mutable=["intermediates"])
            k_pages, v_pages = kv_pool.write_pages(
                k_pages, v_pages, muts["intermediates"], kv_layers, page_row)
            moved = (kv_pool.write_pages(
                *win[:2], muts["intermediates"], window_layers, win[2][0]),
                     ) if win else ()
            moved += (kv_pool.write_slot_state(
                *slot_state[:2], muts["intermediates"], ssm_layers,
                slot_state[2]),) if slot_state else ()
            row = logits[0, prompt_len - 1, :vocab]
            nxt = jnp.argmax(row)
            # the logits row rides out so sampled requests can draw
            # their FIRST token through serve.sample_tok (greedy ones
            # take nxt and never touch it)
            return (_with_stats(nxt.astype(jnp.int32),
                                muts["intermediates"], layers),
                    row, k_pages, v_pages, *moved)

        prog = devprof.wrap(
            "serve.prefill",
            jax.jit(serve_prefill, donate_argnums=self._donated(3, 6)),
            bucket=t_bucket)
        self._prefill_progs[t_bucket] = prog
        return prog

    def _decode_prog(self, n_slots: int, n_pages: int) -> Callable:
        prog = self._decode_progs.get((n_slots, n_pages))
        if prog is not None:
            return prog
        model, vocab = self.model, self.cfg.vocab_size
        layers, kv_layers, ssm_layers = (self._layers, self._kv_layers,
                                         self._ssm_layers)
        window_layers, split = self._window_layers, self._split_behind

        def serve_decode(params, k_pages, v_pages, page_tables, seq_lens,
                         tokens, *behind):
            win, slot_state = split(behind)
            # paged attention: each block reads its OWN page-pool slice
            # directly through the table (ops/paged_attention.py — the
            # fused gather+attend kernel on TPU, its XLA twin off-TPU).
            # The dense [L, B, S, H, D] gathered context the pre-kernel
            # spelling materialized here per token no longer exists.
            kv_pages = tuple(zip(k_pages, v_pages))
            logits, muts = model.apply(
                {"params": params}, tokens[:, None],
                position_ids=seq_lens[:, None],
                kv_pages=kv_pages, page_tables=page_tables,
                kv_lens=seq_lens,
                sow_kv=True, mutable=["intermediates"],
                **kv_pool.window_kwargs(win), **_state_kwargs(slot_state))
            k_pages, v_pages = kv_pool.write_next_row(
                k_pages, v_pages, muts["intermediates"], kv_layers,
                page_tables, seq_lens)
            moved = kv_pool.write_window_rows(
                win, muts["intermediates"], window_layers,
                seq_lens[:, None], True)
            # the state pools come back moved on by the layers themselves
            moved += (kv_pool.sown_state(muts["intermediates"], ssm_layers),
                      ) if slot_state else ()
            nxt = jnp.argmax(logits[:, -1, :vocab], axis=-1)
            return (_with_stats(nxt.astype(jnp.int32),
                                muts["intermediates"], layers),
                    k_pages, v_pages, *moved)

        prog = devprof.wrap(
            "serve.decode",
            jax.jit(serve_decode, donate_argnums=self._donated(1, 6)),
            bucket=f"{n_slots}x{n_pages}")
        self._decode_progs[(n_slots, n_pages)] = prog
        return prog

    def _decode_sample_prog(self, n_slots: int, n_pages: int) -> Callable:
        """The sampled twin of :meth:`_decode_prog`: identical forward,
        scatter, and (slot, page) bucketing — only the token pick
        differs (seeded temperature/top-p via
        :func:`_sample_from_logits`; ``temps == 0`` lanes still argmax,
        so greedy requests inside a mixed batch stay greedy)."""
        prog = self._decode_sample_progs.get((n_slots, n_pages))
        if prog is not None:
            return prog
        model, vocab = self.model, self.cfg.vocab_size
        layers, kv_layers, ssm_layers = (self._layers, self._kv_layers,
                                         self._ssm_layers)
        window_layers, split = self._window_layers, self._split_behind

        def serve_decode_sample(params, k_pages, v_pages, page_tables,
                                seq_lens, tokens, temps, top_ps, seeds,
                                tok_idx, *behind):
            win, slot_state = split(behind)
            kv_pages = tuple(zip(k_pages, v_pages))
            logits, muts = model.apply(
                {"params": params}, tokens[:, None],
                position_ids=seq_lens[:, None],
                kv_pages=kv_pages, page_tables=page_tables,
                kv_lens=seq_lens,
                sow_kv=True, mutable=["intermediates"],
                **kv_pool.window_kwargs(win), **_state_kwargs(slot_state))
            k_pages, v_pages = kv_pool.write_next_row(
                k_pages, v_pages, muts["intermediates"], kv_layers,
                page_tables, seq_lens)
            moved = kv_pool.write_window_rows(
                win, muts["intermediates"], window_layers,
                seq_lens[:, None], True)
            moved += (kv_pool.sown_state(muts["intermediates"], ssm_layers),
                      ) if slot_state else ()
            nxt = _sample_from_logits(logits[:, -1, :vocab], temps,
                                      top_ps, seeds, tok_idx)
            return (_with_stats(nxt, muts["intermediates"], layers),
                    k_pages, v_pages, *moved)

        prog = devprof.wrap(
            "serve.decode_sample",
            jax.jit(serve_decode_sample,
                    donate_argnums=self._donated(1, 10)),
            bucket=f"{n_slots}x{n_pages}")
        self._decode_sample_progs[(n_slots, n_pages)] = prog
        return prog

    def _prefill_ctx_prog(self, t_bucket: int, pb: int) -> Callable:
        """Suffix prefill over cached context: ``ctx_len`` prompt tokens
        already lie in this slot's pages (the prefix cache mapped them,
        or this prompt's earlier chunk wrote them), so only the tail runs
        the model — ``t_bucket`` fresh tokens attend the paged context
        (the model's ``kv_pages`` hook; Tq>1 rides the XLA paths of
        ops/paged_attention.py) and their kv scatters into this slot's
        pages at arbitrary offsets (padded tail rows land on trash page
        0). A family with per-slot state passes its pools and the slot's
        row behind: the recurrent layers CONTINUE from the row (restored
        from a snapshot, or left by the chunk before) and the row is
        written with the state after the suffix."""
        prog = self._prefill_ctx_progs.get((t_bucket, pb))
        if prog is not None:
            return prog
        model, P, vocab = self.model, self.page_size, self.cfg.vocab_size
        layers, kv_layers, ssm_layers = (self._layers, self._kv_layers,
                                         self._ssm_layers)
        window_layers, split = self._window_layers, self._split_behind
        cap = self.max_seq_len

        def serve_prefill_ctx(params, tokens, ctx_len, suffix_len,
                              k_pages, v_pages, page_table, *behind):
            win, slot_state = split(behind)
            kv_pages = tuple(zip(k_pages, v_pages))
            pos = ctx_len + jnp.arange(t_bucket)
            valid = jnp.arange(t_bucket) < suffix_len
            # the recurrent layers must know which rows are padding (a
            # pad row would move the state); a family without them is
            # told nothing, as before
            state = dict(
                attention_mask=valid.astype(jnp.int32)[None, :],
                ssm_init=kv_pool.slot_state_rows(*slot_state)
            ) if slot_state else {}
            logits, muts = model.apply(
                {"params": params}, tokens,
                position_ids=jnp.minimum(pos, cap - 1)[None, :],
                kv_pages=kv_pages, page_tables=page_table,
                kv_lens=jnp.reshape(ctx_len, (1,)),
                sow_kv=True, mutable=["intermediates"],
                **kv_pool.window_kwargs(win), **state)
            page_idx = jnp.where(
                valid, page_table[0, jnp.minimum(pos // P, pb - 1)], 0)
            k_pages, v_pages = kv_pool.write_rows(
                k_pages, v_pages, muts["intermediates"], kv_layers,
                page_idx[None, :], (pos % P)[None, :])
            moved = kv_pool.write_window_rows(
                win, muts["intermediates"], window_layers, pos[None, :],
                valid[None, :])
            moved += (kv_pool.write_slot_state(
                *slot_state[:2], muts["intermediates"], ssm_layers,
                slot_state[2]),) if slot_state else ()
            row = logits[0, suffix_len - 1, :vocab]
            nxt = jnp.argmax(row)
            return (_with_stats(nxt.astype(jnp.int32),
                                muts["intermediates"], layers),
                    row, k_pages, v_pages, *moved)

        prog = devprof.wrap(
            "serve.prefill_ctx",
            jax.jit(serve_prefill_ctx, donate_argnums=self._donated(4, 7)),
            bucket=f"{t_bucket}x{pb}")
        self._prefill_ctx_progs[(t_bucket, pb)] = prog
        return prog

    def _copy_state_row(self, which: str, src_row: int, dst_row: int
                        ) -> None:
        """``"restore"``: row ``src_row`` of the snapshot pool over row
        ``dst_row`` of the slot pool (a hit's admission);
        ``"snapshot"``: the slot's row over the snapshot's (a prompt's
        registration). One program each way, the written pool donated."""
        prog = self._state_copy_progs.get(which)
        fresh = prog is None
        if fresh:
            def serve_state_copy(d_states, d_tails, s_states, s_tails,
                                 src, dst):
                return kv_pool.copy_state_row(
                    (d_states, d_tails), (s_states, s_tails), src, dst)

            prog = self._state_copy_progs[which] = devprof.wrap(
                f"serve.state_{which}",
                jax.jit(serve_state_copy,
                        donate_argnums=(0, 1) if self._donate else ()),
                bucket=1)
        dst, src = ((self._ssm, self._snap) if which == "restore"
                    else (self._snap, self._ssm))
        args = (*dst, *src, np.int32(src_row), np.int32(dst_row))
        with obs.phase(f"serve.prefix.{which}"):
            out = _timed_compile(prog, *args) if fresh else prog(*args)
        if which == "restore":
            self._ssm = out
        else:
            self._snap = out

    def _verify_prog(self, n_slots: int, n_pages: int) -> Callable:
        """The speculative verify pass: score W = draft_k + 1 positions
        per slot in ONE batched forward — position 0 consumes
        ``last_tok`` (exactly what plain decode would), positions
        1..k_i consume that slot's draft proposals, padded lanes beyond
        ``n_input`` scatter to trash page 0. The multi-token forward is
        the same suffix-prefill machinery ``serve.prefill_ctx`` uses
        (the model's ``kv_pages`` hook; Tq>1 rides the XLA twin of the
        Pallas paged-attention kernel — no new attention path), batched
        over slots on the SAME (slot, page) buckets as serve.decode.

        The pick at window position w is the token the PLAIN path would
        emit at stream index ``tok_idx0 + w`` given the tokens before
        it: greedy lanes argmax, sampled lanes run the identical seeded
        top-p draw at the identical counter index. Acceptance on the
        host is therefore prefix-matching proposals against these picks
        — the accept/resample rule under a counter PRNG whose draw is a
        pure function of (seed, index), which is what makes speculative
        output BIT-identical to the spec-off stream, not merely
        same-distribution."""
        prog = self._verify_progs.get((n_slots, n_pages))
        if prog is not None:
            return prog
        model, P, vocab = self.model, self.page_size, self.cfg.vocab_size
        layers = self._layers
        W = self.draft_k + 1
        cap = self.max_seq_len

        def serve_verify(params, k_pages, v_pages, page_tables, seq_lens,
                         tokens, n_input, temps, top_ps, seeds, tok_idx0):
            kv_pages = tuple(zip(k_pages, v_pages))
            pos = seq_lens[:, None] + jnp.arange(W)[None, :]   # [B, W]
            logits, muts = model.apply(
                {"params": params}, tokens,
                position_ids=jnp.minimum(pos, cap - 1),
                kv_pages=kv_pages, page_tables=page_tables,
                kv_lens=seq_lens,
                sow_kv=True, mutable=["intermediates"])
            valid = jnp.arange(W)[None, :] < n_input[:, None]
            page_idx = jnp.where(
                valid,
                jnp.take_along_axis(
                    page_tables, jnp.minimum(pos // P, n_pages - 1),
                    axis=1),
                0)                                          # [B, W]
            k_pages, v_pages = kv_pool.write_rows(
                k_pages, v_pages, muts["intermediates"], layers,
                page_idx, pos % P)
            flat = logits[:, :, :vocab].reshape(n_slots * W, vocab)
            tok_idx = (tok_idx0[:, None]
                       + jnp.arange(W)[None, :]).reshape(-1)
            picks = _sample_from_logits(
                flat, jnp.repeat(temps, W), jnp.repeat(top_ps, W),
                jnp.repeat(seeds, W), tok_idx)
            return picks.reshape(n_slots, W), k_pages, v_pages

        prog = devprof.wrap(
            "serve.verify",
            jax.jit(serve_verify,
                    donate_argnums=(1, 2) if self._donate else ()),
            bucket=f"{n_slots}x{n_pages}")
        self._verify_progs[(n_slots, n_pages)] = prog
        return prog

    def _sample_tok(self, row, req: ServeRequest, idx: int) -> int:
        """Draw one token from a prefill logits row through the shared
        sampling math (``serve.sample_tok`` — one bucket-free program,
        compiled once at the first sampled admission)."""
        prog = self._sample_tok_prog_
        if prog is None:
            def serve_sample_tok(row, temp, top_p, seed, tok_idx):
                return _sample_from_logits(
                    row[None, :], temp[None], top_p[None], seed[None],
                    tok_idx[None])[0]

            prog = devprof.wrap("serve.sample_tok",
                                jax.jit(serve_sample_tok),
                                bucket=1)
            self._sample_tok_prog_ = prog
        args = (row, np.float32(req.temperature), np.float32(req.top_p),
                np.int32(req.seed & 0x7FFFFFFF), np.int32(idx))
        if not self._sample_tok_warm:
            self._sample_tok_warm = True
            return int(_timed_compile(prog, *args))
        return int(prog(*args))

    def _page_copy_prog(self) -> Callable:
        prog = self._page_copy_prog_
        if prog is None:
            def serve_page_copy(k_pages, v_pages, src, dst):
                return kv_pool.copy_page(k_pages, v_pages, src, dst)

            prog = devprof.wrap(
                "serve.page_copy",
                jax.jit(serve_page_copy,
                        donate_argnums=(0, 1) if self._donate else ()),
                bucket=1)
            self._page_copy_prog_ = prog
        return prog

    def _copy_page(self, src: int, dst: int) -> None:
        """Whole-page KV copy (``serve.page_copy``) — the copy-on-write
        primitive: garbage rows beyond the valid length copy too, but
        they stay masked behind ``kv_lens`` until overwritten."""
        prog = self._page_copy_prog()
        k_pages, v_pages = self._kv
        if not self._page_copy_warm:
            self._page_copy_warm = True
            self._kv = _timed_compile(prog, k_pages, v_pages,
                                      np.int32(src), np.int32(dst))
        else:
            self._kv = prog(k_pages, v_pages, np.int32(src), np.int32(dst))

    def _decode_bucket(self, need_slots: int, need_pages: int,
                       progs: dict | None = None) -> tuple[int, int]:
        progs = self._decode_progs if progs is None else progs
        sb = self._slot_ladder.bucket_for(need_slots)
        pb = self._page_ladder.bucket_for(need_pages)
        if self.prefer_compiled and (sb, pb) not in progs:
            # joint pad-up: a compiled (bigger, bigger) program beats a
            # fresh exact-fit compile on BOTH axes (the per-dimension
            # ladders only see their own axis)
            cands = [k for k in progs
                     if k[0] >= need_slots and k[1] >= need_pages]
            if cands:
                return min(cands, key=lambda k: k[0] * k[1])
        return sb, pb

    # -- paging -------------------------------------------------------------
    def _alloc_pages(self, n: int) -> list | None:
        """Allocate ``n`` fresh pages (refcount 1 each). When the pool
        runs dry, evict unreferenced prefix-cache entries LRU-first —
        cached pages some live slot still shares are never reclaimed
        (refcount > 1 pins them)."""
        pages = self.pool.alloc(n)
        while pages is None:
            if self._cache is None or not self._cache.evict_one():
                return None
            pages = self.pool.alloc(n)
        return pages

    def _release(self, slot: _Slot) -> None:
        # decref, not free: pages the prefix cache (or a sibling slot)
        # still holds survive this slot's exit
        for p in slot.pages:
            self.pool.decref(p)
        slot.pages = []
        slot.released = True
        self._window.release(slot.req.rid)
        if slot.req.rid in self._state_of:
            # the row is free as it lies: the next prefill overwrites it
            self._state_free.append(self._state_of.pop(slot.req.rid))
        if self._draft is not None:
            # every slot exit — finish, preemption, restart-swap
            # requeue — drops the drafter's per-request state with it:
            # draft KV for a stream that is no longer committed must
            # never survive to propose against a different future
            self._draft.drop(slot.req.rid)

    def _trace_flush(self, slot: _Slot) -> None:
        """Fold the slot's lazy decode/tpot accumulators into its trace.

        Per-token work is an int bump on the slot; the timeline only
        sees one coalesced span per contiguous decode run, flushed when
        the request's story moves on (spec/cow/preempt/finish)."""
        if slot.tr_decode_n:
            self.trace.stage_span(slot.req.rid, "decode",
                                  slot.tr_decode_t0, slot.tr_decode_t1,
                                  slot.tr_decode_n,
                                  tokens=slot.tr_decode_n)
            slot.tr_decode_n = 0
        if slot.tr_tpot_n:
            self.trace.note_latency(slot.req.rid,
                                    tpot_sum_ms=slot.tr_tpot_sum,
                                    tpot_n=slot.tr_tpot_n)
            slot.tr_tpot_sum = 0.0
            slot.tr_tpot_n = 0

    def _finish(self, slot: _Slot, status: str) -> None:
        self._admit_hold = False
        self._release(slot)
        slot.req.status = status
        slot.req.revision = self.revision
        if self.trace is not None:
            # terminal "emit" stage + burn-monitor feed + reservoir
            # entry — before done_evt so a waiter observes a closed trace
            self._trace_flush(slot)
            self.trace.finish(slot.req, status)
        slot.req.done_evt.set()
        self._active.remove(slot)
        if status == "truncated":
            obs.count("serve.truncated")

    def _preempt_one(self, protect: _Slot | None = None) -> bool:
        """Free the youngest active slot's pages and requeue its request
        (greedy decode regenerates identically). The page-exhaustion
        escape hatch."""
        victims = [s for s in self._active if s is not protect]
        if not victims:
            return False
        victim = max(victims, key=lambda s: s.order)
        self._release(victim)
        self._active.remove(victim)
        self._requeue_front(victim.req)
        self._admit_hold = True
        if self.trace is not None:
            self._trace_flush(victim)
            self.trace.stage(victim.req.rid, "preempt",
                             seq_len=victim.seq_len)
        obs.count("serve.preempted")
        logger.info("preempted request %d (page pool exhausted)",
                    victim.req.rid)
        return True

    # -- hot swap -----------------------------------------------------------
    def _maybe_swap_draft(self) -> None:
        """The drafter's own hot-swap lane: a new fleet-averaged draft
        revision installs between steps. ``install_params`` flushes ALL
        draft KV (it is a pure function of draft params, exactly like
        the prefix cache under a target swap); live requests re-prefill
        their draft context at the next propose. No drain needed —
        proposals never cross a step boundary, and a flushed drafter
        can only lower acceptance, never correctness."""
        draft = self._draft
        watcher = getattr(draft, "watcher", None) \
            if draft is not None else None
        if watcher is None:
            return
        staged = watcher.take_pending()
        if staged is None:
            return
        rev, placed = staged
        draft.install_params(placed, revision=rev)
        obs.count("serve.spec_draft_swaps")
        flight.record("swap", outcome="draft_swapped", revision=rev or "")
        logger.info("hot-swapped draft to revision %s", rev)

    def _maybe_swap(self) -> None:
        self._maybe_swap_draft()
        if self.watcher is not None:
            staged = self.watcher.take_pending()
            if staged is not None:
                self._pending_swap = staged   # latest staged revision wins
        if self._pending_swap is None:
            return
        if self.swap_policy == "restart" and self._active:
            # what the old revision has already run is emitted first
            self._collect()
            # in-flight sequences restart from their prompts on the new
            # revision; their pages go back to the pool first
            for slot in list(self._active):
                if self._draft is not None:
                    # mid-speculation target swap: this slot's draft
                    # state (and any proposal it would seed) was built
                    # against output of the OLD params — _release drops
                    # it; counted so the swap/spec interaction is
                    # observable
                    obs.count("serve.spec_invalidations")
                self._release(slot)
                self._active.remove(slot)
                self._requeue_front(slot.req)
                if self.trace is not None:
                    self._trace_flush(slot)
                    self.trace.stage(slot.req.rid, "swap_invalidate",
                                     seq_len=slot.seq_len)
                obs.count("serve.swap_restarts")
        if self._active:
            return   # drain: finish in-flight on their revision first
        rev, placed = self._pending_swap
        t0 = time.perf_counter()
        self._params = placed
        self.revision = rev
        self._pending_swap = None
        if self._cache is not None:
            # cached KV was computed under the OLD params — every entry
            # is stale the instant the revision lands
            self._cache.flush()
        obs.observe("serve.swap_stall_ms",
                    (time.perf_counter() - t0) * 1e3)
        obs.count("serve.swaps")
        flight.record("swap", outcome="swapped", revision=rev or "",
                      policy=self.swap_policy)
        logger.info("hot-swapped base to revision %s", rev)

    def _cow_page(self, slot: _Slot, idx: int) -> bool:
        """Copy-on-write: give ``slot`` a private copy of its
        ``idx``-th page before a write would bleed into sequences
        sharing it. Returns False when the pool can't supply the copy
        target (caller preempts or truncates)."""
        got = self._alloc_pages(1)
        if got is None:
            return False
        src = slot.pages[idx]
        self._copy_page(src, got[0])
        self.pool.decref(src)
        slot.pages[idx] = got[0]
        self.cow_copies += 1
        if self.trace is not None:
            self._trace_flush(slot)
            self.trace.stage(slot.req.rid, "cow", pages=1)
        obs.count("serve.cow_copies")
        return True

    # -- scheduling ---------------------------------------------------------
    def _can_admit(self) -> bool:
        return (self._pending_swap is None or self.swap_policy == "restart") \
            and not (self._admit_hold and self._active) \
            and len(self._active) < self.max_slots

    def _admit(self) -> None:
        if self._flight is not None and self.queue_depth \
                and not self._can_admit():
            # a slot whose last token is in flight may make the room
            self._collect()
        while self._can_admit():
            req = self._pop_queued()
            if req is None:
                return
            if not self._admit_one(req):
                return

    def _admit_one(self, req: ServeRequest) -> bool:
        """Admit one request: consult the prefix cache for shared
        context pages (increfs them), allocate the rest fresh, then
        run full or suffix prefill. On pool exhaustion the request
        goes back to the queue front with its increfs rolled back."""
        P = self.page_size
        plen = len(req.prompt)
        # queue age (submit -> admission attempt): the "how long did
        # this request wait" half of TTFT — exported for fleet_report's
        # q_age95 column whether or not per-request tracing is on
        queue_age_ms = (time.perf_counter() - req.submitted_pc) * 1e3
        if req.kv_ref is not None and self._kv_adopter is not None:
            verdict = self._try_adopt(req, queue_age_ms)
            if verdict == "ok":
                return True
            if verdict == "full":
                return False
            # "degrade": any transfer defect falls through to the
            # classic local-prefill admission below — counted, loud,
            # and output-identical (prefill is deterministic in the
            # served revision)
        shared: list[int] = []
        matched = 0
        snap_key = None
        if self._cache is not None:
            if self._recurrent:
                shared, matched, snap_key = self._cache.match_state(
                    list(req.prompt))
            else:
                shared, matched = self._cache.match(list(req.prompt))
            if matched:
                for p in shared:
                    self.pool.incref(p)
                self._cache.hits += 1
                self._cache.tokens_saved += matched
                self._cache.pages_shared += len(shared)
                obs.count("serve.prefix_hits")
                obs.count("serve.prefix_tokens_saved", matched)
                obs.count("serve.prefix_pages_shared", len(shared))
            else:
                self._cache.misses += 1
                obs.count("serve.prefix_misses")
        need = plen // P + 1 - len(shared)
        # both groups or neither: the window group says whether it has
        # the most this prompt will hold of it at once, and hands out
        # nothing yet
        fresh = (self._alloc_pages(need)
                 if self._window.admit(req.rid, plen) else None)
        if fresh is None:
            self._window.release(req.rid)
            for p in shared:
                self.pool.decref(p)
            self._requeue_front(req)
            return False
        pages = shared + fresh
        if matched and matched % P:
            # the suffix's first write lands mid-way into the last
            # matched page — it must be private before prefill scatters
            # into it
            idx = matched // P
            slot_stub = _Slot(req=req, pages=pages, seq_len=0, last_tok=0,
                              order=-1)
            if self.pool.refs(pages[idx]) > 1 and \
                    not self._cow_page(slot_stub, idx):
                for p in pages:
                    self.pool.decref(p)
                self._requeue_front(req)
                return False
            pages = slot_stub.pages
        obs.observe("serve.queue_age_ms", queue_age_ms)
        if self.trace is not None:
            # a request the scheduler already admitted once (then
            # preempted / swap-invalidated) re-enters as "readmit" —
            # the waterfall distinguishes first-wait from churn-wait
            if self.trace.seen(req.rid, "admit"):
                self.trace.stage(req.rid, "readmit",
                                 queue_age_ms=queue_age_ms)
            else:
                self.trace.stage(req.rid, "admit",
                                 queue_age_ms=queue_age_ms)
        if self._recurrent:
            # the request's row of the state pools, its own until
            # _release; a prefill overwrites whatever it held
            row = self._state_of[req.rid] = self._state_free.pop()
            if snap_key is not None:
                self._copy_state_row(
                    "restore", self._cache.snapshot_row(snap_key), row)
                obs.count("serve.prefix.snapshots_restored")
        # to completion: both return the first token ON THE HOST, and the
        # `serve.prefill` span's time around it
        if matched:
            tok, dur_ms = self._prefill_shared(req, pages, matched)
        else:
            tok, dur_ms = self._prefill(req, pages)
        obs.count("serve.prefills")
        obs.count("serve.prefill_tokens", plen - matched)
        self.prefills_done += 1
        if self.trace is not None:
            self.trace.stage(req.rid, "prefill", pfx_hit=int(matched > 0),
                             pfx_tokens=matched, prompt_tokens=plen,
                             dur_ms=round(dur_ms, 3))
        if self._cache is not None:
            # a prompt that HIT is registered too: its new pages extend
            # the chain, so a session gains on every turn, not on one
            snap_row = None
            if self._recurrent:
                snap_row = self._cache.take_snapshot_row()
                if snap_row is not None:
                    self._copy_state_row(
                        "snapshot", self._state_of[req.rid], snap_row)
                    obs.count("serve.prefix.snapshots_taken")
            self._cache.register(list(req.prompt), pages, snap_row,
                                 snap_key)
        self._activate(req, pages, tok)
        return True

    def _try_adopt(self, req: ServeRequest, queue_age_ms: float) -> str:
        """Admit one request on ADOPTED KV pages — the decode worker's
        side of the disaggregated hop. Returns "ok" (slot active on the
        transferred pages), "full" (pool exhausted; requeued, stop
        admitting), or "degrade" (absent/torn manifest, hash miss,
        geometry skew, or base-revision mismatch — fall through to
        local prefill; every fallback is counted, never silent)."""
        t0 = time.perf_counter()
        got = self._kv_adopter.fetch(req.kv_ref)
        if got is None:
            obs.count("serve.kv_adopt_failures")
            self.kv_reprefills += 1
            obs.count("serve.kv_reprefills")
            return "degrade"
        if got["revision"] != (self.revision or ""):
            # loud by contract: KV is a pure function of (params,
            # tokens), so pages prefilled on another base revision are
            # garbage here — not approximately right
            self.kv_rev_mismatch += 1
            obs.count("serve.kv_rev_mismatch")
            self.kv_reprefills += 1
            obs.count("serve.kv_reprefills")
            logger.warning(
                "kv adoption refused: pages prefilled on revision %r, "
                "serving %r (request %s) — re-prefilling locally",
                got["revision"], self.revision, req.request_id)
            return "degrade"
        P = self.page_size
        plen = len(req.prompt)
        want = {"layers": len(self._layers), "page_size": P,
                "kv_heads": self._kv_geom[0],
                "head_dim": self._kv_geom[1],
                "dtype": str(jnp.dtype(self.cfg.compute_dtype()))}
        if got["geometry"] != want or got["prompt_len"] != plen \
                or len(got["pages"]) != (plen + P - 1) // P:
            obs.count("serve.kv_adopt_failures")
            self.kv_reprefills += 1
            obs.count("serve.kv_reprefills")
            return "degrade"
        pages = self._alloc_pages(plen // P + 1)
        if pages is None:
            self._requeue_front(req)
            return "full"
        for i, (k, v) in enumerate(got["pages"]):
            self._adopt_page(pages[i], k, v)
        dur_ms = (time.perf_counter() - t0) * 1e3
        obs.observe("serve.queue_age_ms", queue_age_ms)
        obs.observe("serve.kv_adopt_ms", dur_ms)
        obs.count("serve.kv_adoptions")
        obs.count("serve.kv_pages_adopted", len(got["pages"]))
        self.kv_adopted += 1
        if self.trace is not None:
            stage = "readmit" if self.trace.seen(req.rid, "admit") \
                else "admit"
            self.trace.stage(req.rid, stage, queue_age_ms=queue_age_ms)
            self.trace.stage(req.rid, "kv_adopt",
                             pages=len(got["pages"]),
                             dur_ms=round(dur_ms, 3))
        if self._cache is not None:
            # adoption = incref'd read-only pages: the cache takes its
            # own reference per page, so a sibling request sharing the
            # prompt prefix reuses them and this slot's first write
            # into a shared page rides the CoW path — the exact
            # invariants --debug-invariants audits on the unified
            # engine
            self._cache.register(list(req.prompt), pages)
        self._activate(req, pages, int(got["first_token"]))
        return "ok"

    def _adopt_page(self, dst: int, k_new, v_new) -> None:
        """Write one fetched KV page into pool slot ``dst`` — the
        ``serve.kv_adopt`` program (engine/kv_transfer.make_adopt_prog):
        bucket-free like ``serve.page_copy``, compiled once at the
        first adoption and warm forever, so the decode worker's
        steady-state fresh-compile pin stays 0."""
        prog = self._kv_adopt_prog_
        if prog is None:
            from . import kv_transfer as _kvt
            prog = _kvt.make_adopt_prog(self._donate)
            self._kv_adopt_prog_ = prog
        k_pages, v_pages = self._kv
        args = (k_pages, v_pages, jnp.asarray(k_new),
                jnp.asarray(v_new), np.int32(dst))
        if not self._kv_adopt_warm:
            self._kv_adopt_warm = True
            self._kv = _timed_compile(prog, *args)
        else:
            self._kv = prog(*args)

    def _finish_prefill(self, req: ServeRequest, pages: list,
                        nxt: int) -> None:
        """Prefill-phase terminal: export the prompt's KV pages as
        content-addressed shards + a per-request manifest (manifest
        LAST — engine/kv_transfer.KVExporter), release the slot-side
        page references, and finish the request as ``prefilled``
        carrying the manifest ref and the first-token decision. With a
        prefix cache attached the pages stay resident, so the next
        same-prefix request's export dedupes to zero fresh wire
        bytes."""
        P = self.page_size
        plen = len(req.prompt)
        ncontent = (plen + P - 1) // P
        t0 = time.perf_counter()
        k_host, v_host = kv_pool.read_pages(
            self._kv, pages[:ncontent], self._kv_geom[0])
        kv_ref = req.request_id or f"rq-rid{req.rid}"
        ok = self._kv_exporter.export(
            request_id=kv_ref, revision=self.revision or "",
            pages=[(k_host[:, i], v_host[:, i]) for i in range(ncontent)],
            prompt_len=plen, first_token=int(nxt), page_size=P)
        dur_ms = (time.perf_counter() - t0) * 1e3
        if ok:
            self.kv_exported += 1
            req.kv_ref = kv_ref
        req.first_token = int(nxt)
        req.tokens.append(int(nxt))
        now = time.perf_counter()
        req.emit_t.append(now)
        self.tokens_emitted += 1
        obs.count("serve.tokens")
        ttft_ms = (now - req.submitted_pc) * 1e3
        obs.observe("serve.ttft_ms", ttft_ms)
        for p in pages:
            self.pool.decref(p)
        req.status = "prefilled"
        req.revision = self.revision
        if self.trace is not None:
            self.trace.stage(req.rid, "kv_export", pages=ncontent,
                             ok=int(ok), dur_ms=round(dur_ms, 3))
            self.trace.note_latency(req.rid, ttft_ms=ttft_ms)
            self.trace.finish(req, "prefilled")
        req.done_evt.set()
        self._admit_hold = False

    def _prefill(self, req: ServeRequest, pages: list) -> tuple[int, float]:
        """Full prefill. Returns the first token and the milliseconds of
        its `serve.prefill` span: input build, dispatch and the token's
        arrival on the host. A prompt longer than the top prefill bucket
        (``prefill_chunk``) runs that many tokens here and the rest as
        continuations over its own pages and state."""
        P = self.page_size
        plen = len(req.prompt)
        head = min(plen, self._chunk_pages * P)
        t_bucket = self._prefill_ladder.bucket_for(
            (head + P - 1) // P) * P
        with obs.phase("serve.prefill", timed=True, rid=req.rid,
                       bucket=t_bucket) as ph:
            mp = t_bucket // P
            toks = np.zeros((1, t_bucket), np.int32)
            toks[0, :head] = req.prompt[:head]
            page_row = np.zeros((mp,), np.int32)
            row = pages[:mp]
            page_row[:len(row)] = row
            # a declared rung (declare_buckets) is seen before it is built
            fresh = t_bucket not in self._prefill_progs
            prog = self._prefill_prog(t_bucket)
            k_pages, v_pages = self._kv
            args = (self._params, toks, np.int32(head), k_pages, v_pages,
                    page_row) + self._window_chunk(req, head, mp)
            if self._recurrent:
                args += self._slot_state(np.int32(self._state_of[req.rid]))
            self._prefill_ladder.mark(t_bucket // P)
            if fresh:
                obs.count("serve.prefill_bucket_compiles")
                nxt, logit_row, k_pages, v_pages, *moved = _timed_compile(
                    prog, *args)
            else:
                nxt, logit_row, k_pages, v_pages, *moved = prog(*args)
            self._keep(k_pages, v_pages, moved)
            self._window_behind(req.rid, head)
            if head < plen:
                _count_chunk(nxt)
                nxt, logit_row = self._prefill_rest(req, pages, head)
            tok = self._first_token(req, nxt, logit_row)
        return tok, ph.dur_ms

    def _prefill_rest(self, req: ServeRequest, pages: list, ctx_len: int
                      ) -> tuple:
        """The prompt's tokens from ``ctx_len`` on, a chunk at a time
        through ``serve.prefill_ctx``, each over the pages (and from the
        state row) the one before it wrote. Returns the LAST chunk's pick
        and logits row as they lie on the device."""
        P = self.page_size
        plen = len(req.prompt)
        pb = self._pctx_p_ladder.bucket_for(plen // P + 1)
        self._pctx_p_ladder.mark(pb)
        table = np.zeros((1, pb), np.int32)
        table[0, :len(pages)] = pages
        while True:
            suffix = min(plen - ctx_len, self._chunk_pages * P)
            t_bucket = self._pctx_t_ladder.bucket_for(
                (suffix + P - 1) // P) * P
            self._pctx_t_ladder.mark(t_bucket // P)
            toks = np.zeros((1, t_bucket), np.int32)
            toks[0, :suffix] = req.prompt[ctx_len:ctx_len + suffix]
            prog = self._prefill_ctx_prog(t_bucket, pb)
            k_pages, v_pages = self._kv
            args = (self._params, toks, np.int32(ctx_len), np.int32(suffix),
                    k_pages, v_pages, table) + self._window_chunk(
                        req, ctx_len + suffix)
            if self._recurrent:
                args += self._slot_state(np.int32(self._state_of[req.rid]))
            if (t_bucket, pb) not in self._pctx_seen:
                self._pctx_seen.add((t_bucket, pb))
                obs.count("serve.prefill_bucket_compiles")
                nxt, logit_row, k_pages, v_pages, *moved = _timed_compile(
                    prog, *args)
            else:
                nxt, logit_row, k_pages, v_pages, *moved = prog(*args)
            self._keep(k_pages, v_pages, moved)
            ctx_len += suffix
            self._window_behind(req.rid, ctx_len)
            if ctx_len >= plen:
                return nxt, logit_row
            _count_chunk(nxt)

    def _prefill_shared(self, req: ServeRequest, pages: list,
                        ctx_len: int) -> tuple[int, float]:
        """Suffix prefill: ``ctx_len`` prompt tokens already live in
        shared cache pages (and, for a family with per-slot state, the
        state after them in the slot's row); only the tail runs the
        model. Returns what ``_prefill`` does."""
        P = self.page_size
        plen = len(req.prompt)
        first = min(plen - ctx_len, self._chunk_pages * P)
        with obs.phase(
                "serve.prefill", timed=True, rid=req.rid,
                bucket=self._pctx_t_ladder.bucket_for((first + P - 1) // P)
                * P,
                ctx_pages=self._pctx_p_ladder.bucket_for(plen // P + 1)
                ) as ph:
            nxt, logit_row = self._prefill_rest(req, pages, ctx_len)
            tok = self._first_token(req, nxt, logit_row)
        return tok, ph.dur_ms

    def _first_token(self, req: ServeRequest, nxt, logit_row) -> int:
        """The prefill's pick on the host: the host waits for the device
        HERE, which is why `serve.prefill` ends after it."""
        nxt, stats = _split_pick(nxt)
        if stats is not None:
            _count_sown(jax.device_get(stats))
        if req.temperature > 0.0:
            return self._sample_tok(logit_row, req, 0)
        return int(nxt)

    def _activate(self, req: ServeRequest, pages: list, nxt: int) -> None:
        if self.phase == "prefill":
            # a prefill worker never decodes: the request's lifecycle
            # ends here with its KV exported and the first-token
            # decision attached for the decode worker to re-emit
            self._finish_prefill(req, pages, nxt)
            return
        req.status = "active"
        slot = _Slot(req=req, pages=pages, seq_len=len(req.prompt),
                     last_tok=nxt, order=next(self._order))
        self._active.append(slot)
        self._emit(slot, nxt)

    def _emit(self, slot: _Slot, tok: int) -> None:
        slot.req.tokens.append(tok)
        self.tokens_emitted += 1
        obs.count("serve.tokens")
        # request-level latency attribution: TTFT = submit -> first
        # token (both on perf_counter), including queue wait — the number a
        # CALLER experiences, which tokens/sec alone cannot show; TPOT =
        # the wall gap between this slot's consecutive tokens (decode
        # step + scheduler overhead as one per-token figure). Both export
        # as dt_serve_ttft_ms_* / dt_serve_tpot_ms_* gauges and ride the
        # server heartbeat into fleet_report's ttft95/tpot95 columns.
        now = time.perf_counter()
        stamps = slot.req.emit_t
        stamps.append(now)
        if len(stamps) == 1:
            ttft_ms = (now - slot.req.submitted_pc) * 1e3
            obs.observe("serve.ttft_ms", ttft_ms)
            if self.trace is not None:
                self.trace.note_latency(slot.req.rid, ttft_ms=ttft_ms)
        else:
            tpot_ms = (now - stamps[-2]) * 1e3
            obs.observe("serve.tpot_ms", tpot_ms)
            if self.trace is not None:
                # lazy: fold into the slot; _trace_flush hands the
                # weighted sum to note_latency in one call per run
                slot.tr_tpot_sum += tpot_ms
                slot.tr_tpot_n += 1
        if (self.eos_id is not None and tok == self.eos_id) or \
                len(slot.req.tokens) >= slot.req.max_new_tokens:
            self._finish(slot, "done")
        elif slot.seq_len >= self.max_seq_len:
            # the next decode would write past the cache; submit()'s
            # length check makes this unreachable, kept as a hard stop
            self._finish(slot, "truncated")

    def _spec_horizon(self, slot: _Slot) -> int:
        """How many tokens this slot may draft this step: capped by
        draft_k, by the tokens it still owes (drafting past
        max_new_tokens is wasted verify work — the run stops at the
        budget anyway), and by cache capacity (the verify window writes
        rows seq_len..seq_len+k, all of which must exist)."""
        if not self._spec_ready():
            return 0
        rem = slot.req.max_new_tokens - len(slot.req.tokens) - 1
        cap = self.max_seq_len - 1 - slot.seq_len
        return max(0, min(self.draft_k, rem, cap))

    def _grow_for_window(self, slot: _Slot, window: int,
                         ahead: int = 0) -> bool:
        """Pages + write exclusivity for the rows this step scatters:
        positions seq_len..seq_len+window (window 0 = the plain decode
        write, the pre-speculation contract verbatim), ``ahead`` rows
        further on when the program in flight is still writing row
        seq_len. Every page in the window that is still shared
        (refcount > 1) is copy-on-write'd BEFORE any multi-token commit
        can bleed into a sibling's or the prefix cache's rows. False on
        pool exhaustion — no preemption here, the caller decides how
        hard to push."""
        P = self.page_size
        first = slot.seq_len + ahead
        # the window group first: pages behind the window back, then the
        # page position ``first`` lands in
        self._window_behind(slot.req.rid, first)
        if not self._window.extend(slot.req.rid, first):
            return False
        need = (first + window) // P + 1
        while len(slot.pages) < need:
            got = self._alloc_pages(1)
            if got is None:
                return False
            slot.pages.extend(got)
        for wp in range(first // P, need):
            while self.pool.refs(slot.pages[wp]) > 1:
                if not self._cow_page(slot, wp):
                    return False
        return True

    def _grow(self, ahead: int = 0) -> None:
        """Ensure every active slot owns the pages this step's writes
        land in — exclusively. Speculative slots ask for their whole
        draft window first; under pool pressure the window shrinks to 0
        (that slot rides the verify pass as a plain-decode lane) before
        anyone gets preempted — losing speculation for a step is free,
        losing a sequence's pages is not. Preemption of the youngest
        remains the final escape hatch, exactly as before. ``ahead`` is
        1 when this step's program is chained on the one in flight
        (:meth:`_chainable` has seen that free pages alone will do)."""
        for slot in list(self._active):
            if slot not in self._active:
                continue   # preempted by an earlier slot's growth
            slot.spec_window = self._spec_horizon(slot)
            while slot in self._active:
                if self._grow_for_window(slot, slot.spec_window, ahead):
                    break
                if slot.spec_window:
                    slot.spec_window = 0
                    continue
                if not self._preempt_one(protect=slot):
                    # nothing left to steal from: cut this one short
                    self._finish(slot, "truncated")
                    break

    def _decode(self, chain: bool = False) -> None:
        if not self._active:
            return
        if self._draft is not None:
            if self._spec_ready():
                self._decoded += self._decode_spec()
                return
            # stale or missing draft (e.g. the fleet has not published
            # a draft base yet): degrade to plain decode — never to
            # wrong output
            obs.count("serve.spec_fallbacks")
        self._decode_plain(chain)

    def _decode_spec(self) -> int:
        """One speculative round: the drafter proposes up to
        ``spec_window`` tokens per slot, ONE ``serve.verify`` dispatch
        scores every slot's K+1 window, and each slot commits the
        longest prefix of its proposals that matches the target's own
        picks plus the target's pick at the first divergence (the plain
        decode token when nothing was drafted or nothing matched — a
        zero-accept round IS a plain decode step). Commit is pure
        length bookkeeping: ``seq_len += accepted + 1``; the verify
        rows past it hold rejected-input KV, stay masked behind
        ``kv_lens``, and are overwritten when those positions are fed
        again."""
        active = self._active
        draft = self._draft
        t0 = time.perf_counter()
        proposals: dict[int, list] = {}
        # the four decode phases of the plain path; the drafter's own
        # dispatches and fetches count as dispatch
        with obs.phase("serve.decode.dispatch", live=len(active)):
            if any(s.spec_window > 0 for s in active):
                try:
                    proposals = draft.propose(active) or {}
                except Exception:
                    # a broken drafter must never break serving: this
                    # round verifies an empty window (= plain decode)
                    logger.exception("draft propose failed; "
                                     "plain-decoding this step")
                    obs.count("serve.spec_fallbacks")
                    proposals = {}
        obs.observe("serve.spec_draft_ms",
                    (time.perf_counter() - t0) * 1e3)
        t1 = time.perf_counter()
        with obs.phase("serve.decode.build"):
            plan = {s.req.rid: [int(t) for t in
                                proposals.get(s.req.rid, [])][:s.spec_window]
                    for s in active}
            W = self.draft_k + 1
            P = self.page_size
            need_pages = max((s.seq_len + len(plan[s.req.rid])) // P + 1
                             for s in active)
            sb, pb = self._decode_bucket(len(active), need_pages,
                                         self._verify_progs)
            tables = np.zeros((sb, pb), np.int32)
            seq_lens = np.zeros((sb,), np.int32)
            tokens = np.zeros((sb, W), np.int32)
            n_input = np.zeros((sb,), np.int32)
            temps = np.zeros((sb,), np.float32)
            top_ps = np.ones((sb,), np.float32)
            seeds = np.zeros((sb,), np.int32)
            tok_idx0 = np.zeros((sb,), np.int32)
            for i, slot in enumerate(active):
                props = plan[slot.req.rid]
                row = slot.pages[:pb]
                tables[i, :len(row)] = row
                seq_lens[i] = slot.seq_len
                tokens[i, 0] = slot.last_tok
                if props:
                    tokens[i, 1:1 + len(props)] = props
                n_input[i] = 1 + len(props)
                temps[i] = slot.req.temperature
                top_ps[i] = slot.req.top_p
                seeds[i] = slot.req.seed & 0x7FFFFFFF
                tok_idx0[i] = len(slot.req.tokens)
            prog = self._verify_prog(sb, pb)
            k_pages, v_pages = self._kv
            self._slot_ladder.mark(sb)
            self._page_ladder.mark(pb)
            args = (self._params, k_pages, v_pages, tables, seq_lens,
                    tokens, n_input, temps, top_ps, seeds, tok_idx0)
        with obs.phase("serve.decode.dispatch", slots=sb, pages=pb,
                       live=len(active)):
            if (sb, pb) not in self._verify_seen:
                self._verify_seen.add((sb, pb))
                obs.count("serve.decode_bucket_compiles")
                picks, k_pages, v_pages = _timed_compile(prog, *args)
            else:
                picks, k_pages, v_pages = prog(*args)
            self._kv = (k_pages, v_pages)
        with obs.phase("serve.decode.fetch"):
            picks = np.asarray(jax.device_get(picks))
        obs.observe("serve.spec_verify_ms",
                    (time.perf_counter() - t1) * 1e3)
        with obs.phase("serve.decode.emit"):
            emitted = self._commit_spec(plan, picks)
        self._spec_rounds += 1
        if self._spec_proposed:
            obs.gauge("serve.spec_accept_rate", self.spec_accept_rate)
        return emitted

    def _commit_spec(self, plan: dict, picks) -> int:
        """Each slot commits the longest prefix of its proposals that
        matches the target's picks, plus the pick at the divergence."""
        draft = self._draft
        emitted = 0
        for i, slot in enumerate(list(self._active)):
            props = plan[slot.req.rid]
            j = 0
            while j < len(props) and props[j] == int(picks[i, j]):
                j += 1
            if props:
                self._spec_proposed += len(props)
                self._spec_accepted += j
                obs.count("serve.spec_proposed_tokens", len(props))
                obs.count("serve.spec_accepted_tokens", j)
            if self.trace is not None:
                # one coalesced "spec" batch per request: rounds (n),
                # proposed/accepted accumulate; tokens counts the
                # verified emits of this round (accepted run + 1)
                self._trace_flush(slot)
                self.trace.stage(slot.req.rid, "spec",
                                 proposed=len(props), accepted=j,
                                 tokens=j + 1)
            for tok in props[:j] + [int(picks[i, j])]:
                slot.seq_len += 1
                slot.last_tok = tok
                self._emit(slot, tok)
                emitted += 1
                if slot.req.status != "active":
                    break   # eos/budget hit inside the accepted run
            if slot.req.status == "active":
                draft.commit(slot.req.rid,
                             list(slot.req.prompt) + list(slot.req.tokens))
        return emitted

    def _chainable(self) -> bool:
        """Whether this step's plain decode program can take the picks of
        the program in flight as they lie on the device: the same slots
        in the same rows of the same slot bucket, each owing a token
        after the one in flight, and every row after the one in flight
        writable with free pages alone. Anything else (an admission, a
        last token in flight, a pending swap, a drafter, a page to copy
        or a slot to preempt, another bucket) is collected first."""
        flight = self._flight
        if flight is None or self._pending_swap is not None \
                or self._spec_ready():
            return False
        active = self._active
        if len(active) != len(flight.slots):
            return False
        P = self.page_size
        short = 0
        if self._window.free < sum(
                self._window.short(s.req.rid, s.seq_len + 1)
                for s in active):
            return False
        for slot, flown in zip(active, flight.slots):
            if slot is not flown or \
                    len(slot.req.tokens) + 1 >= slot.req.max_new_tokens:
                return False
            page = (slot.seq_len + 1) // P
            if page >= len(slot.pages):
                short += page + 1 - len(slot.pages)
            elif self.pool.refs(slot.pages[page]) > 1:
                return False
        return short <= self.pool.free \
            and self._plain_bucket(ahead=1)[0] == flight.sb

    def _plain_bucket(self, ahead: int = 0) -> tuple[int, int]:
        """The (slot, page) bucket of a plain decode program over the
        active slots, written ``ahead`` rows past their lengths."""
        active = self._active
        progs = (self._decode_sample_progs
                 if any(s.req.temperature > 0.0 for s in active)
                 else self._decode_progs)
        need_pages = max((s.seq_len + ahead) // self.page_size + 1
                         for s in active)
        return self._decode_bucket(len(active), need_pages, progs)

    def _collect(self, flight: _Flight | None = None) -> None:
        """Fetch and emit what a dispatched program picked (the one in
        flight, unless the caller hands over an earlier one): the host
        waits for the device HERE. A row whose slot was released
        meanwhile (an end-of-sequence on the token before) is dropped."""
        if flight is None:
            flight, self._flight = self._flight, None
            if flight is None:
                return
        with obs.phase("serve.decode.fetch"):
            nxt, stats = jax.device_get(_split_pick(flight.out))
            nxt = np.asarray(nxt)
            _count_sown(stats, per_step=True)
        with obs.phase("serve.decode.emit"):
            trace_t = self.trace.clock() if self.trace is not None else 0.0
            for i, slot in enumerate(flight.slots):
                if slot.released:
                    obs.count("serve.decode.rows_dropped")
                    continue
                slot.seq_len += 1
                slot.last_tok = int(nxt[i])
                if self.trace is not None:
                    # lazy per-slot accumulation: the hot path is three
                    # scalar bumps against one hoisted clock read — the
                    # timeline gets one coalesced span at _trace_flush
                    # (spec/cow/preempt/finish), zero device work
                    if slot.tr_decode_n == 0:
                        slot.tr_decode_t0 = trace_t
                    slot.tr_decode_n += 1
                    slot.tr_decode_t1 = trace_t
                self._emit(slot, int(nxt[i]))
                self._decoded += 1

    def _decode_plain(self, chain: bool) -> None:
        """Dispatch one plain decode program and return without waiting
        for it. ``chain``: the program in flight runs the same slots, so
        this one takes ITS picks as ``tokens`` where they lie on the
        device, and only then is that one collected: the device runs
        this program while the host emits the last one's tokens."""
        active = self._active
        earlier, ahead = (self._flight, 1) if chain else (None, 0)
        with obs.phase("serve.decode.build"):
            sampled = any(s.req.temperature > 0.0 for s in active)
            sb, pb = self._plain_bucket(ahead)
            tables = np.zeros((sb, pb), np.int32)
            seq_lens = np.zeros((sb,), np.int32)
            for i, slot in enumerate(active):
                row = slot.pages[:pb]
                tables[i, :len(row)] = row
                seq_lens[i] = slot.seq_len + ahead
            if obs.enabled():
                # the share of (rows x table width) that holds context:
                # what the paged decode kernel walks of the bucket
                obs.observe("serve.decode.table_live_pct", 100.0 * int(
                    np.sum(-(-seq_lens // self.page_size))) / (sb * pb))
            if chain:
                tokens = _split_pick(earlier.out)[0]
            else:
                # a device array on both ways in, so that a chained call
                # finds the executable the unchained one compiled
                tokens = np.zeros((sb,), np.int32)
                for i, slot in enumerate(active):
                    tokens[i] = slot.last_tok
                tokens = jax.device_put(tokens)
            k_pages, v_pages = self._kv
            self._slot_ladder.mark(sb)
            self._page_ladder.mark(pb)
            if sampled:
                # one program serves any greedy/sampled mix: temperature
                # 0 lanes argmax inside the jitted sampler, so batch
                # composition never forces a recompile
                temps = np.zeros((sb,), np.float32)
                top_ps = np.ones((sb,), np.float32)
                seeds = np.zeros((sb,), np.int32)
                tok_idx = np.zeros((sb,), np.int32)
                for i, slot in enumerate(active):
                    temps[i] = slot.req.temperature
                    top_ps[i] = slot.req.top_p
                    seeds[i] = slot.req.seed & 0x7FFFFFFF
                    tok_idx[i] = len(slot.req.tokens) + ahead
                prog = self._decode_sample_prog(sb, pb)
                seen = self._decode_sample_seen
                args = (self._params, k_pages, v_pages, tables, seq_lens,
                        tokens, temps, top_ps, seeds, tok_idx)
            else:
                prog = self._decode_prog(sb, pb)
                seen = self._decode_seen
                args = (self._params, k_pages, v_pages, tables, seq_lens,
                        tokens)
            if self._window.arity:
                args += self._window.tail([s.req.rid for s in active],
                                          self._window.decode_pages, sb)
                if obs.enabled():
                    kv, held, live = self.kv_holdings()
                    obs.observe("serve.kv.pages_held", kv / len(active))
                    obs.observe("serve.kv.window.pages_held",
                                held / len(active))
                    obs.count("serve.kv.window.live_tokens", live)
            if self._recurrent:
                # padding rows move the pools' spare row, as their page
                # writes land on page 0
                rows = np.full((sb,), self.max_slots, np.int32)
                for i, slot in enumerate(active):
                    rows[i] = self._state_of[slot.req.rid]
                args += self._slot_state(rows)
                # again here: a sink attached after the pool was made
                obs.gauge(self._state_gauge, self._ssm_bytes)
        with obs.phase("serve.decode.dispatch", slots=sb, pages=pb,
                       live=len(active)):
            if (sb, pb) not in seen:
                seen.add((sb, pb))
                obs.count("serve.decode_bucket_compiles")
                out, k_pages, v_pages, *moved = _timed_compile(prog, *args)
            else:
                out, k_pages, v_pages, *moved = prog(*args)
            self._keep(k_pages, v_pages, moved)
            # the picks start for the host now, not when it asks
            jax.tree_util.tree_map(lambda x: x.copy_to_host_async(), out)
            self._flight = _Flight(list(active), sb, out)
        obs.count("serve.decode.chained" if chain
                  else "serve.decode.collected_first")
        if chain:
            self._collect(earlier)

    def step(self) -> dict:
        """One scheduler iteration: swap check, admission, one decode
        program over the active batch dispatched and NOT waited for; the
        tokens collected are those of the program dispatched a step
        earlier (docs/serving.md, "What `step()` does"). Returns step
        stats; ``emitted`` counts the decode tokens that reached the
        host in this step."""
        if self._params is None:
            raise RuntimeError("no base installed; call install_params "
                               "(or attach a watcher and publish a base)")
        # the phases below are host spans on the profiler's clock and
        # feed serve.<phase>_ms (utils/obs.phase; docs/observability.md
        # has the table); off, each is one branch (serve.step is timed
        # either way: the caller gets step_ms)
        before = self._decoded
        with obs.phase("serve.step", timed=True) as whole:
            self._maybe_swap()
            with obs.phase("serve.admit"):
                self._admit()
            chain = self._chainable()
            if not chain:
                self._collect()
            with obs.phase("serve.grow"):
                self._grow(ahead=int(chain))
            self._decode(chain)
        emitted = self._decoded - before
        dur = whole.dur_ms / 1e3
        self.steps += 1
        if emitted:
            rate = emitted / max(dur, 1e-9)
            self._tok_rate_ema = rate if self._tok_rate_ema is None else (
                self._tok_rate_ema + 0.2 * (rate - self._tok_rate_ema))
            obs.gauge("serve.tokens_per_sec", self._tok_rate_ema)
        obs.gauge("serve.queue_depth", self.queue_depth)
        obs.gauge("serve.active_slots", len(self._active))
        obs.gauge("serve.free_pages", self.pool.free)
        if self.debug_invariants:
            self._check_invariants()
        return {"emitted": emitted, "active": len(self._active),
                "queued": self.queue_depth, "step_ms": whole.dur_ms,
                "revision": self.revision}

    def _check_invariants(self) -> None:
        """Page-pool accounting audit (debug flag / DT_SERVE_DEBUG):
        every referenced page must be explained by exactly its holders —
        active slots plus prefix-cache entries — and free + referenced
        must tile the pool."""
        expected: dict[int, int] = {}
        for slot in self._active:
            for p in slot.pages:
                expected[p] = expected.get(p, 0) + 1
        if self._cache is not None:
            for p in self._cache.pages():
                expected[p] = expected.get(p, 0) + 1
        self.pool.check(expected)
        if self._cache is not None:
            self._cache.check()
        self._window.check_held([s.req.rid for s in self._active])
        assert (len(self._state_free) + len(self._state_of)
                == (self.max_slots if self._recurrent else 0)), \
            "a row of the per-slot state pools is neither free nor owned"
        if self._draft is not None:
            self._draft.check()

    # -- conveniences -------------------------------------------------------
    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int | None = None,
                 *, max_steps: int = 100_000, temperature: float = 0.0,
                 top_p: float = 1.0, seed: int = 0) -> list[list[int]]:
        """Submit a batch and drive the scheduler to completion (tests,
        one-shot CLI use)."""
        reqs = [self.submit(p, max_new_tokens, temperature=temperature,
                            top_p=top_p, seed=seed) for p in prompts]
        for _ in range(max_steps):
            if all(r.done_evt.is_set() for r in reqs):
                break
            self.step()
        else:
            raise RuntimeError("generation did not converge in "
                               f"{max_steps} steps")
        return [list(r.tokens) for r in reqs]

    def close(self) -> None:
        if self.watcher is not None:
            self.watcher.close()
        if self._draft is not None:
            self._draft.close()
        # a dispatched program's tokens are not thrown away
        self._collect()
        for slot in list(self._active):
            self._finish(slot, "truncated")
        with self._qlock:
            drained = list(self._queue)
            self._queue.clear()
        for req in drained:
            req.status = "truncated"
            req.done_evt.set()
        if self.trace is not None:
            # a run shorter than one reservoir window still freezes its
            # tail exemplars on the way out
            self.trace.seal_window()


# ---------------------------------------------------------------------------
# Serve loop + HTTP frontend (neurons/server.py wires these)
# ---------------------------------------------------------------------------

class ServeLoop:
    """Drives ``engine.step()`` on a daemon thread (named ``serve-loop``)
    so HTTP handler threads only ever touch the thread-safe ``submit``
    path. Parks on the engine's work event when idle — no busy spin."""

    def __init__(self, engine: GenerationEngine, *,
                 idle_poll_s: float = 0.2):
        self.engine = engine
        self.idle_poll_s = idle_poll_s
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "ServeLoop":
        if self._thread is None:
            self._thread = threading.Thread(target=self._run,
                                            name="serve-loop", daemon=True)
            self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                if self.engine.idle:
                    self.engine.wait_for_work(self.idle_poll_s)
                    continue
                self.engine.step()
            except Exception:
                logger.exception("serve loop step failed")
                self._stop.wait(0.5)

    def close(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=10.0)


class ServeHTTPFrontend:
    """Minimal stdlib JSON frontend (same shape as ObsHTTPExporter —
    no new dependencies, 127.0.0.1 by default, daemon threads, tracked
    for the conftest socket guard).

    - ``POST /generate`` ``{"tokens": [...]} | {"text": "..."}`` plus
      optional ``max_new_tokens`` — blocks until the request finishes
      (or ``timeout_s``) and returns generated tokens (+ text when a
      tokenizer is attached), status, and the base revision served.
    - ``POST /prefill`` (prefill-phase workers only) — same body as
      ``/generate``; runs the prefill leg, exports the KV pages, and
      returns ``kv_ref`` + ``first_token`` + ``prompt_len`` for the
      router to hand to a decode worker.
    - ``GET /healthz`` — queue depth, active slots, revision,
      tokens/sec, worker ``phase``.
    """

    def __init__(self, engine: GenerationEngine, port: int = 0, *,
                 host: str = "127.0.0.1", tokenizer=None,
                 timeout_s: float = 120.0):
        self.engine = engine
        self.host = host
        self.port = port
        self.tokenizer = tokenizer
        self.timeout_s = timeout_s
        self._server: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None

    def start(self) -> int:
        if self._server is not None:
            return self.port
        fe = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                logger.debug("serve_http: " + fmt, *args)

            def _send(self, code: int, obj,
                      headers: dict | None = None) -> None:
                body = (json.dumps(obj) + "\n").encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler API
                if self.path.split("?", 1)[0] == "/healthz":
                    e = fe.engine
                    reg = obs.registry()
                    names = reg.names()
                    out = {
                        "ok": True, "queue_depth": e.queue_depth,
                        "active": e.active_count,
                        "revision": e.revision,
                        "tokens_per_sec": e.tokens_per_sec,
                        "max_queue": e.max_queue,
                        "shed": e.shed_count,
                        # worker class for phase-aware routing
                        # (engine/router.py): prefill | decode |
                        # unified — an old router ignores the field
                        # and keeps treating this backend as unified
                        "phase": e.phase,
                        "kv_exported": e.kv_exported,
                        "kv_adopted": e.kv_adopted}
                    if e.prefix_hits + e.prefix_misses > 0:
                        out["prefix_hit_rate"] = e.prefix_hit_rate
                    if e.speculative:
                        # drafter-aware health: the router scales a
                        # backend's effective speed by its acceptance
                        out["spec_accept_rate"] = e.spec_accept_rate
                        out["spec_k"] = e.draft_k
                    for key, metric in (("ttft_ms_p95", "serve.ttft_ms"),
                                        ("tpot_ms_p95", "serve.tpot_ms"),
                                        ("q_age_ms_p95",
                                         "serve.queue_age_ms")):
                        if metric in names and \
                                reg.histogram(metric).count:
                            out[key] = reg.histogram(metric).percentiles(
                                (95.0,))["p95"]
                    burn = e.trace.burn if e.trace is not None else None
                    if burn is not None:
                        out["slo_burn"] = burn.max_burn()
                    self._send(200, out)
                else:
                    self._send(404, {"error": "not found"})

            def do_POST(self):  # noqa: N802
                path = self.path.split("?", 1)[0]
                if path not in ("/generate", "/prefill"):
                    self._send(404, {"error": "not found"})
                    return
                # phase discipline: a prefill worker only serves
                # /prefill, everything else only /generate — a
                # mis-routed call fails loudly instead of returning a
                # one-token "generation"
                if path == "/prefill" and fe.engine.phase != "prefill":
                    self._send(409, {"error": "not a prefill-phase "
                                              "worker"})
                    return
                if path == "/generate" and fe.engine.phase == "prefill":
                    self._send(409, {"error": "prefill-phase worker; "
                                              "POST /prefill"})
                    return
                # admission control BEFORE parsing: a saturated server
                # answers cheaply and immediately instead of queueing
                # the caller into the latency knee
                # the caller's identity (router-minted) or None — a
                # refusal still gets a traced request_id so the 429/503
                # shows up in the same per-request stream
                req_id = self.headers.get(reqtrace.REQUEST_ID_HEADER)
                state, retry = fe.engine.admission_state()
                if state == "shed":
                    fe.engine.shed_count += 1
                    obs.count("serve.shed")
                    if fe.engine.trace is not None:
                        req_id = fe.engine.trace.reject(
                            req_id, "shed", retry_after_s=round(retry, 3))
                    self._send(429, {"error": "overloaded",
                                     "retry_after_s": retry,
                                     "request_id": req_id},
                               {"Retry-After": str(max(1, int(retry))),
                                reqtrace.REQUEST_ID_HEADER: req_id or ""})
                    return
                if state == "drain":
                    obs.count("serve.drain_rejects")
                    if fe.engine.trace is not None:
                        req_id = fe.engine.trace.reject(
                            req_id, "drain", retry_after_s=round(retry, 3))
                    self._send(503, {"error": "draining for base swap",
                                     "retry_after_s": retry,
                                     "request_id": req_id},
                               {"Retry-After": str(max(1, int(retry))),
                                reqtrace.REQUEST_ID_HEADER: req_id or ""})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    toks = payload.get("tokens")
                    if toks is None and "text" in payload:
                        if fe.tokenizer is None:
                            raise ValueError(
                                "text prompts need a tokenizer; send "
                                "token ids")
                        toks = fe.tokenizer.encode(payload["text"])
                    if not isinstance(toks, list) or not toks:
                        raise ValueError("need a non-empty 'tokens' list "
                                         "or 'text'")
                    # disaggregated hop (decode workers): the router
                    # forwards the prefill leg's manifest ref + first
                    # token with the original sampling params
                    kv_ref = payload.get("kv_ref")
                    ft = payload.get("first_token")
                    req = fe.engine.submit(
                        toks, payload.get("max_new_tokens"),
                        temperature=float(payload.get("temperature", 0.0)),
                        top_p=float(payload.get("top_p", 1.0)),
                        seed=int(payload.get("seed", 0)),
                        request_id=req_id,
                        kv_ref=(str(kv_ref) if kv_ref else None),
                        first_token=(int(ft) if ft is not None else None))
                except (ValueError, TypeError, json.JSONDecodeError) as e:
                    self._send(400, {"error": str(e)})
                    return
                # echo the (possibly engine-minted) identity on every
                # outcome so callers and the router can correlate
                hdr = {reqtrace.REQUEST_ID_HEADER: req.request_id or ""}
                if not req.wait(fe.timeout_s):
                    self._send(504, {"error": "generation timed out",
                                     "rid": req.rid,
                                     "request_id": req.request_id}, hdr)
                    return
                out = {"rid": req.rid, "tokens": req.tokens,
                       "status": req.status, "revision": req.revision,
                       "request_id": req.request_id}
                if path == "/prefill":
                    # the decode leg's inputs: manifest ref (None when
                    # the export failed — the router then falls back
                    # to unified) + the first-token decision
                    out["kv_ref"] = req.kv_ref
                    out["first_token"] = req.first_token
                    out["prompt_len"] = len(req.prompt)
                if fe.tokenizer is not None:
                    try:
                        out["text"] = fe.tokenizer.decode(req.tokens)
                    except Exception:
                        pass
                self._send(200, out, hdr)

        self._server = ThreadingHTTPServer((self.host, self.port), Handler)
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name=f"serve-http-{self.port}",
                                        daemon=True)
        self._thread.start()
        _LIVE_FRONTENDS.add(self)
        logger.info("serving generation on http://%s:%d/generate",
                    self.host, self.port)
        return self.port

    @property
    def running(self) -> bool:
        return self._server is not None

    def close(self) -> None:
        server, self._server = self._server, None
        thread, self._thread = self._thread, None
        if server is not None:
            server.shutdown()
            server.server_close()
        if thread is not None:
            thread.join(timeout=5.0)
        _LIVE_FRONTENDS.discard(self)
