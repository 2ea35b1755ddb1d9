"""Speculative decoding: a fleet-trained draft proposes, the target verifies.

ROADMAP item 2(c): decode is memory-bandwidth-bound (the devprof roofline
table), so the biggest remaining tpot lever is amortizing the weight/KV
sweep over more than one token — a small DRAFT model autoregressively
proposes ``K`` tokens per slot, the big TARGET scores all K+1 positions
in ONE batched pass (``serve.verify`` in engine/serve.py), and the
longest prefix the target agrees with commits. The fleet already trains
the draft for free: the small GPT-2 base the miners converge is the
natural drafter for the larger llama target.

Losslessness, in one paragraph. The serving plane's sampler is a COUNTER
PRNG: the token a request emits at stream index *t* is a pure function
of ``(logits_t, fold_in(PRNGKey(seed), t))`` — never of batch layout or
time (engine/serve.py, round 16). The verify pass therefore computes, at
every drafted position, *exactly the token the plain decode path would
have picked there* (greedy lanes argmax, sampled lanes run the identical
seeded top-p draw at the identical ``tok_idx``). The standard
accept/resample rule collapses to prefix matching against those picks:
accept drafted tokens while they equal the target's own pick at the
previous position, then emit the target's pick at the first divergence
(or the bonus K+1-th pick when everything matched). Greedy output is
token-identical to the decode oracle and sampled output is BIT-identical
to the spec-off stream — not merely same-distribution — because both
paths draw from the same key at the same index. A zero-accept round
degenerates to exactly one plain decode step; speculation can be slower,
never wrong.

Two drafter flavors share one duck-typed protocol (``ready`` /
``propose(slots)`` / ``commit(rid, known)`` / ``drop(rid)`` /
``flush()`` / ``check()``):

- :class:`DraftEngine` — the real thing: holds the small model with its
  OWN slot-aligned paged KV pool (same trash-page-0 / BucketLadder /
  refcount discipline as the target's pool, but private pages only — the
  draft never shares or CoWs), and proposes K tokens through one jitted
  ``serve.draft`` program family on a (slot, page) ladder. Rejected
  draft KV rolls back by LENGTH bookkeeping (``commit`` truncates the
  ingested-token list to the verified prefix; stale rows are overwritten
  when those positions are fed again), never by copy. A
  :class:`serve.BaseRevisionWatcher` can ride along: a new fleet-averaged
  draft revision installs between steps and flushes ALL draft KV —
  cached draft KV is a function of draft params, exactly like the prefix
  cache under a target swap.
- :class:`ScriptedDraftSource` — a host-side drafter with no model and
  no KV: proposals come from a pure function of the request's known
  tokens. Tests use it to force exact 0-accept / all-accept rounds;
  fleetsim's load points draft through it.

The engine integration (engine/serve.py ``draft=`` / ``draft_k=``)
treats either one identically; a drafter that is not ``ready`` (missing
or stale params) degrades the whole step to plain decode — never to
wrong output.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import sys
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import devprof, obs
from . import kv_pool, serve_weights
from .batched_eval import _timed_compile
from .serve import (DEFAULT_PAGE_SIZE, BucketLadder, PagePool,
                    _layer_keys, _sample_from_logits)

logger = logging.getLogger(__name__)

Params = Any


def compat_reason(draft_model, target_cfg) -> str | None:
    """Why ``draft_model`` cannot draft for a target with ``target_cfg``
    (None = compatible). Delegates to the model family's ``draft_compat``
    hook (models/gpt2.py, models/llama.py) — the load-bearing check is
    shared REAL vocabulary: draft proposals are token ids the target
    scores verbatim, so the id spaces must mean the same thing."""
    for cfg in (draft_model.cfg, target_cfg):
        if cfg.cache_row_widths is not None:
            return kv_pool.LATENT_CACHE_REASON
        if kv_pool.has_recurrent_state(cfg):
            return kv_pool.RECURRENT_STATE_REASON
    mod = sys.modules.get(type(draft_model).__module__)
    fn = getattr(mod, "draft_compat", None)
    if fn is None:
        return None
    return fn(draft_model.cfg, target_cfg)


@dataclasses.dataclass
class _DraftState:
    """Per-request draft cache bookkeeping. ``toks[i]`` is the token
    whose KV row sits at draft-cache position *i*; ``stable`` counts the
    leading rows already confirmed against committed output (so commit
    re-checks only what the last round touched). Rollback = truncating
    ``toks`` — the rows beyond stay in memory but are masked by length
    and overwritten when those positions are fed again."""
    pages: list = dataclasses.field(default_factory=list)
    toks: list = dataclasses.field(default_factory=list)
    stable: int = 0


class DraftEngine:
    """The small fleet-trained model as a proposal machine over its own
    paged KV pool. Mirrors GenerationEngine's geometry (trash page 0,
    page-aligned capacity, power-of-two ladders, zero steady-state fresh
    compiles) at draft scale; holds one :class:`_DraftState` per live
    request id, created lazily at the first propose and dropped when the
    serving engine releases the slot."""

    def __init__(self, model, params: Params | None = None, *,
                 revision: str | None = None,
                 max_slots: int = 8,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 pool_pages: int = 0,
                 max_seq_len: int = 0,
                 prefer_compiled: bool = True,
                 watcher=None):
        if max_slots < 1 or page_size < 1:
            raise ValueError("max_slots and page_size must be >= 1")
        cfg = model.cfg
        cfg = dataclasses.replace(cfg, remat=False, scan_blocks=False)
        self.model = type(model)(cfg)
        self.cfg = cfg
        self.page_size = page_size
        self.max_slots = max_slots
        self.watcher = watcher
        if watcher is not None:
            watcher.prepare = functools.partial(serve_weights.make, cfg)
        cap = getattr(cfg, "n_positions", None) or getattr(
            cfg, "max_seq_len", 0)
        self.max_seq_len = (min(max_seq_len or cap, cap)
                            // page_size) * page_size
        if self.max_seq_len < page_size:
            raise ValueError(f"draft max_seq_len {self.max_seq_len} < "
                             f"page_size {page_size}")
        self.pages_per_slot = self.max_seq_len // page_size
        self.pool_pages = pool_pages or (
            1 + self.max_slots * self.pages_per_slot)

        self._slot_ladder = BucketLadder(max_slots,
                                         prefer_compiled=prefer_compiled)
        self._page_ladder = BucketLadder(self.pages_per_slot,
                                         prefer_compiled=prefer_compiled)
        self._prefill_ladder = BucketLadder(self.pages_per_slot,
                                            prefer_compiled=prefer_compiled)
        self.prefer_compiled = prefer_compiled
        self._step_progs: dict[tuple[int, int], Callable] = {}
        self._prefill_progs: dict[int, Callable] = {}
        self._step_seen: set[tuple[int, int]] = set()
        self._donate = jax.default_backend() not in ("cpu",)

        self._params: Params | None = None
        self.revision: str | None = None
        self._layers: list[str] | None = None
        self._kv: kv_pool.Pool | None = None
        self.pool: PagePool | None = None
        self._states: dict[int, _DraftState] = {}
        self.flush_count = 0
        # set by GenerationEngine when request tracing is on: the
        # drafter's cold catch-up prefills ("spec_draft") land on the
        # same per-request timelines (utils/reqtrace.py)
        self.trace = None
        if params is not None:
            self.install_params(params, revision=revision)

    # -- weights ------------------------------------------------------------
    @property
    def ready(self) -> bool:
        return self._params is not None

    def install_params(self, params: Params, *,
                       revision: str | None = None) -> None:
        """Bind a draft revision. Draft KV is a pure function of (draft
        params, tokens), so every cached state is stale the instant a
        new revision lands — flush, exactly like the prefix cache under
        a target-base swap. What is bound is the draft's serving tree
        (engine/serve_weights.py), as for the target."""
        placed = serve_weights.make(self.cfg, params)
        if self._layers is None:
            self._layers = _layer_keys(placed)
            self._init_kv()
        self._params = placed
        self.revision = revision
        self.flush()

    def _init_kv(self) -> None:
        cfg = self.cfg
        self._kv = kv_pool.make_pool(
            len(self._layers), self.pool_pages, self.page_size,
            kv_pool.row_widths(cfg), cfg.compute_dtype())
        self.pool = PagePool(self.pool_pages)

    # -- state lifecycle ----------------------------------------------------
    def drop(self, rid: int) -> None:
        st = self._states.pop(rid, None)
        if st is not None and self.pool is not None:
            for p in st.pages:
                self.pool.decref(p)

    def flush(self) -> None:
        """Drop every per-request draft state and release its pages —
        the draft-swap twin of ``PrefixCache.flush``. Live requests
        simply re-prefill their draft context at the next propose."""
        for rid in list(self._states):
            self.drop(rid)
        self.flush_count += 1

    def commit(self, rid: int, known: Sequence[int]) -> None:
        """Reconcile the draft cache with the committed stream after a
        verify round: ``known`` is prompt + emitted tokens. The valid
        draft rows are the longest prefix of ingested tokens that equals
        the committed stream; everything past it (rejected proposals)
        rolls back by truncation — length bookkeeping, never a copy."""
        st = self._states.get(rid)
        if st is None:
            return
        i, n = st.stable, min(len(st.toks), len(known))
        while i < n and st.toks[i] == known[i]:
            i += 1
        del st.toks[i:]
        st.stable = i

    def check(self) -> None:
        """Draft-pool accounting audit: every referenced page is owned
        by exactly one live draft state (draft pages are never shared)."""
        if self.pool is None:
            return
        expected: dict[int, int] = {}
        for st in self._states.values():
            for p in st.pages:
                expected[p] = expected.get(p, 0) + 1
        self.pool.check(expected)

    def close(self) -> None:
        if self.watcher is not None:
            self.watcher.close()
        self.flush()

    # -- programs -----------------------------------------------------------
    def _step_prog(self, n_slots: int, n_pages: int) -> Callable:
        """One draft decode step: identical shape discipline to the
        target's ``serve.decode_sample`` (paged attention through the
        draft's own tables, scatter ONE row, seeded pick) — the pick
        uses the SAME ``fold_in(seed, tok_idx)`` key the target's verify
        will use at that stream index, so sampled drafts are
        common-random-number coupled to the verifier and the acceptance
        rate is as high as the models allow."""
        prog = self._step_progs.get((n_slots, n_pages))
        if prog is not None:
            return prog
        model, vocab = self.model, self.cfg.vocab_size
        layers = self._layers

        def draft_step(params, k_pages, v_pages, page_tables, seq_lens,
                       tokens, temps, top_ps, seeds, tok_idx):
            kv_pages = tuple(zip(k_pages, v_pages))
            logits, muts = model.apply(
                {"params": params}, tokens[:, None],
                position_ids=seq_lens[:, None],
                kv_pages=kv_pages, page_tables=page_tables,
                kv_lens=seq_lens,
                sow_kv=True, mutable=["intermediates"])
            k_pages, v_pages = kv_pool.write_next_row(
                k_pages, v_pages, muts["intermediates"], layers,
                page_tables, seq_lens)
            nxt = _sample_from_logits(logits[:, -1, :vocab], temps,
                                      top_ps, seeds, tok_idx)
            return nxt, k_pages, v_pages

        prog = devprof.wrap(
            "serve.draft",
            jax.jit(draft_step,
                    donate_argnums=(1, 2) if self._donate else ()),
            bucket=f"{n_slots}x{n_pages}")
        self._step_progs[(n_slots, n_pages)] = prog
        return prog

    def _prefill_prog(self, t_bucket: int) -> Callable:
        """Draft context prefill (cold start / post-flush catch-up):
        run the committed tokens through the draft forward and page the
        KV out. No pick rides out — the committed stream already tells
        us every next token up to the live position."""
        prog = self._prefill_progs.get(t_bucket)
        if prog is not None:
            return prog
        model, P = self.model, self.page_size
        mp = t_bucket // P
        layers = self._layers

        def draft_prefill(params, tokens, n_tok, k_pages, v_pages,
                          page_row):
            amask = (jnp.arange(t_bucket)[None, :]
                     < n_tok).astype(jnp.int32)
            _, muts = model.apply(
                {"params": params}, tokens, attention_mask=amask,
                sow_kv=True, mutable=["intermediates"])
            return kv_pool.write_pages(
                k_pages, v_pages, muts["intermediates"], layers, page_row)

        prog = devprof.wrap(
            "serve.draft",
            jax.jit(draft_prefill,
                    donate_argnums=(3, 4) if self._donate else ()),
            bucket=f"p{mp}")
        self._prefill_progs[t_bucket] = prog
        return prog

    # -- proposing ----------------------------------------------------------
    def _ensure_pages(self, st: _DraftState, need: int) -> bool:
        while len(st.pages) < need:
            got = self.pool.alloc(1)
            if got is None:
                return False
            st.pages.extend(got)
        return True

    def _prefill_state(self, st: _DraftState, toks: list) -> None:
        P = self.page_size
        t_bucket = self._prefill_ladder.bucket_for(
            (len(toks) + P - 1) // P) * P
        mp = t_bucket // P
        buf = np.zeros((1, t_bucket), np.int32)
        buf[0, :len(toks)] = toks
        page_row = np.zeros((mp,), np.int32)
        row = st.pages[:mp]
        page_row[:len(row)] = row
        prog = self._prefill_prog(t_bucket)
        k_pages, v_pages = self._kv
        if self._prefill_ladder.mark(t_bucket // P):
            obs.count("serve.spec_bucket_compiles")
            k_pages, v_pages = _timed_compile(
                prog, self._params, buf, np.int32(len(toks)),
                k_pages, v_pages, page_row)
        else:
            k_pages, v_pages = prog(self._params, buf, np.int32(len(toks)),
                                    k_pages, v_pages, page_row)
        self._kv = (k_pages, v_pages)
        st.toks = list(toks)
        st.stable = len(st.toks)   # prefill ingests only committed tokens

    def _step_batch(self, jobs: list[dict], feeds: list[int],
                    idx_off: list[int]) -> np.ndarray:
        """One batched draft step over ``jobs``: feed token *i* of each
        job at its state's current length, scatter the KV row, return
        the seeded picks. ``idx_off[i]`` is the stream index the pick is
        a candidate for (drives the coupled PRNG key)."""
        sb = self._slot_ladder.bucket_for(len(jobs))
        need_pages = max(len(j["st"].toks) // self.page_size + 1
                         for j in jobs)
        pb = self._page_ladder.bucket_for(need_pages)
        if self.prefer_compiled and (sb, pb) not in self._step_progs:
            cands = [k for k in self._step_progs
                     if k[0] >= len(jobs) and k[1] >= need_pages]
            if cands:
                sb, pb = min(cands, key=lambda k: k[0] * k[1])
        tables = np.zeros((sb, pb), np.int32)
        seq_lens = np.zeros((sb,), np.int32)
        tokens = np.zeros((sb,), np.int32)
        temps = np.zeros((sb,), np.float32)
        top_ps = np.ones((sb,), np.float32)
        seeds = np.zeros((sb,), np.int32)
        tok_idx = np.zeros((sb,), np.int32)
        for i, j in enumerate(jobs):
            st, req = j["st"], j["slot"].req
            row = st.pages[:pb]
            tables[i, :len(row)] = row
            seq_lens[i] = len(st.toks)
            tokens[i] = feeds[i]
            temps[i] = req.temperature
            top_ps[i] = req.top_p
            seeds[i] = req.seed & 0x7FFFFFFF
            tok_idx[i] = idx_off[i]
        prog = self._step_prog(sb, pb)
        k_pages, v_pages = self._kv
        self._slot_ladder.mark(sb)
        self._page_ladder.mark(pb)
        args = (self._params, k_pages, v_pages, tables, seq_lens, tokens,
                temps, top_ps, seeds, tok_idx)
        if (sb, pb) not in self._step_seen:
            self._step_seen.add((sb, pb))
            obs.count("serve.spec_bucket_compiles")
            nxt, k_pages, v_pages = _timed_compile(prog, *args)
        else:
            nxt, k_pages, v_pages = prog(*args)
        self._kv = (k_pages, v_pages)
        for i, j in enumerate(jobs):
            j["st"].toks.append(int(feeds[i]))
        return np.asarray(jax.device_get(nxt))

    def propose(self, slots: Sequence) -> dict[int, list[int]]:
        """Propose up to ``slot.spec_window`` tokens for each slot:
        catch the draft cache up to the committed stream (prefill when
        cold, batched single-token steps for the steady-state 0/1-token
        gap), then run the proposal loop — every step one ``serve.draft``
        dispatch over all still-proposing slots. A slot the draft pool
        or position capacity cannot carry simply drops out (its lane
        rides the verify program as plain decode)."""
        if self._params is None:
            return {}
        jobs: list[dict] = []
        for slot in slots:
            k = int(getattr(slot, "spec_window", 0))
            if k <= 0:
                continue
            known = list(slot.req.prompt) + list(slot.req.tokens)
            tgt_len = slot.seq_len
            if tgt_len + k > self.max_seq_len or tgt_len >= len(known):
                continue
            st = self._states.get(slot.req.rid)
            if st is None:
                st = self._states[slot.req.rid] = _DraftState()
            if st.toks[:st.stable] != known[:st.stable]:
                # desync (should be unreachable under the drop/commit
                # discipline) — rebuild rather than propose garbage
                st.toks = []
                st.stable = 0
            if not self._ensure_pages(st, (tgt_len + k) // self.page_size
                                      + 1):
                continue
            if len(st.toks) < tgt_len and \
                    tgt_len - len(st.toks) > self.page_size:
                st.toks = []
                st.stable = 0
            if not st.toks and tgt_len > 0:
                t0 = time.perf_counter()
                self._prefill_state(st, known[:tgt_len])
                if self.trace is not None:
                    # cold drafter rebuild: the hidden prefill a request
                    # pays after a draft swap/flush — invisible in
                    # aggregate spec_draft_ms, causal in the waterfall
                    self.trace.stage(
                        slot.req.rid, "spec_draft", tokens=tgt_len,
                        dur_ms=round((time.perf_counter() - t0) * 1e3, 3))
            jobs.append({"slot": slot, "st": st, "known": known, "k": k,
                         "out": []})
        if not jobs:
            return {}
        # catch-up: feed committed tokens the draft cache is missing
        # (steady state this is empty or one token — the bonus token of
        # an all-accepted round)
        while True:
            lag = [j for j in jobs if len(j["st"].toks) < j["slot"].seq_len]
            if not lag:
                break
            self._step_batch(
                lag, [j["known"][len(j["st"].toks)] for j in lag],
                [0] * len(lag))
        # proposal loop: step s proposes the candidate for stream index
        # len(req.tokens) + s, feeding last_tok first and then its own
        # previous pick
        max_k = max(j["k"] for j in jobs)
        for s in range(max_k):
            live = [j for j in jobs if s < j["k"]]
            if not live:
                break
            feeds = [j["known"][j["slot"].seq_len] if s == 0
                     else j["out"][-1] for j in live]
            idx = [len(j["slot"].req.tokens) + s for j in live]
            picks = self._step_batch(live, feeds, idx)
            for i, j in enumerate(live):
                j["out"].append(int(picks[i]))
        return {j["slot"].req.rid: j["out"] for j in jobs}


class ScriptedDraftSource:
    """Host-side drafter: proposals come from ``fn(req, k) -> tokens``
    with no model, no KV, and no device dispatch. Two uses: fleetsim's
    load points (an oracle drafter on the virtual clock) and tests that
    need exact 0-accept or all-accept rounds.
    ``commit``/``drop``/``flush`` are bookkeeping no-ops — nothing to
    roll back."""

    def __init__(self, fn: Callable[[Any, int], Sequence[int]], *,
                 revision: str | None = "scripted"):
        self._fn = fn
        self.revision = revision
        self.ready = True

    def propose(self, slots: Sequence) -> dict[int, list[int]]:
        out: dict[int, list[int]] = {}
        for slot in slots:
            k = int(getattr(slot, "spec_window", 0))
            if k <= 0:
                continue
            toks = [int(t) for t in self._fn(slot.req, k)][:k]
            if toks:
                out[slot.req.rid] = toks
        return out

    def commit(self, rid: int, known: Sequence[int]) -> None:
        pass

    def drop(self, rid: int) -> None:
        pass

    def flush(self) -> None:
        pass

    def check(self) -> None:
        pass

    def close(self) -> None:
        pass
