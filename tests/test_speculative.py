"""Speculative decoding (engine/speculative.py + engine/serve.py).

The contract under test is LOSSLESSNESS, not speed: with any drafter —
model-backed, scripted oracle, scripted adversary, stale, or absent —
the engine's output must be token-identical to what plain decode would
have produced. Greedy lanes pin against ``reference_generate``; sampled
lanes pin BIT-identical against the spec-off engine (the counter PRNG
makes the accept/resample rule collapse to prefix matching, so the
stream is the same draw-for-draw). Everything else — CoW pages, pool
accounting, draft hot-swap, target restart-swap invalidation, compile
discipline — is tested as "still token-identical under X".
"""

import jax
import numpy as np
import pytest

from distributedtraining_tpu.engine.serve import (GenerationEngine,
                                                  reference_generate)
from distributedtraining_tpu.engine.speculative import (DraftEngine,
                                                        ScriptedDraftSource,
                                                        compat_reason)
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.utils import obs

TINY = gpt2.GPT2Config(vocab_size=128, n_positions=64, n_embd=32,
                       n_layer=2, n_head=2, dtype="float32",
                       vocab_multiple=64)

GEN = 8

_REF_CACHE: dict = {}


@pytest.fixture(scope="module")
def setup():
    model, cfg = gpt2.make_model(TINY)
    params1 = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    params2 = model.init_params(jax.random.PRNGKey(7), seq_len=8)
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(0, cfg.vocab_size, size=n)]
               for n in (5, 11, 3, 17)]
    return model, cfg, params1, params2, prompts


@pytest.fixture()
def sink():
    class _Sink:
        def __init__(self):
            self.records = []

        def log(self, rec, **kw):
            self.records.append(rec)

    s = _Sink()
    obs.configure(s, role="server")
    try:
        yield s
    finally:
        obs.reset()


def refs_for(model, params, prompts, n=GEN):
    out = []
    for p in prompts:
        key = (id(model), id(params), tuple(p), n)
        if key not in _REF_CACHE:
            _REF_CACHE[key] = reference_generate(model, params, p, n)
        out.append(_REF_CACHE[key])
    return out


def spec_engine(model, params, draft, *, k=4, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("debug_invariants", True)
    return GenerationEngine(model, params, draft=draft, draft_k=k, **kw)


def oracle_for(model, params, prompts, n=GEN):
    """A scripted drafter that always proposes the target's own next
    tokens — acceptance 1.0 by construction."""
    ref_map = {tuple(p): r for p, r in zip(prompts,
                                           refs_for(model, params,
                                                    prompts, n))}

    def fn(req, k):
        full = ref_map[tuple(req.prompt)]
        return full[len(req.tokens):len(req.tokens) + k]

    return ScriptedDraftSource(fn)


# ---------------------------------------------------------------------------
# Greedy identity
# ---------------------------------------------------------------------------

def test_greedy_identity_self_draft(setup, sink):
    """Self-drafting (draft == target): every proposal must verify, so
    acceptance is exactly 1.0 — which also proves the draft-KV position
    and commit bookkeeping are exact (one misfed position would skew
    the draft logits and break the 1.0)."""
    model, cfg, params, _, prompts = setup
    draft = DraftEngine(model, params, max_slots=4, page_size=8)
    eng = spec_engine(model, params, draft)
    try:
        assert eng.generate(prompts, GEN) == refs_for(model, params, prompts)
        assert eng.spec_accept_rate == 1.0
        assert eng.spec_rounds < GEN * len(prompts)  # actually speculated
    finally:
        eng.close()


def test_greedy_identity_mismatched_draft(setup, sink):
    """A draft with DIFFERENT weights proposes mostly-wrong tokens;
    output must still be token-identical to the oracle (rejection
    resamples the target's own pick), acceptance lands somewhere in
    [0, 1)."""
    model, cfg, params1, params2, prompts = setup
    draft = DraftEngine(model, params2, max_slots=4, page_size=8)
    eng = spec_engine(model, params1, draft)
    try:
        assert eng.generate(prompts, GEN) == refs_for(model, params1,
                                                      prompts)
        assert 0.0 <= eng.spec_accept_rate < 1.0
    finally:
        eng.close()


def test_scripted_zero_accept_degenerates_to_plain_decode(setup, sink):
    """An adversarial drafter (always wrong): every round accepts 0
    tokens and emits exactly the target's pick — plain decode in
    disguise, token-identical, acceptance 0.0."""
    model, cfg, params, _, prompts = setup
    refs = refs_for(model, params, prompts)
    ref_map = {tuple(p): r for p, r in zip(prompts, refs)}

    def anti(req, k):   # oracle token + 1 (mod V): guaranteed mismatch
        full = ref_map[tuple(req.prompt)]
        nxt = full[len(req.tokens):len(req.tokens) + k]
        return [(t + 1) % cfg.vocab_size for t in nxt]

    eng = spec_engine(model, params, ScriptedDraftSource(anti))
    try:
        assert eng.generate(prompts, GEN) == refs
        assert eng.spec_accept_rate == 0.0
        assert eng.tokens_emitted == GEN * len(prompts)
    finally:
        eng.close()


def test_scripted_all_accept_commits_k_at_a_time(setup, sink):
    """The oracle drafter: every proposal verifies, each round commits
    K+1 tokens, so the whole batch finishes in far fewer verify rounds
    than tokens."""
    model, cfg, params, _, prompts = setup
    eng = spec_engine(model, params, oracle_for(model, params, prompts))
    try:
        assert eng.generate(prompts, GEN) == refs_for(model, params, prompts)
        assert eng.spec_accept_rate == 1.0
        # 8 tokens at K=4 -> ceil(8 / (4+1)) = 2 rounds per request
        assert eng.spec_rounds <= 2
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Sampled lanes: bit-identity spec-on vs spec-off
# ---------------------------------------------------------------------------

def _sampled_run(eng, prompts, *, n=GEN):
    reqs = [eng.submit(p, n) if i % 2 == 0 else
            eng.submit(p, n, temperature=0.8, top_p=0.9, seed=100 + i)
            for i, p in enumerate(prompts)]
    while not all(r.done_evt.is_set() for r in reqs):
        eng.step()
    return [list(r.tokens) for r in reqs]


def test_sampled_stream_bit_identical_spec_on_off(setup, sink):
    """Mixed greedy/sampled batch: the spec-on streams must equal the
    spec-off streams DRAW FOR DRAW — the counter PRNG keys every pick by
    (seed, stream index), so verify's picks are the plain path's picks."""
    model, cfg, params1, params2, prompts = setup
    plain = GenerationEngine(model, params1, max_slots=4, page_size=8)
    off = _sampled_run(plain, prompts)
    plain.close()
    draft = DraftEngine(model, params2, max_slots=4, page_size=8)
    eng = spec_engine(model, params1, draft)
    try:
        assert _sampled_run(eng, prompts) == off
    finally:
        eng.close()


def test_sampled_stream_batch_composition_invariant(setup, sink):
    """Each request run SOLO through a speculating engine produces the
    same stream it produced inside the full batch — the per-request
    (seed, index) keying means batch layout can never leak into
    output."""
    model, cfg, params1, params2, prompts = setup
    draft = DraftEngine(model, params2, max_slots=4, page_size=8)
    eng = spec_engine(model, params1, draft)
    try:
        batched = _sampled_run(eng, prompts)
    finally:
        eng.close()
    for i, p in enumerate(prompts):
        draft = DraftEngine(model, params2, max_slots=4, page_size=8)
        solo = spec_engine(model, params1, draft)
        try:
            if i % 2 == 0:
                r = solo.submit(p, GEN)
            else:
                r = solo.submit(p, GEN, temperature=0.8, top_p=0.9,
                                seed=100 + i)
            while not r.done_evt.is_set():
                solo.step()
            assert list(r.tokens) == batched[i]
        finally:
            solo.close()


@pytest.mark.parametrize("k", [1, 2, 8])
def test_draft_k_variations(setup, sink, k):
    """Output is invariant in K (only round count changes)."""
    model, cfg, params, params2, prompts = setup
    draft = DraftEngine(model, params2, max_slots=4, page_size=8)
    eng = spec_engine(model, params, draft, k=k)
    try:
        assert eng.generate(prompts, GEN) == refs_for(model, params, prompts)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Acceptance-prefix edge cases under shared CoW pages
# ---------------------------------------------------------------------------

def test_mid_page_commit_under_shared_prefix_pages(setup, sink):
    """Requests sharing a cached system-prompt prefix speculate while
    their tails CoW off shared pages; multi-token commits land mid-page
    with ``debug_invariants`` auditing PagePool refcounts and the draft
    pool every step. Output pinned against the plain engine."""
    model, cfg, params, params2, prompts = setup
    rng = np.random.RandomState(3)
    sys_prompt = [int(t) for t in rng.randint(0, cfg.vocab_size, size=17)]
    shared = [sys_prompt + p for p in prompts]
    plain = GenerationEngine(model, params, max_slots=4, page_size=8)
    want = plain.generate(shared, GEN)
    plain.close()
    draft = DraftEngine(model, params2, max_slots=4, page_size=8)
    eng = spec_engine(model, params, draft, prefix_cache=True)
    try:
        cold = eng.generate(shared[:1], GEN)      # seeds the prefix cache
        warm = eng.generate(shared[1:], GEN)      # CoW off cached pages
        assert cold + warm == want
        assert eng.prefix_hits >= 1
    finally:
        eng.close()


def test_draft_pool_accounting(setup, sink):
    """Draft states own their pages exactly once; finishing requests
    release them (the ``_release`` -> ``draft.drop`` hook), and an
    explicit audit passes at every point."""
    model, cfg, params, _, prompts = setup
    draft = DraftEngine(model, params, max_slots=4, page_size=8)
    eng = spec_engine(model, params, draft)
    try:
        eng.generate(prompts, GEN)
        draft.check()
        assert not draft._states      # every slot released on finish
        assert draft.pool.free == draft.pool.total  # no page leaked
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Swap interactions
# ---------------------------------------------------------------------------

class _FakeWatcher:
    """Stands in for BaseRevisionWatcher: the engine only calls
    ``take_pending`` (between steps) and ``close``."""

    def __init__(self):
        self.staged = None

    def take_pending(self):
        staged, self.staged = self.staged, None
        return staged

    def close(self):
        pass


def test_draft_not_ready_degrades_to_plain_decode(setup, sink):
    """A DraftEngine with no installed params is not ``ready``: the
    engine must serve plain decode (token-identical), counting the
    fallback."""
    model, cfg, params, _, prompts = setup
    draft = DraftEngine(model, max_slots=4, page_size=8)
    assert not draft.ready
    eng = spec_engine(model, params, draft)
    try:
        assert eng.generate(prompts, GEN) == refs_for(model, params, prompts)
        assert eng.spec_rounds == 0
        assert obs.registry().counter("serve.spec_fallbacks").value >= 1
    finally:
        eng.close()


def test_draft_hot_swap_mid_run(setup, sink):
    """A new draft revision lands mid-generation: the watcher lane
    installs it between steps, flushing all draft KV; output stays
    token-identical (draft params can only change ACCEPTANCE) and the
    swap is counted."""
    model, cfg, params1, params2, prompts = setup
    watcher = _FakeWatcher()
    draft = DraftEngine(model, params2, max_slots=4, page_size=8,
                        revision="d1", watcher=watcher)
    eng = spec_engine(model, params1, draft)
    try:
        reqs = [eng.submit(p, GEN) for p in prompts]
        for _ in range(2):
            eng.step()
        flushes = draft.flush_count
        watcher.staged = ("d2", jax.device_put(params1))  # self-draft now
        while not all(r.done_evt.is_set() for r in reqs):
            eng.step()
        assert [list(r.tokens) for r in reqs] == refs_for(model, params1,
                                                          prompts)
        assert draft.revision == "d2"
        assert draft.flush_count > flushes
        assert obs.registry().counter("serve.spec_draft_swaps").value == 1
    finally:
        eng.close()


def test_target_restart_swap_invalidates_draft(setup, sink):
    """THE drain-swap interaction fix: a target-base hot swap under the
    restart policy lands mid-speculation. Every in-flight draft state
    was built against output of the OLD target params — the restart
    must drop them all (counted as ``serve.spec_invalidations``), and
    the requeued requests must finish token-identical to the NEW
    params' oracle, with no stale draft KV surviving."""
    model, cfg, params1, params2, prompts = setup
    n = 24     # long enough that the swap lands mid-speculation
    draft = DraftEngine(model, params1, max_slots=4, page_size=8)
    eng = spec_engine(model, params1, draft, swap_policy="restart")
    try:
        reqs = [eng.submit(p, n) for p in prompts]
        eng.step()                    # prefill + first speculation
        eng.step()
        assert draft._states          # speculation is in flight
        stale = dict(draft._states)
        eng._pending_swap = ("r2", jax.device_put(params2))
        eng.step()                    # swap installs, slots restart
        assert eng.revision == "r2"
        # the same step re-admits the requeued requests and speculates
        # again — but from FRESH draft states: every pre-swap state
        # (draft KV seeded by the old params' output) was dropped
        for rid, st in draft._states.items():
            assert st is not stale.get(rid)
        inval = obs.registry().counter("serve.spec_invalidations").value
        assert inval == len(prompts)
        while not all(r.done_evt.is_set() for r in reqs):
            eng.step()
        assert [list(r.tokens) for r in reqs] == refs_for(model, params2,
                                                          prompts, n)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Compile discipline
# ---------------------------------------------------------------------------

def test_zero_steady_state_fresh_compiles(setup, sink):
    """Two identical mixed greedy/sampled waves through a speculating
    engine: wave 2 must add ZERO fresh compiles — draft, verify, and
    prefill families are all warm on their shared (slot, page)
    ladders."""
    model, cfg, params, _, prompts = setup
    draft = DraftEngine(model, params, max_slots=4, page_size=8)
    eng = spec_engine(model, params, draft)
    try:
        _sampled_run(eng, prompts)               # warm every family
        reg = obs.registry()
        before = reg.histogram("compile.ms").count
        wave2 = _sampled_run(eng, prompts)
        assert reg.histogram("compile.ms").count == before
        plain = GenerationEngine(model, params, max_slots=4, page_size=8)
        assert wave2 == _sampled_run(plain, prompts)
        plain.close()
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Compatibility / plumbing
# ---------------------------------------------------------------------------

def test_compat_vocab_mismatch_rejected(setup):
    model, cfg, params, _, _ = setup
    other, _ = gpt2.make_model(gpt2.GPT2Config(
        vocab_size=64, n_positions=32, n_embd=16, n_layer=1, n_head=1,
        vocab_multiple=64))
    assert compat_reason(other, cfg) is not None
    with pytest.raises(ValueError, match="incompatible draft"):
        GenerationEngine(model, params, max_slots=2, page_size=8,
                         draft=DraftEngine(other, max_slots=2,
                                           page_size=8))


def test_router_backend_speed_factor():
    """Heartbeat spec extras scale the router's outstanding-work score;
    defaults leave non-speculating fleets byte-identical."""
    from distributedtraining_tpu.engine.router import (BackendState,
                                                       RouterPolicy)
    plain = BackendState(url="a")
    plain.update({"ok": True, "queue_depth": 2, "active": 1})
    spec = BackendState(url="b")
    spec.update({"ok": True, "queue_depth": 2, "active": 1,
                 "spec_accept_rate": 0.75, "spec_k": 4})
    assert plain.speed_factor == 1.0
    assert spec.speed_factor == 4.0
    pol = RouterPolicy()
    assert pol.score(spec) < pol.score(plain)
    assert pol.choose([plain, spec]) is spec


def test_speculative_step_feeds_the_four_decode_phases(setup, sink):
    """A speculative step is timed by the same four decode phases as a
    plain one (the drafter's propose is a second dispatch of that step),
    beside the two spec histograms it had."""
    model, cfg, params, _, prompts = setup
    draft = DraftEngine(model, params, max_slots=4, page_size=8)
    eng = spec_engine(model, params, draft)
    try:
        eng.submit(prompts[0], GEN)
        eng.step()
        assert eng.spec_rounds == 1
        reg = obs.registry()
        count = {n: reg.peek(f"serve.{n}_ms").count for n in (
            "step", "decode.build", "decode.dispatch", "decode.fetch",
            "decode.emit", "spec_draft", "spec_verify")}
        assert count == {"step": 1, "decode.build": 1, "decode.dispatch": 2,
                         "decode.fetch": 1, "decode.emit": 1,
                         "spec_draft": 1, "spec_verify": 1}
        total = {n: reg.peek(f"serve.{n}_ms").total for n in count}
        assert total["step"] >= sum(total[f"decode.{p}"] for p in (
            "build", "dispatch", "fetch", "emit"))
    finally:
        eng.close()
