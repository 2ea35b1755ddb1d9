"""The serve engine one decode program ahead of the host (engine/serve.py,
``_decode_plain`` / ``_chainable`` / ``_collect``), over the three tiny
families that serve: GPT-2, the latent-attention expert model
(``tiny-kanana``) and the Mamba-2 hybrid (``tiny-nemotron-h``).

What is held: every request's tokens are what it gets ALONE from an engine
that never chains (every program collected before the next is built: the
order of work before this mechanism), and, for greedy requests, what
``reference_generate`` makes; the engine is never more than one program
ahead; the counters say exactly how often it chained, collected first and
dropped a row; a chained call compiles nothing; the programs' lowered text
is what the benchmark drivers lower."""

import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine.serve import (GenerationEngine,
                                                  reference_generate)
from distributedtraining_tpu.models import deepseek_v3 as ds
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.models import nemotron_h as nh
from distributedtraining_tpu.utils import obs

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")

# float32 throughout: a cached and a recomputed spelling then pick the same
# token (tests/test_serve.py says why)
TINY = gpt2.GPT2Config(vocab_size=128, n_positions=64, n_embd=32,
                       n_layer=2, n_head=2, dtype="float32",
                       vocab_multiple=64)
FAMILIES = ("gpt2", "kanana", "nemotron")


@dataclasses.dataclass
class Family:
    name: str
    model: object
    params: object      # the revision served
    params2: object     # another revision, for the swap
    vocab: int


@pytest.fixture(scope="module")
def drivers():
    """The benchmark's drivers, imported as the benchmark imports them:
    the expert families' weights come through their conversion, at the
    signal sizes their own test modules use."""
    sys.path.insert(0, _BENCH)
    try:
        from drivers import open_loop_mla_moe, open_loop_ssm_moe
        from reference import deepseek_v3, nemotron_h
        yield {"kanana": (ds, "tiny-kanana", deepseek_v3,
                          open_loop_mla_moe, {}),
               "nemotron": (nh, "tiny-nemotron-h", nemotron_h,
                            open_loop_ssm_moe, {"matrix_std": 0.16})}
    finally:
        sys.path.remove(_BENCH)
        for name in [m for m in sys.modules
                     if m.split(".")[0] in ("drivers", "reference")]:
            del sys.modules[name]


@pytest.fixture(scope="module", params=FAMILIES)
def fam(request, drivers):
    if request.param == "gpt2":
        model, cfg = gpt2.make_model(TINY)
        return Family("gpt2", model,
                      model.init_params(jax.random.PRNGKey(0), seq_len=8),
                      model.init_params(jax.random.PRNGKey(7), seq_len=8),
                      cfg.vocab_size)
    mod, preset, reference, driver, assumed = drivers[request.param]
    pc = mod.PRESETS[preset]
    config = dict({f.name: getattr(pc, f.name)
                   for f in dataclasses.fields(pc)},
                  assumed=dict(assumed, padded_vocab=pc.padded_vocab))
    mcfg = reference.model_cfg(config)
    model, _ = mod.make_model(pc)
    return Family(request.param, model,
                  driver.program_params(mcfg, 7, jnp.float32),
                  driver.program_params(mcfg, 8, jnp.float32),
                  pc.vocab_size)


@pytest.fixture()
def sink():
    class _Sink:
        def log(self, rec, **kw):
            pass

    obs.configure(_Sink(), role="server")
    try:
        yield obs.registry()
    finally:
        obs.reset()


def _engine(fam, params=None, **kw):
    kw = dict(dict(max_slots=3, page_size=8, max_seq_len=64,
                   debug_invariants=True), **kw)
    return GenerationEngine(fam.model, params or fam.params, **kw)


def _prompts(fam, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, fam.vocab, n).tolist() for n in lengths]


def _count(reg, name):
    c = reg.peek(name)
    return c.value if c is not None else 0


def _fetched(reg):
    h = reg.peek("serve.decode.fetch_ms")
    return h.count if h is not None else 0


def _dispatched(reg):
    return (_count(reg, "serve.decode.chained")
            + _count(reg, "serve.decode.collected_first"))


def _drive(eng, reg, plan, limit=400):
    """Submit ``plan[k]`` (a list of ``(prompt, n_new, sampling)``) before
    step k and step until idle; after every step the engine is at most one
    program ahead of what it has collected."""
    reqs = []
    for k in range(limit):
        for prompt, n_new, sampling in plan.get(k, ()):
            reqs.append(eng.submit(prompt, n_new, **sampling))
        if k > max(plan) and eng.idle:
            return reqs
        eng.step()
        ahead = _dispatched(reg) - _fetched(reg)
        assert ahead == int(eng._flight is not None) and ahead in (0, 1)
    raise AssertionError("the schedule did not drain")


def _alone_unchained(fam, prompt, n_new, params=None, eos_id=None,
                     **sampling):
    """What one request gets alone from an engine that never chains."""
    eng = _engine(fam, params, eos_id=eos_id)
    eng._chainable = lambda: False
    try:
        req = eng.submit(prompt, n_new, **sampling)
        while not req.done_evt.is_set():
            eng.step()
        return list(req.tokens)
    finally:
        eng.close()


# -- the tokens, under a schedule that takes every branch --------------------

SAMPLED = dict(temperature=0.8, top_p=0.9, seed=5)


def test_every_branch_serves_the_tokens_of_an_engine_that_never_chains(
        fam, sink):
    """Requests admitted mid-stream, budgets that end on different steps,
    an end-of-sequence token that is hit, a pool small enough to preempt,
    a sampled lane among greedy ones, three slots for five requests (so a
    state row is written over by the next admission): token for token
    what each request gets alone without chaining."""
    prompts = _prompts(fam, (5, 11, 3, 17, 9))
    budgets = (12, 6, 9, 14, 4)
    sampling = ({}, {}, SAMPLED, {}, {})
    # an end-of-sequence id that request 1 reaches at its fourth token
    eos = _alone_unchained(fam, prompts[1], budgets[1])[3]
    want = [_alone_unchained(fam, p, n, eos_id=eos, **s)
            for p, n, s in zip(prompts, budgets, sampling)]
    assert len(want[1]) <= 4 and want[1][-1] == eos
    for p, n, s, w in zip(prompts, budgets, sampling, want):
        if not s and fam.name == "gpt2":
            assert w == reference_generate(fam.model, fam.params, p, n,
                                           eos_id=eos)
    eng = _engine(fam, eos_id=eos, max_seq_len=48, pool_pages=1 + 6)
    try:
        jobs = list(zip(prompts, budgets, sampling))
        reqs = _drive(eng, sink, {0: jobs[:2], 3: jobs[2:3], 4: jobs[3:4],
                                  10: jobs[4:]})
        assert [r.status for r in reqs] == ["done"] * 5
        assert [list(r.tokens) for r in reqs] == want
        assert eng._flight is None and eng.idle
    finally:
        eng.close()
    assert _count(sink, "serve.preempted") >= 1
    assert _count(sink, "serve.decode.chained") >= 1
    assert _count(sink, "serve.decode.collected_first") >= 5
    assert _fetched(sink) == _dispatched(sink)


def test_restart_swap_mid_stream_collects_then_restarts(fam, sink):
    """A `restart` swap with a program in flight: what the old revision
    already ran is emitted, then the request starts over and its tokens
    are the new revision's."""
    prompt, = _prompts(fam, (7,), seed=3)
    want = _alone_unchained(fam, prompt, 10, params=fam.params2)
    eng = _engine(fam, revision="r1", swap_policy="restart")
    try:
        req = eng.submit(prompt, 10)
        for _ in range(3):
            eng.step()
        assert eng._flight is not None and len(req.tokens) == 3
        eng._pending_swap = ("r2", jax.device_put(fam.params2))
        eng.step()
        assert eng.revision == "r2"
        assert _count(sink, "serve.swap_restarts") == 1
        assert _count(sink, "serve.decode.rows_dropped") == 0
        while not req.done_evt.is_set():
            eng.step()
        assert list(req.tokens) == want and req.revision == "r2"
    finally:
        eng.close()


# -- how often it engages, exactly -------------------------------------------

def test_counters_of_a_known_schedule(fam, sink):
    """One request of 8 tokens, a second of 3 admitted before step 3.
    Step 0 prefills and dispatches on host tokens; 1 and 2 chain; 3 admits
    (collects first); 4 chains; 5 finds the second request's last token in
    flight (collects first); 6 chains; 7 finds the first request's last
    token in flight, collects it and has nothing left to run: 4 chained, 3
    dispatched on host tokens, 7 programs fetched, no row dropped."""
    a, b = _prompts(fam, (6, 4), seed=1)
    eng = _engine(fam)
    try:
        ra = eng.submit(a, 8)
        seen = []
        for k in range(8):
            if k == 3:
                rb = eng.submit(b, 3)
            eng.step()
            seen.append((_count(sink, "serve.decode.chained"),
                         _count(sink, "serve.decode.collected_first"),
                         len(ra.tokens)))
        rb_tokens = list(rb.tokens)
        assert seen == [(0, 1, 1), (1, 1, 2), (2, 1, 3), (2, 2, 4),
                        (3, 2, 5), (3, 3, 6), (4, 3, 7), (4, 3, 8)]
        assert ra.status == rb.status == "done" and eng.idle
        assert _fetched(sink) == 7
        assert _count(sink, "serve.decode.rows_dropped") == 0
        assert list(ra.tokens) == _alone_unchained(fam, a, 8)
        assert rb_tokens == _alone_unchained(fam, b, 3)
    finally:
        eng.close()


def test_a_row_run_past_the_end_of_sequence_is_dropped_and_counted(fam,
                                                                    sink):
    """With `eos_id` set a slot may ride one program past its end: the
    engine is not idle while that program is in flight, its row is
    dropped when it is collected, and the slot's state row and pages
    serve the next request untouched by it."""
    first, second = _prompts(fam, (9, 5), seed=2)
    free_run = _alone_unchained(fam, first, 10)
    eos = free_run[3]
    stop = free_run.index(eos) + 1      # the first time it shows
    eng = _engine(fam, eos_id=eos, max_slots=1)
    try:
        req = eng.submit(first, 10)
        while not req.done_evt.is_set():
            eng.step()
        assert list(req.tokens) == free_run[:stop]
        if stop > 2:
            # the program behind the one that made the last token was
            # chained on it before the host saw that token
            assert not eng._active and eng._flight is not None
            assert not eng.idle
        nxt = eng.submit(second, 6)
        while not nxt.done_evt.is_set():
            eng.step()
        while not eng.idle:
            eng.step()
        assert _count(sink, "serve.decode.rows_dropped") == int(stop > 2)
        assert list(nxt.tokens) == _alone_unchained(fam, second, 6,
                                                    eos_id=eos)
        assert eng.pool.free == eng.pool.total
    finally:
        eng.close()


def test_close_with_a_program_in_flight_emits_its_tokens(fam):
    prompt, = _prompts(fam, (6,), seed=4)
    want = _alone_unchained(fam, prompt, 8)
    eng = _engine(fam)
    req = eng.submit(prompt, 8)
    eng.step()
    eng.step()
    assert eng._flight is not None and not eng.idle
    assert list(req.tokens) == want[:2]
    eng.close()
    assert eng._flight is None and not eng._active
    assert list(req.tokens) == want[:3] and req.status == "truncated"
    assert req.done_evt.is_set() and eng.pool.free == eng.pool.total


# -- the programs are the ones the cells warmed ------------------------------

def test_a_chained_call_compiles_nothing(fam):
    """The cells' warm-up reaches most buckets by an unchained call only;
    the first chained call at such a bucket falls inside the measured
    window and must find that executable: no backend compile between an
    unchained and a chained call at one bucket."""
    import jax.monitoring as mon

    events = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(event)

    prompt, = _prompts(fam, (9,), seed=5)    # four rows on one page
    eng = _engine(fam)
    mon.register_event_duration_secs_listener(listen)
    try:
        req = eng.submit(prompt, 8)
        eng.step()                       # prefill + the unchained call
        assert eng._flight is not None and events
        before = len(events)
        (prog,) = eng._decode_progs.values()
        assert prog.__wrapped__._cache_size() == 1
        for _ in range(3):               # three chained calls
            eng.step()
        assert len(req.tokens) == 4
        assert len(events) == before
        # nor a second entry in the jit's own cache: the picks are the
        # kind of argument the host's tokens were
        assert len(eng._decode_progs) == 1
        assert prog.__wrapped__._cache_size() == 1
    finally:
        mon.unregister_event_duration_listener(listen)
        eng.close()


# what `.lower(...).as_text()` gives for the presets' programs before this
# mechanism (commit c19e5e3; the same on this tree): no program changed,
# none was added
LOWERED = {
    ("tiny", "decode"): "4ad6a781a8d11f07",
    ("tiny", "prefill"): "bc593917578d6b4e",
    ("tiny-kanana", "decode"): "339f08e6c21a0c1a",
    ("tiny-kanana", "prefill"): "2db5a3f9557a4eaf",
    ("tiny-nemotron-h", "decode"): "c5d64ce864b94ce8",
    ("tiny-nemotron-h", "prefill"): "e334c65a8ae99c19",
}
_PRESET_MODULE = {"tiny": gpt2, "tiny-kanana": ds, "tiny-nemotron-h": nh}


@pytest.fixture(scope="module", params=sorted(_PRESET_MODULE))
def preset_engine(request):
    model, _ = _PRESET_MODULE[request.param].make_model(request.param)
    eng = GenerationEngine(model, model.init_params(jax.random.PRNGKey(0)),
                           max_slots=2, page_size=8, max_seq_len=64)
    yield request.param, eng
    eng.close()


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_lowered_text_is_the_parents(preset_engine, program):
    """Lowered with numpy arguments, as `benchmarks/drivers/open_loop.py:
    _decode_mosaic_calls` and its two copies lower `_decode_progs`: the
    drivers' call still lowers, and to the text it had."""
    preset, eng = preset_engine
    k_pages, v_pages = eng._kv
    if program == "decode":
        eng._decode_prog(2, 2)
        (slots, pages), prog = max(eng._decode_progs.items())
        text = prog.lower(
            eng._params, k_pages, v_pages,
            np.zeros((slots, pages), np.int32),
            np.zeros((slots,), np.int32), np.zeros((slots,), np.int32),
            *eng._slot_state(np.zeros((slots,), np.int32))).as_text()
    else:
        text = eng._prefill_prog(16).lower(
            eng._params, np.zeros((1, 16), np.int32), np.int32(3),
            k_pages, v_pages, np.zeros((2,), np.int32),
            *eng._slot_state(np.int32(0))).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        LOWERED[preset, program]
