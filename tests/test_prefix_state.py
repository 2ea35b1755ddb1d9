"""The prefix cache for EVERY family (engine/serve.py): pages for the
layers that cache a token, and beside them snapshots of the state for the
layers that keep one a slot. Parametrised over a tiny preset of each state
family (per-channel delta rule + K/V heads, scalar delta rule + a latent
pair, Mamba-2 + K/V heads) and one K/V-only family, float32 on the CPU, on
seeded random weights.

What is held: a turn served on a HIT gives the first-token logits and the
greedy tokens of the same prompt served cold (2e-4: the continuation sums
the same float32 terms in chunks cut at another place); a hit prompt is
registered, so a third turn reuses the second's pages and state; a stale,
evicted or foreign snapshot is a miss, never a wrong hit; refcounts and
rows balance under ``debug_invariants`` at every step; a miss longer than
the top prefill bucket equals the model's own full forward; with the cache
off a state family builds no snapshot pool and no continuation program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import kv_pool, serve
from distributedtraining_tpu.models import family_of

TOL = 2e-4
STATE = ["tiny-solar", "tiny-gigachat", "tiny-nemotron-h"]
EVERY = STATE + ["tiny-llama"]


def _family(preset):
    module = family_of(preset)
    # float32 throughout (tiny-llama computes in bfloat16 as published)
    model, cfg = module.make_model(dataclasses.replace(
        module.PRESETS[preset], dtype="float32"))
    return model, cfg, model.init_params(jax.random.PRNGKey(3))


@pytest.fixture(scope="module", params=EVERY)
def family(request):
    return _family(request.param)


@pytest.fixture(scope="module", params=STATE)
def state_family(request):
    return _family(request.param)


def _engine(fam, **kw):
    model, _, params = fam
    kw = dict(dict(max_slots=3, page_size=8, max_seq_len=128,
                   max_new_tokens=8, debug_invariants=True), **kw)
    return serve.GenerationEngine(model, params, **kw)


def _tokens(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).tolist()


def _serve(eng, prompt, n_new=6):
    """One request to its end: (tokens, the prefill's logits row)."""
    rows = []
    first = eng._first_token

    def spy(req, nxt, logit_row):
        rows.append(np.asarray(logit_row))
        return first(req, nxt, logit_row)

    eng._first_token = spy
    try:
        out = eng.generate([prompt], n_new)[0]
    finally:
        eng._first_token = first
    return out, rows[-1]


def _session(fam, turns=3, **kw):
    """`turns` turns of one session through a caching engine: each
    prompt is the whole text so far plus a new message."""
    eng = _engine(fam, prefix_cache=True, **kw)
    cfg = fam[1]
    text, served = _tokens(cfg, 21, 0), []
    for t in range(turns):
        before = (eng.prefix_hits, eng.prefix_tokens_saved)
        out, row = _serve(eng, text)
        served.append((list(text), out, row,
                       eng.prefix_hits - before[0],
                       eng.prefix_tokens_saved - before[1]))
        text = text + out + _tokens(cfg, 7, 10 + t)
    return eng, served


def test_a_turn_served_on_a_hit_is_the_turn_served_cold(family):
    eng, served = _session(family)
    cold = _engine(family)
    for t, (prompt, out, row, hit, saved) in enumerate(served):
        assert hit == (1 if t else 0)
        out_c, row_c = _serve(cold, prompt)
        assert float(np.max(np.abs(row - row_c))) < TOL
        assert out == out_c
    eng.close(), cold.close()


def test_a_hit_prompt_is_registered_so_every_turn_gains(family):
    eng, served = _session(family)
    recurrent = kv_pool.has_recurrent_state(family[1])
    (p1, o1, *_), (p2, o2, _, _, saved2), (p3, _, _, _, saved3) = served
    if recurrent:
        # all or nothing, at a registered prompt's END
        assert (saved2, saved3) == (len(p1), len(p2))
    else:
        # pages alone: the overlap runs on into the partial page
        assert saved2 >= len(p1) and saved3 >= len(p2)
    # before hit prompts were registered the third turn found the FIRST
    # turn's pages only
    assert saved3 > len(p1) + 8
    # nothing but the cache holds a page once the session is idle, and
    # the audit (pages, state rows, snapshot rows) ran at every step
    assert not eng._active
    eng._check_invariants()
    assert sorted(eng._cache.pages()) == sorted(eng.pool._refs)
    eng.close()


def test_a_miss_longer_than_the_top_bucket_is_the_full_forward(family):
    model, cfg, params = family
    prompt = _tokens(cfg, 45, 5)
    eng = _engine(family, prefill_chunk=16)
    out, row = _serve(eng, prompt)
    # three chunks: one prefill program, then continuations
    assert sorted(eng._prefill_progs) == [16]
    assert eng._prefill_ctx_progs
    logits = model.apply({"params": params}, jnp.asarray([prompt]))
    assert float(np.max(np.abs(
        row - np.asarray(logits[0, -1, :cfg.vocab_size])))) < TOL
    whole = _engine(family)
    assert _serve(whole, prompt)[0] == out
    assert not whole._prefill_ctx_progs
    eng.close(), whole.close()


def test_a_chunked_miss_is_registered_and_hit(family):
    cfg = family[1]
    prompt = _tokens(cfg, 45, 6)
    eng = _engine(family, prefix_cache=True, prefill_chunk=16)
    out, _ = _serve(eng, prompt)
    longer = prompt + out + _tokens(cfg, 20, 7)     # suffix > one chunk
    got, row = _serve(eng, longer)
    assert eng.prefix_hits == 1
    cold = _engine(family)
    want, row_c = _serve(cold, longer)
    assert got == want and float(np.max(np.abs(row - row_c))) < TOL
    eng.close(), cold.close()


def test_an_evicted_or_foreign_snapshot_is_a_miss(state_family):
    cfg = state_family[1]
    a, b = _tokens(cfg, 21, 1), _tokens(cfg, 19, 2)
    eng = _engine(state_family, prefix_cache=True, snapshot_rows=1)
    out_a, _ = _serve(eng, a)
    out_b, _ = _serve(eng, b)            # takes the one row: A's is gone
    assert len(eng._cache._snaps) == 1
    assert eng.prefix_snapshots_evicted == 1     # nothing had extended it
    # A's pages are all still cached, its state is not: a miss, and right
    ext = a + out_a + _tokens(cfg, 5, 3)
    got, row = _serve(eng, ext)
    assert eng.prefix_hits == 0 and eng.prefix_misses == 3
    cold = _engine(state_family)
    want, row_c = _serve(cold, ext)
    assert got == want and float(np.max(np.abs(row - row_c))) < TOL
    # B's snapshot never serves a prompt that is not B's: the row now
    # holds A-extended's state; B's own extension misses too
    _serve(eng, b + out_b + _tokens(cfg, 5, 4))
    assert eng.prefix_hits == 0
    eng.close(), cold.close()


def test_a_snapshot_whose_pages_were_evicted_is_a_miss(state_family):
    cfg = state_family[1]
    a = _tokens(cfg, 21, 1)
    eng = _engine(state_family, prefix_cache=True)
    out_a, _ = _serve(eng, a)
    while eng._cache.evict_one():
        pass
    assert len(eng._cache._snaps) == 1 and not eng._cache.pages()
    _serve(eng, a + out_a + _tokens(cfg, 5, 3))
    assert eng.prefix_hits == 0
    eng.close()


def test_a_stale_snapshot_row_would_be_seen(state_family):
    """The test's own control: what the comparisons above would read if a
    hit restored ANOTHER session's state. It must not pass."""
    cfg = state_family[1]
    a, b = _tokens(cfg, 21, 1), _tokens(cfg, 21, 2)
    eng = _engine(state_family, prefix_cache=True)
    out_a, _ = _serve(eng, a)
    _serve(eng, b)
    key_a = next(iter(eng._cache._snaps))
    key_b = [k for k in eng._cache._snaps if k != key_a][0]
    eng._cache._snaps[key_a], eng._cache._snaps[key_b] = (
        eng._cache._snaps[key_b], eng._cache._snaps[key_a])
    ext = a + out_a + _tokens(cfg, 5, 3)
    _, row = _serve(eng, ext)
    assert eng.prefix_hits == 1
    cold = _engine(state_family)
    _, row_c = _serve(cold, ext)
    assert float(np.max(np.abs(row - row_c))) > 10 * TOL
    eng.close(), cold.close()


def test_with_the_cache_off_a_state_family_builds_nothing_new(state_family):
    cfg = state_family[1]
    eng = _engine(state_family)
    eng.generate([_tokens(cfg, 9, 1), _tokens(cfg, 30, 2)], 4)
    assert eng._cache is None and eng._snap == ((), ())
    assert not eng._prefill_ctx_progs and not eng._state_copy_progs
    assert eng._snapshot_rows == 0
    eng.close()


def test_snapshot_counters_and_gauge(state_family):
    from distributedtraining_tpu.utils import obs

    class _Null:
        def log(self, *a, **k):
            pass

        def close(self):
            pass

    obs.configure(_Null(), role="server")
    try:
        eng, served = _session(state_family, snapshot_rows=2)
        reg = obs.registry()

        def count(name):
            c = reg.peek(name)
            return 0 if c is None else int(c.value)

        assert count("serve.prefix.snapshots_taken") == 3
        assert count("serve.prefix.snapshots_restored") == 2
        # two rows, three prompts: the first turn's went to make room,
        # and it was the session's own past: retired, not evicted
        assert count("serve.prefix.snapshots_retired") == 1
        assert count("serve.prefix.snapshots_evicted") == 0
        assert eng.prefix_snapshots_evicted == 0
        total = sum(len(p) for p, *_ in served)
        saved = count("serve.prefix_tokens_saved")
        assert saved == len(served[0][0]) + len(served[1][0])
        assert count("serve.prefill_tokens") == total - saved
        per_row = sum(x.nbytes // x.shape[0]
                      for half in eng._snap for x in half)
        assert reg.peek("serve.prefix.snapshot_bytes").value == 2 * per_row
        assert reg.peek("serve.prefix.restore_ms").count == 2
        assert reg.peek("serve.prefix.snapshot_ms").count == 3
        eng.close()
    finally:
        obs.reset()


# -- the index alone ----------------------------------------------------------

def _index(rows):
    pool = serve.PagePool(64)
    return pool, serve.PrefixCache(pool, 4, rows)


def _register(pool, cache, prompt, extends=None):
    pages = pool.alloc(len(prompt) // 4 + 1)
    row = cache.take_snapshot_row()
    cache.register(prompt, pages, row, extends)
    for p in pages:
        pool.decref(p)                      # the slot lets go
    return row


def test_match_state_is_all_or_nothing_at_a_prompts_end():
    pool, cache = _index(4)
    base = list(range(10))                  # two pages and a tail of 2
    _register(pool, cache, base)
    assert cache.match_state(base) == ([], 0, None)   # nothing to run
    pages, n, key = cache.match_state(base + [99])
    assert n == 10 and len(pages) == 3 and key is not None
    # a prompt that shares 9 of the 10 tokens has pages and no state
    assert cache.match_state(base[:9] + [77, 78]) == ([], 0, None)
    # the longest registered prompt wins, whole pages or not
    longer = base + [50, 51]                # ends on a page boundary
    _register(pool, cache, longer, key)
    pages, n, key2 = cache.match_state(longer + [1, 2, 3])
    assert n == 12 and len(pages) == 3 and key2 != key
    cache.check()


def test_a_sessions_previous_turn_is_the_first_snapshot_to_go():
    pool, cache = _index(3)
    s1, s2 = [1] * 6, [2] * 6
    _register(pool, cache, s1)
    _register(pool, cache, s2)
    _, _, k1 = cache.match_state(s1 + [9])
    _register(pool, cache, s1 + [9, 9], k1)   # s1's turn 2 extends it
    assert next(iter(cache._snaps)) == k1     # cold end, though just used
    _register(pool, cache, [3] * 6)           # needs a row: s1's old turn
    assert k1 not in cache._snaps
    assert cache.match_state(s2 + [9])[2] is not None
    assert cache.match_state(s1 + [9, 9, 9])[1] == 8
    cache.check()


def test_a_prefix_many_extend_stays():
    pool, cache = _index(4)
    system = [7] * 6
    _register(pool, cache, system)
    _, _, key = cache.match_state(system + [1])
    _register(pool, cache, system + [1, 1], key)
    _, _, key = cache.match_state(system + [2])
    _register(pool, cache, system + [2, 2], key)   # a SECOND extension
    assert next(iter(cache._snaps)) != key
    _register(pool, cache, [5] * 6)
    _register(pool, cache, [6] * 6)                 # evicts the oldest
    assert key in cache._snaps
    cache.check()
