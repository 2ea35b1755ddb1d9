"""The window page group (engine/kv_pool.py: `WindowPages`, a `PagePool`
that gives pages back behind the window) alone, and inside the engine
beside the global layers' group: what a request holds, when a page goes
back, that admission, growth, preemption and finish account BOTH groups."""

import dataclasses

import jax
import numpy as np
import pytest

from distributedtraining_tpu.engine import kv_pool, serve
from distributedtraining_tpu.models import afmoe

P, WINDOW, CHUNK = 4, 8, 8


def _group(pool_pages=64, **kw):
    return kv_pool.WindowPages(pool_pages, P, WINDOW, CHUNK, **kw)


def test_page_pool_is_one_class_under_both_names():
    assert serve.PagePool is kv_pool.PagePool
    assert issubclass(kv_pool.WindowPages, kv_pool.PagePool)


def test_table_widths_are_the_windows_not_the_contexts():
    g = _group()
    assert g.table_pages == (WINDOW + CHUNK) // P + 2 == 6
    assert g.decode_pages == 8              # 3 pages, in whole kernel chunks
    big = kv_pool.WindowPages(2, 16, 2048, 1024)
    assert big.table_pages == 194 and big.decode_pages == 136
    assert kv_pool.window_table_pages(2048, 1024, 16) == 194


@pytest.mark.parametrize("context", [1, 7, 8, 9, 12, 13, 300, 32768])
def test_a_decoding_request_holds_the_windows_pages_and_no_more(context):
    """Decoding from position 0: what is held at every step is the pages
    from the first one a query at `newest` still sees to the one `newest`
    is written to; a short request holds ceil(len / P) + the page ahead,
    a long one never more than window / P + 1."""
    g = kv_pool.WindowPages(4096, 16, 2048, 1024)
    assert g.admit(7, 1)
    held = g.held[7]
    released = 0
    step = 1 if context < 100 else 97
    for newest in list(range(0, context, step)) + [context]:
        released += g.release_behind(7, newest)
        assert g.extend(7, newest)
        first = max(0, newest - 2048 + 1) // 16
        assert held.first == first
        assert len(held.pages) == newest // 16 + 1 - first <= 129
        g.check_held([7])
    assert released == held.first
    if context == 300:
        assert len(held.pages) == 19            # ceil(300 / 16) = 19
    if context == 32768:
        assert len(held.pages) == 129           # and not 2,049
    g.release(7)
    g.release(7)                                # a slot released twice
    assert g.free == g.total and not g.held


def test_release_is_by_the_newest_query_not_by_the_write():
    g = _group()
    assert g.admit(1, 20) and g.extend(1, 20)   # pages 0..5
    held = g.held[1]
    assert len(held.pages) == 6 and g.short(1, 23) == 0
    # a query at 20 sees positions 13..20: page 3 on (13 // 4)
    assert g.release_behind(1, 20) == 3 and held.first == 3
    assert g.release_behind(1, 20) == 0
    # the page that holds position `newest - window + 1` stays whole
    assert g.release_behind(1, 23) == 1 and held.first == 4
    g.pools = ("k", "v")
    k, v, tables, starts = g.tail([1], 8, rows=2)
    assert (k, v) == ("k", "v") and tables.shape == (2, 8)
    assert list(starts) == [16, 0]
    assert list(tables[0, :2]) == held.pages and not tables[0, 2:].any()
    assert not tables[1].any()                  # a padding row: the trash page
    assert g.tail([1])[2].shape == (1, g.table_pages)


def test_admission_asks_for_the_most_a_prompt_holds_at_once():
    g = _group(pool_pages=6)                    # 5 pages to hand out
    assert g.admit(1, 3)                        # 1 page
    assert g.admit(2, 16)                       # 5 pages
    assert not g.admit(3, 20)                   # table_pages = 6 > 5
    assert not g.admit(4, 10_000)
    assert set(g.held) == {1, 2}                # a refusal holds nothing
    assert g.extend(2, 16) and g.free == 0
    assert not g.extend(2, 20)                  # nothing left, nothing taken
    assert len(g.held[2].pages) == 5
    with pytest.raises(AssertionError, match="without its slot"):
        g.check_held([2])
    g.release(1)
    g.release(2)
    g.check_held([])


# -- inside the engine -------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    model, cfg = afmoe.make_model("tiny-trinity")
    return model, cfg, model.init_params(jax.random.PRNGKey(0))


def _engine(tiny, **kw):
    model, _, params = tiny
    kw = dict(dict(max_slots=4, page_size=P, max_seq_len=128,
                   max_new_tokens=8, prefill_chunk=CHUNK,
                   debug_invariants=True), **kw)
    return serve.GenerationEngine(model, params, **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def test_pages_behind_the_window_go_back_while_the_global_group_keeps_all(
        tiny):
    eng = _engine(tiny)
    bound = eng._window.table_pages
    assert bound == 6
    req = eng.submit(_prompt(70), 40)
    seen = []
    while not req.done_evt.is_set():
        eng.step()
        for slot in eng._active:
            held = eng._window.held[slot.req.rid]
            seen.append(len(held.pages))
            # the global layer's pages cover every token; the window's
            # start where the newest query's window does
            assert len(slot.pages) >= slot.seq_len // P + 1
            assert held.first >= max(0, slot.seq_len - WINDOW + 1) // P - 1
    assert seen and max(seen) <= WINDOW // P + 2 <= bound
    kv, window, live = eng.kv_holdings()
    assert (kv, window, live) == (0, 0, 0)
    assert eng._window.free == eng._window.total
    assert eng.pool.free == eng.pool.total
    eng.close()


@pytest.mark.parametrize("short", ["kv", "window"])
def test_admission_waits_when_either_group_is_short(tiny, short):
    """Two prompts of 40 tokens; the short group has room for one. The
    second is admitted when the first has finished, and both serve what a
    roomy engine serves."""
    sizes = {"kv": dict(pool_pages=1 + 64 // P),
             "window": dict(window_pool_pages=1 + 7)}[short]
    sizes["max_seq_len"] = 64
    prompts = [_prompt(40, seed=s) for s in (1, 2)]
    roomy = _engine(tiny, max_seq_len=64)
    want = roomy.generate(prompts, 6)
    roomy.close()
    eng = _engine(tiny, **sizes)
    reqs = [eng.submit(p, 6) for p in prompts]
    eng.step()
    assert eng.active_count == 1 and eng.queue_depth == 1
    assert len(eng._window.held) == 1
    while not eng.idle:
        eng.step()
    assert [r.tokens for r in reqs] == want
    assert all(r.status == "done" for r in reqs)
    eng.close()


def test_preemption_and_finish_return_both_groups(tiny):
    """A window pool too small for four growing requests: growth preempts
    the youngest, whose pages of BOTH groups go back; every request still
    serves what a roomy engine serves."""
    prompts = [_prompt(6, seed=s) for s in range(4)]
    roomy = _engine(tiny, max_new_tokens=16)
    want = roomy.generate(prompts, 16)
    roomy.close()
    eng = _engine(tiny, max_new_tokens=16, window_pool_pages=1 + 9)
    reqs = [eng.submit(p, 16) for p in prompts]
    preempted = 0
    while not eng.idle:
        before = {s.req.rid for s in eng._active}
        eng.step()
        gone = before - {s.req.rid for s in eng._active}
        preempted += sum(1 for r in reqs
                         if r.rid in gone and r.status == "queued")
        assert set(eng._window.held) == {s.req.rid for s in eng._active}
    assert preempted > 0
    assert [r.tokens for r in reqs] == want
    assert eng._window.free == eng._window.total
    assert eng.pool.free == eng.pool.total
    eng.close()


def test_counters_say_what_the_groups_hold(tiny):
    from distributedtraining_tpu.utils import obs

    class Sink:
        def write(self, record):
            pass

        def close(self):
            pass

    obs.configure(Sink(), role="server")
    try:
        eng = _engine(tiny)
        eng.generate([_prompt(50)], 8)
        reg = obs.registry()
        assert reg.peek("serve.kv.window.pages_released").value >= 10
        assert reg.peek("serve.kv.window.live_tokens").value == 7 * WINDOW
        held = reg.peek("serve.kv.window.pages_held")
        assert held is not None and reg.peek("serve.kv.pages_held") is not None
        eng.close()
    finally:
        obs.reset()


def test_families_without_the_statement_have_no_window_group():
    from distributedtraining_tpu.models import gpt2
    model, cfg = gpt2.make_model(dataclasses.replace(
        gpt2.PRESETS["tiny"], n_layer=1))
    eng = serve.GenerationEngine(
        model, model.init_params(jax.random.PRNGKey(0)), max_slots=2,
        page_size=8, max_seq_len=32)
    none = eng._window
    assert isinstance(none, kv_pool.NoWindow) and not none.held
    assert none.admit(1, 10_000) and none.extend(1, 10_000)
    assert none.short(1, 5) == none.release_behind(1, 5) == none.free == 0
    assert none.tail([1], 8, 2) == () and none.keep([3]) == [3]
    none.release(1)
    none.check_held([1])
    assert eng._split_behind((1, 2, 3)) == ((), (1, 2, 3))
    assert eng.kv_holdings() == (0, 0, 0)
    eng.generate([[1, 2, 3]], 2)
    assert none.pools == ((), ())
    eng.close()
