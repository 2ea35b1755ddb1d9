"""The serve engine one decode program ahead of the host, on the chip:
`gpt2-large` through the Pallas paged decode kernel, a few hundred steps.

tests/test_serve_ahead.py holds the scheduling on the CPU. What only the
chip shows is a QUEUED program taking the donated pools and the picks of
the program before it, through a Mosaic call, while that program may still
be running: the tokens must be those of the same engine made to collect
every program before it builds the next (the order of work before the
mechanism), on the same weights and requests.
"""

import jax
import numpy as np
import pytest

from distributedtraining_tpu.engine import serve
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.utils import obs

SLOTS, P, SEQ = 8, 16, 512
MOSAIC_CALL = "tpu_custom_call"


class _Sink:
    def log(self, rec, **kw):
        pass


def _requests(vocab):
    """(step to submit before, prompt, budget): admissions mid-stream,
    budgets that end on different steps, lengths that cross page rungs."""
    rng = np.random.default_rng(20260929)
    plan = [(0, 40, 150), (0, 55, 60), (7, 33, 120), (30, 60, 90),
            (31, 48, 40), (90, 21, 140), (160, 50, 75)]
    return [(k, rng.integers(0, vocab, n).tolist(), new)
            for k, n, new in plan]


def _serve(model, params, requests, chained: bool):
    obs.configure(_Sink(), role="server")
    eng = serve.GenerationEngine(model, params, max_slots=SLOTS,
                                 page_size=P, max_seq_len=SEQ,
                                 prefix_cache=True)
    if not chained:
        eng._chainable = lambda: False
    try:
        reqs, k = [], 0
        while len(reqs) < len(requests) or not eng.idle:
            reqs += [eng.submit(p, n) for at, p, n in requests if at == k]
            eng.step()
            k += 1
            assert k < 2000
        reg = obs.registry()
        counts = {n: getattr(reg.peek(f"serve.decode.{n}"), "value", 0)
                  for n in ("chained", "collected_first", "rows_dropped")}
        (slots, pages), prog = max(eng._decode_progs.items())
        k_pages, v_pages = eng._kv
        mosaic = prog.lower(
            eng._params, k_pages, v_pages,
            np.zeros((slots, pages), np.int32),
            np.zeros((slots,), np.int32),
            np.zeros((slots,), np.int32)).as_text().count(MOSAIC_CALL)
        assert all(r.status == "done" for r in reqs)
        return [list(r.tokens) for r in reqs], counts, k, mosaic
    finally:
        eng.close()
        obs.reset()


@pytest.fixture(scope="module")
def large():
    model, cfg = gpt2.make_model(gpt2.PRESETS["gpt2-774m"])
    params = model.init_params(jax.random.PRNGKey(7), seq_len=8)
    # the serving tree once, for both engines (`install_params` takes it)
    tree = serve.serve_weights.make(cfg, params)
    del params
    return model, cfg, tree


def test_chained_dispatch_serves_the_unchained_engines_tokens(large):
    model, cfg, tree = large
    requests = _requests(cfg.vocab_size)
    want, plain, _, _ = _serve(model, tree, requests, chained=False)
    got, counts, steps, mosaic = _serve(model, tree, requests, chained=True)
    print(f"tests_tpu: {steps} steps, {counts}, unchained {plain}, "
          f"{mosaic} Mosaic call(s) in the largest decode program")
    assert mosaic >= 1, "the decode program did not take the paged kernel"
    assert plain["chained"] == 0
    assert counts["chained"] >= 200 and counts["rows_dropped"] == 0
    assert [len(t) for t in got] == [n for _, _, n in requests]
    assert got == want
