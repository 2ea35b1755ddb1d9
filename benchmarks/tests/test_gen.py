"""The traffic generator: every seed gets the same sizes and gaps in the
same order with other tokens, and seeds beyond 2**31 are fine."""

import numpy as np

from traffic import gen

MIX = {"kind": "open_loop", "rate_rps": 10.0, "sharing": "none",
       "prompt_tokens": {"dist": "pareto", "min": 64, "max": 768,
                         "shape": 1.2},
       "output_tokens": {"dist": "pareto", "min": 16, "max": 256,
                         "shape": 1.5},
       "max_total": 1024, "tokens": {"dist": "uniform"}}


def test_same_schedule_for_every_seed_other_tokens():
    a = gen.open_loop_requests(MIX, 1, 30.0, 50257)
    b = gen.open_loop_requests(MIX, 2**31 + 12345, 30.0, 50257)
    assert len(a) == len(b) == 300
    assert [(d, len(p), n) for d, p, n in a] == [
        (d, len(p), n) for d, p, n in b]
    assert [p for _, p, _ in a] != [p for _, p, _ in b]
    assert all(0 <= d < 30.0 for d, _, _ in a)
    assert all(len(p) + n <= 1024 and n >= 1 for _, p, n in a)
    assert a == gen.open_loop_requests(MIX, 1, 30.0, 50257)
    # the one order is the generator's; a mix may name another, and the
    # sizes are the distributions' mid-quantiles whatever the order
    assert a == gen.open_loop_requests(dict(MIX, order_seed=gen.ORDER_SEED),
                                       1, 30.0, 50257)
    other = gen.open_loop_requests(dict(MIX, order_seed=24), 1, 30.0, 50257)
    assert sorted(len(p) for _, p, _ in a) == sorted(
        len(p) for _, p, _ in other)
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in other]
    np.testing.assert_allclose(
        np.sort(np.diff([0.0] + [d for d, _, _ in a])),
        np.sort(np.diff([0.0] + [d for d, _, _ in other])), rtol=1e-9)


def test_no_two_prompts_of_a_run_open_alike():
    reqs = gen.open_loop_requests(MIX, 7, 30.0, 50257)
    firsts = [p[0] for _, p, _ in reqs] + [
        gen.warmup_prompt(MIX, 7, i, 100, 50257)[0] for i in range(200)]
    assert len(set(firsts)) == len(firsts)


def test_packed_batches_are_whole_rows_of_documents():
    mix = {"kind": "packed_steps", "batch": 4, "seq_len": 256,
           "doc_tokens": {"dist": "pareto", "min": 16, "max": 256,
                          "shape": 1.2},
           "docs_per_cycle": 32, "tokens": {"dist": "zipf", "exponent": 1.0}}
    it = gen.packed_batches(mix, 2**32 + 5, 1000)
    b1, b2 = next(it), next(it)
    assert b1["input_ids"].shape == (4, 256)
    assert b1["input_ids"].max() < 1000
    assert not np.array_equal(b1["input_ids"], b2["input_ids"])
    seg, pos, mask = b1["segment_ids"], b1["position_ids"], b1["loss_mask"]
    assert (pos[:, 0] == 0).all() and (seg[:, 0] == 0).all()
    starts = pos == 0
    assert (np.diff(seg, axis=1)[starts[:, 1:]] == 1).all()
    # mask is 0 exactly on a document's last token
    last = np.concatenate([starts[:, 1:], np.ones((4, 1), bool)], axis=1)
    assert ((mask == 0) == last).all()
