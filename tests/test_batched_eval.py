"""Batched cohort evaluation (engine/batched_eval.py).

Three contracts pinned here:

1. PARITY — cohort scores equal the sequential score_miner spelling to fp
   tolerance, including zero-padded slots, the folded-in base, the
   GeneticMerge candidate expansion, and a round with screened-out /
   missing miners mixed in.
2. PIPELINE — stage_cohorts really overlaps staging of cohort n+1 with
   the caller's (device) work on cohort n when pipelined, stages lazily
   in caller order when not, and stops promptly on close().
3. SHARDING — on a mesh the candidate axis SHARDS across devices instead
   of replicating the K x param stack, checked on the placed arrays and
   in the compiled HLO (the test_parameterized_mesh_merge_lowers_to_
   allreduce discipline).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu import delta
from distributedtraining_tpu.chain import LocalChain
from distributedtraining_tpu.data import ByteTokenizer, batch_iterator, text_corpus
from distributedtraining_tpu.engine import (
    BatchedCohortEvaluator, FakeClock, TrainEngine, Validator, stage_cohorts)
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.transport import InMemoryTransport
from distributedtraining_tpu.utils import devprof

SEQ = 32
BATCH = 4


@pytest.fixture(scope="module")
def setup():
    model, cfg = gpt2.make_model("tiny")
    engine = TrainEngine(model, seq_len=SEQ)
    tok = ByteTokenizer()
    val_docs = text_corpus(split="val", n_docs=12, source="synthetic")

    def val_batches():
        return list(batch_iterator(val_docs, tok, batch_size=BATCH,
                                   seq_len=SEQ, max_vocab=cfg.vocab_size))[:3]

    base = model.init_params(jax.random.PRNGKey(0))
    return model, cfg, engine, val_batches, base


def _make_deltas(base, n, scale=0.01):
    leaves, treedef = jax.tree_util.tree_flatten(base)
    key = jax.random.PRNGKey(7)
    out = []
    for _ in range(n):
        key, k = jax.random.split(key)
        ks = jax.random.split(k, len(leaves))
        out.append(jax.tree_util.tree_unflatten(
            treedef, [scale * jax.random.normal(kk, l.shape, l.dtype)
                      for kk, l in zip(ks, leaves)]))
    return out


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def test_cohort_matches_sequential_with_padding(setup):
    """3 candidates in a 4-bucket (one zero-padded slot) + the base folded
    into slot 0: every score equals the one-at-a-time engine.evaluate
    spelling to fp tolerance, and padding perturbs nothing."""
    model, cfg, engine, val_batches, base = setup
    deltas = _make_deltas(base, 3)
    ev = BatchedCohortEvaluator(engine)
    assert ev.bucket_for(len(deltas) + 1) == 4  # base + 3 -> one padded slot

    got = ev.evaluate_cohort(base, deltas, val_batches(), include_base=True)
    assert len(got) == 4

    want = [engine.evaluate(base, val_batches())]
    want += [engine.evaluate(delta.apply_delta(base, d), val_batches())
             for d in deltas]
    for (gl, gp), (wl, wp) in zip(got, want):
        assert gl == pytest.approx(wl, rel=2e-4, abs=1e-6)
        assert gp == pytest.approx(wp, rel=2e-4, abs=1e-6)


def test_cohort_round_dispatch_counts(setup):
    """The K-fold dispatch reduction, counted on the programs' own
    names by the device observatory: K=4 candidates over 3 eval batches
    cost 12 eval-step dispatches one candidate at a time and 3 cohort
    dispatches batched."""
    model, cfg, engine, val_batches, base = setup
    deltas = _make_deltas(base, 4)
    batches = val_batches()
    assert len(batches) == 3
    ev = BatchedCohortEvaluator(engine)

    def calls():
        return {r.prog: r.calls for r in devprof.records()
                if r.prog in ("train.eval", "eval.cohort")}

    devprof.enable()
    try:
        for d in deltas:
            engine.evaluate(delta.apply_delta(base, d), batches)
        assert calls() == {"train.eval": 12}
        ev.evaluate_cohort(base, deltas, batches)
        assert calls() == {"train.eval": 12, "eval.cohort": 3}
    finally:
        devprof.reset()


def test_bucket_ladder():
    class E:  # engine stub: bucket_for touches only .mesh
        mesh = None

    ev = BatchedCohortEvaluator(E())
    assert [ev.bucket_for(k) for k in (1, 2, 3, 5, 8, 9, 16)] == \
        [1, 2, 4, 8, 8, 16, 16]
    assert ev.bucket_for(17) == 32   # beyond the ladder: multiples of 16
    assert ev.bucket_for(33) == 48
    with pytest.raises(ValueError):
        ev.bucket_for(0)


def test_validator_cohort_round_matches_sequential(setup, tmp_path):
    """Full validator round, batched (cohort 4, pipelined) vs sequential
    (cohort 0): identical reasons for the screened-out NaN miner and the
    no-delta hotkeys, and equal scores/losses to fp tolerance for the
    real submissions — padded slots included (2 valid miners in a cohort
    sized 4)."""
    model, cfg, engine, val_batches, base = setup
    transport = InMemoryTransport()
    transport.publish_base(base)
    d1, d2 = _make_deltas(base, 2)
    transport.publish_delta("hotkey_1", d1)
    transport.publish_delta("hotkey_2", d2)
    transport.publish_delta("hotkey_3", jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, jnp.nan), base))  # screened out

    def make(csize, pdepth):
        chain = LocalChain(str(tmp_path / f"c{csize}"), my_hotkey="hotkey_95",
                           epoch_length=0, clock=FakeClock())
        v = Validator(engine, transport, chain, eval_batches=val_batches,
                      cohort_size=csize, pipeline_depth=pdepth)
        v.bootstrap(jax.random.PRNGKey(0))
        return {s.hotkey: s for s in v.validate_and_score()}

    batched = make(4, 1)
    seq = make(0, 0)

    assert set(batched) == set(seq)
    assert batched["hotkey_3"].reason == seq["hotkey_3"].reason == "nonfinite"
    assert batched["hotkey_4"].reason == "no_delta"
    for h in ("hotkey_1", "hotkey_2"):
        assert batched[h].reason == "ok"
        assert batched[h].loss == pytest.approx(seq[h].loss,
                                                rel=2e-4, abs=1e-6)
        assert batched[h].score == pytest.approx(seq[h].score,
                                                 rel=2e-4, abs=2e-4)


def test_genetic_candidate_expansion_matches_weighted_merge(setup):
    """combine_candidate_deltas + evaluate_stacked (GeneticMerge's batched
    population eval) reproduces weighted_merge + engine.evaluate per
    weight vector."""
    model, cfg, engine, val_batches, base = setup
    deltas = _make_deltas(base, 3)
    stacked = delta.stack_deltas(deltas)
    ws = [jnp.asarray(w, jnp.float32) for w in
          ([1.0, 0.0, 0.0], [0.2, 0.5, 0.3], [1 / 3] * 3)]

    cands = delta.combine_candidate_deltas(stacked, jnp.stack(ws))
    ev = BatchedCohortEvaluator(engine)
    got = ev.evaluate_stacked(base, cands, len(ws), val_batches())

    for w, (gl, gp) in zip(ws, got):
        wl, wp = engine.evaluate(delta.weighted_merge(base, stacked, w),
                                 val_batches())
        assert gl == pytest.approx(wl, rel=2e-4, abs=1e-6)
        assert gp == pytest.approx(wp, rel=2e-4, abs=1e-6)


def test_empty_batches_give_nan(setup):
    model, cfg, engine, val_batches, base = setup
    ev = BatchedCohortEvaluator(engine)
    got = ev.evaluate_cohort(base, _make_deltas(base, 2), iter(()))
    assert len(got) == 2 and all(np.isnan(l) and np.isnan(p)
                                 for l, p in got)
    assert ev.evaluate_cohort(base, [], iter(())) == []


# ---------------------------------------------------------------------------
# fetch/eval pipeline
# ---------------------------------------------------------------------------

class _SlowTransport(InMemoryTransport):
    """Fake transport whose per-delta fetch takes ``latency`` seconds —
    the network half of the fetch/eval overlap under test."""

    def __init__(self, latency=0.05):
        super().__init__()
        self.latency = latency
        self.fetched = []

    def fetch_delta_bytes(self, miner_id):
        # the artifact pull fetch_delta_any routes every validation through
        time.sleep(self.latency)
        self.fetched.append((miner_id, time.monotonic()))
        return super().fetch_delta_bytes(miner_id)


def test_stage_cohorts_overlaps_staging_with_eval(setup, tmp_path):
    """With pipeline=True the stager runs AHEAD of the consumer: while the
    consumer still holds cohort 0 (the device-eval phase), the background
    worker has already fetched cohort 1's submissions through the slow
    transport. Event-ordered, not wall-clock-timed, so CI jitter cannot
    flake it."""
    model, cfg, engine, val_batches, base = setup
    transport = _SlowTransport(latency=0.02)
    transport.publish_base(base)
    hotkeys = [f"hotkey_{i}" for i in range(1, 5)]
    for h, d in zip(hotkeys, _make_deltas(base, 4)):
        transport.publish_delta(h, d)
    chain = LocalChain(str(tmp_path), my_hotkey="hotkey_95",
                       epoch_length=0, clock=FakeClock())
    v = Validator(engine, transport, chain, eval_batches=val_batches,
                  cohort_size=2, pipeline_depth=1)
    v.bootstrap(jax.random.PRNGKey(0))

    staged = stage_cohorts(hotkeys, 2, v._stage_miner, pipeline=True, depth=1)
    first = next(staged)
    assert [h for h, d, r in first] == hotkeys[:2]
    assert all(d is not None for _, d, _ in first)
    # consumer has NOT asked for cohort 1 — the worker must fetch it anyway
    deadline = time.monotonic() + 5.0
    while len(transport.fetched) < 4 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(transport.fetched) >= 4, \
        "cohort 1 was not staged while cohort 0 was held by the consumer"
    second = next(staged)
    assert [h for h, d, r in second] == hotkeys[2:]
    staged.close()


def test_stage_cohorts_inline_is_lazy(setup, tmp_path):
    """pipeline=False (the multi-host discipline): staging happens on the
    CONSUMER thread, strictly on demand — after pulling cohort 0 nothing
    of cohort 1 has been fetched, so broadcast collectives inside
    stage_one interleave deterministically with the eval program's."""
    model, cfg, engine, val_batches, base = setup
    transport = _SlowTransport(latency=0.0)
    transport.publish_base(base)
    hotkeys = [f"hotkey_{i}" for i in range(1, 5)]
    for h, d in zip(hotkeys, _make_deltas(base, 4)):
        transport.publish_delta(h, d)
    chain = LocalChain(str(tmp_path), my_hotkey="hotkey_95",
                       epoch_length=0, clock=FakeClock())
    v = Validator(engine, transport, chain, eval_batches=val_batches,
                  cohort_size=2, pipeline_depth=0)
    v.bootstrap(jax.random.PRNGKey(0))

    staged = stage_cohorts(hotkeys, 2, v._stage_miner, pipeline=False)
    next(staged)
    assert [h for h, _ in transport.fetched] == hotkeys[:2]
    next(staged)
    assert [h for h, _ in transport.fetched] == hotkeys


def test_stage_cohorts_close_stops_worker():
    """close() mid-round (a failed validation round) stops the background
    stager promptly instead of letting it drain the whole miner list."""
    staged_items = []
    release = threading.Event()

    def stage_one(x):
        staged_items.append(x)
        release.wait(2.0)
        return x

    staged = stage_cohorts(list(range(8)), 1, stage_one,
                           pipeline=True, depth=1)
    deadline = time.monotonic() + 2.0
    while not staged_items and time.monotonic() < deadline:
        time.sleep(0.005)
    staged.close()
    release.set()
    time.sleep(0.1)
    n = len(staged_items)
    time.sleep(0.1)
    # worker stopped: no further items staged after close settled
    assert len(staged_items) <= n + 1 < 8


def test_stage_cohorts_rejects_bad_cohort_size():
    with pytest.raises(ValueError):
        stage_cohorts([1, 2], 0, lambda x: x)


# ---------------------------------------------------------------------------
# mesh: candidate axis shards, not replicates
# ---------------------------------------------------------------------------

def test_mesh_cohort_shards_candidate_axis(setup, devices):
    """The K x param stack must SHARD over the mesh's merge axis (each
    device holds k_pad/axis_size candidates), the compiled program's only
    collective is the trailing all-gather of per-candidate scalars, and
    the sharded scores still match the single-device engine to fp
    tolerance."""
    from distributedtraining_tpu.parallel import MeshConfig, make_mesh
    from distributedtraining_tpu.parallel.collectives import merge_axis

    model, cfg, engine, val_batches, base = setup
    mesh = make_mesh(MeshConfig(dp=8))
    mesh_engine = TrainEngine(model, mesh=mesh, seq_len=SEQ)
    ev = BatchedCohortEvaluator(mesh_engine)

    deltas = _make_deltas(base, 3)
    # bucket 4 rounds up to a multiple of the 8-way merge axis
    assert ev.bucket_for(len(deltas)) == 8

    placed_base = mesh_engine.place_params(base)
    stacked, k_real = ev.stack_cohort(deltas)
    assert k_real == 3
    axis = merge_axis(mesh)
    for leaf in jax.tree_util.tree_leaves(stacked):
        assert leaf.shape[0] == 8
        # sharded, not replicated: each device holds ONE candidate slice
        shard = leaf.addressable_shards[0]
        assert shard.data.shape[0] == 8 // mesh.shape[axis]

    prog = ev._program()
    placed = ev._place_batch(val_batches()[0])
    txt = prog.lower(placed_base, stacked, placed).compile().as_text()
    assert "all-gather" in txt, \
        "candidate-sharded cohort compiled without the trailing all-gather"

    got = ev.evaluate_stacked(placed_base, stacked, k_real, val_batches())
    want = [engine.evaluate(delta.apply_delta(base, d), val_batches())
            for d in deltas]
    for (gl, gp), (wl, wp) in zip(got, want):
        assert gl == pytest.approx(wl, rel=2e-4, abs=1e-6)
        assert gp == pytest.approx(wp, rel=2e-4, abs=1e-6)
