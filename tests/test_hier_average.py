"""Hierarchical sharded averager (engine/hier_average.py + the packed
accumulate path in delta.py + the cached sharded cohort merge in
parallel/collectives.py).

The parity pins here are the round's acceptance contract: a sub-averager
gathering a MIXED fleet (v1 dense and v2 packed miners) must produce
aggregates identical to the flat merge of the same set; the root's merge
of sub aggregates must equal the flat weighted merge of every miner
within fp tolerance; the packed accumulate must never materialize a
dense M x params stack; and a sub-averager killed mid-publish must
degrade the root to the surviving subtrees, never sink the round.
"""

import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from distributedtraining_tpu import delta as dl
from distributedtraining_tpu.engine.average import (AveragerLoop,
                                                    WeightedAverage)
from distributedtraining_tpu.engine.hier_average import (SubAverager,
                                                         plan_fanout,
                                                         subtree_weights)
from distributedtraining_tpu.engine.ingest import DeltaIngestor
from distributedtraining_tpu.parallel import collectives
from distributedtraining_tpu.parallel.mesh import MeshConfig, make_mesh
from distributedtraining_tpu.transport import base as tbase
from distributedtraining_tpu.transport.chaos import ChaosSpec, ChaosTransport
from distributedtraining_tpu.transport.localfs import LocalFSTransport
from distributedtraining_tpu.transport.memory import InMemoryTransport
from distributedtraining_tpu.transport.retry import RetryPolicy
from distributedtraining_tpu.utils import obs

FAST_RETRY = RetryPolicy(attempts=2, base_delay=0.0, max_delay=0.0,
                         jitter=0.0)


def _tree(seed=0, big=(300, 40), small=(32,)):
    """A delta tree with one above-cutoff tensor (top-k sparsified on the
    v2 wire) and one below-cutoff tensor (dense-form entry)."""
    rs = np.random.RandomState(seed)
    return {"wte": (rs.randn(*big) * 0.01).astype(np.float32),
            "ln": {"g": (rs.randn(*small) * 0.01).astype(np.float32)}}


def _template(tree=None):
    return jax.tree_util.tree_map(
        lambda x: np.zeros(np.shape(x), np.float32), tree or _tree())


def _leaves(t):
    return [np.asarray(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(t)]


def _sub(transport, node, template, assigned, **kw):
    kw.setdefault("retry_policy", FAST_RETRY)
    kw.setdefault("publish_retry", FAST_RETRY)
    kw.setdefault("meta_retry", FAST_RETRY)
    kw.setdefault("ingest_workers", 1)
    return SubAverager(transport, node, template, assigned, **kw)


# ---------------------------------------------------------------------------
# Fanout planning + subtree weights
# ---------------------------------------------------------------------------

def test_plan_fanout_deterministic_balanced_and_total():
    hotkeys = [f"m{i}" for i in range(10)]
    plan = plan_fanout(hotkeys, fanout=4)
    assert sorted(plan) == ["sub0", "sub1", "sub2"]   # ceil(10/4) nodes
    # every miner assigned exactly once, slices balanced to within one
    assigned = [h for slice_ in plan.values() for h in slice_]
    assert sorted(assigned) == sorted(hotkeys)
    sizes = {len(s) for s in plan.values()}
    assert max(sizes) - min(sizes) <= 1
    # deterministic under enumeration order (round-robin over SORTED keys)
    plan2 = plan_fanout(list(reversed(hotkeys)), fanout=4)
    assert plan == plan2
    # explicit node list: every node present even when the fleet shrinks
    plan3 = plan_fanout(["m0"], nodes=["a", "b"])
    assert plan3 == {"a": ["m0"], "b": []}
    with pytest.raises(ValueError):
        plan_fanout(hotkeys)


def test_subtree_weights_mass_and_uniform_fallback():
    w, mass = subtree_weights(["a", "b"], {"a": 3.0, "b": 1.0})
    np.testing.assert_allclose(np.asarray(w), [0.75, 0.25])
    assert mass == 4.0
    # no score mass -> uniform vector, miner-COUNT mass (the spelling
    # under which the root's C_j/sum(C) telescopes to flat uniform 1/M)
    w, mass = subtree_weights(["a", "b", "c"], {})
    np.testing.assert_allclose(np.asarray(w), [1 / 3] * 3)
    assert mass == 3.0
    w, mass = subtree_weights(["a"], {"a": -5.0})
    np.testing.assert_allclose(np.asarray(w), [1.0])
    assert mass == 1.0


def test_normalized_weights_use_unpadded_m():
    """The 1-miner-on-a-mesh edge (satellite pin): weights normalize over
    the REAL m; padding to an axis or bucket adds zero-weight slots that
    change nothing. A normalization over the padded m would publish
    1/axis_size of the update."""
    w = dl.normalized_merge_weights(["only"], {})
    np.testing.assert_array_equal(np.asarray(w), [1.0])
    padded = dl.pad_merge_weights(w, 8)
    assert padded.shape == (8,)
    assert float(padded.sum()) == 1.0      # mass preserved, not 1/8

    base = _tree(99)
    d = _tree(7)
    stacked = dl.pad_stack(dl.stack_deltas([d]), 8)
    assert dl.miner_axis_size(stacked) == 8
    merged = dl.weighted_merge_jit(base, stacked,
                                   dl.pad_merge_weights(w, 8))
    for got, b, x in zip(_leaves(merged), _leaves(base), _leaves(d)):
        np.testing.assert_array_equal(got, b + x)   # exactly base + delta


def test_one_miner_mesh_merge_exact(devices):
    """Same pin through the sharded path: a 1-miner cohort padded to an
    8-wide mesh axis merges to exactly base + delta."""
    collectives.reset_merge_cache()
    base = _tree(1)
    d = _tree(2)
    mesh = make_mesh(MeshConfig(dp=8))
    w = dl.normalized_merge_weights(["only"], None)
    merged = collectives.sharded_cohort_merge(
        base, dl.stack_deltas([d]), w, mesh, axis="dp")
    for got, b, x in zip(_leaves(merged), _leaves(base), _leaves(d)):
        np.testing.assert_allclose(got, b + x, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# Packed accumulate (the merge path that never densifies a stack)
# ---------------------------------------------------------------------------

def test_accumulate_packed_matches_densify_path():
    """The packed scatter-add decodes with the densifier's arithmetic —
    equal to acc + w * densify up to XLA multiply-add fusion (~1 ulp)."""
    delta = _tree(3)
    packed, _ = dl.pack_delta_v2(delta, density=1 / 8)
    packed = jax.device_get(packed)
    acc = _tree(4)
    w = 0.37
    got = dl.accumulate_delta(acc, packed, w)
    dense = dl.densify_packed_v2(packed, _template())
    ref = jax.tree_util.tree_map(
        lambda a, x: a + np.float32(w) * x, acc, dense)
    for g, r in zip(_leaves(got), _leaves(ref)):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-8)


def test_aggregate_deltas_mixed_fleet_matches_flat_merge():
    """A mixed v1-dense + v2-packed cohort aggregates identically to the
    flat weighted merge of the densified set (satellite pin)."""
    dense_deltas = [_tree(i) for i in range(2)]
    packed_deltas = []
    for i in range(2, 4):
        p, _ = dl.pack_delta_v2(_tree(i), density=1 / 8)
        packed_deltas.append(jax.device_get(p))
    mixed = dense_deltas + packed_deltas
    w = dl.normalized_merge_weights(
        ["a", "b", "c", "d"], {"a": 1.0, "b": 2.0, "c": 3.0, "d": 4.0})
    agg = dl.aggregate_deltas(_template(), mixed, w)

    densified = dense_deltas + [dl.densify_packed_v2(p, _template())
                                for p in packed_deltas]
    flat = dl.weighted_merge(_template(), dl.stack_deltas(densified), w)
    for g, r in zip(_leaves(agg), _leaves(flat)):
        np.testing.assert_allclose(g, r, rtol=1e-6, atol=1e-7)


def test_packed_accumulate_never_builds_a_stack_or_densifies(monkeypatch):
    """The acceptance invariant, asserted structurally: aggregating an
    all-packed cohort must touch neither stack_deltas (the M x params
    stack) nor densify_packed_v2 (a dense per-miner copy)."""
    def boom(*a, **k):
        raise AssertionError("packed merge path materialized dense state")

    monkeypatch.setattr(dl, "stack_deltas", boom)
    monkeypatch.setattr(dl, "densify_packed_v2", boom)
    packed = [jax.device_get(dl.pack_delta_v2(_tree(i), density=1 / 8)[0])
              for i in range(6)]
    agg = dl.aggregate_deltas(_template(), packed,
                              np.full((6,), 1 / 6, np.float32))
    assert all(np.isfinite(l).all() for l in _leaves(agg))


# ---------------------------------------------------------------------------
# Cached sharded cohort merge (the pjit'd mesh path)
# ---------------------------------------------------------------------------

def test_sharded_cohort_merge_parity_and_bucket_reuse(devices):
    collectives.reset_merge_cache()
    base = _tree(0)
    deltas = [_tree(i + 1) for i in range(5)]
    w5 = dl.normalized_merge_weights(
        [f"m{i}" for i in range(5)], {f"m{i}": float(i + 1)
                                      for i in range(5)})
    mesh = make_mesh(MeshConfig(dp=8))

    got = collectives.sharded_cohort_merge(
        base, dl.stack_deltas(deltas), w5, mesh, axis="dp")
    ref = collectives.psum_weighted_merge(
        base, dl.stack_deltas(deltas), w5, mesh, axis="dp")
    for a, b in zip(_leaves(got), _leaves(ref)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    # a wobbling cohort (3 then 5 then 7) lands on ONE padded bucket (8
    # on an 8-wide axis) and ONE compiled program — no compile storm
    for m in (3, 7):
        sub = deltas[:m] if m <= len(deltas) else deltas + [
            _tree(10 + i) for i in range(m - len(deltas))]
        wm = dl.normalized_merge_weights([str(i) for i in range(m)], None)
        collectives.sharded_cohort_merge(
            base, dl.stack_deltas(sub), wm, mesh, axis="dp")
    assert len(collectives._MERGE_PROGRAMS) == 1
    seen = {t for (mk, ak, t) in collectives._MERGE_BUCKETS_SEEN
            if mk is mesh}
    assert seen == {8}

    # prefer_compiled: a 9-miner cohort would target 16, but with no 16
    # program compiled and none bigger, it compiles 16; afterwards a
    # 10-miner cohort reuses it instead of minting a new rung
    assert collectives.merge_bucket(9, mesh, "dp") == 16
    collectives.mark_merge_bucket(16, mesh, "dp")
    assert collectives.merge_bucket(10, mesh, "dp") == 16
    collectives.reset_merge_cache()


def test_merge_bucket_ladder_single_device():
    collectives.reset_merge_cache()
    assert collectives.merge_bucket(1) == 1
    assert collectives.merge_bucket(5) == 8
    assert collectives.merge_bucket(17) == 32
    # prefer_compiled pads an uncompiled rung up to a compiled one
    collectives.mark_merge_bucket(8)
    assert collectives.merge_bucket(3) == 8
    assert collectives.merge_bucket(3, prefer_compiled=False) == 4
    collectives.reset_merge_cache()


# ---------------------------------------------------------------------------
# WeightedAverage: weight memoization + packed host lists
# ---------------------------------------------------------------------------

class _Sink:
    def __init__(self):
        self.records = []

    def log(self, rec, step=None):
        self.records.append(rec)


def test_weighted_average_memoizes_consensus_weights():
    obs.configure(_Sink(), role="test")
    try:
        strat = WeightedAverage()
        engine = SimpleNamespace(mesh=None)
        base = _tree(0)
        deltas = [_tree(1), _tree(2)]
        ids = ["a", "b"]
        cons = {"a": 1.0, "b": 3.0}
        m1, w1 = strat.merge(engine, base, list(deltas), ids,
                             consensus=cons)
        assert obs.registry().snapshot().get("merge.weights_reused",
                                             0) == 0
        m2, w2 = strat.merge(engine, base, list(deltas), ids,
                             consensus=dict(cons))   # equal, not identical
        assert obs.registry().snapshot()["merge.weights_reused"] == 1
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        for a, b in zip(_leaves(m1), _leaves(m2)):
            np.testing.assert_array_equal(a, b)
        # a changed score (or cohort) recomputes
        strat.merge(engine, base, list(deltas), ids,
                    consensus={"a": 2.0, "b": 3.0})
        assert obs.registry().snapshot()["merge.weights_reused"] == 1
        np.testing.assert_allclose(np.asarray(w1), [0.25, 0.75])
    finally:
        obs.reset()


def test_weighted_average_merges_packed_host_list():
    strat = WeightedAverage()
    engine = SimpleNamespace(mesh=None)
    base = _tree(0)
    packed = [jax.device_get(dl.pack_delta_v2(_tree(i), density=1 / 8)[0])
              for i in (1, 2)]
    dense = [_tree(3)]
    merged, w = strat.merge(engine, base, packed + dense,
                            ["a", "b", "c"], consensus=None)
    densified = [dl.densify_packed_v2(p, _template()) for p in packed] \
        + dense
    ref = dl.weighted_merge(base, dl.stack_deltas(densified), w)
    for a, b in zip(_leaves(merged), _leaves(ref)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# Agg rider validation
# ---------------------------------------------------------------------------

def test_agg_rider_weight_defensive_parse():
    from distributedtraining_tpu.engine.ingest import _rider_agg_weight

    assert _rider_agg_weight({"agg": {"weight": 4.5}}) == 4.5
    assert _rider_agg_weight({"agg": {"weight": 0}}) == 0.0
    for hostile in (None, {}, {"agg": None}, {"agg": []},
                    {"agg": {"weight": "big"}}, {"agg": {"weight": -1}},
                    {"agg": {"weight": float("nan")}},
                    {"agg": {"weight": float("inf")}},
                    {"agg": {"weight": True}}, {"agg": {}}):
        assert _rider_agg_weight(hostile) is None


def test_ingestor_keeps_packed_form_when_densify_off():
    transport = InMemoryTransport()
    template = _template()
    packed, _ = dl.pack_delta_v2(_tree(5), density=1 / 8)
    from distributedtraining_tpu.engine.publish import DeltaPublisher

    class _R:
        pushes = pushes_failed = pushes_superseded = 0

    pub = DeltaPublisher(transport, "m0", report=_R(),
                         publish_retry=FAST_RETRY, meta_retry=FAST_RETRY,
                         wire_spec={"format": 2, "density": 1 / 8,
                                    "quant": "int8"})
    try:
        assert pub.publish_now(jax.device_get(packed), None, "r1")
        ing = DeltaIngestor(transport, template, workers=1,
                            max_delta_abs=1e3, retry_policy=FAST_RETRY,
                            densify=False)
        try:
            s = ing.stage(["m0"])[0]
            assert s.ok and dl.is_packed_v2(s.delta)
            # and the cache serves the packed form back on a warm round
            s2 = ing.stage(["m0"])[0]
            assert s2.cached and dl.is_packed_v2(s2.delta)
        finally:
            ing.close()
    finally:
        pub.close()


# ---------------------------------------------------------------------------
# SubAverager rounds
# ---------------------------------------------------------------------------

def test_sub_averager_publishes_flat_equivalent_aggregate(tmp_path):
    transport = LocalFSTransport(str(tmp_path))
    transport.publish_base(_tree(100))
    base_rev = transport.base_revision()
    template = _template()

    # mixed fleet: two dense v1 miners, one packed v2 miner
    d0, d1 = _tree(1), _tree(2)
    transport.publish_delta("m0", d0)
    transport.publish_delta("m1", d1)
    p2, _ = dl.pack_delta_v2(_tree(3), density=1 / 8)
    from distributedtraining_tpu.engine.publish import DeltaPublisher

    class _R:
        pushes = pushes_failed = pushes_superseded = 0

    vpub = DeltaPublisher(transport, "m2", report=_R(),
                          publish_retry=FAST_RETRY, meta_retry=FAST_RETRY,
                          wire_spec={"format": 2, "density": 1 / 8,
                                     "quant": "int8"})
    cons = {"m0": 1.0, "m1": 2.0, "m2": 5.0}
    sub = _sub(transport, "n0", template, ["m0", "m1", "m2"],
               consensus=cons)
    try:
        assert vpub.publish_now(jax.device_get(p2), None, base_rev)
        assert sub.run_round() is True
        assert sub.report.last_accepted == 3
        assert sub.report.pushes == 1

        # the aggregate is an ordinary delta under the reserved id
        aid = tbase.agg_id("n0")
        got = transport.fetch_delta(aid, template)
        assert got is not None
        meta = transport.fetch_delta_meta(aid)
        assert meta["agg"]["weight"] == 8.0          # clamped mass
        assert meta["agg"]["miners"] == 3
        assert meta["base_revision"] == base_rev

        d2 = dl.densify_packed_v2(jax.device_get(p2), template)
        w = dl.normalized_merge_weights(["m0", "m1", "m2"], cons)
        ref = dl.weighted_merge(template, dl.stack_deltas([d0, d1, d2]), w)
        for a, b in zip(_leaves(got), _leaves(ref)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    finally:
        sub.close()
        vpub.close()


def test_sub_averager_wire_v2_aggregate_is_lossless(tmp_path):
    """wire_spec=True ships the aggregate itself on the v2 shard wire at
    density 1.0 + quant none — LOSSLESS, so the root decodes the exact
    aggregate tree and parity survives the extra hop."""
    transport = LocalFSTransport(str(tmp_path))
    transport.publish_base(_tree(100))
    template = _template()
    transport.publish_delta("m0", _tree(1))
    sub = _sub(transport, "n0", template, ["m0"], wire_spec=True)
    try:
        assert sub.run_round() is True
        aid = tbase.agg_id("n0")
        ing = DeltaIngestor(transport, template, workers=1,
                            max_delta_abs=1e3, retry_policy=FAST_RETRY)
        try:
            s = ing.stage([aid])[0]
            assert s.ok
            assert s.agg_weight == 1.0
            for a, b in zip(_leaves(s.delta), _leaves(_tree(1))):
                np.testing.assert_allclose(a, b, rtol=0, atol=0)
        finally:
            ing.close()
    finally:
        sub.close()


def test_sub_averager_empty_round_publishes_nothing(tmp_path):
    transport = LocalFSTransport(str(tmp_path))
    transport.publish_base(_tree(100))
    sub = _sub(transport, "n0", _template(), ["ghost0", "ghost1"])
    try:
        assert sub.run_round() is False
        assert transport.delta_revision(tbase.agg_id("n0")) is None
    finally:
        sub.close()


def test_sub_averager_lease_standdown(tmp_path):
    """A sub-averager is just another lease-holding role (PR-6 machinery):
    when a rival holds subavg.<node> at a higher epoch, the round merges
    but publishes nothing."""
    from distributedtraining_tpu.engine.remediate import LeaseManager

    transport = LocalFSTransport(str(tmp_path))
    transport.publish_base(_tree(100))
    transport.publish_delta("m0", _tree(1))
    rival = LeaseManager(transport, "rival", role="subavg.n0")
    assert rival.acquire()
    mine = LeaseManager(transport, "me", role="subavg.n0")
    sub = _sub(transport, "n0", _template(), ["m0"], lease=mine)
    try:
        assert mine.acquire()          # epoch rival+1: now the holder
        assert rival.renew() is False  # rival stands down
        assert sub.run_round() is True
        assert sub.report.pushes == 1  # held lease -> published
        # rival steals the lease back at a higher epoch: next round
        # merges but stands down instead of double-writing the aggregate
        assert rival.acquire()
        assert sub.run_round() is True
        assert sub.report.pushes == 1
        assert sub.report.skipped_publishes == 1
    finally:
        sub.close()


def test_root_ingress_is_one_artifact_per_subtree(tmp_path):
    """What the tree is for, as a count: under a fan-out the root's
    ingress is ONE artifact per sub-averager, whatever the fleet's size
    (a flat averager fetches one per miner), and every miner's artifact
    crosses the wire once, to its own sub-averager."""
    fetched = []

    class CountingFS(LocalFSTransport):
        def fetch_delta_bytes(self, miner_id):
            fetched.append(miner_id)
            return super().fetch_delta_bytes(miner_id)

    transport = CountingFS(str(tmp_path))
    transport.publish_base(_tree(100))
    template = _template()
    hotkeys = [f"m{i}" for i in range(8)]
    for i, h in enumerate(hotkeys):
        transport.publish_delta(h, _tree(i + 1))
    plan = plan_fanout(hotkeys, fanout=4)
    nodes = sorted(plan)
    assert len(nodes) == 2
    subs = [_sub(transport, n, template, plan[n]) for n in nodes]
    root = DeltaIngestor(transport, template, workers=1, cache_bytes=0,
                         max_delta_abs=1e3)
    try:
        for sub in subs:
            assert sub.run_round() is True
        assert sorted(fetched) == hotkeys            # each miner, once
        fetched.clear()
        staged = root.stage([tbase.agg_id(n) for n in nodes])
        assert all(s.ok for s in staged)
        assert sorted(fetched) == sorted(tbase.agg_id(n) for n in nodes)
        # uniform consensus: an aggregate's weight is its subtree's size
        assert sum(s.agg_weight for s in staged) == len(hotkeys)
    finally:
        root.close()
        for sub in subs:
            sub.close()


# ---------------------------------------------------------------------------
# Root round: hierarchy == flat, and degradation under chaos
# ---------------------------------------------------------------------------

def _engine_fixture():
    from distributedtraining_tpu.engine import TrainEngine
    from distributedtraining_tpu.models import gpt2

    model, cfg = gpt2.make_model("tiny")
    return TrainEngine(model, seq_len=16), cfg


def _eval_batches(cfg, n=1):
    rs = np.random.RandomState(0)
    batches = [{"input_ids": rs.randint(0, cfg.vocab_size, (2, 16))
                .astype(np.int32)} for _ in range(n)]

    def factory():
        return iter(list(batches))

    return factory


class _Chain:
    def __init__(self, hotkeys, consensus=None, my_hotkey="avg"):
        self.my_hotkey = my_hotkey
        self._hotkeys = list(hotkeys)
        self._consensus = dict(consensus or {})

    def sync(self):
        return SimpleNamespace(hotkeys=self._hotkeys + [self.my_hotkey])

    def consensus_scores(self):
        return dict(self._consensus)


def _publish_fleet(transport, template, consensus):
    """Six miners: four dense v1, two packed v2 — the mixed fleet."""
    from distributedtraining_tpu.engine.publish import DeltaPublisher

    deltas = {}
    for i in range(4):
        h = f"m{i}"
        deltas[h] = jax.tree_util.tree_map(
            lambda x, s=i: (0.01 * (s + 1)
                            * np.random.RandomState(s).randn(*np.shape(x))
                            ).astype(np.float32), template)
        transport.publish_delta(h, deltas[h])
    for i in range(4, 6):
        h = f"m{i}"
        raw = jax.tree_util.tree_map(
            lambda x, s=i: (0.01 * (s + 1)
                            * np.random.RandomState(s).randn(*np.shape(x))
                            ).astype(np.float32), template)
        packed, _ = dl.pack_delta_v2(raw, density=1 / 8)
        packed = jax.device_get(packed)

        class _R:
            pushes = pushes_failed = pushes_superseded = 0

        pub = DeltaPublisher(transport, h, report=_R(),
                             publish_retry=FAST_RETRY,
                             meta_retry=FAST_RETRY,
                             wire_spec={"format": 2, "density": 1 / 8,
                                        "quant": "int8"})
        try:
            assert pub.publish_now(packed, None, None)
        finally:
            pub.close()
        deltas[h] = dl.densify_packed_v2(packed, template)
    return deltas


def test_hierarchy_parity_with_flat_merge(tmp_path):
    """END-TO-END parity pin (acceptance): fanout-2 hierarchy over a
    mixed 6-miner fleet publishes the same base as the flat single-node
    merge of the identical submissions, within fp tolerance."""
    from distributedtraining_tpu.engine.train import host_wire_template

    engine, cfg = _engine_fixture()
    template = host_wire_template(engine)
    hotkeys = [f"m{i}" for i in range(6)]
    consensus = {h: float(i + 1) for i, h in enumerate(hotkeys)}

    results = {}
    for mode in ("flat", "hier"):
        transport = LocalFSTransport(str(tmp_path / mode))
        chain = _Chain(hotkeys, consensus)
        loop = AveragerLoop(
            engine, transport, chain, WeightedAverage(),
            val_batches=_eval_batches(cfg), publish_policy="always",
            stale_deltas="skip", ingest_workers=1,
            hierarchy=None if mode == "flat" else ["n0", "n1", "n2"])
        loop.bootstrap(rng=jax.random.PRNGKey(0))
        deltas = _publish_fleet(transport, template, consensus)
        subs = []
        try:
            if mode == "hier":
                plan = plan_fanout(hotkeys, nodes=["n0", "n1", "n2"])
                for node, slice_ in plan.items():
                    sub = _sub(transport, node, template, slice_,
                               consensus=consensus)
                    subs.append(sub)
                    assert sub.run_round() is True
            assert loop.run_round() is True
            assert loop.report.last_accepted == (6 if mode == "flat"
                                                 else 3)
            fetched = transport.fetch_base(template)
            assert fetched is not None
            results[mode] = fetched[0]
        finally:
            for sub in subs:
                sub.close()
            loop.close()

    # reference check: the flat merge really is sum (c_i / C) d_i
    for a, b in zip(_leaves(results["flat"]), _leaves(results["hier"])):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_root_degrades_when_sub_killed_mid_publish(tmp_path):
    """ChaosTransport acceptance round: a sub-averager whose publish path
    dies mid-round leaves its OLD aggregate (rider naming the previous
    base) behind; the root's stale skip retires it and the round merges
    the surviving subtrees only."""
    from distributedtraining_tpu.engine.train import host_wire_template

    engine, cfg = _engine_fixture()
    template = host_wire_template(engine)
    hotkeys = [f"m{i}" for i in range(6)]
    consensus = {h: float(i + 1) for i, h in enumerate(hotkeys)}

    inner = LocalFSTransport(str(tmp_path))
    chain = _Chain(hotkeys, consensus)
    loop = AveragerLoop(
        engine, inner, chain, WeightedAverage(),
        val_batches=_eval_batches(cfg), publish_policy="always",
        stale_deltas="skip", ingest_workers=1,
        hierarchy=["n0", "n1"])
    loop.bootstrap(rng=jax.random.PRNGKey(0))
    _publish_fleet(inner, template, consensus)

    plan = plan_fanout(hotkeys, nodes=["n0", "n1"])
    chaos = {node: ChaosTransport(inner, ChaosSpec(), role=node)
             for node in plan}
    subs = {node: _sub(chaos[node], node, template, plan[node],
                       consensus=consensus) for node in plan}
    try:
        for sub in subs.values():
            assert sub.run_round() is True
        assert loop.run_round() is True
        assert loop.report.last_accepted == 2
        base2 = inner.base_revision()
        base2_tree = inner.fetch_base(template)[0]

        # round 2: n0 republishes against the new base; n1's publish path
        # is killed mid-publish (fetches fine, every publish op faults)
        assert subs["n0"].run_round() is True
        chaos["n1"].spec = ChaosSpec(publish_error_rate=1.0)
        assert subs["n1"].run_round() is True     # merged...
        assert subs["n1"].report.pushes_failed >= 1   # ...but not landed
        meta = inner.fetch_delta_meta(tbase.agg_id("n1"))
        assert meta["base_revision"] != base2     # the STALE leftover

        assert loop.run_round() is True
        # the root degraded to the surviving subtree instead of
        # double-applying n1's aggregate-vs-superseded-base
        assert loop.report.last_accepted == 1
        assert loop.report.last_rejected == 1
        # and the published base is exactly base2 + n0's aggregate (the
        # lone survivor carries normalized weight 1.0)
        a0 = inner.fetch_delta(tbase.agg_id("n0"), template)
        base3_tree = inner.fetch_base(template)[0]
        for b3, b2, a in zip(_leaves(base3_tree), _leaves(base2_tree),
                             _leaves(a0)):
            np.testing.assert_allclose(b3, b2 + a, rtol=2e-5, atol=1e-6)
    finally:
        for sub in subs.values():
            sub.close()
        loop.close()


def test_fleet_ledger_tiers_aggregates(tmp_path):
    """The contribution ledger (and fleet_report's tier column) tells
    aggregates from miner deltas."""
    import importlib.util
    import sys

    from distributedtraining_tpu.engine.health import FleetMonitor

    transport = LocalFSTransport(str(tmp_path))
    transport.publish_base(_tree(100))
    transport.publish_delta("m0", _tree(1))
    fm = FleetMonitor(transport)
    sub = _sub(transport, "n0", _template(), ["m0"], fleet=fm)
    try:
        assert sub.run_round() is True
        ing = DeltaIngestor(transport, _template(), workers=1,
                            max_delta_abs=1e3, retry_policy=FAST_RETRY,
                            observer=fm.record_staging)
        try:
            s = ing.stage([tbase.agg_id("n0")])[0]
            assert s.ok
        finally:
            ing.close()
        led = fm.ledger()
        assert led["miner/m0"]["tier"] == "miner"
        agg_key = f"miner/{tbase.agg_id('n0')}"
        assert led[agg_key]["tier"] == "agg"
        assert led[agg_key]["accepted"] == 1

        # fleet_report renders the column (older records default "miner")
        spec = importlib.util.spec_from_file_location(
            "fleet_report", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "scripts", "fleet_report.py"))
        fr = importlib.util.module_from_spec(spec)
        sys.modules.setdefault("fleet_report", fr)
        spec.loader.exec_module(fr)
        assert fr._cell(led[agg_key], "tier") == "agg"
        assert fr._cell({}, "tier") == "miner"
        assert "tier" in fr.COLUMNS
    finally:
        sub.close()
