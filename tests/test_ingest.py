"""Concurrent revision-aware delta ingest (engine/ingest.py).

Pins the ISSUE-4 contracts: the content-addressed host cache (hit on an
unchanged revision, invalidation on a new one, LRU eviction under the
byte budget), batched-screen parity with the per-miner ``screen_delta``,
span-context propagation into the pool's worker threads, and a
concurrent-fetch round trip over the localfs transport that downloads
each artifact exactly once per revision.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu import delta as delta_lib
from distributedtraining_tpu.engine.ingest import (DeltaCache, DeltaIngestor,
                                                   IngestPool, tree_nbytes)
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.transport import (InMemoryTransport,
                                               LocalFSTransport)
from distributedtraining_tpu.utils import obs


@pytest.fixture(scope="module")
def base():
    model, cfg = gpt2.make_model("tiny")
    return model.init_params(jax.random.PRNGKey(0))


def _delta(base, scale, seed=0):
    key = jax.random.PRNGKey(seed)
    leaves, treedef = jax.tree_util.tree_flatten(base)
    ks = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [scale * jax.random.normal(k, l.shape, l.dtype)
                  for k, l in zip(ks, leaves)])


def _host_template(base):
    return jax.tree_util.tree_map(lambda x: np.zeros(x.shape, x.dtype), base)


# ---------------------------------------------------------------------------
# DeltaCache
# ---------------------------------------------------------------------------

def test_cache_hit_on_unchanged_revision(base):
    cache = DeltaCache(1 << 30)
    d = jax.device_get(_delta(base, 0.01))
    cache.put("m0", "rev1", delta=d, reason="ok", cid="m0-000001")
    e = cache.lookup("m0", "rev1")
    assert e is not None and e.reason == "ok" and e.cid == "m0-000001"
    assert e.delta is d
    # a different miner or a different revision is never served
    assert cache.lookup("m1", "rev1") is None
    assert cache.lookup("m0", "rev2") is None


def test_cache_invalidation_on_new_revision(base):
    cache = DeltaCache(1 << 30)
    d1 = jax.device_get(_delta(base, 0.01, seed=1))
    d2 = jax.device_get(_delta(base, 0.02, seed=2))
    cache.put("m0", "rev1", delta=d1)
    before = cache.nbytes
    cache.put("m0", "rev2", delta=d2)   # new push REPLACES the old entry
    assert cache.lookup("m0", "rev1") is None
    assert cache.lookup("m0", "rev2").delta is d2
    assert len(cache) == 1              # one entry per hotkey, ever
    assert cache.nbytes == before       # old bytes released


def test_cache_lru_eviction_under_byte_budget(base):
    d = jax.device_get(_delta(base, 0.01))
    one = tree_nbytes(d)
    cache = DeltaCache(int(2.5 * one))   # room for two entries
    cache.put("m0", "r", delta=d)
    cache.put("m1", "r", delta=d)
    assert cache.lookup("m0", "r") is not None   # m0 is now most-recent
    cache.put("m2", "r", delta=d)                # evicts the LRU = m1
    assert cache.lookup("m1", "r") is None
    assert cache.lookup("m0", "r") is not None
    assert cache.lookup("m2", "r") is not None
    assert cache.nbytes <= cache.max_bytes
    # an entry bigger than the whole budget is refused, not thrashed
    small = DeltaCache(one // 2)
    small.put("m9", "r", delta=d)
    assert small.lookup("m9", "r") is None and small.nbytes == 0


def test_cache_disabled_and_negative_entries(base):
    off = DeltaCache(0)
    off.put("m0", "r", delta=jax.device_get(_delta(base, 0.01)))
    assert off.lookup("m0", "r") is None
    cache = DeltaCache(1 << 20)
    cache.put("m0", "r", delta=None, reason="nonfinite")
    e = cache.lookup("m0", "r")
    assert e.delta is None and e.reason == "nonfinite"
    assert cache.nbytes == 0


# ---------------------------------------------------------------------------
# Batched screening parity
# ---------------------------------------------------------------------------

def test_screen_deltas_parity_with_screen_delta(base):
    host = _host_template(base)
    good = jax.device_get(_delta(base, 0.01, seed=3))
    big = jax.tree_util.tree_map(lambda x: np.full(x.shape, 2e3, x.dtype),
                                 host)
    nan = jax.tree_util.tree_map(
        lambda x: np.full(x.shape, np.nan, x.dtype), host)
    bf16 = jax.tree_util.tree_map(
        lambda x: np.asarray(x, jnp.bfloat16), good)      # wire spelling
    f64 = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float64), good)        # must reject
    wrong_shape = jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape + (1,), x.dtype), host)
    cohort = [good, big, nan, bf16, f64, wrong_shape]
    batched = delta_lib.screen_deltas(cohort, host, max_abs=1e3)
    serial = [delta_lib.screen_delta(d, host, max_abs=1e3) for d in cohort]
    for (bok, brea), (sok, srea) in zip(batched, serial):
        assert bok == sok
        assert brea.split("(")[0] == srea.split("(")[0]
    assert [ok for ok, _ in batched] == [True, False, False, True, False,
                                         False]
    assert batched[1][1].startswith("magnitude_exceeded")
    assert batched[2][1] == "nonfinite"
    assert batched[4][1] == "shape_mismatch"
    assert batched[5][1] == "shape_mismatch"
    # max_abs disabled spellings (None and <= 0) pass the big delta
    for cap in (None, 0):
        assert delta_lib.screen_deltas([big], host, max_abs=cap)[0][0]


def test_screen_deltas_chunking_covers_long_cohorts(base):
    host = _host_template(base)
    cohort = [jax.device_get(_delta(base, 0.01, seed=i)) for i in range(11)]
    cohort[7] = jax.tree_util.tree_map(
        lambda x: np.full(x.shape, np.inf, x.dtype), host)
    out = delta_lib.screen_deltas(cohort, host, max_abs=1e3, chunk=4)
    assert len(out) == 11
    assert [i for i, (ok, _) in enumerate(out) if not ok] == [7]
    assert out[7][1] == "nonfinite"


# ---------------------------------------------------------------------------
# IngestPool
# ---------------------------------------------------------------------------

def test_pool_preserves_order_and_parallelizes():
    pool = IngestPool(4)
    # every call waits here for the other three: the map returns only
    # if all four are in flight at once (the timeout is a hang guard: a
    # pool that ran them one after another breaks the barrier and fails)
    together = threading.Barrier(4)

    def job(x):
        together.wait(timeout=30)
        return x * 2

    try:
        assert pool.map(job, list(range(4))) == [0, 2, 4, 6]
    finally:
        pool.close()


def test_pool_serial_modes_run_inline():
    pool = IngestPool(1)
    main = threading.get_ident()
    seen = []
    assert pool.map(lambda x: seen.append(threading.get_ident()) or x,
                    [1, 2]) == [1, 2]
    assert set(seen) == {main}          # workers==1: no cross-thread hop
    assert pool.map(lambda x: x, [5]) == [5]   # single item: inline too
    assert pool.alive_workers() == 0
    pool.close()


def test_pool_propagates_span_context(tmp_path):
    """Satellite: spans opened inside pool workers keep the submitting
    thread's parent nesting and correlation id (obs.capture_context /
    use_context) — concurrent avg.fetch spans stay joinable on cid."""
    from distributedtraining_tpu.utils.metrics import JSONLSink

    path = str(tmp_path / "spans.jsonl")
    sink = JSONLSink(path)
    obs.configure(sink, role="test")
    pool = IngestPool(3)
    try:
        def inner(i):
            with obs.span(f"inner_{i}"):
                return None

        with obs.correlate("cid-xyz"):
            with obs.span("outer"):
                pool.map(inner, [0, 1])

        def work(i):
            with obs.span("worker_fetch", miner=f"m{i}"):
                return threading.current_thread().name

        with obs.span("outer2"):
            names = pool.map(work, [0, 1, 2])
        assert any(n.startswith("ingest-worker-") for n in names)
    finally:
        pool.close()
        obs.reset()
        sink.close()
    import json
    recs = [json.loads(l) for l in open(path)]
    fetch = [r for r in recs if r.get("span") == "worker_fetch"]
    assert len(fetch) == 3
    for r in fetch:
        assert r["parent"] == "outer2", r   # nesting crossed the thread
        assert r["depth"] == 1, r
    inner = [r for r in recs if str(r.get("span", "")).startswith("inner_")]
    assert inner and all(r.get("cid") == "cid-xyz" for r in inner)


def test_pool_reraises_worker_exception_and_workers_idle_out():
    pool = IngestPool(2, idle_timeout=0.2)

    def boom(x):
        if x == 1:
            raise ValueError("job 1 failed")
        return x

    with pytest.raises(ValueError, match="job 1 failed"):
        pool.map(boom, [0, 1, 2])
    deadline = time.monotonic() + 3.0
    while pool.alive_workers() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert pool.alive_workers() == 0, "workers did not idle out"
    # the pool is reusable after an idle-out AND after close()
    assert pool.map(lambda x: x + 1, [1, 2]) == [2, 3]
    pool.close()
    assert pool.map(lambda x: x, [7, 8]) == [7, 8]
    pool.close()


# ---------------------------------------------------------------------------
# DeltaIngestor round trips
# ---------------------------------------------------------------------------

class _CountingFS(LocalFSTransport):
    """localfs with download/probe accounting and optional fetch latency."""

    def __init__(self, root, latency=0.0):
        super().__init__(root)
        self.latency = latency
        self.downloads = []
        self.stamps = []        # (start, end) of every artifact fetch
        self.probes = 0

    def fetch_delta_bytes(self, miner_id):
        t_in = time.perf_counter()
        try:
            if self.latency:
                time.sleep(self.latency)
            self.downloads.append(miner_id)
            return super().fetch_delta_bytes(miner_id)
        finally:
            self.stamps.append((t_in, time.perf_counter()))

    def most_in_flight(self) -> int:
        """The most fetches open at one instant, by their stamps' order."""
        edges = sorted([(a, 1) for a, _ in self.stamps]
                       + [(b, -1) for _, b in self.stamps])
        most = level = 0
        for _, step in edges:
            level += step
            most = max(most, level)
        return most

    def delta_revision(self, miner_id):
        self.probes += 1
        return super().delta_revision(miner_id)


def _publish_fleet(transport, base, n=4, scale=0.01):
    deltas = []
    for i in range(n):
        d = jax.device_get(_delta(base, scale, seed=10 + i))
        transport.publish_delta(f"m{i}", d)
        transport.publish_delta_meta(
            f"m{i}", {"base_revision": "base-r1", "delta_id": f"m{i}-000001"})
        deltas.append(d)
    return deltas


def test_concurrent_localfs_round_trip_downloads_once_per_revision(
        base, tmp_path):
    host = _host_template(base)
    transport = _CountingFS(str(tmp_path), latency=0.05)
    _publish_fleet(transport, base, n=4)
    ing = DeltaIngestor(transport, host, workers=4, max_delta_abs=1e3)
    try:
        hotkeys = [f"m{i}" for i in range(4)] + ["ghost"]
        staged = ing.stage(hotkeys, base_revision="base-r1")
        assert [s.hotkey for s in staged] == hotkeys          # input order
        assert [s.reason for s in staged] == ["ok"] * 4 + ["no_delta"]
        assert all(s.cid == f"m{i}-000001"
                   for i, s in enumerate(staged[:4]))
        assert sorted(transport.downloads) == ["m0", "m1", "m2", "m3"]
        assert transport.most_in_flight() >= 2, "cold stage not concurrent"
        # -- warm round: revisions unchanged -> ZERO artifact downloads ---
        transport.downloads.clear()
        warm = ing.stage(hotkeys, base_revision="base-r1")
        assert [s.reason for s in warm] == ["ok"] * 4 + ["no_delta"]
        assert all(s.cached for s in warm[:4])
        assert transport.downloads == []
        # byte-identical to the cold round's accepted deltas
        for a, b in zip(staged[:4], warm[:4]):
            assert all(np.array_equal(np.asarray(x), np.asarray(y))
                       for x, y in zip(jax.tree_util.tree_leaves(a.delta),
                                       jax.tree_util.tree_leaves(b.delta)))
        # -- one miner re-pushes: only that artifact is re-downloaded ----
        transport.publish_delta(
            "m2", jax.device_get(_delta(base, 0.03, seed=99)))
        third = ing.stage(hotkeys, base_revision="base-r1")
        assert transport.downloads == ["m2"]
        assert [s.cached for s in third[:4]] == [True, True, False, True]
        assert all(s.reason == "ok" for s in third[:4])
    finally:
        ing.close()


def test_pool_overlaps_fetches_by_stamps(base, tmp_path):
    """Serial ingest (one worker, no cache: the shape of a plain gather
    loop) has at most ONE artifact fetch open at any instant; the pooled
    ingestor has several open together on a cold round, and stages the
    same accepted deltas byte for byte. Read from the start and end
    stamps every fetch records, not from a race of two wall clocks."""
    from distributedtraining_tpu import serialization as ser

    host = _host_template(base)
    transport = _CountingFS(str(tmp_path), latency=0.03)
    _publish_fleet(transport, base, n=4)
    hotkeys = [f"m{i}" for i in range(4)]
    serial = DeltaIngestor(transport, host, workers=1, cache_bytes=0,
                           max_delta_abs=1e3)
    pooled = DeltaIngestor(transport, host, workers=4, max_delta_abs=1e3)
    try:
        staged_serial = serial.stage(hotkeys)
        assert len(transport.stamps) == 4
        assert transport.most_in_flight() == 1
        transport.stamps.clear()
        staged_pooled = pooled.stage(hotkeys)
        assert len(transport.stamps) == 4
        assert transport.most_in_flight() >= 2

        def accepted(staged):
            return [(s.hotkey, ser.to_msgpack(s.delta)) for s in staged
                    if s.delta is not None]

        assert len(accepted(staged_serial)) == 4
        assert accepted(staged_serial) == accepted(staged_pooled)
    finally:
        serial.close()
        pooled.close()


def test_stale_skip_avoids_download_and_recovers(base, tmp_path):
    host = _host_template(base)
    transport = _CountingFS(str(tmp_path))
    _publish_fleet(transport, base, n=2)
    ing = DeltaIngestor(transport, host, stale_deltas="skip", workers=2)
    try:
        # rider names base-r1; the receiver sits at base-r2 -> stale, and
        # the full-model artifact is NEVER downloaded
        staged = ing.stage(["m0", "m1"], base_revision="base-r2")
        assert [s.reason for s in staged] == ["stale_base"] * 2
        assert transport.downloads == []
        # matching base: accepted, fetched now (rider-only entry upgrades)
        staged = ing.stage(["m0", "m1"], base_revision="base-r1")
        assert [s.reason for s in staged] == ["ok"] * 2
        assert sorted(transport.downloads) == ["m0", "m1"]
        # riderless submissions are never stale
        transport.publish_delta(
            "bare", jax.device_get(_delta(base, 0.01, seed=5)))
        (s,) = ing.stage(["bare"], base_revision="base-r2")
        assert s.reason == "ok"
    finally:
        ing.close()


def test_ingestor_isolates_per_miner_failures(base):
    host = _host_template(base)

    class Flaky(InMemoryTransport):
        def fetch_delta_bytes(self, miner_id):
            if miner_id == "cursed":
                raise OSError("transport exploded")
            return super().fetch_delta_bytes(miner_id)

    t = Flaky()
    d = jax.device_get(_delta(base, 0.01))
    t.publish_delta("good", d)
    t.publish_delta("cursed", d)
    ing = DeltaIngestor(t, host, workers=2)
    try:
        staged = ing.stage(["good", "cursed"])
        assert {s.hotkey: s.reason for s in staged} == {
            "good": "ok", "cursed": "fetch_error"}
    finally:
        ing.close()


def test_ingestor_screen_caches_negative_verdicts(base):
    host = _host_template(base)
    t = InMemoryTransport()
    nan = jax.tree_util.tree_map(
        lambda x: np.full(x.shape, np.nan, x.dtype), host)
    t.publish_delta("m0", nan)
    fetches = []
    orig = t.fetch_delta_bytes
    t.fetch_delta_bytes = lambda h: fetches.append(h) or orig(h)
    ing = DeltaIngestor(t, host, workers=1)
    try:
        assert ing.stage(["m0"])[0].reason == "nonfinite"
        assert fetches == ["m0"]
        # same revision: the screened-out verdict is served from cache —
        # a hostile artifact costs one decode per revision, not per round
        assert ing.stage(["m0"])[0].reason == "nonfinite"
        assert fetches == ["m0"]
    finally:
        ing.close()
