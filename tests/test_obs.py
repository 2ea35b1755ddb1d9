"""Round-trip observability layer (utils/obs.py + scripts/obs_report.py).

Covers: span nesting/ordering through the configured sink, histogram
percentiles against the numpy reference, registry name/kind linting,
JSONLSink thread-safety, anomaly triggers arming a TraceCapture exactly
once, TraceCapture arm gating, and the full correlation-id round trip —
a localfs miner -> validator -> averager mini-round whose three JSONL
streams join into one per-delta phase trace via scripts/obs_report.py.
"""

import json
import os
import sys
import threading

import jax
import numpy as np
import pytest

from distributedtraining_tpu.engine import TrainEngine
from distributedtraining_tpu.engine.average import AveragerLoop, WeightedAverage
from distributedtraining_tpu.engine.train import MinerLoop
from distributedtraining_tpu.engine.validate import Validator
from distributedtraining_tpu.chain.local import LocalChain
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.transport import LocalFSTransport
from distributedtraining_tpu.utils import obs
from distributedtraining_tpu.utils.metrics import (InMemorySink, JSONLSink,
                                                   TraceCapture,
                                                   device_metrics,
                                                   live_captures)
from distributedtraining_tpu.utils.obs import AnomalyMonitor, Registry

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import obs_report  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    reg = Registry()
    h = reg.histogram("test.latency_ms")
    rng = np.random.default_rng(0)
    vals = rng.uniform(0.0, 100.0, size=200)
    for v in vals:
        h.observe(float(v))
    p = h.percentiles()
    for q in (50, 95, 99):
        assert p[f"p{q}"] == pytest.approx(np.percentile(vals, q), abs=1e-9)
    assert h.count == 200
    snap = reg.snapshot()
    assert snap["test.latency_ms.count"] == 200.0
    assert snap["test.latency_ms.p95"] == p["p95"]


def test_histogram_ring_is_bounded():
    h = Registry().histogram("test.h")
    for v in range(10_000):
        h.observe(float(v))
    assert h.count == 10_000
    assert len(h._ring) == h.capacity
    # percentiles reflect the most recent window only
    assert h.percentiles()["p50"] >= 10_000 - h.capacity


def test_metric_name_lint():
    reg = Registry()
    for bad in ("Bad", "a-b", "a b", "", "UPPER.case", "x/y"):
        with pytest.raises(ValueError):
            reg.counter(bad)
    reg.counter("ok.name_1")  # valid
    # duplicate registration under a different kind is rejected
    with pytest.raises(ValueError):
        reg.histogram("ok.name_1")
    # get-or-create under the SAME kind returns the same instrument
    assert reg.counter("ok.name_1") is reg.counter("ok.name_1")


def test_registry_flush_to_sink():
    reg = Registry()
    reg.counter("c.x").inc(3)
    reg.histogram("h.y").observe(2.0)
    sink = InMemorySink()
    snap = reg.flush_to(sink, step=7)
    assert snap["c.x"] == 3.0
    assert sink.records[-1]["step"] == 7
    assert sink.records[-1]["h.y.count"] == 1.0


def test_module_helpers_noop_when_disabled():
    obs.count("x.y", 2)
    obs.observe("x.z", 1.0)
    with obs.span("x.phase"):
        pass
    assert not obs.dirty()  # nothing recorded, nothing configured


def test_registry_cardinality_cap_drops_new_names():
    reg = Registry(max_names=3)
    reg.counter("a").inc()
    reg.histogram("b").observe(1.0)
    reg.gauge("c").set(5.0)
    # past the cap: fully-usable DETACHED instruments, never snapshotted
    dropped = reg.counter("d")
    dropped.inc(99)
    reg.histogram("e").observe(1.0)
    assert len(reg) == 3
    assert reg.dropped_names == 2
    assert set(reg.names()) == {"a", "b", "c"}
    assert "d" not in reg.snapshot()
    # existing names keep working at the cap
    assert reg.counter("a") is reg.counter("a")
    with pytest.raises(ValueError):
        Registry(max_names=0)


def test_registry_merge_folds_all_instrument_kinds():
    a, b = Registry(), Registry()
    a.counter("c").inc(2)
    b.counter("c").inc(3)
    b.counter("only_b").inc(1)
    a.gauge("g").set(1.0)
    b.gauge("g").set(7.0)
    a.histogram("h").observe(1.0)
    b.histogram("h").observe(3.0)
    b.histogram("h").observe(5.0)
    out = a.merge(b)
    assert out is a
    snap = a.snapshot()
    assert snap["c"] == 5.0                  # counters add
    assert snap["only_b"] == 1.0             # new names materialize
    assert snap["g"] == 7.0                  # gauges: last-merged-wins
    assert snap["h.count"] == 3.0 and snap["h.sum"] == 9.0
    assert a.histogram("h").percentiles()["p50"] == 3.0
    # kind mismatch is the usual duplicate-registration lint
    c = Registry()
    c.histogram("c")
    with pytest.raises(ValueError):
        c.merge(a)
    # merging into a capped registry drops-and-counts past the cap
    capped = Registry(max_names=1)
    capped.merge(a)
    assert len(capped) == 1 and capped.dropped_names >= 1


def test_registry_peek_never_creates():
    reg = Registry()
    assert reg.peek("ghost") is None
    assert len(reg) == 0
    h = reg.histogram("h")
    assert reg.peek("h") is h


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_span_nesting_ordering_and_cid_inheritance():
    sink = InMemorySink()
    obs.configure(sink, role="tester")
    with obs.span("outer", cid="cid-1", foo="bar"):
        with obs.span("inner"):
            pass
    spans = [r for r in sink.records if "span" in r]
    assert [s["span"] for s in spans] == ["inner", "outer"]  # exit order
    inner, outer = spans
    assert inner["parent"] == "outer" and inner["depth"] == 1
    assert "parent" not in outer and outer["depth"] == 0
    assert inner["cid"] == "cid-1"  # inherited from the enclosing span
    assert outer["cid"] == "cid-1" and outer["foo"] == "bar"
    assert outer["role"] == inner["role"] == "tester"
    assert outer["dur_ms"] >= inner["dur_ms"]
    assert outer["t0"] <= inner["t0"]
    # span latencies also land in the registry
    assert obs.registry().histogram("span.outer_ms").count == 1


def test_span_records_error_flag():
    sink = InMemorySink()
    obs.configure(sink)
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    rec = [r for r in sink.records if r.get("span") == "boom"][0]
    assert rec["error"] is True


# ---------------------------------------------------------------------------
# phase: the hot-loop primitive beside span
# ---------------------------------------------------------------------------

class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: keeps, per thread, the
    order in which names were entered and left."""

    log: list = []
    made: list = []     # (name, arguments) of every annotation built

    def __init__(self, name, **args):
        self.name = name
        _FakeAnnotation.made.append((name, args))

    def __enter__(self):
        _FakeAnnotation.log.append(
            (threading.get_ident(), "enter", self.name))

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(
            (threading.get_ident(), "exit", self.name))


@pytest.fixture()
def fake_annotation(monkeypatch):
    _FakeAnnotation.log = []
    _FakeAnnotation.made = []
    monkeypatch.setattr(obs, "_trace_annotation", lambda: _FakeAnnotation)
    return _FakeAnnotation.log


def test_phase_disabled_is_one_shared_noop(fake_annotation):
    a, b = obs.phase("serve.admit"), obs.phase("serve.step", hist="x_ms",
                                               rid="rq-1")
    assert a is b                       # no allocation per call site
    with a as got:
        assert got is None
    assert not obs.dirty() and len(obs.registry()) == 0
    assert fake_annotation == []
    # disabled, not even the name is looked at
    with obs.phase("Not A Name"):
        pass


def test_phase_timed_gives_the_time_with_obs_off_and_on(fake_annotation):
    """A site that needs the time either way (`step()`'s `step_ms`, the
    request trace's prefill stage) reads it off the phase: off, the clock
    pair alone; on, the number the histogram was fed."""
    with obs.phase("serve.prefill", timed=True) as off:
        pass
    assert off is not obs.phase("serve.prefill")     # not the shared no-op
    assert off.dur_ms >= 0.0
    assert not obs.dirty() and len(obs.registry()) == 0
    assert fake_annotation == []
    reg = obs.configure(InMemorySink(), role="server")
    with obs.phase("serve.prefill", timed=True) as on:
        pass
    assert reg.peek("serve.prefill_ms").total == pytest.approx(on.dur_ms)
    assert [e[1:] for e in fake_annotation] == [
        ("enter", "serve.prefill"), ("exit", "serve.prefill")]


def test_phase_enabled_feeds_its_histogram_and_nothing_else(fake_annotation):
    sink = InMemorySink()
    reg = obs.configure(sink, role="server")
    with obs.phase("serve.admit"):
        with obs.phase("serve.prefill"):
            pass
    with obs.phase("serve.decode.fetch", hist="serve.wait_ms"):
        pass
    assert reg.names() == ["serve.admit_ms", "serve.prefill_ms",
                           "serve.wait_ms"]
    assert all(reg.peek(n).count == 1 for n in reg.names())
    assert reg.peek("serve.admit_ms").total >= \
        reg.peek("serve.prefill_ms").total
    assert sink.records == []           # nothing per close
    obs.flush()                         # the registry at the role's cadence
    assert sink.records[0]["serve.admit_ms.count"] == 1.0
    me = threading.get_ident()
    assert fake_annotation == [
        (me, "enter", "serve.admit"), (me, "enter", "serve.prefill"),
        (me, "exit", "serve.prefill"), (me, "exit", "serve.admit"),
        (me, "enter", "serve.decode.fetch"),
        (me, "exit", "serve.decode.fetch")]


def test_phase_hands_its_arguments_to_the_annotation(fake_annotation):
    """What ties a span to a request or a shape rides as annotation
    arguments: built into the annotation when enabled, dropped unread
    when disabled (a timed phase included)."""
    with obs.phase("serve.prefill", timed=True, rid="rq-1", bucket=128):
        pass
    assert _FakeAnnotation.made == []
    obs.configure(InMemorySink(), role="server")
    with obs.phase("serve.prefill", timed=True, rid="rq-1", bucket=128):
        pass
    with obs.phase("serve.grow"):
        pass
    assert _FakeAnnotation.made == [
        ("serve.prefill", {"rid": "rq-1", "bucket": 128}),
        ("serve.grow", {})]


def test_phase_nests_per_thread(fake_annotation):
    """Two threads inside the same phases at once: every thread's enters
    and exits pair up last-in-first-out, and the shared histograms count
    both."""
    reg = obs.configure(InMemorySink(), role="server")
    inside = threading.Barrier(2, timeout=10)

    def work():
        with obs.phase("serve.step"):
            with obs.phase("serve.decode.fetch"):
                inside.wait()           # both threads are in both phases

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert reg.peek("serve.step_ms").count == 2
    assert reg.peek("serve.decode.fetch_ms").count == 2
    for ident in {e[0] for e in fake_annotation}:
        mine = [e[1:] for e in fake_annotation if e[0] == ident]
        assert mine == [("enter", "serve.step"),
                        ("enter", "serve.decode.fetch"),
                        ("exit", "serve.decode.fetch"),
                        ("exit", "serve.step")]


def test_phase_lints_a_name_once_and_survives_without_jax(monkeypatch):
    obs.configure(InMemorySink(), role="server")
    with pytest.raises(ValueError):
        obs.phase("Bad Name")
    with pytest.raises(ValueError):
        obs.phase("ok.name", hist="Bad Hist")
    seen = []
    real = obs.check_metric_name
    monkeypatch.setattr(obs, "check_metric_name",
                        lambda n: seen.append(n) or real(n))
    obs._phase_hist.cache_clear()
    monkeypatch.setattr(obs, "_trace_annotation", lambda: None)  # no jax
    for _ in range(3):
        with obs.phase("serve.grow"):
            pass
    assert seen.count("serve.grow") == 1    # Histogram() lints its own too
    assert obs.registry().peek("serve.grow_ms").count == 3


def test_phase_observes_when_its_body_raises(fake_annotation):
    reg = obs.configure(InMemorySink(), role="server")
    with pytest.raises(StopIteration):
        with obs.phase("serve.admit"):
            next(iter(()))
    assert reg.peek("serve.admit_ms").count == 1
    assert [e[1] for e in fake_annotation] == ["enter", "exit"]


def _frozen_span_records(monkeypatch):
    """The records two nested spans and a failing one write, clocks
    frozen: (JSON lines, histogram counts)."""
    ticks = iter(range(100, 200))
    monkeypatch.setattr(obs.time, "time", lambda: 1700000000.25)
    monkeypatch.setattr(obs.time, "perf_counter",
                        lambda: next(ticks) / 8.0)
    sink = InMemorySink()
    reg = obs.configure(sink, role="miner")
    with obs.span("push.snapshot", cid="m0-000001"):
        with obs.span("push.upload", bytes=12, ok=True):
            pass
    with pytest.raises(KeyError):
        with obs.span("avg.fetch", miner="m1"):
            raise KeyError("gone")
    monkeypatch.undo()
    return ([json.dumps(r) for r in sink.records],
            {n: reg.peek(n).count for n in reg.names()})


def test_span_record_is_byte_for_byte_what_it_was(monkeypatch,
                                                  fake_annotation):
    """The annotation a span holds since PR 36 adds nothing to its record,
    its histogram or its nesting (the lines below: the parent commit's
    obs.span under the same frozen clocks)."""
    lines, counts = _frozen_span_records(monkeypatch)
    assert lines == [
        '{"step": null, "span": "push.upload", "dur_ms": 125.0, '
        '"t0": 1700000000.25, "depth": 1, "role": "miner", '
        '"parent": "push.snapshot", "cid": "m0-000001", "bytes": 12, '
        '"ok": true}',
        '{"step": null, "span": "push.snapshot", "dur_ms": 375.0, '
        '"t0": 1700000000.25, "depth": 0, "role": "miner", '
        '"cid": "m0-000001"}',
        '{"step": null, "span": "avg.fetch", "dur_ms": 125.0, '
        '"t0": 1700000000.25, "depth": 0, "role": "miner", "error": true, '
        '"miner": "m1"}']
    assert counts == {"span.avg.fetch_ms": 1, "span.push.snapshot_ms": 1,
                      "span.push.upload_ms": 1}


def test_span_opens_one_annotation_while_a_sink_is_on_and_none_off(
        fake_annotation):
    """`obs.span` is on the profiler's clock: the annotation a phase opens
    (one helper), on the opening thread's line, with the span's cid; a
    raising body closes it; off, nothing is built."""
    with obs.span("push.snapshot", cid="m0-000001"):
        pass
    assert fake_annotation == [] and _FakeAnnotation.made == []
    obs.configure(InMemorySink(), role="miner")
    with obs.span("push.snapshot", cid="m0-000001"):
        with obs.span("push.upload"):       # inherits the cid
            pass
    with pytest.raises(KeyError):
        with obs.span("avg.fetch"):
            raise KeyError("gone")
    assert _FakeAnnotation.made == [
        ("push.snapshot", {"cid": "m0-000001"}),
        ("push.upload", {"cid": "m0-000001"}),
        ("avg.fetch", {})]
    me = threading.get_ident()
    assert fake_annotation == [
        (me, "enter", "push.snapshot"), (me, "enter", "push.upload"),
        (me, "exit", "push.upload"), (me, "exit", "push.snapshot"),
        (me, "enter", "avg.fetch"), (me, "exit", "avg.fetch")]


def test_span_and_phase_share_a_thread_line(fake_annotation):
    """A worker's span lies on the worker's line, beside the phases the
    train thread writes meanwhile: `push.upload` next to `miner.*`."""
    obs.configure(InMemorySink(), role="miner")

    def worker():
        with obs.correlate("m0-000002"):
            with obs.span("push.upload"):
                pass

    with obs.phase("miner.actions"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
    me = threading.get_ident()
    assert [e[1:] for e in fake_annotation if e[0] == me] == [
        ("enter", "miner.actions"), ("exit", "miner.actions")]
    assert [e[1:] for e in fake_annotation if e[0] != me] == [
        ("enter", "push.upload"), ("exit", "push.upload")]
    assert ("push.upload", {"cid": "m0-000002"}) in _FakeAnnotation.made


def test_correlate_is_thread_local():
    sink = InMemorySink()
    obs.configure(sink)
    seen = {}

    def worker():
        seen["worker_cid"] = obs.current_cid()
        with obs.correlate("w-1"):
            with obs.span("w.phase"):
                pass

    with obs.correlate("main-1"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert obs.current_cid() == "main-1"
    assert seen["worker_cid"] is None  # main's cid never leaked across
    rec = [r for r in sink.records if r.get("span") == "w.phase"][0]
    assert rec["cid"] == "w-1"


# ---------------------------------------------------------------------------
# JSONLSink thread-safety (PR satellite)
# ---------------------------------------------------------------------------

def test_jsonl_sink_concurrent_writers_no_torn_lines(tmp_path):
    path = tmp_path / "metrics.jsonl"
    sink = JSONLSink(str(path))
    n_threads, n_records = 8, 200

    def writer(tid):
        for i in range(n_records):
            sink.log({"tid": tid, "i": i, "pad": "x" * 64})

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.close()
    lines = path.read_text().splitlines()
    assert len(lines) == n_threads * n_records
    recs = [json.loads(line) for line in lines]  # every line parses whole
    per_tid = {}
    for r in recs:
        per_tid.setdefault(r["tid"], []).append(r["i"])
    for tid, seq in per_tid.items():
        assert seq == list(range(n_records))  # per-writer order preserved


def test_jsonl_sink_lazy_file_creation(tmp_path):
    path = tmp_path / "lazy.jsonl"
    sink = JSONLSink(str(path))
    assert not path.exists()  # no file until the first record
    sink.log({"a": 1})
    assert path.exists()
    sink.close()


# ---------------------------------------------------------------------------
# Anomaly triggers + TraceCapture arming
# ---------------------------------------------------------------------------

class _StubCapture:
    def __init__(self):
        self.arm_calls = 0
        self.ticks = 0
        self.closed = False

    def arm(self):
        self.arm_calls += 1

    def tick(self):
        self.ticks += 1

    def close(self):
        self.closed = True


def test_anomaly_loss_spike_arms_capture_exactly_once():
    cap = _StubCapture()
    mon = AnomalyMonitor(cap, loss_warmup=2, push_failure_streak=2)
    for _ in range(3):
        mon.observe_loss(1.0)
    assert mon.triggered is None
    mon.observe_loss(10.0)  # > 2x EMA
    assert mon.triggered == "loss_spike"
    assert cap.arm_calls == 1
    # later anomalies of ANY kind never re-arm
    mon.observe_loss(100.0)
    mon.observe_push_counters(0, 5)
    mon.observe_loss(float("nan"))
    assert cap.arm_calls == 1
    assert mon.triggered == "loss_spike"  # first reason wins


def test_anomaly_push_failure_streak():
    cap = _StubCapture()
    mon = AnomalyMonitor(cap, push_failure_streak=3)
    mon.observe_push_counters(pushes=1, failed=1)
    mon.observe_push_counters(pushes=2, failed=1)  # success resets streak
    mon.observe_push_counters(pushes=2, failed=2)
    mon.observe_push_counters(pushes=2, failed=3)
    assert mon.triggered is None
    mon.observe_push_counters(pushes=2, failed=4)
    assert mon.triggered == "push_failure_streak"
    assert cap.arm_calls == 1


def test_anomaly_step_time_p99_blowout():
    cap = _StubCapture()
    mon = AnomalyMonitor(cap, step_warmup=64, check_every=32,
                         step_p99_factor=8.0)
    for _ in range(63):
        mon.observe_step_ms(1.0)
    assert mon.triggered is None
    for _ in range(33):  # p99 >> 8x p50 once the check lands
        mon.observe_step_ms(500.0)
    assert mon.triggered == "step_time_p99"
    assert cap.arm_calls == 1


def test_anomaly_nonfinite_loss_triggers():
    mon = AnomalyMonitor(None)  # capture-less monitor: detection only
    mon.observe_loss(float("inf"))
    assert mon.triggered == "loss_nonfinite"


class _FakeProfiler:
    def __init__(self):
        self.started = []
        self.stopped = 0

    def start_trace(self, d):
        self.started.append(d)

    def stop_trace(self):
        self.stopped += 1


class _FakeJax:
    def __init__(self):
        self.profiler = _FakeProfiler()


def test_tracecapture_arm_gating(tmp_path):
    cap = TraceCapture(str(tmp_path / "tr"), steps=2, skip=1, arm=False)
    cap._jax = _FakeJax()  # never touch the real profiler in tests
    for _ in range(10):
        cap.tick()  # disarmed: free no-ops
    assert not cap._jax.profiler.started and not cap._done
    cap.arm()
    assert cap.armed
    cap.tick()                       # skip window
    assert not cap._jax.profiler.started
    cap.tick()                       # starts
    assert cap._jax.profiler.started == [str(tmp_path / "tr")]
    assert cap in live_captures()
    cap.tick()                       # in-window
    cap.tick()                       # stops (seen > skip + steps)
    assert cap._jax.profiler.stopped == 1 and cap._done
    assert cap not in live_captures()
    cap.arm()                        # a finished capture can never re-arm
    cap.tick()
    assert cap._jax.profiler.stopped == 1
    assert len(cap._jax.profiler.started) == 1


def test_tracecapture_default_is_armed(tmp_path):
    cap = TraceCapture(str(tmp_path / "tr"), steps=1, skip=0)
    cap._jax = _FakeJax()
    cap.tick()
    assert cap._jax.profiler.started  # legacy behavior: live immediately
    cap.close()
    assert cap._jax.profiler.stopped == 1


def test_device_metrics_cached_psutil_state():
    a = device_metrics()
    b = device_metrics()
    assert "chain_abandoned_workers" in a
    # psutil ships in this image; the cached-state path must keep serving
    if "rss_mb" in a:
        assert "rss_mb" in b and b["rss_mb"] > 0


# ---------------------------------------------------------------------------
# obs_report joining
# ---------------------------------------------------------------------------

def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_obs_report_joins_three_streams(tmp_path):
    cid = "hotkey_0-000001"
    _write_jsonl(tmp_path / "miner.jsonl", [
        {"ts": 1.0, "train_loss": 3.2},  # non-span records are ignored
        {"span": "push.snapshot", "cid": cid, "dur_ms": 5.0, "t0": 100.0},
        {"span": "push.upload", "cid": cid, "dur_ms": 50.0, "t0": 100.01},
    ])
    _write_jsonl(tmp_path / "validator.jsonl", [
        {"span": "val.fetch", "cid": cid, "dur_ms": 8.0, "t0": 101.0},
        {"span": "val.screen", "cid": cid, "dur_ms": 2.0, "t0": 101.01},
        {"span": "val.cohort_eval", "cids": [cid, "other-000007"],
         "dur_ms": 30.0, "t0": 102.0},
    ])
    _write_jsonl(tmp_path / "averager.jsonl", [
        {"span": "avg.merge", "cids": [cid], "dur_ms": 20.0, "t0": 110.0},
    ])
    rep = obs_report.report([str(tmp_path / f) for f in
                             ("miner.jsonl", "validator.jsonl",
                              "averager.jsonl")])
    tr = rep["deltas"][cid]
    assert set(tr["phases_ms"]) == {"snapshot", "upload", "fetch", "screen",
                                    "eval", "merge"}
    assert tr["phases_ms"]["upload"] == pytest.approx(50.0)
    assert tr["phases_ms"]["eval"] == pytest.approx(30.0)
    assert tr["shared_by"]["eval"] == 2  # cohort program shared by 2 cids
    assert tr["roundtrip_s"] == pytest.approx(110.02 - 100.0, abs=1e-3)
    # the cohort-mate got its own (eval-only) trace
    assert "other-000007" in rep["deltas"]
    table = obs_report.format_table(rep)
    assert cid in table and "roundtrip_s" in table


def test_obs_report_tolerates_torn_tail(tmp_path):
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"span": "push.upload", "cid": "c-1",
                            "dur_ms": 1.0, "t0": 1.0}) + "\n")
        f.write('{"span": "push.m')  # crashed writer's torn last line
    rep = obs_report.report([str(p)])
    assert list(rep["deltas"]) == ["c-1"]


# ---------------------------------------------------------------------------
# Correlation round trip: localfs miner -> validator -> averager
# ---------------------------------------------------------------------------

def _batch(cfg, n=2, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": np.asarray(
        rng.integers(0, cfg.vocab_size, (n, seq)), np.int32)}


def test_correlation_id_roundtrip_localfs(tmp_path):
    model, cfg = gpt2.make_model("tiny")
    transport = LocalFSTransport(str(tmp_path / "artifacts"))
    chain_dir = str(tmp_path / "chain")
    batch = _batch(cfg)

    def eval_batches():
        yield _batch(cfg, seed=1)

    paths = {r: str(tmp_path / f"{r}.jsonl")
             for r in ("miner", "validator", "averager")}

    # -- miner: train a few steps, push with a correlation id --------------
    sink = JSONLSink(paths["miner"])
    obs.configure(sink, role="miner")
    try:
        loop = MinerLoop(TrainEngine(model, seq_len=16), transport,
                         "hotkey_0", send_interval=1e9,
                         check_update_interval=1e9, metrics=sink,
                         log_every=2)
        loop.bootstrap(jax.random.PRNGKey(0))
        loop.run(iter([batch] * 3), max_steps=3)
        loop.flush()  # the push: snapshot/upload spans + delta_id rider
        assert loop.report.pushes == 1
    finally:
        obs.reset()
        sink.close()

    meta = transport.fetch_delta_meta("hotkey_0")
    cid = obs.rider_delta_id(meta)
    assert cid == "hotkey_0-000001"

    # -- validator: cohort-scores the delta, spans tagged with the cid -----
    sink = JSONLSink(paths["validator"])
    obs.configure(sink, role="validator")
    try:
        val = Validator(TrainEngine(model, seq_len=16), transport,
                        LocalChain(chain_dir, my_hotkey="hotkey_91"),
                        eval_batches=eval_batches, metrics=sink,
                        cohort_size=8, pipeline_depth=1)
        val.bootstrap(rng=jax.random.PRNGKey(0))
        results = val.validate_and_score()
        assert any(s.hotkey == "hotkey_0" and s.loss is not None
                   for s in results)
    finally:
        obs.reset()
        sink.close()

    # -- averager: merges it, the merge span records the cid ---------------
    sink = JSONLSink(paths["averager"])
    obs.configure(sink, role="averager")
    try:
        avg = AveragerLoop(TrainEngine(model, seq_len=16), transport,
                           LocalChain(chain_dir, my_hotkey="hotkey_99"),
                           WeightedAverage(uniform=True),
                           val_batches=eval_batches, metrics=sink)
        avg.bootstrap(rng=jax.random.PRNGKey(0))
        assert avg.run_round() is True
        assert avg.report.last_accepted == 1
    finally:
        obs.reset()
        sink.close()

    # -- join: one trace covering the artifact's whole life ----------------
    rep = obs_report.report(list(paths.values()))
    assert cid in rep["deltas"], rep["deltas"].keys()
    phases = rep["deltas"][cid]["phases_ms"]
    for phase in ("snapshot", "upload", "fetch", "screen", "eval", "merge"):
        assert phase in phases, f"missing {phase}: {phases}"
    assert rep["deltas"][cid]["roundtrip_s"] >= 0
    # per-role roles tagged correctly in the raw records
    recs = obs_report.load_records([paths["validator"]])
    vs = [r for r in recs if r.get("span") == "val.fetch"
          and r.get("cid") == cid]
    assert vs and vs[0]["role"] == "validator"
    # the averager's metrics record names which delta ids entered the merge
    arecs = obs_report.load_records([paths["averager"]])
    merged_ids = [r["merge_delta_ids"] for r in arecs
                  if "merge_delta_ids" in r]
    assert merged_ids and merged_ids[-1] == {"hotkey_0": cid}


# ---------------------------------------------------------------------------
# Doc-drift lint: every dt_* name the exporter can emit is documented
# ---------------------------------------------------------------------------

def test_every_exporter_metric_name_is_documented():
    """Doc-drift lint (PR-13 satellite, the metric twin of the
    EVENT_KINDS/devprof producer-lint discipline): every dt_* metric
    name the Prometheus exporter (utils/obs_http.py) can emit must
    appear in docs/observability.md. Three emission sources:

    - registry names: every LITERAL first argument of obs.count /
      obs.gauge across the package (dynamic f-string names are covered
      by their documented ``<rule>``-style placeholder rows and are
      not enumerable statically);
    - span names: every literal obs.span(...) name (rendered as
      ``span.<name>_ms`` / the span vocabulary table), and every
      literal obs.phase(...) name with the histogram it feeds;
    - labeled families: the _FLEET_SERIES ledger series, the SLO
      breach family, and every literal dt_* family in
      utils/devprof.py + utils/obs_http.py.

    A metric added without a doc row fails HERE, at the producer, not
    in a dashboard review months later."""
    import ast
    import glob as _glob
    import re

    import distributedtraining_tpu as pkg
    from distributedtraining_tpu.utils import devprof, obs_http

    root = os.path.dirname(pkg.__file__)
    doc_path = os.path.join(os.path.dirname(root), "docs",
                            "observability.md")
    doc = open(doc_path).read()

    counter_names: set[str] = set()
    span_names: set[str] = set()
    for path in _glob.glob(os.path.join(root, "**", "*.py"),
                           recursive=True):
        tree = ast.parse(open(path).read(), filename=path)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "obs"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            if node.func.attr in ("count", "gauge"):
                counter_names.add(node.args[0].value)
            elif node.func.attr == "span":
                span_names.add(node.args[0].value)
            elif node.func.attr == "phase":
                # the span's name, and the histogram it feeds
                name = node.args[0].value
                hist = [k.value.value for k in node.keywords
                        if k.arg == "hist"]
                span_names.add(name)
                counter_names.add(hist[0] if hist else f"{name}_ms")

    families = {"dt_" + suffix for _, suffix, _ in obs_http._FLEET_SERIES}
    families.add("dt_fleet_slo_breached")
    for mod in (devprof, obs_http):
        src = open(mod.__file__).read()
        families |= set(re.findall(r'"(dt_[a-z0-9_]+)"', src))

    missing = sorted(
        [n for n in counter_names if n not in doc]
        + [f"span:{n}" for n in span_names if n not in doc]
        + [f for f in families if f not in doc])
    assert not missing, (
        "metric names the exporter can emit are missing from "
        f"docs/observability.md: {missing} — add a table row (or a "
        "placeholder rule row) for each")


def test_concurrent_scrapes_during_registry_flush():
    """Satellite: /metrics and /debug/dump raced from two scraper
    threads while the main thread churns the registry with flushes and
    new series — every response parses, no 500s, no torn Prometheus
    text (partial lines / missing trailing newline), and the exporter
    survives to serve a clean final scrape."""
    import urllib.request

    from distributedtraining_tpu.utils import flight
    from distributedtraining_tpu.utils.obs_http import ObsHTTPExporter

    sink = InMemorySink()
    obs.configure(sink, role="scraper")
    flight.configure("scraper", "s0")
    exp = ObsHTTPExporter(0, role="scraper")
    port = exp.start()
    stop = threading.Event()
    errors: list = []
    bodies: list = []

    def _scrape(path, parse):
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}",
                        timeout=10) as r:
                    raw = r.read().decode()
                    assert r.status == 200
                parse(raw)
                bodies.append(path)
            except Exception as e:  # noqa: BLE001 - collected for assert
                errors.append((path, repr(e)))
                return

    def _parse_prom(raw):
        assert raw.endswith("\n"), "torn text: no trailing newline"
        for ln in raw.splitlines():
            if ln and not ln.startswith("#"):
                name = ln.split("{")[0].split(" ")[0]
                assert name.startswith("dt_"), f"torn line: {ln!r}"
                float(ln.rsplit(" ", 1)[1])

    threads = [
        threading.Thread(target=_scrape, args=("/metrics", _parse_prom)),
        threading.Thread(target=_scrape,
                         args=("/debug/dump", json.loads)),
    ]
    for t in threads:
        t.start()
    try:
        # churn: new counter names, histogram traffic, full flushes and
        # flight events racing the scrapers' renders — keep churning
        # until both endpoints have been scraped several times
        import time as _time
        deadline = _time.time() + 30.0
        i = 0
        while (bodies.count("/metrics") < 4
               or bodies.count("/debug/dump") < 4) and not errors \
                and _time.time() < deadline:
            obs.count(f"scrape.race_{i % 7}")
            obs.observe("scrape.lat_ms", float(i))
            obs.gauge("scrape.g", float(i))
            flight.record("note", text=f"race {i}")
            obs.registry().flush_to(sink, step=i)
            i += 1
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        exp.close()
        flight.shutdown()
    assert not errors, errors
    # both endpoints actually got scraped repeatedly under churn
    assert bodies.count("/metrics") > 3
    assert bodies.count("/debug/dump") > 3
