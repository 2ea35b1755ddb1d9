"""The split of the device's idle time by the program's own spans: on a
trace small enough to count by hand, and on a slice recorded from the chip
(`data/trace_program_spans.json`: the engine thread's `serve.*` spans, the
device's busy intervals and its `XLA Modules` runs)."""

import json
import os
import types

import pytest

from readers import program_spans, trace_idle, xplane

MS = 1_000_000
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_program_spans.json")


def _hand():
    """Window 0..100 ms. The device is busy 10..40 and 50..90: idle 0..10,
    40..50 and 90..100 (30 ms). The engine's thread: serve.step 5..95,
    inside it serve.admit 5..20 (with serve.prefill 8..20) and
    serve.decode.fetch 38..92. Another thread: a push.upload span 60..98,
    which covers no idle instant that the engine's thread leaves open
    except 95..98."""
    ops = {"/device:TPU:0": [("a", 10 * MS, 30 * MS), ("b", 50 * MS, 40 * MS)]}
    trace = xplane.from_events(ops, [(xplane.WINDOW_SPAN, 0, 100 * MS)])
    engine = (("serve.step", 5 * MS, 95 * MS),
              ("serve.admit", 5 * MS, 20 * MS),
              ("serve.prefill", 8 * MS, 20 * MS),
              ("serve.decode.fetch", 38 * MS, 92 * MS))
    other = (("push.upload", 60 * MS, 98 * MS),)
    return trace, (engine, other)


def _pct(trace, threads, **kw):
    return program_spans.idle_pct(trace, threads, **kw)


def test_the_innermost_span_gets_the_gap():
    trace, threads = _hand()
    engine = threads[:1]
    # 0..10: none 0..5, serve.admit 5..8 (it starts with serve.step and
    # is the shorter), serve.prefill 8..10
    assert _pct(trace, engine, spans=["serve.admit"]) == pytest.approx(3.0)
    assert _pct(trace, engine, spans=["serve.prefill"]) == pytest.approx(2.0)
    # 40..50 and 90..92 lie in serve.decode.fetch (inside serve.step),
    # 92..95 in serve.step alone
    assert _pct(trace, engine, spans=["serve.decode.fetch"]) == \
        pytest.approx(12.0)
    assert _pct(trace, engine, spans=["serve.step"]) == pytest.approx(3.0)


def test_a_span_on_another_thread_takes_only_what_it_covers():
    trace, threads = _hand()
    # push.upload (60..98) is open during the busy 60..90 and the idle
    # 90..98; it started AFTER serve.decode.fetch, so it is the innermost
    # there: 90..98 is its own, and none of 40..50, which it does not cover
    assert _pct(trace, threads, spans=["push.upload"]) == pytest.approx(8.0)
    assert _pct(trace, threads, spans=["serve.decode.fetch"]) == \
        pytest.approx(10.0)
    assert _pct(trace, threads, spans=["serve.step"]) == pytest.approx(0.0)


def test_the_partition_adds_up_to_the_idle_share():
    trace, threads = _hand()
    rec = types.SimpleNamespace(trace=trace)
    idle = trace_idle.read(rec)
    assert idle == pytest.approx(30.0)
    names = {n for th in threads for n, _, _ in th}
    inside = sum(_pct(trace, threads, spans=[n]) for n in names)
    # idle under no span at all: 0..5 and 98..100
    pieces = program_spans.idle_pieces(trace, threads)
    nowhere = sum(d for d, inner, _ in pieces if inner is None) / MS
    assert nowhere == pytest.approx(7.0)
    assert inside + nowhere == pytest.approx(idle)
    # outside serve.step: 0..5 and 95..100, whatever else is open there
    assert _pct(trace, threads, outside="serve.step") == pytest.approx(10.0)


def test_no_program_span_reads_as_nothing_and_bad_arguments_raise():
    trace, threads = _hand()
    assert _pct(trace, (), spans=["serve.step"]) is None
    assert _pct(trace, (), outside="serve.step") is None
    with pytest.raises(ValueError):
        _pct(trace, threads)
    with pytest.raises(ValueError):
        _pct(trace, threads, spans=["serve.step"], outside="serve.step")
    rec = types.SimpleNamespace(trace=None, run=types.SimpleNamespace(
        trace_dir=None))
    assert program_spans.read(rec, spans=["serve.step"]) is None


def test_spans_are_clipped_to_the_window():
    ops = {"/device:TPU:0": [("a", 20 * MS, 60 * MS)]}
    trace = xplane.from_events(ops, [(xplane.WINDOW_SPAN, 10 * MS, 80 * MS)])
    threads = ((("serve.step", 0, 30 * MS), ("serve.step", 85 * MS, 99 * MS),
                ("serve.grow", 86 * MS, 200 * MS)),)
    # window 10..90, idle 10..20 and 80..90; the first step covers 10..20,
    # the second 85..90 with serve.grow innermost from 86
    assert _pct(trace, threads, spans=["serve.step"]) == pytest.approx(
        100 * 11 / 80)
    assert _pct(trace, threads, spans=["serve.grow"]) == pytest.approx(
        100 * 4 / 80)
    assert _pct(trace, threads, outside="serve.step") == pytest.approx(
        100 * 5 / 80)


def test_a_span_s_own_time_is_clipped_to_the_window_too():
    """`of="time"`: what the loop spent inside the named spans, whatever
    the device did meanwhile."""
    ops = {"/device:TPU:0": [("a", 10 * MS, 70 * MS)]}   # never idle
    trace = xplane.from_events(ops, [(xplane.WINDOW_SPAN, 10 * MS, 70 * MS)])
    threads = ((("serve.decode.fetch", 0, 12 * MS),        # 2 ms inside
                ("serve.decode.emit", 12 * MS, 40 * MS),
                ("serve.decode.fetch", 40 * MS, 41 * MS),
                ("serve.decode.fetch", 79 * MS, 95 * MS)),  # 1 ms inside
               (("serve.decode.fetch", 50 * MS, 51 * MS),))  # another loop
    fetch = ["serve.decode.fetch"]
    assert program_spans.time_pct(trace, threads, fetch) == \
        pytest.approx(100 * 5 / 70)
    assert _pct(trace, threads, spans=fetch) == 0.0
    assert program_spans.time_pct(trace, (), fetch) is None


def _recorded():
    with open(DATA) as f:
        rec = json.load(f)
    lo, hi = rec["window"]
    trace = xplane.from_events(
        {p: [tuple(e) for e in evs] for p, evs in rec["device_ops"].items()},
        [(xplane.WINDOW_SPAN, lo, hi - lo)],
        {p: [tuple(e) for e in evs]
         for p, evs in rec["device_modules"].items()})
    threads = tuple(tuple(tuple(s) for s in th) for th in rec["threads"])
    return rec, trace, threads


def test_recorded_slice_partitions_the_idle_share():
    """The three serve metrics' groups, as their files give them, add up to
    `device.idle_pct.serve` on the chip's own slice."""
    rec, trace, threads = _recorded()
    idle = trace_idle.read(types.SimpleNamespace(trace=trace))
    parts = {}
    for name in ("admit", "decode", "outside_step"):
        with open(os.path.join(os.path.dirname(DATA), "..", "..",
                               "layer_metrics",
                               f"serve.idle_pct.{name}.json")) as f:
            parts[name] = _pct(trace, threads, **json.load(f)["args"])
    assert sum(parts.values()) == pytest.approx(idle, abs=1e-9)
    for name, want in rec["expect"]["idle_pct"].items():
        assert parts[name] == pytest.approx(want, abs=1e-6), name
    assert idle == pytest.approx(rec["expect"]["idle_pct_total"], abs=1e-6)


def test_recorded_slice_against_an_instant_by_instant_count():
    """The sweep against the definition: at a sample of idle instants, the
    innermost open span found by looking at every span."""
    rec, trace, threads = _recorded()
    spans = [s for th in threads for s in th]
    for a, b in program_spans.idle_gaps(trace)[::7]:
        t = (a + b) // 2
        open_ = [s for s in spans if s[1] <= t < s[2]]
        want = max(open_, key=lambda s: (s[1], -s[2]))[0] if open_ else None
        lo, hi = trace.window
        got = [inner for t0, t1, inner, _ in
               program_spans.timeline(threads, lo, hi) if t0 <= t < t1]
        assert got == [want]
