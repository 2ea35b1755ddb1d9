"""The reduction from events to busy time, idle gaps and time by operation,
on a trace small enough to count by hand, and on a slice recorded from the
chip (`data/trace_sample.json`, written by tools/trace_dump.py)."""

import json
import os

import pytest

from readers import xplane

MS = 1_000_000


def _hand_trace():
    ops = {"/device:TPU:0": [
        ("fusion.1", 10 * MS, 20 * MS),      # 10..30
        ("while", 25 * MS, 30 * MS),         # 25..55 overlaps: union 10..55
        ("flash_fwd", 70 * MS, 10 * MS),     # 70..80
        ("fusion.1", 95 * MS, 20 * MS),      # 95..115, clipped at 100
    ]}
    host = [(xplane.WINDOW_SPAN, 0, 100 * MS),
            ("bench.next_batch", 0, 12 * MS),
            ("bench.train_step_dispatch", 12 * MS, 50 * MS),
            ("bench.next_batch", 62 * MS, 30 * MS)]
    return xplane.from_events(ops, host)


def test_busy_is_the_union_inside_the_window():
    t = _hand_trace()
    assert t.window_s == pytest.approx(0.1)
    # 10..55 (45) + 70..80 (10) + 95..100 (5) = 60 ms
    assert xplane.busy_seconds(t) == pytest.approx(0.060)


def test_time_by_operation_and_matching():
    t = _hand_trace()
    by = xplane.seconds_by_op(t)
    assert by["fusion.1"] == pytest.approx(0.025)
    assert by["flash_fwd"] == pytest.approx(0.010)
    assert xplane.matching_ops(t, "flash") == [("flash_fwd", 0.010)]
    # an instruction's OWN name and opcode decide, not its operands'
    t2 = xplane.from_events({"/device:TPU:0": [
        ("%flash_fwd.3 = bf16[4]{0} custom-call(bf16[4]{0} %x)", 0, 5 * MS),
        ("%slice-start.9 = bf16[2]{0} async-start(bf16[4]{0} %flash_fwd.3)",
         5 * MS, 1)]}, [(xplane.WINDOW_SPAN, 0, 10 * MS)])
    assert xplane.matching_ops(t2, "flash", "custom-call") == [
        ("%flash_fwd.3", 0.005)]
    assert xplane.matching_ops(t2, "flash", "fusion") == []


def test_idle_gaps_are_named_by_the_host_span_over_them():
    t = _hand_trace()
    idle = xplane.idle_by_host_span(t)
    # gaps: 0..10 (next_batch), 55..70 (dispatch 55..62 = 7, next_batch
    # 62..70 = 8 -> next_batch), 80..95 (next_batch 80..92)
    assert idle == {"bench.next_batch": pytest.approx(0.040)}
    assert sum(idle.values()) + xplane.busy_seconds(t) == pytest.approx(0.1)


def test_breakdown_shape():
    b = xplane.breakdown(_hand_trace())
    assert b["device_ops"][0] == ["while", pytest.approx(0.030)]
    long = "%flash_mha_bwd_dq_block_q_1024.83 = (bf16[8,12,1024,64]) custom-call()"
    assert xplane.short_name(long) == "%flash_mha_bwd_dq_block_q_1024"
    assert xplane.short_name(
        "%fusion.17 = (f32[50304,1280]{1,0}) fusion(f32[1] %x)") == (
        "%fusion.17 (f32[50304,1280]{1,0})")
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10


def test_recorded_slice_from_the_chip():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_sample.json")
    with open(path) as f:
        rec = json.load(f)
    t = xplane.from_events(
        {p: [tuple(e) for e in evs] for p, evs in rec["device_ops"].items()},
        [tuple(e) for e in rec["host_spans"]]
        + [(xplane.WINDOW_SPAN, rec["window"][0],
            rec["window"][1] - rec["window"][0])])
    busy = xplane.busy_seconds(t)
    assert 0 < busy <= t.window_s
    assert busy == pytest.approx(rec["expect"]["busy_s"])
    top = xplane.breakdown(t)["device_ops"][0][0]
    assert top == rec["expect"]["top_op"]
